"""Tests of the on-chip benchmark (``bench/``): the repository root goes
on the import path so that ``import bench`` finds it."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


import dataclasses  # noqa: E402

import pytest  # noqa: E402

TINY_MIX = {"arrivals": {"kind": "mmpp2", "low_factor": 0.3,
                         "high_factor": 2.0, "state_seconds": 1.0},
            "prompt": {"kind": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 64},
            "output": {"kind": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 16},
            "lead_in_s": 0.5, "drain_s": 20.0}
TINY_CELL = {"rate_per_s": 6.0,
             "tiers": [{"name": "interactive", "share": 0.7,
                        "ttft_ms": 5000, "tpot_ms": 500},
                       {"name": "batch", "share": 0.3, "ttft_ms": 15000,
                        "tpot_ms": 1500}],
             "check_tokens": 120, "check_max_seqs": 8,
             "check_min_tokens": 10, "check_min_merged": 3}


@pytest.fixture
def tiny_cell(monkeypatch):
    """A cell of the benchmark at a size the CPU runs in seconds: the
    program's reduced config of ``arch`` (2 layers, d_model 256, vocab
    512, bf16), 4 slots of 128 tokens, a short bursty mix. Returns
    ``run(arch, seed, limit) -> result`` that drives ``bench.run.run``
    past its look for a chip; ``run.spec(arch, limit)`` is the cell."""
    import jax
    from repro.configs import get_config

    from bench import harness, layout
    from bench import run as bench_run

    monkeypatch.setattr(layout, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})

    def spec(arch, limit):
        small = get_config(arch).reduced()
        monkeypatch.setattr(
            harness, "program_config",
            lambda config: dataclasses.replace(
                small, num_layers=config["num_hidden_layers"]))
        config = {"name": arch, "arch": arch, "attention": small.attention,
                  "reference": "dense_gqa",
                  "dtype": "bfloat16", "hidden_size": small.d_model,
                  "num_attention_heads": small.num_heads,
                  "num_key_value_heads": small.num_kv_heads,
                  "head_dim": small.head_dim,
                  "intermediate_size": small.d_ff,
                  "vocab_size": small.vocab_size, "num_hidden_layers": 2,
                  "rope_theta": small.rope_theta,
                  "rms_norm_eps": small.norm_eps,
                  "tie_word_embeddings": small.tie_embeddings,
                  "serving": {"max_len": 128, "slots": 4, "max_batch": 4}}
        return {"entry": {"chips": 1}, "config": config, "mix": TINY_MIX,
                "cell": dict(TINY_CELL, max_logit_gap=limit)}

    monkeypatch.setattr(layout, "metrics_for", lambda name, kind: [
        {"name": "sla_attainment", "unit": "%"},
        {"name": "setup_s", "unit": "s"}])

    def run(arch, seed, limit, seconds=2.0):
        return bench_run.run(spec(arch, limit), "tiny", seed, seconds, False,
                             jax, jax.devices())

    run.spec = spec
    return run
