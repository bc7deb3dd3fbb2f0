"""The serving launcher's jax-engine sizing, the compile-cache helper, the
reference generation chip_smoke.py checks against, and chip_smoke.py's
refusal to run without a TPU. Nothing here allocates a full-width
model."""
import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.configs import get_config
from repro.launch import serve
from repro.serving.engine import JaxEngine, reference_generate
from repro.serving.workload import LengthDist, from_model_config

REPO = Path(__file__).resolve().parents[1]


def _engine_args(argv=()):
    ap = argparse.ArgumentParser()
    serve.add_jax_engine_args(ap)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(list(argv))


def test_default_jax_engine_serves_reduced_config_at_64():
    cfg, max_len = serve.jax_model_spec("llama3.2-1b", _engine_args())
    assert cfg == get_config("llama3.2-1b").reduced()
    assert max_len == 64


def test_default_jax_engine_traffic_is_the_cpu_smoke_mix():
    args = _engine_args()
    assert serve._lengths(args.prompt_lens) == LengthDist(
        (6, 8, 10, 12), (0.25,) * 4)
    assert serve._lengths(args.decode_lens) == LengthDist(
        (2, 3, 4, 5), (0.25,) * 4)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mistral-nemo-12b"])
def test_full_width_serves_published_config_unchanged(arch):
    args = _engine_args(["--full-width", "--max-len", "1024"])
    cfg, max_len = serve.jax_model_spec(arch, args)
    assert cfg == get_config(arch)
    assert max_len == 1024


def test_unknown_model_name_falls_back_to_llama():
    cfg, _ = serve.jax_model_spec("transformer",
                                  _engine_args(["--full-width"]))
    assert cfg == get_config("llama3.2-1b")


def test_max_len_above_kernel_block_must_be_a_multiple_of_512():
    with pytest.raises(SystemExit, match="multiple of 512"):
        serve.jax_model_spec("llama3.2-1b", _engine_args(["--max-len",
                                                          "768"]))


def test_traffic_longer_than_the_arena_is_refused_before_allocating():
    args = _engine_args(["--max-len", "64", "--prompt-lens", "60",
                         "--decode-lens", "8"])
    with pytest.raises(SystemExit, match="exceeds --max-len"):
        serve._jax_engine("llama3.2-1b", args)


@pytest.mark.parametrize("spec", ["", "128,x", "0,4", "-3"])
def test_bad_length_lists_are_refused(spec):
    with pytest.raises(SystemExit, match="positive ints"):
        serve._lengths(spec)


def test_cpu_launcher_smoke_still_serves_reduced(monkeypatch, tmp_path,
                                                 capsys):
    # an env-set cache directory keeps this test's compiles out of the
    # checkout (the helper leaves JAX's own reading of it alone)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    session = serve.main(["--engine", "jax", "--rate", "20",
                          "--duration", "0.2", "--max-batch", "4"])
    engine = session.backend
    assert engine.cfg == get_config("llama3.2-1b").reduced()
    assert engine.max_len == 64
    assert not engine.model.flags.pallas_decode     # CPU default
    stats = session.stats()
    assert stats.summary()["completed"] == len(session.handles) > 0
    assert "engine=jax" in capsys.readouterr().out


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_keeps_env_set_directory(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_defaults_to_one_fixed_ignored_checkout_dir(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.setup_compile_cache()
    second = compile_cache.setup_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_reference_generate_feeds_forced_tokens():
    cfg = get_config("llama3.2-1b").reduced()
    wl = from_model_config(cfg, prompt_dist=LengthDist((8,), (1.0,)),
                           decode_dist=LengthDist((4,), (1.0,)))
    engine = JaxEngine(cfg, max_len=64, n_slots=1)
    prompt = np.arange(2, 10)
    xs_free, xs_forced = [], []
    free = reference_generate(engine, wl, prompt, 4, on_head=xs_free.append)
    assert len(xs_free) == 4 and xs_free[0].shape == (1, cfg.d_model)
    # fed its own picks, the run is the free run
    assert reference_generate(engine, wl, prompt, 4, forced=free) == free
    other = [(t + 1) % cfg.vocab_size for t in free]
    picks = reference_generate(engine, wl, prompt, 4, forced=other,
                               on_head=xs_forced.append)
    assert picks[0] == free[0]              # step 0 sees only the prompt
    np.testing.assert_array_equal(xs_forced[0], xs_free[0])
    # step 1 decodes the forced token, not the pick
    assert not np.array_equal(xs_forced[1], xs_free[1])


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(doc, dict) and doc.get("ok")), line
