"""The benchmark finds every file by name, keeps to its peak table, and
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import layout

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    spec = layout.cell(name)
    assert spec["config"]["name"] == spec["entry"]["config"]
    for key in ("arrivals", "prompt", "output", "lead_in_s", "drain_s"):
        assert key in spec["mix"]
    for key in ("rate_per_s", "tiers", "max_logit_gap", "check_tokens",
                "check_max_seqs", "check_min_tokens", "check_min_merged"):
        assert key in spec["cell"]
    assert abs(sum(t["share"] for t in spec["cell"]["tiers"]) - 1) < 1e-9
    reported = {m["name"] for m in layout.metrics_for(name, "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert layout.metrics_for(name, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader_matches_its_entry(metric):
    entry = next(m for m in METRICS if m["name"] == metric)
    mod = layout.reader(metric)
    assert mod.UNIT == entry["unit"] and mod.SOURCE == entry["source"]
    if "layer" in entry:
        assert mod.LAYER == entry["layer"] and mod.MOVES == entry["moves"]
    assert callable(mod.read)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    for key in ("assumed", "deployment", "dtype", "serving"):
        assert data[key]
    assert data["dtype"] == data["torch_dtype"] == "bfloat16"


def test_peaks_known_and_unknown_device():
    p = layout.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        layout.peaks("TPU v9 imaginary")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_a_machine_without_tpu():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_refuses_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_cell_may_lower_its_batch():
    spec = layout.cell("nemo12b-longdoc-steady")
    config = layout.load_json(ROOT / "bench" / "configs"
                              / "mistral-nemo-12b.json")
    assert spec["config"]["serving"] == dict(config["serving"], max_batch=4)
    assert layout.cell("nemo12b-chat-bursty")["config"]["serving"] \
        == config["serving"]
