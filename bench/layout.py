"""Where the benchmark's files are, and how a name finds its file.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
is a file of its own (``configs/<config>.json``, ``traffic/<mix>.json``),
the cell's own rate, limits and any serving size of its own (a smaller
``max_batch``) are ``cells/<cell>.json``, and every metric
is a small reader ``metrics/<metric>.py``. A later change adds a cell or a
metric by adding such files and entries, without editing any file here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict[str, dict]:
    """Everything one cell runs from: its entry in ``BENCHMARK.json`` and
    the config, mix and cell files that entry names."""
    spec = benchmark()
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]
    ref = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT / ref["file"])
    own = load_json(BENCH / "cells" / f"{name}.json")
    # a cell may serve its traffic at a smaller batch than the config's
    config["serving"] = {**config["serving"], **own.get("serving", {})}
    return {"entry": entry, "config": config,
            "mix": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
            "cell": own}


def metrics_for(name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that cell ``name``
    reports: those that list it, or list no cells."""
    spec = benchmark()
    return [m for m in spec[kind]
            if name in m.get("workloads", [name])]


def reader(metric: str) -> ModuleType:
    """The module ``metrics/<metric>.py``: its ``read(ctx)`` gives the
    metric's value, or None where the run has nothing to read."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
