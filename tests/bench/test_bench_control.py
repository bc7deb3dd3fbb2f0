"""The lower-precision control of the correctness check, at a size the
CPU holds: the reference computed through float8 (e4m3, per-channel
weight and per-token activation scales) picks tokens that the check which
decides ``correct`` (``correctness.judge``, at the cell's committed
limit) refuses, while it passes the bf16 program's served tokens at the
same positions. At the cell's own size on the chip the same readings
(``bench/control.py``) set the limit; see PERF.md."""
import json
from pathlib import Path

import jax
import pytest

from bench import control

ROOT = Path(__file__).resolve().parents[2]
CELLS = {"mistral-nemo-12b": "nemo12b-chat-bursty"}


@pytest.mark.parametrize("arch", sorted(CELLS))
def test_fp8_control_reads_far_above_the_served_program(arch, tiny_cell):
    limit = json.loads((ROOT / "bench" / "cells"
                        / f"{CELLS[arch]}.json").read_text())["max_logit_gap"]
    spec = tiny_cell.spec(arch, limit)
    for seed in (1, 2, 3):
        out = control.readings(spec, seed, 2.0, True, jax)
        served_gap, served_ok, checks = out["served"]
        ctl_gap, ctl_ok, _ = out["control"]
        print(seed, served_gap, ctl_gap, checks)
        assert served_ok, checks
        assert not ctl_ok, (ctl_gap, limit)
