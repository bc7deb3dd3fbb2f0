"""Reduction of a profiler trace to device busy and idle time, kernel
time and the breakdown: on hand-made events, and on a small trace
recorded on a TPU v5e (``data/small.xplane.pb``: three rounds of a
bench.step span around the Pallas ragged decode-attention kernel and a
1024 x 1024 matmul, with a bench.wait span between, inside a
bench.slice span)."""
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps():
    assert TR.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def test_reduce_hand_made_events():
    ops = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("k_ragged_decode", 1.5,
                                                       3.0),
                             ("fusion.1", 5.0, 6.0), ("late", 9.5, 11.0)]}
    host = [("bench.slice", 0.0, 10.0), ("bench.step", 0.5, 6.5),
            ("bench.execute_run", 0.9, 6.2), ("bench.wait", 6.5, 10.0)]
    r = TR.reduce_events(ops, host)
    assert r.window_s == 10.0
    assert r.busy_s == pytest.approx(2.0 + 1.0 + 0.5)   # [1,3] [5,6] [9.5,10]
    assert r.op_seconds == pytest.approx({"fusion.1": 2.0,
                                          "k_ragged_decode": 1.5,
                                          "late": 0.5})
    assert r.kernel_seconds("ragged_decode") == pytest.approx(1.5)
    # gaps: [0,1] mid 0.5 -> bench.step; [3,5] mid 4 -> execute_run;
    # [6,9.5] mid 7.75 -> bench.wait
    assert r.gap_seconds == pytest.approx({"bench.step": 1.0,
                                           "bench.execute_run": 2.0,
                                           "bench.wait": 3.5})
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", 2.0]
    assert b["idle_gaps"][0] == ["bench.wait", 3.5]


def test_reduce_needs_the_slice_span():
    with pytest.raises(ValueError):
        TR.reduce_events({"/device:TPU:0": []}, [("bench.step", 0, 1)])


def test_containers_count_as_busy_but_not_as_ops():
    ops = {"/device:TPU:0": [("while.3", 1.0, 5.0), ("fusion.2", 1.5, 2.0),
                             ("call", 6.0, 7.0)]}
    r = TR.reduce_events(ops, [("bench.slice", 0.0, 10.0)])
    assert r.busy_s == pytest.approx(5.0)
    assert r.op_seconds == pytest.approx({"fusion.2": 0.5})


def test_op_name_is_the_instruction_name():
    assert TR.op_name("%fusion.21 = (bf16[1]) fusion(...)") == "fusion.21"
    assert TR.op_name("copy.1") == "copy.1"


def test_recorded_trace():
    import jax

    r = TR.read_xplane(str(DATA))
    assert r.n_devices == 1
    # independent reduction of the same file: the slice from the host
    # plane, device intervals from the XLA Ops line, merged by hand
    pd = jax.profiler.ProfileData.from_file(str(DATA))
    host = [(e.start_ns, e.start_ns + e.duration_ns) for p in pd.planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name == "bench.slice"]
    (s0, s1), = host
    iv = sorted((max(e.start_ns, s0), min(e.start_ns + e.duration_ns, s1))
                for p in pd.planes if p.name == "/device:TPU:0"
                for ln in p.lines if ln.name == "XLA Ops" for e in ln.events
                if e.start_ns + e.duration_ns > s0 and e.start_ns < s1)
    busy, end = 0, None
    for a, b in iv:
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    assert r.window_s == pytest.approx((s1 - s0) * 1e-9)
    assert r.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < r.busy_s < r.window_s
    kernel = r.kernel_seconds("ragged_decode")
    assert kernel > 0
    assert r.op_seconds["ragged_decode_attention.1"] == pytest.approx(kernel)
    assert set(r.gap_seconds) <= {"bench.step", "bench.execute_run",
                                  "bench.wait", "idle"}
    assert sum(r.gap_seconds.values()) == pytest.approx(
        r.window_s - r.busy_s)
