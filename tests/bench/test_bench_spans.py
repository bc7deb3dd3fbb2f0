"""Reading the program's own spans: idle gaps split between the spans open
during them, named device programs, clipping to the traced slice (on
hand-made events and on a trace recorded on a TPU v5e), and the readers
of the recorder's spans and counters on a served window at a CPU size.

``data/small_serve.xplane.pb``: a 2-layer llama3.2-1b at published widths
in bf16 (Pallas decode kernel) serves three requests of 96 prompt and 8
output tokens through LazyBatching, the third arriving while the first
two decode, with the program's recorder on; every shape was served once
before the profiler started, and the served window is the host span
``bench.slice``."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import layout
from bench import spans as S
from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data" / "small_serve.xplane.pb"

NEW = ("tail.queue_wait_p95_ms", "engine_host_ms_per_run",
       "prefill_pad_share", "decode_device_ms_per_step",
       "idle_engine_host_share", "idle_sched_share", "setup_compile_s")


def trace():
    ops = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.2", 5.0, 6.0),
                             ("late", 9.5, 11.0)]}
    mods = {"/device:TPU:0": [("jit_decode_megastep(123)", -1.0, 2.0),
                              ("jit_prefill_run(9)", 5.0, 6.0),
                              ("jit_decode_megastep(123)", 9.5, 11.0)]}
    host = [("bench.slice", 0.0, 10.0), ("bench.step", 0.0, 10.0),
            ("serve.step", 0.5, 8.0),
            ("serve.sched.policy", 0.5, 1.5),
            ("serve.engine.run", 1.5, 7.0),
            ("serve.engine.plan", 1.5, 1.6),
            ("serve.engine.dispatch", 2.5, 4.0),
            ("serve.engine.compile", 3.0, 4.0),
            ("serve.engine.sync", 4.0, 6.5),
            ("serve.engine.epilogue", 6.5, 7.0),
            ("serve.sched.epilogue", 7.0, 8.0)]
    return S.from_events(ops, mods, host)


def test_innermost_pieces_follow_nesting():
    pieces = S.innermost([("a", 0.0, 4.0), ("b", 1.0, 2.0), ("c", 2.0, 3.0)],
                         -1.0, 5.0)
    assert pieces == [(-1.0, 0.0, None), (0.0, 1.0, "a"), (1.0, 2.0, "b"),
                      (2.0, 3.0, "c"), (3.0, 4.0, "a"), (4.0, 5.0, None)]


def test_gap_is_split_across_the_spans_open_during_it():
    st = trace()
    assert st.window == (0.0, 10.0) and st.busy["/device:TPU:0"] == [
        (1.0, 2.0), (5.0, 6.0), (9.5, 10.0)]
    idle = st.idle_by_span()
    # gap [0, 1]: outside serve.* until 0.5, then the policy
    # gap [2, 5]: run 2-2.5, dispatch 2.5-3, compile 3-4, sync 4-5
    # gap [6, 9.5]: sync 6-6.5, epilogue 6.5-7, sched epilogue 7-8, none
    assert idle == pytest.approx({
        None: 0.5 + 1.5, "serve.sched.policy": 0.5,
        "serve.engine.run": 0.5, "serve.engine.dispatch": 0.5,
        "serve.engine.compile": 1.0, "serve.engine.sync": 1.5,
        "serve.engine.epilogue": 0.5, "serve.sched.epilogue": 1.0})
    assert sum(idle.values()) == pytest.approx(10.0 - 2.5)


def test_named_module_time_is_clipped_to_the_slice():
    st = trace()
    assert S.module_name("jit_decode_megastep(8852587370456195745)") == \
        "jit_decode_megastep"
    # [-1, 2] -> [0, 2]; [9.5, 11] -> [9.5, 10]
    assert st.module_seconds("jit_decode_megastep") == pytest.approx(2.5)
    assert st.module_seconds("jit_prefill_run") == pytest.approx(1.0)
    assert st.module_seconds("jit_head") == 0.0


def test_device_readers_on_hand_made_events():
    st = trace()
    rec = SimpleNamespace(counters=[("decode_cycles", 0.5, 4),
                                    ("decode_cycles", 20.0, 9)], spans=[])
    ctx = SimpleNamespace(span_trace=st, spans=rec,
                          res=SimpleNamespace(trace_bounds=(0.0, 10.0)))
    eng = layout.reader("idle_engine_host_share").read(ctx)
    sched = layout.reader("idle_sched_share").read(ctx)
    assert eng == pytest.approx(100 * (0.5 + 1.0 + 0.5) / 10)
    assert sched == pytest.approx(100 * (0.5 + 1.0) / 10)
    idle = 100 * (1 - 2.5 / 10)
    assert eng + sched <= idle
    assert layout.reader("decode_device_ms_per_step").read(ctx) == \
        pytest.approx(1e3 * 2.5 / 4)


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_nothing_without_the_recorder(metric):
    """The harness of a program without the recorder has no spans to
    pass: each reader returns None and does not raise."""
    mod = layout.reader(metric)
    ctx = SimpleNamespace(res=None, dims={}, peak={}, reduced=None,
                          setup_s=1.0)
    assert mod.read(ctx) is None
    assert mod.UNIT and mod.SOURCE in ("host_clock", "device_trace")


def test_untraced_run_keeps_no_span_and_annotates_nothing(tiny_cell,
                                                        monkeypatch):
    import jax.profiler
    from repro.serving import tracing

    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: made.append(name))
    out = tiny_cell("mistral-nemo-12b", 2**31 + 5, 0.25)
    assert out["correct"] and out["attempted"] > 0
    assert not tracing.ON
    assert tracing.records().spans == [] and made == []


def test_host_readers_on_a_served_window(tiny_cell):
    from repro.serving import tracing

    from bench import harness
    from bench import traffic as T

    spec = tiny_cell.spec("mistral-nemo-12b", 0.25)
    tracing.enable()
    try:
        prog = harness.build_program(spec["config"], spec["mix"], 5)
        harness.warm_up(prog, spec["mix"], 5)
        sched = T.schedule(spec["mix"], spec["cell"], 5, 2.0)
        res = harness.serve_window(prog, sched, spec["mix"], 2.0, 5)
    finally:
        tracing.disable()
    rec = tracing.records()
    ctx = SimpleNamespace(res=res, spans=rec, span_trace=None)
    read = {m: layout.reader(m).read(ctx) for m in NEW}
    assert read["decode_device_ms_per_step"] is None     # no device trace
    assert read["idle_engine_host_share"] is None
    wait, ttft = read["tail.queue_wait_p95_ms"], harness.percentile(
        harness.ttft_values(res), 95) * 1e3
    assert 0 <= wait <= ttft
    runs = harness.runs_between(res.timeline, res.open, res.close)
    wall = 1e3 * sum(r.t1 - r.t0 for r in runs) / len(runs)
    assert 0 < read["engine_host_ms_per_run"] < wall
    assert 0 <= read["prefill_pad_share"] < 100
    assert read["setup_compile_s"] > 0
    assert read["setup_compile_s"] < res.open - min(s.t0 for s in rec.spans)
    tracing.reset()


def test_recorded_trace_names_programs_and_splits_idle():
    st = S.read_xplane(str(DATA))
    reduced = TR.read_xplane(str(DATA))
    lo, hi = st.window
    assert hi - lo == pytest.approx(reduced.window_s)
    # the engine's programs under their stable names, nothing as jit_fn
    assert st.module_seconds("jit_decode_megastep") > 0
    assert st.module_seconds("jit_prefill_run") > 0
    names = {n for evs in st.modules.values() for n, _, _ in evs}
    assert "jit_fn" not in names
    # the kernel's pinned name still matches decode_attn_roofline's
    kernel = layout.reader("decode_attn_roofline").KERNEL
    assert reduced.kernel_seconds(kernel) > 0
    assert all(n.startswith("ragged_decode_attention")
               for n in reduced.op_seconds if "ragged" in n)
    names = {n for n, _, _ in st.spans}
    assert {"serve.step", "serve.engine.run", "serve.engine.plan",
            "serve.engine.dispatch", "serve.engine.sync",
            "serve.engine.epilogue", "serve.sched.policy"} <= names
    idle = st.idle_by_span()
    assert sum(idle.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)
    ctx = SimpleNamespace(span_trace=st)
    eng = layout.reader("idle_engine_host_share").read(ctx)
    sched = layout.reader("idle_sched_share").read(ctx)
    idle_share = layout.reader("device_idle_share").read(
        SimpleNamespace(reduced=reduced))
    assert 0 < eng and 0 < sched and eng + sched <= idle_share <= 100
