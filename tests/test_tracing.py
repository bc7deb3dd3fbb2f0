"""The span and counter recorder of the served path
(``repro.serving.tracing``): off, it keeps nothing and annotates nothing;
on, spans nest under their parents, a request's stamps come in order, the
simulator's statistics do not move, and an engine run records its plan,
dispatch, sync and epilogue without a second host sync."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import LazyBatching, SlackPredictor
from repro.serving import tracing
from repro.serving.engine import JaxEngine
from repro.serving.npu_model import PAPER_NPU, NPUPerfModel
from repro.serving.server import SimExecutor
from repro.serving.session import ServingSession, run_trace
from repro.serving.traffic import poisson_trace
from repro.serving.workload import LengthDist, from_model_config, \
    get_workload

PERF = NPUPerfModel(PAPER_NPU)


@pytest.fixture(autouse=True)
def _tracing_off():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@pytest.fixture
def annotations(monkeypatch):
    """Counts every profiler annotation the recorder makes."""
    import jax.profiler

    made = []

    class Counting:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return made


def _tiny_engine():
    cfg = dataclasses.replace(get_config("llama3.2-1b").reduced(),
                              d_model=64, d_ff=128, vocab_size=128,
                              num_prefix_embeddings=0)
    wl = from_model_config(cfg, prompt_dist=LengthDist((6,), (1.0,)),
                           decode_dist=LengthDist((3,), (1.0,)))
    return JaxEngine(cfg, max_len=64), wl


def _serve_engine(engine, wl, n=3):
    pred = SlackPredictor.build([wl], PERF, 10.0)
    session = ServingSession(LazyBatching(pred, max_batch=4), engine,
                             seed=0)
    rng = np.random.default_rng(0)
    hs = [session.submit(wl.sample_request(rng, 0.0)) for _ in range(n)]
    session.drain()
    return session, hs


def _sim_stats():
    wl = get_workload("transformer")
    pred = SlackPredictor.build([wl], PERF, 0.1)
    stats = run_trace(LazyBatching(pred), SimExecutor(PERF),
                      poisson_trace(wl, 300.0, 0.2, seed=5))
    reqs = sorted(stats.finished, key=lambda r: r.arrival)
    return stats.summary(sla=0.1), [
        (r.arrival, r.t_first_issue, r.t_first_token, r.t_finish)
        for r in reqs]


def test_off_records_and_annotates_nothing(annotations):
    assert not tracing.ON
    _sim_stats()
    engine, wl = _tiny_engine()
    _serve_engine(engine, wl)
    rec = tracing.records()
    assert rec.spans == [] and rec.counters == [] and rec.dropped == 0
    assert annotations == []


def test_on_spans_nest_and_annotate(annotations):
    tracing.enable()
    outer = tracing.begin(tracing.STEP)
    inner = tracing.begin(tracing.SCHED_POLICY)
    tracing.stamp(tracing.SUBMIT, rid=7)
    tracing.end(inner)
    left_open = tracing.begin(tracing.SCHED_EPILOGUE)
    tracing.end(outer, note=1)              # closes left_open too
    spans = tracing.records().spans
    assert [s.name for s in spans] == [tracing.STEP, tracing.SCHED_POLICY,
                                       tracing.SUBMIT, tracing.SCHED_EPILOGUE]
    assert [s.parent for s in spans] == [-1, 0, 1, 0]
    assert spans[2].t0 == spans[2].t1 and spans[2].attrs == {"rid": 7}
    assert left_open.t1 == outer.t1 and outer.attrs == {"note": 1}
    assert all(s.t0 <= s.t1 for s in spans)
    # stamps make no annotation; the three spans each made one
    assert annotations == [tracing.STEP, tracing.SCHED_POLICY,
                           tracing.SCHED_EPILOGUE]


def test_buffer_is_bounded_and_counts_drops(monkeypatch):
    monkeypatch.setattr(tracing, "CAPACITY", 3)
    tracing.enable()
    for _ in range(3):
        tracing.end(tracing.begin(tracing.STEP))
    sp = tracing.begin(tracing.STEP)
    tracing.count(tracing.DECODE_CYCLES, 1)
    tracing.end(sp)                         # a dropped span still closes
    rec = tracing.records()
    assert len(rec.spans) == 3 and rec.counters == [] and rec.dropped == 2


def test_simulator_stats_identical_with_tracing_on():
    off = _sim_stats()
    tracing.enable()
    on = _sim_stats()
    assert on == off
    names = {s.name for s in tracing.records().spans}
    assert {tracing.SUBMIT, tracing.STEP, tracing.SCHED_ADMIT,
            tracing.SCHED_POLICY, tracing.SCHED_EPILOGUE} <= names
    assert tracing.SCHED_ARBITER not in names      # one model: no arbiter


def test_engine_run_spans_counters_and_one_sync():
    engine, wl = _tiny_engine()
    tracing.enable()
    session, hs = _serve_engine(engine, wl)
    rec = tracing.records()
    spans = rec.spans
    runs = [i for i, s in enumerate(spans) if s.name == tracing.ENGINE_RUN]
    assert runs and engine.sanitizer_stats().max_syncs_per_run == 1
    hits = [t for name, t, _ in rec.counters
            if name == tracing.LOOKAHEAD_HITS]
    for i in runs:
        assert spans[spans[i].parent].name == tracing.STEP
        kids = [s.name for s in spans if s.parent == i]
        assert kids[-2:] == [tracing.ENGINE_SYNC, tracing.ENGINE_EPILOGUE]
        assert kids[0] == tracing.ENGINE_PLAN
        # a run dispatches, or takes the cycle the run before dispatched
        assert tracing.ENGINE_DISPATCH in kids or any(
            spans[i].t0 <= t <= spans[i].t1 for t in hits)
        assert spans[i].attrs["rows"] == len(spans[i].attrs["rids"])
    programs = {s.attrs["program"] for s in spans
                if s.name == tracing.ENGINE_DISPATCH}
    assert programs == {"prefill_run", "decode_megastep"}
    # the first runs compiled: compile spans sit inside their dispatch
    compiles = [s for s in spans if s.name == tracing.ENGINE_COMPILE]
    assert compiles
    assert all(spans[s.parent].name == tracing.ENGINE_DISPATCH
               and s.attrs == spans[s.parent].attrs for s in compiles)
    assert len(compiles) == engine.sanitizer_stats().retraces
    # a request's stamps are ordered: submitted, then its first run
    for h in hs:
        rid = h.request.rid
        sub = [s.t0 for s in spans if s.name == tracing.SUBMIT
               and s.attrs["rid"] == rid]
        first = min(spans[i].t0 for i in runs
                    if rid in spans[i].attrs["rids"])
        assert len(sub) == 1 and sub[0] <= first
    total = {}
    for name, _, n in rec.counters:
        total[name] = total.get(name, 0) + n
    # three 6-token prompts prefill 5 tokens each in one 4 x 8 bucket
    assert total[tracing.PREFILL_TOKENS] == 15
    assert total[tracing.PREFILL_POSITIONS] == 32
    assert total[tracing.DECODE_CYCLES] == sum(
        s.attrs["cycles"] for s in (spans[i] for i in runs)) == 3
    assert all(len(h.tokens) == 3 for h in hs)


def _totals(counters):
    total = {}
    for name, _, n in counters:
        total[name] = total.get(name, 0) + n
    return total


def test_lookahead_counts_add_up_with_decode_cycles():
    engine, wl = _tiny_engine()
    tracing.enable()
    _serve_engine(engine, wl)
    rec = tracing.records()
    total = _totals(rec.counters)
    cycles = total[tracing.DECODE_CYCLES]
    ahead = total[tracing.LOOKAHEAD_DISPATCHED]
    hits = total[tracing.LOOKAHEAD_HITS]
    # three requests of three tokens decode as one batch: the first two
    # cycles each queue the next, which the following run takes
    assert (cycles, ahead, hits) == (3, 2, 2)
    stats = engine.sanitizer_stats()
    assert (stats.lookahead_dispatched, stats.lookahead_hits) == (ahead, hits)
    # every megastep dispatched is a cycle run for itself or run ahead
    mega = sum(1 for s in rec.spans if s.name == tracing.ENGINE_DISPATCH
               and s.attrs["program"] == "decode_megastep")
    assert mega == cycles - hits + ahead
    # off, the same sites record nothing while the engine still counts
    tracing.disable()
    tracing.reset()
    engine, wl = _tiny_engine()
    _serve_engine(engine, wl)
    rec = tracing.records()
    assert rec.spans == [] and rec.counters == []
    stats = engine.sanitizer_stats()
    assert (stats.lookahead_dispatched, stats.lookahead_hits) == (2, 2)
