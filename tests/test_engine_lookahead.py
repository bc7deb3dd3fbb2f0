"""One-cycle decode lookahead in ``JaxEngine.execute_run``.

A run that is one full decode cycle with its head queues the batch's next
cycle before its sync; the next run takes it when it is the same cycle for
the same batch, and discards it otherwise. Whatever happens between runs
(admission and LIFO merge, a finishing row, a cancel, an arena grow or
shrink), every served token must be IDENTICAL to the same trace served
one node per dispatch and to generating the prompt alone
(``reference_generate``), with at most one host sync a run. Stacks with
recurrent state never run ahead; engines behind a ``MultiBackend`` run
ahead each on its own batches.
"""
import dataclasses
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import LazyBatching, SlackPredictor
from repro.core.arbiter import LeastSlackArbiter
from repro.serving.backend import MultiBackend
from repro.serving.engine import JaxEngine, reference_generate
from repro.serving.npu_model import NPUPerfModel, TPU_V5E
from repro.serving.session import ServingSession
from repro.serving.workload import LengthDist, from_model_config

PERF = NPUPerfModel(TPU_V5E)


def _tiny(arch):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, d_model=64, d_ff=128, vocab_size=128,
                               num_prefix_embeddings=0)


def _workload(cfg):
    return from_model_config(cfg, prompt_dist=LengthDist((6,), (1.0,)),
                             decode_dist=LengthDist((3,), (1.0,)))


def _req(wl, prompt_len, decode_len):
    r = wl.sample_request(np.random.default_rng(0), 0.0)
    seq, prefix_len, cycle_len = wl.build_sequence(prompt_len, decode_len)
    r.sequence, r.prefix_len, r.cycle_len = seq, prefix_len, cycle_len
    r.prompt_len, r.decode_len = prompt_len, decode_len
    return r


def _session(engine, wl):
    pred = SlackPredictor.build([wl], PERF, 60.0)
    return ServingSession(LazyBatching(pred, max_batch=4), engine, seed=0)


def _serve(engine, wl, script, events=None):
    """Serve ``script``: (step, prompt_len, decode_len) submissions, each
    made before that session step; ``events[step](engine, handles)`` runs
    between steps. Returns the handles, their prompts and the engine's
    (lookahead_dispatched, lookahead_hits) after every step."""
    events = events or {}
    session = _session(engine, wl)
    rng = np.random.default_rng(7)
    handles, prompts, trail = [], [], []
    step = 0
    while True:
        for at, plen, dlen in script:
            if at == step:
                prompt = rng.integers(2, engine.cfg.vocab_size, size=plen)
                r = _req(wl, plen, dlen)
                r.arrival = session.now
                handles.append(session.submit(r, prompt_tokens=prompt))
                prompts.append(prompt)
        if step in events:
            events[step](engine, handles)
        pending = step < max(at for at, _, _ in script)
        if not session.step() and not pending:
            break
        st = engine.sanitizer_stats()
        trail.append((st.lookahead_dispatched, st.lookahead_hits))
        step += 1
    return handles, prompts, trail


def _check(cfg, wl, script, handles, prompts, events=None, **kw):
    """Tokens equal the same script served one node per dispatch and the
    isolated reference (a prefix of it for a cancelled request)."""
    per_node = JaxEngine(cfg, max_len=32, fused=False, **kw)
    ref_handles, _, _ = _serve(per_node, wl, script, events)
    ref_engine = JaxEngine(cfg, max_len=32)
    for h, rh, prompt in zip(handles, ref_handles, prompts):
        assert h.tokens == rh.tokens
        ref = reference_generate(ref_engine, wl, prompt,
                                 h.request.decode_len)
        assert h.tokens == ref[:len(h.tokens)]
        if h.request.fate is None:
            assert len(h.tokens) == h.request.decode_len


def _ok(engine):
    assert engine.sanitizer_stats().max_syncs_per_run <= 1


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b",
                                  "granite-moe-3b-a800m"])
def test_steady_batch_hits(arch):
    cfg = _tiny(arch)
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32)
    script = [(0, 6, 5), (0, 6, 5)]
    handles, prompts, trail = _serve(engine, wl, script)
    # prefill, then five cycles: the first four each queue the next,
    # which the following run takes; the last finishes both rows
    assert trail[-1] == (4, 4)
    _ok(engine)
    _check(cfg, wl, script, handles, prompts)


def test_hit_latency_starts_where_the_previous_run_ended():
    """A taken cycle ran on the device from the previous run's end, so
    its latency covers the host's time between the runs: the session's
    clock keeps the device's time."""
    cfg = _tiny("llama3.2-1b")
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32)
    calls = []
    inner = engine.execute_run

    def execute_run(model, sb, node_ids):
        hits = engine.sanitizer_stats().lookahead_hits
        t_in = time.perf_counter()
        lat, per_node = inner(model, sb, node_ids)
        t_out = time.perf_counter()
        hit = engine.sanitizer_stats().lookahead_hits > hits
        calls.append((t_in, t_out, lat, hit))
        return lat, per_node

    engine.execute_run = execute_run
    _serve(engine, wl, [(0, 6, 5), (0, 6, 5)])
    assert [c[3] for c in calls] == [False, False] + [True] * 4
    for (p_in, _, _, _), (t_in, t_out, lat, hit) in zip(calls, calls[1:]):
        if hit:
            assert t_out - t_in < lat < t_out - p_in
        else:
            assert lat <= t_out - t_in


def test_admission_and_merge_miss_then_hit_again():
    cfg = _tiny("llama3.2-1b")
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32)
    # A and B decode; C arrives after their second cycle, prefills on top
    # of the stack and merges into their batch at the next cycle start
    script = [(0, 6, 8), (0, 6, 8), (3, 7, 4)]
    handles, prompts, trail = _serve(engine, wl, script)
    hits = [b - a for (_, a), (_, b) in zip(trail, trail[1:])]
    ahead = [b - a for (a, _), (b, _) in zip(trail, trail[1:])]
    # the step that prefills C does not take the queued cycle ...
    assert trail[3][1] == trail[2][1] and trail[2] == (2, 1)
    # ... and the merged batch runs ahead and hits again after it
    assert sum(hits[3:]) >= 2 and sum(ahead) > sum(hits)
    assert trail[-1][0] > trail[-1][1]
    _ok(engine)
    _check(cfg, wl, script, handles, prompts)


def test_finishing_row_does_not_run_ahead():
    cfg = _tiny("llama3.2-1b")
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32)
    script = [(0, 6, 2), (0, 6, 5)]
    handles, prompts, trail = _serve(engine, wl, script)
    # step 0 prefills; step 1 queues cycle 2; step 2 takes it and A
    # finishes there, so nothing is queued; B alone then runs ahead
    assert trail[:3] == [(0, 0), (1, 0), (1, 1)]
    assert trail[3] == (2, 1) and trail[-1] == (3, 3)
    _ok(engine)
    _check(cfg, wl, script, handles, prompts)


def test_cancel_between_runs():
    cfg = _tiny("llama3.2-1b")
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32)
    script = [(0, 6, 6), (0, 6, 6)]
    events = {3: lambda eng, hs: hs[1].cancel()}
    handles, prompts, trail = _serve(engine, wl, script, events)
    assert handles[1].request.fate == "cancelled"
    assert len(handles[1].tokens) == 2
    # the queued cycle was for A and B: A alone misses once and then
    # hits to its end
    assert trail[2] == (2, 1) and trail[3] == (3, 1)
    assert trail[-1] == (5, 4)
    assert engine.slots_in_use == 0
    _ok(engine)
    _check(cfg, wl, script, handles, prompts, events)


def _grab(n, grows):
    def grab(engine, handles):
        wl = handles[0].request.workload
        engine.extra = [_req(wl, 6, 1) for _ in range(n)]
        for r in engine.extra:
            engine.slot_of(r)
        assert engine.n_grows == grows
    return grab


def _drop(engine, handles):
    assert [engine.slot_of(h.request) for h in handles] == [4, 5]
    for r in engine.extra:
        engine.release_slot(r)
    assert engine.n_shrinks == 1 and engine.n_slots == 4
    assert [engine.slot_of(h.request) for h in handles] == [0, 1]


@pytest.mark.parametrize("change", ["grow", "shrink"])
def test_arena_grow_and_shrink_invalidate(change):
    cfg = _tiny("llama3.2-1b")
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32, min_slots=2)
    script = [(0, 6, 6), (0, 6, 6)]
    if change == "grow":
        # A and B hold both slots; a third grows the arena between runs
        events = {3: _grab(1, grows=1)}
    else:
        # four slots held before A and B prefill put them in slots 4 and
        # 5 of 8; freeing the four between runs shrinks the arena to 4
        # and moves A and B to slots 0 and 1
        events = {0: _grab(4, grows=1), 3: _drop}
    handles, prompts, trail = _serve(engine, wl, script, events)
    # the run after the change misses, the ones after it hit again
    assert trail[2] == (2, 1) and trail[3] == (3, 1)
    assert trail[-1] == (5, 4)
    _ok(engine)
    _check(cfg, wl, script, handles, prompts, events, min_slots=2)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "recurrentgemma-9b"])
def test_recurrent_state_never_runs_ahead(arch):
    cfg = _tiny(arch)
    wl = _workload(cfg)
    engine = JaxEngine(cfg, max_len=32)
    script = [(0, 6, 4), (0, 6, 4)]
    handles, prompts, trail = _serve(engine, wl, script)
    assert trail[-1] == (0, 0)
    _ok(engine)
    _check(cfg, wl, script, handles, prompts)


def test_engines_behind_a_multibackend_run_ahead():
    cfg = _tiny("llama3.2-1b")
    wl = _workload(cfg)
    engines = {"a": JaxEngine(cfg, max_len=32, seed=0),
               "b": JaxEngine(cfg, max_len=32, seed=1)}
    mux = MultiBackend(engines)
    session = ServingSession(backend=mux,
                             arbiter=LeastSlackArbiter(sla_default=60.0))
    for name in engines:
        pred = SlackPredictor.build([wl], PERF, 60.0)
        session.register(name, wl, policy=LazyBatching(pred, max_batch=4))
    rng = np.random.default_rng(3)
    handles = []
    for name in ("a", "b", "a", "b"):
        prompt = rng.integers(2, cfg.vocab_size, size=6)
        handles.append((name, prompt, session.submit(
            _req(wl, 6, 4), model=name, prompt_tokens=prompt)))
    t0 = time.perf_counter()
    session.drain()
    wall = time.perf_counter() - t0
    # a taken cycle's latency starts where the last run on the device
    # ended, whichever engine ran it: no time is counted twice
    assert session.log.busy_time <= wall
    # another engine's run between two of an engine's runs leaves its
    # queued cycle standing
    stats = [e.sanitizer_stats() for e in engines.values()]
    assert all(st.lookahead_hits > 0 for st in stats)
    total = mux.sanitizer_stats()
    assert total.lookahead_hits == sum(st.lookahead_hits for st in stats)
    assert total.max_syncs_per_run <= 1
    for name, prompt, h in handles:
        ref = reference_generate(JaxEngine(cfg, max_len=32,
                                           seed=0 if name == "a" else 1),
                                 wl, prompt, 4)
        assert h.tokens == ref
