"""Failure model: cancellation, expiry, seeded faults, retry, shedding.

Simulator cases exercise the session-level machinery (terminal handle
states, retry/backoff bookkeeping, bounded-ingress + brownout shedding,
the drain liveness guard); JAX cases prove the device-side contract —
a faulted run's retry replays prefill and regenerates tokens BIT-EXACT
vs a fault-free run, with the slot pool an exact partition afterwards.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (LazyBatching, Serial, SLAClass, SlackPredictor)
from repro.core.request import Request
from repro.serving import (BrownoutConfig, FaultInjectingBackend, FaultSpec,
                           HandleState, NPUPerfModel, PAPER_NPU, RetryPolicy,
                           ServingSession, SimExecutor, TransientBackendError,
                           get_workload, parse_fault_spec, parse_fault_specs)

PERF = NPUPerfModel(PAPER_NPU)
MS = 1e-3


def lazyb(wl, sla=0.1, max_batch=16):
    return LazyBatching(SlackPredictor.build([wl], PERF, sla),
                        max_batch=max_batch)


def _submit_n(session, wl, n, rng, arrival=0.0, sla=None):
    handles = []
    for _ in range(n):
        r = wl.sample_request(rng, arrival)
        if sla is not None:
            r.sla = sla
        handles.append(session.submit(r))
    return handles


# ---------------------------------------------------------------------------
# FaultSpec parsing and validation
# ---------------------------------------------------------------------------

def test_fault_spec_parses_all_kinds():
    spec = parse_fault_spec("transient:0.05,oom:0.01,straggler:0.1x8,"
                            "latency:0.002")
    assert spec == FaultSpec(p_transient=0.05, p_oom=0.01, p_straggler=0.1,
                             straggler_factor=8.0, fault_latency=0.002)
    assert parse_fault_spec("straggler:0.2").straggler_factor == 4.0


def test_fault_spec_per_model_and_validation():
    specs = parse_fault_specs("bulk=transient:0.1;gold=straggler:0.02x6")
    assert set(specs) == {"bulk", "gold"}
    assert specs["bulk"].p_transient == 0.1
    assert specs["gold"].straggler_factor == 6.0
    assert isinstance(parse_fault_specs("transient:0.1"), FaultSpec)
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_fault_spec("cosmic:0.1")
    with pytest.raises(ValueError, match="sum"):
        FaultSpec(p_transient=0.7, p_oom=0.4)
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        BrownoutConfig(floor=0.0)


def test_fault_injection_is_seed_deterministic():
    """Two identically seeded wrapped backends inject byte-identical
    fault sequences regardless of instance identity."""
    spec = FaultSpec(p_transient=0.3, p_oom=0.1, p_straggler=0.2)
    wl = get_workload("transformer")

    def run(seed):
        backend = FaultInjectingBackend(SimExecutor(PERF), spec, seed=seed)
        session = ServingSession(lazyb(wl), backend, seed=7,
                                 retry=RetryPolicy(max_retries=2,
                                                   backoff_base=1e-4))
        _submit_n(session, wl, 12, np.random.default_rng(3))
        session.drain()
        return backend.fault_stats()

    a, b, c = run(11), run(11), run(12)
    assert a == b
    assert a != c                      # a different seed faults differently
    per = a["default"]
    assert per["draws"] > 0


# ---------------------------------------------------------------------------
# Retry with backoff
# ---------------------------------------------------------------------------

class _FaultNth(SimExecutor):
    """Deterministically raise a retryable fault on chosen dispatches."""

    def __init__(self, perf, fault_on=(), latency=0.0, **kw):
        super().__init__(perf, **kw)
        self.fault_on = set(fault_on)
        self.latency = latency
        self.dispatch = 0
        self.committed_s = 0.0       # latency of the runs that completed

    def execute_run(self, model, sb, node_ids):
        self.dispatch += 1
        if self.dispatch in self.fault_on:
            raise TransientBackendError(
                f"injected on dispatch {self.dispatch}",
                latency=self.latency)
        out = super().execute_run(model, sb, node_ids)
        self.committed_s += out[0]
        return out


def test_transient_faults_retry_to_completion():
    wl = get_workload("transformer")
    backend = _FaultNth(PERF, fault_on={2, 7})
    session = ServingSession(lazyb(wl, sla=1.0), backend, seed=1,
                             retry=RetryPolicy(max_retries=10,
                                               backoff_base=0.1 * MS))
    handles = _submit_n(session, wl, 4, np.random.default_rng(2))
    stats = session.drain()
    assert session.log.faults == 2
    assert all(h.state is HandleState.DONE for h in handles)
    assert len(stats.finished) == 4
    assert stats.retried == session.retried > 0
    assert any(h.retries > 0 for h in handles)
    # SLA accounting: everything finished, judged against ORIGINAL arrival
    assert stats.summary(sla=1.0)["retried"] == stats.retried
    # no simulated residency leaked across the fault/retry cycle
    assert backend.memory_stats().slots_live == 0


def test_fault_latency_burns_device_time_without_committing_nodes():
    wl = get_workload("transformer")
    backend = _FaultNth(PERF, fault_on={1}, latency=2 * MS)
    session = ServingSession(lazyb(wl, sla=1.0), backend, seed=1,
                             retry=RetryPolicy(max_retries=3,
                                               backoff_base=0.1 * MS))
    (h,) = _submit_n(session, wl, 1, np.random.default_rng(2))
    session.drain()
    assert h.state is HandleState.DONE
    # the faulted dispatch's detection latency is in busy_time, but its
    # nodes were never committed: nodes_executed counts only the re-run's
    assert session.log.busy_time == pytest.approx(
        backend.committed_s + 2 * MS)
    assert session.log.nodes_executed == len(h.request.sequence)
    assert session.log.faults == 1


def test_retry_exhaustion_turns_failed_and_counts_as_violation():
    wl = get_workload("transformer")
    backend = FaultInjectingBackend(SimExecutor(PERF),
                                    FaultSpec(p_transient=1.0), seed=0)
    session = ServingSession(lazyb(wl), backend,
                             retry=RetryPolicy(max_retries=2,
                                               backoff_base=0.1 * MS))
    (h,) = _submit_n(session, wl, 1, np.random.default_rng(0))
    stats = session.drain()
    assert h.state is HandleState.FAILED
    assert h.done and h.retries == 2
    assert stats.failed_requests and not stats.finished
    # a failed request is a violation of its own deadline
    assert stats.sla_violation_rate(0.1) == 1.0
    assert stats.attainment(0.1) == 0.0
    # exhaustion released everything: no residency, no scheduler state
    assert session.policy.outstanding == 0
    assert backend.memory_stats().slots_live == 0


def test_without_retry_policy_backend_errors_propagate():
    """No RetryPolicy => the failure model is OFF: a dispatch fault
    raises out of drain() instead of being absorbed, so an engine's own
    capacity errors stay loud unless the caller opted in."""
    wl = get_workload("transformer")
    session = ServingSession(lazyb(wl), _FaultNth(PERF, fault_on=(1,)))
    _submit_n(session, wl, 2, np.random.default_rng(2))
    with pytest.raises(TransientBackendError):
        session.drain()


def test_non_retryable_fault_fails_immediately():
    wl = get_workload("transformer")

    class OneShotFatal(SimExecutor):
        def __init__(self, perf):
            super().__init__(perf)
            self.tripped = False

        def execute_run(self, model, sb, node_ids):
            if not self.tripped:
                self.tripped = True
                raise TransientBackendError("wedged", retryable=False)
            return super().execute_run(model, sb, node_ids)

    session = ServingSession(lazyb(wl), OneShotFatal(PERF),
                             retry=RetryPolicy())
    h1, h2 = _submit_n(session, wl, 2, np.random.default_rng(1))
    stats = session.drain()
    states = {h1.state, h2.state}
    assert HandleState.FAILED in states       # the faulted batch died...
    assert session.retried == 0               # ...without burning retries
    assert len(stats.failed_requests) == 2    # (both rode the same batch)


# ---------------------------------------------------------------------------
# Cancellation and expiry
# ---------------------------------------------------------------------------

def test_cancel_queued_and_midflight_leaves_survivors_alone():
    wl = get_workload("transformer")
    backend = SimExecutor(PERF, max_slots=16)
    session = ServingSession(lazyb(wl), backend)
    handles = _submit_n(session, wl, 4, np.random.default_rng(6))
    assert handles[0].cancel()                  # cancel while QUEUED
    assert handles[0].state is HandleState.CANCELLED
    assert not handles[0].cancel()              # idempotent: already dead
    session.step()                              # admit + first run
    victim = next(h for h in handles[1:]
                  if h.state in (HandleState.ADMITTED, HandleState.RUNNING))
    assert victim.cancel()                      # cancel mid-flight
    assert victim.state is HandleState.CANCELLED
    assert backend.memory_stats().slots_live <= 2   # slot freed eagerly
    stats = session.drain()
    survivors = [h for h in handles if h not in (handles[0], victim)]
    assert all(h.state is HandleState.DONE for h in survivors)
    assert len(stats.finished) == 2
    assert len(stats.cancelled_requests) == 2
    assert session.policy.outstanding == 0
    assert backend.memory_stats().slots_live == 0
    # cancelled handles can be released like any other terminal handle
    session.release(victim)
    assert victim.request.rid not in session.handles


def test_cancel_expired_reaps_provably_blown_deadlines():
    """Under cancel_expired, a request whose deadline passed mid-queue
    goes terminal EXPIRED at the next run boundary instead of burning
    batch capacity on a guaranteed violation."""
    wl = get_workload("transformer")
    session = ServingSession(lazyb(wl, sla=10.0), SimExecutor(PERF),
                             cancel_expired=True)
    rng = np.random.default_rng(8)
    doomed = wl.sample_request(rng, 0.0)
    doomed.sla = SLAClass("tight", 1e-6)        # provably unmeetable
    hd = session.submit(doomed)
    ok = _submit_n(session, wl, 3, rng)
    stats = session.drain()
    assert hd.state is HandleState.EXPIRED
    assert all(h.state is HandleState.DONE for h in ok)
    assert len(stats.expired_requests) == 1
    assert len(stats.finished) == 3
    # expiry is a violation of the victim's own class deadline
    assert stats.per_class(sla=10.0)["tight"]["expired"] == 1
    assert stats.per_class(sla=10.0)["tight"]["sla_violation_rate"] == 1.0


def test_without_cancel_expired_nothing_is_dropped():
    wl = get_workload("transformer")
    session = ServingSession(lazyb(wl, sla=10.0), SimExecutor(PERF))
    rng = np.random.default_rng(8)
    doomed = wl.sample_request(rng, 0.0)
    doomed.sla = SLAClass("tight", 1e-6)
    hd = session.submit(doomed)
    session.drain()
    assert hd.state is HandleState.DONE         # late, but served


# ---------------------------------------------------------------------------
# Load shedding: bounded ingress + brownout
# ---------------------------------------------------------------------------

def test_bounded_ingress_sheds_lowest_tier_loosest_deadline():
    wl_a, wl_b = get_workload("transformer"), get_workload("resnet")
    session = ServingSession(backend=SimExecutor(PERF), max_queue=3)
    session.register("gold", wl_a, policy=lazyb(wl_a), shed_priority=1)
    session.register("bulk", wl_b, policy=lazyb(wl_b), shed_priority=0)
    rng = np.random.default_rng(9)
    hb = [session.submit(wl_b.sample_request(rng, 0.0), model="bulk")
          for _ in range(3)]
    hg = [session.submit(wl_a.sample_request(rng, 0.0), model="gold")
          for _ in range(3)]
    stats = session.drain()
    # gold never sheds while a lower tier is available to victimize
    assert all(h.state is HandleState.DONE for h in hg)
    assert sum(h.state is HandleState.SHED for h in hb) == 3
    assert len(stats.shed_requests) == 3
    assert stats.per_model()["bulk"]["shed"] == 3


def test_brownout_sheds_lower_tier_when_protected_attainment_dips():
    wl_a, wl_b = get_workload("transformer"), get_workload("resnet")
    session = ServingSession(
        backend=SimExecutor(PERF),
        brownout=BrownoutConfig(floor=0.9, window=8, min_samples=2))
    session.register("gold", wl_a, policy=lazyb(wl_a, sla=10.0),
                     shed_priority=1)
    session.register("bulk", wl_b, policy=lazyb(wl_b, sla=10.0),
                     shed_priority=0)
    rng = np.random.default_rng(10)
    # gold requests with unmeetable deadlines: every finish is a miss
    hg = []
    for _ in range(4):
        r = wl_a.sample_request(rng, 0.0)
        r.sla = SLAClass("tight", 1e-6)
        hg.append(session.submit(r, model="gold"))
    # bulk arrives later, after the protected tier's attainment collapsed
    hb = [session.submit(wl_b.sample_request(rng, 1.0), model="bulk")
          for _ in range(4)]
    stats = session.drain()
    assert session.brownouts == 1
    assert all(h.state is HandleState.DONE for h in hg)
    assert all(h.state is HandleState.SHED for h in hb)
    assert len(stats.shed_requests) == 4


def test_single_tier_brownout_never_engages():
    wl = get_workload("transformer")
    session = ServingSession(
        lazyb(wl, sla=10.0), SimExecutor(PERF),
        brownout=BrownoutConfig(floor=0.9, window=8, min_samples=2))
    rng = np.random.default_rng(11)
    handles = _submit_n(session, wl, 6, rng, sla=SLAClass("tight", 1e-6))
    session.drain()
    # attainment collapses, brownout activates — but with one priority
    # level there is nothing lower-tier to shed: no work is dropped
    assert all(h.state is HandleState.DONE for h in handles)


# ---------------------------------------------------------------------------
# drain() liveness guard
# ---------------------------------------------------------------------------

def test_drain_raises_on_livelock_with_diagnostics():
    class WedgedPolicy(Serial):
        """Queues work it never offers, with a timer stuck at t=0: every
        step 'progresses' to the same instant forever."""
        def next_work(self, now):
            return None

        def next_timer(self, now):
            return 0.0

    wl = get_workload("transformer")
    session = ServingSession(WedgedPolicy(), SimExecutor(PERF))
    session.submit(wl.sample_request(np.random.default_rng(0), 0.0))
    with pytest.raises(RuntimeError, match="livelock") as ei:
        session.drain(stall_limit=50)
    assert "backlog" in str(ei.value)
    assert "queued" in str(ei.value)


def test_drain_with_faults_still_terminates():
    wl = get_workload("transformer")
    backend = FaultInjectingBackend(SimExecutor(PERF),
                                    FaultSpec(p_transient=0.4), seed=3)
    session = ServingSession(lazyb(wl), backend,
                             retry=RetryPolicy(max_retries=3))
    _submit_n(session, wl, 8, np.random.default_rng(4))
    stats = session.drain()          # must not trip the liveness guard
    assert len(stats.finished) + len(stats.failed_requests) == 8


# ---------------------------------------------------------------------------
# JAX engine: retry is bit-exact, slot pool stays a partition
# ---------------------------------------------------------------------------

def _tiny():
    cfg = get_config("llama3.2-1b").reduced()
    return dataclasses.replace(cfg, d_model=64, d_ff=128, vocab_size=128,
                               num_prefix_embeddings=0)


def test_jax_retry_regenerates_tokens_bit_exact_no_slot_leak():
    """Transient faults over the real engine: every request completes,
    retried requests replay prefill and regenerate the SAME tokens as a
    fault-free run (same session seed => same prompts), and the arena
    free pool is an exact partition of slots afterwards."""
    from repro.serving.engine import JaxEngine
    from test_engine_memory import _pool_consistent, _workload

    cfg = _tiny()
    wl = _workload(cfg)
    perf = NPUPerfModel(PAPER_NPU)

    def serve(spec):
        engine = JaxEngine(cfg, max_len=32, n_slots=4)
        backend = (engine if spec is None
                   else FaultInjectingBackend(engine, spec, seed=21))
        pol = LazyBatching(SlackPredictor.build([wl], perf, 60.0),
                           max_batch=4)
        # generous budget: with p=0.1 and a handful of dispatches per
        # pass, exhaustion probability is ~1e-15 — the test is stable
        session = ServingSession(pol, backend, seed=9,
                                 retry=RetryPolicy(max_retries=30,
                                                   backoff_base=0.1 * MS))
        rng = np.random.default_rng(14)
        handles = [session.submit(wl.sample_request(rng, 0.0))
                   for _ in range(5)]
        session.drain()
        return engine, session, handles

    eng_f, sess_f, faulted = serve(FaultSpec(p_transient=0.1,
                                             fault_latency=0.2 * MS))
    assert sess_f.log.faults > 0, "spec/seed injected no faults — retune"
    assert sess_f.retried > 0
    eng_c, sess_c, clean = serve(None)
    assert all(h.state is HandleState.DONE for h in faulted)
    for hf, hc in zip(faulted, clean):
        assert hf.tokens, "finished request streamed no tokens"
        assert hf.tokens == hc.tokens            # bit-exact vs fault-free
    assert eng_f.slots_in_use == 0
    _pool_consistent(eng_f)


def test_jax_cancel_midflight_keeps_survivors_bit_exact():
    """Cancelling one batch member mid-decode frees its slot immediately
    and leaves the survivors' remaining tokens bit-exact."""
    from repro.serving.engine import JaxEngine
    from test_engine_memory import _pool_consistent, _workload

    cfg = _tiny()
    wl = _workload(cfg)
    perf = NPUPerfModel(PAPER_NPU)

    def serve(cancel_idx):
        engine = JaxEngine(cfg, max_len=32, n_slots=4)
        pol = LazyBatching(SlackPredictor.build([wl], perf, 60.0),
                           max_batch=4)
        session = ServingSession(pol, engine, seed=9)
        rng = np.random.default_rng(14)
        handles = [session.submit(wl.sample_request(rng, 0.0))
                   for _ in range(4)]
        session.step()                   # admit + first committed run
        if cancel_idx is not None:
            assert handles[cancel_idx].cancel()
        session.drain()
        return engine, handles

    eng, handles = serve(cancel_idx=1)
    _, ref = serve(cancel_idx=None)
    assert handles[1].state is HandleState.CANCELLED
    for i in (0, 2, 3):
        assert handles[i].state is HandleState.DONE
        assert handles[i].tokens == ref[i].tokens
    assert eng.slots_in_use == 0
    _pool_consistent(eng)
