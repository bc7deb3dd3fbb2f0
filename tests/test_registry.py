"""Multi-tenant serving: ModelRegistry + cross-model SLA arbitration.

Covers the registry redesign's contracts:
  * a single registered model is BIT-identical to the legacy
    single-model ``ServingSession`` across the policy × rate grid,
  * per-model RNG streams: adding/reordering mixture components never
    perturbs another model's sampled arrivals or lengths,
  * two-model overload: the LazyBatching stack (per-model lazyb policies
    + least-slack arbiter) beats per-model GraphBatching round-robin on
    aggregate SLA attainment, and the tight-SLA model's p99 stays far
    below the bulk model's (per-model p99 ordering),
  * MultiBackend routes every model-keyed call to the right backend,
  * round-robin arbitration alternates between backlogged models,
  * per-model stats are NaN-safe for registered-but-idle models,
  * the retired ``Executor`` alias warns and resolves to ``Backend``.
"""
import numpy as np
import pytest

from repro.core import (GraphBatching, LazyBatching, LeastSlackArbiter,
                        Oracle, OracleSlackPredictor, RoundRobinArbiter,
                        Serial, SLAClass, SlackPredictor)
from repro.serving import (Backend, MultiBackend, NPUPerfModel, PAPER_NPU,
                           ServingSession, SimExecutor, get_workload,
                           poisson_mixture, poisson_trace, run_mixture,
                           run_trace)

PERF = NPUPerfModel(PAPER_NPU)

WL = {name: get_workload(name)
      for name in ("transformer", "gnmt", "resnet")}


def make_policy(kind, wl, sla=0.1, max_batch=16):
    if kind == "serial":
        return Serial()
    if kind == "graphb":
        return GraphBatching(window=0.01, max_batch=max_batch)
    if kind == "lazyb":
        return LazyBatching(SlackPredictor.build([wl], PERF, sla),
                            max_batch=max_batch)
    return Oracle(OracleSlackPredictor(sla, PERF), max_batch=max_batch)


# ---------------------------------------------------------------------------
# Registry equivalence: one registered model == the legacy session, exactly
# ---------------------------------------------------------------------------

def _request_key(stats):
    """Exact per-request timing signature (float equality intended)."""
    return sorted((r.rid, r.t_first_issue, r.t_first_token, r.t_finish)
                  for r in stats.finished)


@pytest.mark.parametrize("kind", ["serial", "graphb", "lazyb", "oracle"])
@pytest.mark.parametrize("rate", [150, 700])
def test_registered_single_model_bit_identical(kind, rate):
    wl = WL["transformer"]
    trace = poisson_trace(wl, rate, 0.06, seed=3)

    legacy = run_trace(make_policy(kind, wl), SimExecutor(PERF),
                       trace.fresh())

    session = ServingSession(backend=SimExecutor(PERF))
    session.register("tfm", wl, policy=make_policy(kind, wl))
    t2 = trace.fresh()
    session.duration = t2.duration
    for r in sorted(t2.requests, key=lambda r: r.arrival):
        session.submit(r, model="tfm")
    registered = session.drain()

    assert _request_key(legacy) == _request_key(registered)
    assert legacy.summary(sla=0.1)["p99_ms"] == \
        registered.summary(sla=0.1)["p99_ms"]


def test_legacy_constructor_registers_default_model():
    wl = WL["transformer"]
    session = ServingSession(make_policy("lazyb", wl), SimExecutor(PERF))
    assert session.registry.names() == ["default"]
    rng = np.random.default_rng(0)
    h = session.submit(wl.sample_request(rng, 0.0))
    session.drain()
    # the handle carries the routing key; the request keeps its (absent)
    # tag so per-model stats fall back to the workload name
    assert h.model == "default"
    assert h.request.model is None
    assert h.request.model_name == wl.name


def test_single_model_session_keeps_workload_fallback_in_per_model():
    """A legacy co-located trace (several workloads, ONE policy/session)
    still breaks down per workload in ServeStats.per_model()."""
    from repro.serving import colocated_trace

    wa, wb = WL["transformer"], WL["resnet"]
    trace = colocated_trace([wa, wb], [200, 200], 0.05, seed=0)
    pred = SlackPredictor.build([wa, wb], PERF, 0.1)
    stats = run_trace(LazyBatching(pred, max_batch=16), SimExecutor(PERF),
                      trace.fresh())
    pm = stats.per_model()
    assert {"transformer", "resnet"} <= set(pm)
    assert pm["transformer"]["completed"] > 0
    assert pm["resnet"]["completed"] > 0


# ---------------------------------------------------------------------------
# Per-model RNG streams (determinism regression)
# ---------------------------------------------------------------------------

def _stream_sig(trace, model):
    return [(r.arrival, r.prompt_len, r.decode_len, r.model)
            for r in trace.requests if r.model == model]


def test_mixture_streams_survive_extra_model_and_reordering():
    wa, wb, wc = WL["transformer"], WL["gnmt"], WL["resnet"]
    two = poisson_mixture([("a", wa, 300), ("b", wb, 200)], 0.3, seed=7)
    three = poisson_mixture([("a", wa, 300), ("c", wc, 500), ("b", wb, 200)],
                            0.3, seed=7)
    swapped = poisson_mixture([("b", wb, 200), ("a", wa, 300)], 0.3, seed=7)
    for m in ("a", "b"):
        assert _stream_sig(two, m) == _stream_sig(three, m), \
            f"registering model c perturbed model {m}'s stream"
        assert _stream_sig(two, m) == _stream_sig(swapped, m), \
            f"reordering the mixture perturbed model {m}'s stream"
    # arrival-sorted superposition, tagged throughout
    arr = [r.arrival for r in three.requests]
    assert arr == sorted(arr)
    assert three.models == ("a", "b", "c")
    # different seeds give different streams (the key actually feeds in)
    other = poisson_mixture([("a", wa, 300)], 0.3, seed=8)
    assert _stream_sig(two, "a") != _stream_sig(other, "a")


def test_mixture_fresh_preserves_model_tags():
    mix = poisson_mixture([("a", WL["transformer"], 300),
                           ("b", WL["gnmt"], 200)], 0.1, seed=0)
    clone = mix.fresh()
    assert [r.model for r in clone.requests] == \
        [r.model for r in mix.requests]


# ---------------------------------------------------------------------------
# Two-model overload: SLA-aware arbitration vs round-robin GraphBatching
# ---------------------------------------------------------------------------

GOLD, BULK = SLAClass("gold", 0.04), SLAClass("bulk", 0.4)


def _gold_bulk_mixture(seed=0, duration=0.25):
    """Interactive (gold, 40 ms) transformer co-located with a batchy
    (bulk, 400 ms) GNMT under combined overload — the paper's §VI-C
    co-location shape."""
    mix = poisson_mixture([("tf", WL["transformer"], 600),
                           ("gn", WL["gnmt"], 400)], duration, seed=seed)
    for r in mix.requests:
        r.sla = GOLD if r.model == "tf" else BULK
    return mix


def _serve_gold_bulk(mix, kind, arbiter):
    models = [("tf", WL["transformer"], make_policy(kind, WL["transformer"])),
              ("gn", WL["gnmt"], make_policy(kind, WL["gnmt"]))]
    return run_mixture(models, SimExecutor(PERF), mix.fresh(),
                       arbiter=arbiter)


@pytest.mark.parametrize("seed", [0, 1])
def test_lazyb_arbiter_beats_graphb_round_robin(seed):
    """Acceptance: on a two-model overload mixture the LazyBatching
    cross-model arbiter beats per-model GraphBatching round-robin on
    aggregate SLA attainment (each request judged against its own class
    deadline)."""
    mix = _gold_bulk_mixture(seed=seed)
    lazy = _serve_gold_bulk(mix, "lazyb", LeastSlackArbiter())
    base = _serve_gold_bulk(mix, "graphb", RoundRobinArbiter())
    assert len(lazy.finished) == len(base.finished) == len(mix.requests)
    a_lazy, a_base = lazy.attainment(), base.attainment()
    assert a_lazy > a_base + 0.2, \
        f"lazyb+least-slack {a_lazy:.3f} vs graphb+rr {a_base:.3f}"
    assert a_lazy > 0.9


def test_two_model_overload_per_model_p99_ordering():
    """The tight-SLA model's p99 must sit far below the bulk model's
    under the SLA-aware arbiter, and both classes hold their own SLAs."""
    mix = _gold_bulk_mixture(seed=0)
    stats = _serve_gold_bulk(mix, "lazyb", LeastSlackArbiter())
    pm = stats.per_model()
    assert set(pm) == {"tf", "gn"}
    assert pm["tf"]["completed"] > 0 and pm["gn"]["completed"] > 0
    # per-model p99 ordering: interactive model far below the batch model
    assert pm["tf"]["p99_ms"] < 0.5 * pm["gn"]["p99_ms"], pm
    # both models still attain their own (very different) deadlines
    assert pm["tf"]["sla_attainment"] > 0.9
    assert pm["gn"]["sla_attainment"] > 0.9
    # per-class view agrees (gold == tf, bulk == gn here)
    pc = stats.per_class()
    assert pc["gold"]["p99_ms"] < pc["bulk"]["p99_ms"]
    # summary carries the per-model keys for multi-tenant runs
    s = stats.summary()
    assert "p99_ms[model:tf]" in s and "sla_viol[model:gn]" in s


def test_least_slack_prefers_urgent_model_over_rr_order():
    """Direct arbiter unit check: with two ready candidates the one whose
    request is closest to violation dispatches first regardless of
    registration order; round-robin alternates instead."""
    wl = WL["resnet"]

    class _Entry:
        def __init__(self, name, index):
            self.name, self.index, self.policy = name, index, Serial()

    rng = np.random.default_rng(0)
    urgent = wl.sample_request(rng, 0.0)
    urgent.sla = SLAClass("tight", 0.01)
    relaxed = wl.sample_request(rng, 0.0)
    relaxed.sla = SLAClass("loose", 10.0)
    from repro.core.request import SubBatch
    cand = [(_Entry("a", 0), SubBatch([relaxed]), ("conv1",)),
            (_Entry("b", 1), SubBatch([urgent]), ("conv1",))]
    assert LeastSlackArbiter().pick(cand, now=0.005) == 1
    rr = RoundRobinArbiter()
    assert rr.pick(cand, now=0.0) == 0
    assert rr.pick(cand, now=0.0) == 1          # alternates
    assert rr.pick(cand, now=0.0) == 0


# ---------------------------------------------------------------------------
# MultiBackend routing + model resolution
# ---------------------------------------------------------------------------

class SpyBackend(Backend):
    def __init__(self, latency=1e-3):
        self.latency = latency
        self.calls = []                 # (model, node_id, rids)
        self.prepared = []
        self.finished = []

    def prepare(self, model, req, rng, prompt_tokens=None):
        self.prepared.append((model, req.rid))

    def execute(self, model, sb, node_id):
        self.calls.append((model, node_id,
                           tuple(r.rid for r in sb.live_requests)))
        return self.latency

    def on_finished(self, model, reqs):
        self.finished.extend(r.rid for r in reqs)


def _mixture_session(spy_a, spy_b, arbiter=None):
    wl_a, wl_b = WL["resnet"], WL["transformer"]
    session = ServingSession(
        backend=MultiBackend({"a": spy_a, "b": spy_b}), arbiter=arbiter)
    session.register("a", wl_a, policy=Serial())
    session.register("b", wl_b, policy=Serial())
    return session, wl_a, wl_b


def test_multibackend_routes_per_model():
    spy_a, spy_b = SpyBackend(), SpyBackend()
    session, wl_a, wl_b = _mixture_session(spy_a, spy_b)
    rng = np.random.default_rng(0)
    ra = [wl_a.sample_request(rng, 0.0) for _ in range(2)]
    rb = [wl_b.sample_request(rng, 0.0) for _ in range(2)]
    for r in ra:
        session.submit(r, model="a")
    for r in rb:
        session.submit(r, model="b")
    stats = session.drain()
    assert len(stats.finished) == 4
    # every call reached the right spy, with the right model key
    assert {m for m, _, _ in spy_a.calls} == {"a"}
    assert {m for m, _, _ in spy_b.calls} == {"b"}
    rids_a = {r.rid for r in ra}
    assert {rid for _, _, rids in spy_a.calls for rid in rids} == rids_a
    assert set(spy_a.finished) == rids_a
    assert {m for m, _ in spy_a.prepared} == {"a"}
    # device-time shares: both models on the one session clock
    assert session.log.busy_by_model["a"] > 0
    assert session.log.busy_by_model["b"] > 0
    assert spy_a.calls and spy_b.calls


def test_round_robin_alternates_between_backlogged_models():
    dispatch_order = []

    class OrderSpy(SpyBackend):
        def __init__(self, tag):
            super().__init__()
            self.tag = tag

        def execute_run(self, model, sb, node_ids):
            dispatch_order.append(model)
            return super().execute_run(model, sb, node_ids)

    session, wl_a, wl_b = _mixture_session(OrderSpy("a"), OrderSpy("b"),
                                           arbiter=RoundRobinArbiter())
    rng = np.random.default_rng(1)
    for _ in range(3):
        session.submit(wl_a.sample_request(rng, 0.0), model="a")
        session.submit(wl_b.sample_request(rng, 0.0), model="b")
    session.drain()
    # Serial commits one whole graph per run: with both models backlogged
    # the round-robin arbiter strictly alternates their runs
    assert dispatch_order == ["a", "b"] * 3
    # multi-model sessions attribute each run's device time to its model
    assert set(session.log.busy_by_model) == {"a", "b"}
    assert all(t > 0 for t in session.log.busy_by_model.values())
    assert sum(session.log.busy_by_model.values()) == \
        pytest.approx(session.log.busy_time)


def test_submit_model_resolution_and_validation():
    wl_a, wl_b = WL["resnet"], WL["transformer"]
    session = ServingSession(backend=SimExecutor(PERF))
    session.register("a", wl_a, policy=Serial())
    session.register("b", wl_b, policy=Serial())
    rng = np.random.default_rng(0)

    with pytest.raises(KeyError, match="not registered"):
        session.submit(wl_a.sample_request(rng, 0.0), model="nope")
    with pytest.raises(ValueError, match="no model tag"):
        session.submit(wl_a.sample_request(rng, 0.0))      # ambiguous
    with pytest.raises(ValueError, match="serves"):
        session.submit(wl_b.sample_request(rng, 0.0), model="a")
    # a tagged request routes itself
    r = wl_b.sample_request(rng, 0.0)
    r.model = "b"
    h = session.submit(r)
    session.drain()
    assert h.done and h.model == "b"


def test_duplicate_model_name_rejected():
    session = ServingSession(backend=SimExecutor(PERF))
    session.register("a", WL["resnet"], policy=Serial())
    with pytest.raises(ValueError, match="already registered"):
        session.register("a", WL["resnet"], policy=Serial())


# ---------------------------------------------------------------------------
# Per-model stats: NaN-safe for idle models
# ---------------------------------------------------------------------------

def test_per_model_stats_nan_safe_for_idle_model():
    wl = WL["transformer"]
    session = ServingSession(backend=SimExecutor(PERF))
    session.register("busy", wl, policy=make_policy("lazyb", wl))
    session.register("idle", WL["resnet"], policy=Serial())
    rng = np.random.default_rng(0)
    for _ in range(3):
        session.submit(wl.sample_request(rng, 0.0), model="busy")
    stats = session.drain()
    pm = stats.per_model(0.1)
    assert set(pm) == {"busy", "idle"}
    assert pm["idle"]["completed"] == 0
    assert np.isnan(pm["idle"]["p99_ms"])
    assert np.isnan(pm["idle"]["sla_attainment"])
    assert pm["busy"]["completed"] == 3
    # registered models recorded on the stats (policy names included)
    assert stats.models == {"busy": "lazyb", "idle": "serial"}


# ---------------------------------------------------------------------------
# Real JAX engines behind a MultiBackend (two models, one device clock)
# ---------------------------------------------------------------------------

def test_jax_two_model_mixture_through_multibackend():
    import dataclasses

    from repro.configs import get_config
    from repro.serving import TPU_V5E
    from repro.serving.engine import JaxEngine
    from repro.serving.workload import LengthDist, from_model_config

    def tiny(arch):
        cfg = get_config(arch).reduced()
        return dataclasses.replace(cfg, d_model=64, d_ff=128, vocab_size=128,
                                   num_prefix_embeddings=0)

    dists = dict(prompt_dist=LengthDist((5, 7), (0.5, 0.5)),
                 decode_dist=LengthDist((2, 3), (0.5, 0.5)))
    cfg_a, cfg_b = tiny("llama3.2-1b"), tiny("mamba2-2.7b")
    wl_a = from_model_config(cfg_a, **dists)
    wl_b = from_model_config(cfg_b, **dists)
    engines = {"llama": JaxEngine(cfg_a, max_len=32, n_slots=8),
               "mamba": JaxEngine(cfg_b, max_len=32, n_slots=8)}
    perf = NPUPerfModel(TPU_V5E)

    def pol(wl):
        return LazyBatching(SlackPredictor.build([wl], perf, 60.0),
                            max_batch=4)

    session = ServingSession(backend=MultiBackend(engines),
                             arbiter=LeastSlackArbiter(sla_default=60.0))
    session.register("llama", wl_a, policy=pol(wl_a))
    session.register("mamba", wl_b, policy=pol(wl_b))
    rng = np.random.default_rng(0)
    handles, t = [], 0.0
    for i in range(4):
        t += rng.exponential(0.01)
        wl, name = ((wl_a, "llama") if i % 2 == 0 else (wl_b, "mamba"))
        handles.append(session.submit(wl.sample_request(rng, t), model=name))
    stats = session.drain()
    assert len(stats.finished) == 4
    for h in handles:
        assert h.done and len(h.tokens) == h.request.decode_len
        # streamed tokens match the owning engine's batch results
        eng = engines[h.model]
        assert h.tokens == eng.states[h.request.rid].generated
    pm = stats.per_model()
    assert pm["llama"]["completed"] == 2 and pm["mamba"]["completed"] == 2
    # both engines' wall-clock accumulated on the one session clock
    assert session.log.busy_by_model["llama"] > 0
    assert session.log.busy_by_model["mamba"] > 0
    assert session.now >= sum(session.log.busy_by_model.values()) - 1e-9
    # slots all released on drain, on both engines
    assert all(e.slots_in_use == 0 for e in engines.values())


# ---------------------------------------------------------------------------
# Retired Executor alias
# ---------------------------------------------------------------------------

def test_executor_alias_warns_and_resolves_to_backend():
    import repro.serving as serving
    import repro.serving.server as server
    from repro.serving.backend import Backend as B
    with pytest.warns(DeprecationWarning, match="Executor is deprecated"):
        assert server.Executor is B
    with pytest.warns(DeprecationWarning):
        assert serving.Executor is B
    with pytest.raises(AttributeError):
        server.NoSuchThing
