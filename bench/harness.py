"""Drives the served path open loop on the wall clock and measures it.

The window drives ``ServingSession.submit`` -> ``ServingSession.step`` ->
``LazyBatching`` (slack predictor on ``NPUPerfModel(TPU_V5E)``) ->
``JaxEngine.execute_run`` -> prefill and decode megasteps, built as the
launcher builds them (``repro.launch.serve``). Requests are submitted at
their due times by ``time.perf_counter`` and the session is stepped in
between, on the gateway's pattern: ``run_until(wall)`` keeps the session
clock level with the wall. Every latency is taken on the host's wall
clock from the request's due time, in the handle's ``on_token`` callback;
nothing is read from the session's own clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Set, Tuple

import numpy as np

from . import traffic as T

DTYPE_NAMES = ("bfloat16", "float32")


def _jnp_dtype(name: str):
    import jax.numpy as jnp
    if name not in DTYPE_NAMES:
        raise ValueError(f"unsupported dtype {name!r}")
    return getattr(jnp, name)


def pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


# ---------------------------------------------------------------------------
# Program construction
# ---------------------------------------------------------------------------

def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file, cut to the
    file's depth. The program's widths must equal the file's: a program
    whose model drifted from the configuration is refused, not run."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    want = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "d_ff": "intermediate_size", "vocab_size": "vocab_size",
            "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
            "tie_embeddings": "tie_word_embeddings"}
    for attr, key in want.items():
        if getattr(cfg, attr) != config[key]:
            raise ValueError(f"program {config['arch']}.{attr}="
                             f"{getattr(cfg, attr)} differs from the "
                             f"configuration's {key}={config[key]}")
    if cfg.attention != config["attention"] or cfg.mla or cfg.moe \
            or cfg.hybrid:
        raise ValueError("program attention kind differs from the config")
    return dataclasses.replace(cfg, num_layers=config["num_hidden_layers"])


def length_dist(spec: dict, n: int = 1000):
    """The program's ``LengthDist`` of a mix's length distribution (what
    the slack predictor profiles from)."""
    from repro.serving.workload import LengthDist
    vals, counts = np.unique(T.length_multiset(spec, n), return_counts=True)
    return LengthDist(tuple(int(v) for v in vals),
                      tuple(float(c) / n for c in counts))


@dataclass
class Program:
    cfg: object
    engine: object
    workload: object
    serving: dict

    def policy(self, sla_s: float):
        """A fresh LazyBatching policy as ``launch/serve.py`` builds it
        with ``--policy lazyb --hw v5e``."""
        from repro.core.policies import LazyBatching
        from repro.core.slack import SlackPredictor
        from repro.serving.npu_model import NPUPerfModel, TPU_V5E
        pred = SlackPredictor.build([self.workload], NPUPerfModel(TPU_V5E),
                                    sla_s)
        return LazyBatching(pred, max_batch=self.serving["max_batch"])

    def session(self, sla_s: float, seed: int):
        from repro.serving.session import ServingSession
        return ServingSession(self.policy(sla_s), self.engine, seed=seed)

    def request(self, arrival: float, prompt_len: int, output_len: int,
                tier: str, deadline_s: float):
        """A request of exact lengths, as ``Workload.sample_request``
        builds one."""
        from repro.core.request import Request, SLAClass
        wl = self.workload
        seq, prefix_len, cycle_len = wl.build_sequence(prompt_len, output_len)
        req = Request(workload=wl, arrival=arrival, sequence=seq,
                      sla=SLAClass(name=f"{tier}.{output_len}",
                                   deadline=deadline_s))
        req.prompt_len, req.decode_len = prompt_len, output_len
        req.prefix_len, req.cycle_len = prefix_len, cycle_len
        return req


def build_program(config: dict, mix: dict, seed: int) -> Program:
    """Engine (weights made on the device from ``seed`` by the engine, in
    the configuration's dtype; the arena fixed at its slot count, so no
    shape changes inside the window) and the workload it serves."""
    from repro.serving.engine import JaxEngine
    from repro.serving.workload import from_model_config
    cfg = program_config(config)
    s = config["serving"]
    engine = JaxEngine(cfg, max_len=s["max_len"], seed=seed,
                       dtype=_jnp_dtype(config["dtype"]),
                       n_slots=s["slots"], max_slots=s["slots"])
    wl = from_model_config(cfg, prompt_dist=length_dist(mix["prompt"]),
                           decode_dist=length_dist(mix["output"]))
    return Program(cfg, engine, wl, s)


# ---------------------------------------------------------------------------
# Host-side timing proxy
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    t0: float
    t1: float
    rows: int                 # live rows of the sub-batch
    cycles: int               # decode cycles (heads) in the run
    prefill_tokens: int       # prompt tokens prefilled in the run
    ctxs: List[int]           # valid context of each row at its 1st cycle


@dataclass
class StepRecord:
    t0: float
    t1: float
    exec_s: float             # execute_run time inside the step


@dataclass
class Timeline:
    runs: List[RunRecord] = field(default_factory=list)
    steps: List[StepRecord] = field(default_factory=list)
    merged: Set[int] = field(default_factory=set)  # id() of requests that
    annotate: bool = False                         # decoded beside others


def _span(name: str, on: bool):
    """A host span in the profiler's trace, in traced runs only."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def instrument(session, engine, tl: Timeline):
    """Time every ``execute_run`` and every ``session.step`` (instance
    attributes over the bound methods; the program is not changed)."""
    run_fn, step_fn = engine.execute_run, session.step
    inside = [0.0]

    def execute_run(model, sb, node_ids):
        reqs = sb.live_requests
        nodes = reqs[0].workload.nodes
        cycles = sum(1 for n in node_ids if nodes[n].phase == "head")
        prefill = sum(r.prompt_len - 1 for r in reqs if r.idx == 0) \
            if any(nodes[n].phase == "emb" for n in node_ids) else 0
        ctxs = []
        if cycles and len(reqs) > 1:
            tl.merged.update(id(r) for r in reqs)
        if cycles:
            for r in reqs:
                done = max(0, (r.idx - r.prefix_len)) // max(1, r.cycle_len)
                ctxs.append(r.prompt_len + done)
        with _span("bench.execute_run", tl.annotate):
            t0 = time.perf_counter()
            out = run_fn(model, sb, node_ids)
            t1 = time.perf_counter()
        inside[0] += t1 - t0
        tl.runs.append(RunRecord(t0, t1, len(reqs), cycles, prefill, ctxs))
        return out

    def step(*a, **kw):
        inside[0] = 0.0
        with _span("bench.step", tl.annotate):
            t0 = time.perf_counter()
            out = step_fn(*a, **kw)
            t1 = time.perf_counter()
        tl.steps.append(StepRecord(t0, t1, inside[0]))
        return out

    engine.execute_run = execute_run
    session.step = step
    return lambda: (delattr(engine, "execute_run"),
                    delattr(session, "step"))


# ---------------------------------------------------------------------------
# Warm-up
# ---------------------------------------------------------------------------

def warm_shapes(mix: dict, serving: dict):
    """(batch bucket, prompt length) pairs that reach every prefill bucket
    x batch bucket, and every decode batch bucket x context bucket, that
    the mix's lengths can reach. A prompt of exactly ``s`` tokens prefills
    ``s - 1`` (bucket ``s``), then decodes at context buckets ``s`` and
    ``2s`` (both capped at ``max_len``; ``warm_up`` shortens a prompt of
    ``max_len`` tokens to leave room for its two output tokens)."""
    lo = pow2(mix["prompt"]["min"] - 1)
    hi = min(pow2(mix["prompt"]["max"] - 1), serving["max_len"])
    buckets = []
    s = lo
    while s <= hi:
        buckets.append(s)
        s *= 2
    batches = []
    b = 1
    while b <= serving["max_batch"]:
        batches.append(b)
        b *= 2
    return [(b, s) for b in batches for s in buckets]


def warm_up(prog: Program, mix: dict, seed: int) -> int:
    """Serve each warm-up group once through a session of its own, so
    that every program the window can run is compiled (or loaded from the
    persistent cache) before it opens. Returns the programs traced."""
    vocab = prog.cfg.vocab_size
    before = prog.engine.sanitizer_stats().retraces
    for i, (b, s) in enumerate(warm_shapes(mix, prog.serving)):
        session = prog.session(1e6, seed)
        hs = []
        n = min(s, prog.serving["max_len"] - 2)
        for j in range(b):
            req = prog.request(session.now, n, 2, "warm", 1e6)
            toks = T.prompt_tokens(seed, 10_000_000 + i * 64 + j, n, vocab)
            hs.append(session.submit(req, prompt_tokens=toks))
        session.drain()
        for h in hs:
            session.release(h)
    return prog.engine.sanitizer_stats().retraces - before


# ---------------------------------------------------------------------------
# The open loop
# ---------------------------------------------------------------------------

# a traced run starts the profiler this long before the window opens
TRACE_LEAD_S = 3.0

@dataclass
class ReqRecord:
    arrival: T.Arrival
    submitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    request: object = None
    handle: object = None


@dataclass
class WindowResult:
    t0: float                       # perf_counter at schedule start
    open: float                     # window bounds, perf_counter
    close: float
    end: float                      # when the loop stopped following
    reqs: List[ReqRecord]
    timeline: Timeline
    retraces: int
    lateness: List[float]
    trace_bounds: Optional[tuple] = None


def serve_window(prog: Program, sched: List[T.Arrival], mix: dict,
                 window_s: float, seed: int,
                 tracer: Optional[Callable] = None,
                 finish: bool = False) -> WindowResult:
    """Offer ``sched`` open loop and follow every request due in the
    window until it finishes or the drain limit passes. ``tracer(event)``
    is told ``lead`` (a few seconds before the window opens, so starting
    the profiler stalls only the lead-in), ``open`` and ``close``.
    ``finish`` serves what is still in flight to the end afterwards (so
    the engine can take another window)."""
    from repro.serving.session import HandleState
    vocab = prog.cfg.vocab_size
    sla = max(a.deadline for a in sched) if sched else 1.0
    session = prog.session(sla, seed)
    tl = Timeline(annotate=tracer is not None)
    undo = instrument(session, prog.engine, tl)
    recs = [ReqRecord(a) for a in sched]
    lateness = []
    lead = mix["lead_in_s"]
    t0 = time.perf_counter()
    w_open, w_close = t0 + lead, t0 + lead + window_s
    hard_end = w_close + mix["drain_s"]
    in_window = [r for r in recs if r.arrival.in_window]
    events = [("lead", w_open - TRACE_LEAD_S), ("open", w_open)]
    retr_open = None
    nxt = 0
    terminal = {HandleState(s) for s in ("done", "rejected", "cancelled",
                                         "expired", "failed", "shed")}

    def on_token(rec):
        def cb(handle, tok):
            now = time.perf_counter()
            rec.token_times.append(now)
            if rec.first is None:
                rec.first = now
            rec.last = now
        return cb

    while True:
        now = time.perf_counter()
        while events and now >= events[0][1]:
            name = events.pop(0)[0]
            if name == "open":
                retr_open = prog.engine.sanitizer_stats().retraces
            if tracer:
                tracer(name)
            now = time.perf_counter()
        with _span("bench.submit", tl.annotate):
            while nxt < len(recs) and t0 + recs[nxt].arrival.due <= now:
                rec = recs[nxt]
                a = rec.arrival
                req = prog.request(a.due, a.prompt_len, a.output_len,
                                   a.tier, a.deadline)
                toks = T.prompt_tokens(seed, nxt, a.prompt_len, vocab)
                rec.submitted = time.perf_counter()
                lateness.append(rec.submitted - (t0 + a.due))
                rec.request = req
                rec.handle = session.submit(req, prompt_tokens=toks,
                                            on_token=on_token(rec))
                nxt += 1
        if now >= w_close:
            open_ = [r for r in in_window if r.handle is None
                     or r.handle.state not in terminal]
            if not open_ or now >= hard_end:
                break
        if session.outstanding:
            session.run_until(now - t0)
        else:
            due = t0 + recs[nxt].arrival.due if nxt < len(recs) else hard_end
            if events:
                due = min(due, events[0][1])
            with _span("bench.wait", tl.annotate):
                time.sleep(max(0.0, min(due - now, 0.005)))
    end = time.perf_counter()
    if tracer:
        tracer("close")
    retraces = prog.engine.sanitizer_stats().retraces - (retr_open or 0)
    undo()
    if finish:
        session.drain()
    return WindowResult(t0, w_open, w_close, end, recs, tl, retraces,
                        lateness)


# ---------------------------------------------------------------------------
# Reductions of a window (what the metric readers read)
# ---------------------------------------------------------------------------

def window_requests(res: WindowResult) -> List[ReqRecord]:
    return [r for r in res.reqs if r.arrival.in_window]


def finished(res: WindowResult, seed: int,
             vocab: int) -> List[Tuple[np.ndarray, List[int], bool]]:
    """(prompt tokens, served tokens, decoded beside other requests) of
    every request due in the window that finished."""
    from repro.serving.session import HandleState
    return [(T.prompt_tokens(seed, i, r.arrival.prompt_len, vocab),
             list(r.handle.tokens), id(r.request) in res.timeline.merged)
            for i, r in enumerate(res.reqs)
            if r.arrival.in_window and r.handle is not None
            and r.handle.state is HandleState.DONE]


def ttft_values(res: WindowResult) -> List[float]:
    """Due time -> first token, seconds, of every request due in the
    window. One that got no token by the end is censored at the end."""
    return [((r.first if r.first is not None else res.end)
             - (res.t0 + r.arrival.due)) for r in window_requests(res)]


def tpot_values(res: WindowResult) -> List[float]:
    out = []
    for r in window_requests(res):
        n = len(r.token_times)
        if n >= 2:
            out.append((r.last - r.first) / (n - 1))
    return out


def met_deadline(res: WindowResult, r: ReqRecord) -> bool:
    from repro.serving.session import HandleState
    return (r.handle is not None and r.handle.state is HandleState.DONE
            and r.last - (res.t0 + r.arrival.due) <= r.arrival.deadline)


def percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def runs_between(tl: Timeline, a: float, b: float) -> List[RunRecord]:
    return [r for r in tl.runs if a <= r.t0 and r.t1 <= b]
