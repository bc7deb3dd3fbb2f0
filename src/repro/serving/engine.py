"""Real-JAX node-level serving engine: slot-arena caches + fused node runs.

The discrete-event simulator (``server.py``) models latency analytically;
this engine executes the SAME policies against the ACTUAL model. Scheduling
stays node-granular — every ``(sub_batch, node_id)`` the scheduler emits is
a valid dispatch — but execution is *run*-granular: when a policy commits a
run of consecutive nodes (the run-commit contract, ``core.policies``), the
engine fuses the whole run into a handful of jitted dispatches instead of
one Python→device round-trip per layer. Decide per node, execute per run.

Node ids come from ``workload.from_model_config`` (each ``NodeDesc``
carries ``phase``/``layer`` metadata the dispatcher keys on):

  * ``emb``   — embed the prompt,
  * ``P<i>``  — prefill layer i over the prompt (writes the KV cache
               directly into the request's arena slot),
  * ``D<i>``  — decode layer i for ONE token, *batched with ragged per-row
               positions* across the merged sub-batch,
  * ``head``  — unembed + greedy-sample the next token.

Cache arena (PR 1; now paged + reclaimable)
-------------------------------------------
Per-request caches live in a preallocated, device-resident slot arena;
requests own a lazily-assigned slot for their lifetime, prefill writes
into the slot in-jit, decode gathers/scatters rows by a ``(B,)`` slot
vector, and slots are released on completion (idempotently again via
``Backend.on_finished``). The arena is *paged*: it doubles on demand up
to an optional ``max_slots`` memory cap and — unless pinned —
**shrinks back** when occupancy drops (live slots are compacted below
the watermark, the slot axis sliced down; bit-exact, see
``_shrink_arena``), so a burst no longer pins peak device memory
forever. ``memory_stats()`` reports slots live/free and actual resident
bytes for SLA-aware, memory-aware admission upstream. Storage is now **per-span, flat-indexed**:
consecutive same-(kind, window) layers form a span whose arena pytree
folds the layer axis into the slot axis — leaves are
``(span_len * n_slots, max_len, ...)`` for time-axis keys (k/v/ckv/krope)
and ``(span_len * n_slots, ...)`` for recurrent state, with layer k's
batch rows at ``slots + k * n_slots``. A whole span is then one
``lax.scan`` over stacked params with the arena riding the carry (aliased
in place by XLA): each layer step gathers/scatters ONLY its B live rows —
scanning the arena as scan inputs/outputs instead would materialize two
full per-layer cache copies per step. Homogeneous models are a single
span; hybrid models get maximal same-kind spans (their span param stacks
duplicate block params once at init — the price of scanned dispatch over
a heterogeneous stack).

Fused node-run execution (this PR's hot path)
---------------------------------------------
``execute_run(sb, node_ids)`` parses a committed run into phase chunks and
dispatches each chunk as ONE jitted call:

  * **decode megasteps** — a chunk ``D_i..D_j[+head]`` runs as a single
    jitted ``lax.scan`` over the stacked span params + span arenas (the
    whole arena list is passed and donated as one pytree), with the head
    (final norm + unembed + argmax) folded into the same dispatch. A
    multi-cycle run loops cycle megasteps *without host sync*: each
    cycle's sampled tokens stay on device and feed the next cycle's
    embedding directly.
  * **bucketed batched prefill** — ``emb + P0..Pk`` prefills all members
    of a sub-batch together: prompts are right-padded to power-of-two
    length buckets (capped at ``max_len``) and same-bucket requests are
    batched; causal attention masks the padding (a valid row only ever
    attends to valid rows), so cache rows are bit-identical to isolated
    prefill, and rows past a request's true length are overwritten by
    decode before they can be read. Enabled for attention-family stacks
    (dense/MLA); MoE/SSM/recurrent stacks prefill per-request but still
    fused across layers in one scanned dispatch.
  * **batch-size bucketing** — decode batches are padded to the next
    power of two so recompiles are bounded by O(log max_batch) instead of
    one per distinct membership size. Padded rows carry an out-of-bounds
    slot sentinel: their arena scatters are dropped (mode="drop"), their
    gathers are clamped, and their outputs discarded on host.
  * **async dispatch** — no per-node ``block_until_ready``; dispatches
    inside a run chain on device and the engine synchronizes ONCE at the
    run boundary (the scheduler-visible point), so the server clock
    measures run latency, not per-node latency.
  * **one-cycle decode lookahead** — when a run is exactly one full
    decode cycle with its head and the batch will run another, the next
    cycle is dispatched before the run's sync, from the device-carried
    tokens at ``pos + 1`` with the same context-bucket rule (no new
    program). The sync then reads only this cycle's tokens (the program
    that sampled them wrote this cycle's arena rows, so the readback
    fences them), and the device runs the next cycle while the host does
    the epilogue, the session streams tokens and the scheduler decides.
    The next run takes that result when it is the same cycle for the
    same batch (same rids, ``Bp`` and positions, no arena change
    between); anything else discards it and runs as without lookahead.
    A run that takes the queued cycle reports its latency from the end of
    the last run on the device (any engine's), where the device started
    on it, and not from its own entry: the host's work between the runs
    (epilogue, token streaming, scheduling) then counts in that run's
    latency and in ``ServerLog.busy_time``, since the device was busy
    through it, and the session's clock keeps level with the wall.
    A discarded cycle only wrote the KV row at ``pos + 1`` of its own
    rows, which any real run at that position rewrites before reading
    it, so lookahead is limited to stacks whose every arena leaf is a
    position-indexed cache row (no recurrent state) and to runs where
    no row finishes.

``execute(sb, node_id)`` (single-node dispatch, one blocking device call
per node) remains fully supported — it is the degenerate run and the
bit-exactness reference. ``cache_mode="legacy"`` keeps the seed
stack/unstack path for parity tests; generated tokens are bit-exact
across legacy / arena / fused-run for the same trace (enforced by
``tests/test_engine_arena.py`` and ``benchmarks/engine_decode_bench.py``).

Token semantics are exact: the prompt's last token is fed as the first
decode-cycle input (prefill covers ``prompt[:-1]``), so every token is
processed exactly once.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.request import Request, SubBatch
from ..models import layers as L
from ..models.cost import _layer_kinds
from ..models.model import Model, RuntimeFlags, _index, _stack
from . import tracing
from .backend import Backend, BackendOOMError

# cache leaves whose leading (post-batch) axis is the KV time axis
_TIME_AXIS_KEYS = ("k", "v", "ckv", "krope")

# slot sentinel for batch-bucket padding rows: far out of bounds for any
# arena size, so scatters drop and clamped gathers read an arbitrary live
# row (output discarded). Must never be reachable by arena growth.
_PAD_SLOT = np.int32(2 ** 30)


def _is_time_leaf(path) -> bool:
    return str(getattr(path[-1], "key", "")) in _TIME_AXIS_KEYS


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class EngineState:
    """Mutable per-request execution state."""

    def __init__(self, prompt_tokens: np.ndarray):
        if len(prompt_tokens) < 2:
            raise ValueError(
                f"engine needs prompts of >= 2 tokens (teacher-forced "
                f"prefill predicts token i+1 from token i), got "
                f"{len(prompt_tokens)}")
        self.prompt = jnp.asarray(prompt_tokens, jnp.int32)
        self.prompt_np = np.asarray(prompt_tokens, np.int32)
        self.prefill_len = int(len(prompt_tokens) - 1)
        self.x: Optional[jax.Array] = None       # activations in flight
        self.caches: Dict[int, object] = {}      # legacy mode: layer -> cache
        self.generated: List[int] = []
        self.next_token: int = int(prompt_tokens[-1])
        self.pos: int = self.prefill_len         # next KV slot to write


class JaxEngine(Backend):
    """Executes workload nodes on a real (reduced) model.

    One engine holds ONE model's parameters and KV arena, so the
    ``model`` key threaded through the Backend contract is accepted and
    ignored — multi-tenant sessions put one engine per registered model
    behind a :class:`~repro.serving.backend.MultiBackend`, which routes
    on the key before it gets here.

    ``cache_mode``: "arena" (default) uses the persistent slot arena;
    "legacy" keeps per-request caches and restacks them per dispatch (the
    seed behavior — kept for parity tests and the decode benchmark).
    ``fused``: fuse committed multi-node runs into scanned megastep
    dispatches (defaults to on for arena mode; ``False`` forces one
    dispatch per node even under the run-commit server loop — the PR-1
    arena baseline).
    ``pallas``: route batched ragged decode attention through the Pallas
    kernel where the config allows (dense attention, no sliding window).
    Defaults to on for accelerator backends, off for CPU (interpret mode
    is functional but slow).
    """

    # end of the last run any engine of this process executed: they share
    # its device, and a queued decode cycle runs there from that point
    _last_run_end = 0.0

    def __init__(self, cfg: ModelConfig, *, max_len: int = 512, seed: int = 0,
                 dtype=jnp.float32, n_slots: Optional[int] = None,
                 max_slots: Optional[int] = None,
                 min_slots: Optional[int] = None,
                 auto_shrink: Optional[bool] = None,
                 cache_mode: str = "arena", pallas: Optional[bool] = None,
                 fused: Optional[bool] = None):
        if cache_mode not in ("arena", "legacy"):
            raise ValueError(f"cache_mode must be 'arena' or 'legacy', "
                             f"got {cache_mode!r}")
        # arena sizing: explicit n_slots WITHOUT max_slots pins the arena
        # (exhaustion raises — the seed behavior); otherwise the arena is
        # *paged*: it starts at n_slots (or min_slots, default 32), doubles
        # on demand up to max_slots (None = unbounded), and — when
        # auto_shrink is on (the paged default) — compacts+halves back
        # toward min_slots as occupancy drops, so one burst no longer pins
        # peak device memory forever.
        pinned = n_slots is not None and max_slots is None
        if n_slots is None:
            n_slots = min_slots if min_slots is not None else 32
            if max_slots is not None:        # default start clamps to the cap
                n_slots = min(n_slots, max_slots)
        if max_slots is not None and max_slots < n_slots:
            raise ValueError(
                f"max_slots ({max_slots}) must be >= the starting arena "
                f"size n_slots ({n_slots})")
        self.max_slots = max_slots
        self._min_slots = min_slots if min_slots is not None else n_slots
        self._auto_grow = not pinned
        self._auto_shrink = (not pinned) if auto_shrink is None else auto_shrink
        self.n_grows = 0
        self.n_shrinks = 0
        if pallas is None:
            # legacy mode is the seed-numerics baseline: never reroute its
            # decode through the Pallas kernel implicitly
            pallas = (cache_mode == "arena"
                      and jax.default_backend() != "cpu")
        self.cfg = cfg
        self.model = Model(cfg, RuntimeFlags(dtype=dtype,
                                             pallas_decode=pallas))
        self.params = self.model.init(jax.random.key(seed))
        self.kinds = _layer_kinds(cfg)
        self.max_len = max_len
        self.cache_mode = cache_mode
        self.fused = (cache_mode == "arena") if fused is None else fused
        self.states: Dict[int, EngineState] = {}
        self.nodes_executed = 0
        self.runs_executed = 0
        self._jit_cache: Dict[tuple, object] = {}
        # hot-path sanitizer counters (Backend.sanitizer_stats): retraces
        # are counted by a Python-side effect at the top of every jitted
        # body (it only executes while JAX traces — i.e. per XLA compile);
        # host syncs count run-boundary synchronization EVENTS (the whole
        # fused-run epilogue is one event)
        self._san_retraces = 0
        self._san_host_syncs = 0
        self._san_max_syncs_per_run = 0
        # batched decode activations keyed by sub-batch membership: while a
        # merged batch advances in lockstep its (B, d) activation tensor is
        # reused across D-nodes / head without per-node stack + unstack;
        # rows are flushed back to per-request state when membership changes
        self._xbatch: Optional[tuple] = None     # (rids tuple, (B, d) array)
        # (B,) slot-index device vector, also keyed by membership: slots are
        # pinned for a request's lifetime, so the vector is invariant until
        # the sub-batch composition changes ((rids, padded_B, array))
        self._slotbatch: Optional[tuple] = None
        # device-resident (Bp,) position / last-token vectors carried across
        # fused runs (keyed by (rids, Bp)): while membership is stable a new
        # run needs NO host->device upload — pos advances by a lazy device
        # add, tokens chain from the previous head. Host state (st.pos /
        # st.next_token) stays authoritative; any membership change or
        # single-node dispatch invalidates and rebuilds from it.
        self._posbatch: Optional[tuple] = None
        self._tokbatch: Optional[tuple] = None
        self._chunk_cache: Dict[tuple, list] = {}
        self.n_slots = n_slots
        self._free_slots: deque = deque(range(n_slots))
        self._slot: Dict[int, int] = {}          # rid -> slot
        # maximal same-(kind, window) layer spans; arenas + param stacks
        # are stored per span so a span is one lax.scan
        spans: List[tuple] = []
        for i in range(len(self.kinds)):
            kind, window = self._kind_window(i)
            if spans and spans[-1][0] == kind and spans[-1][1] == window:
                spans[-1] = (kind, window, spans[-1][2], i)
            else:
                spans.append((kind, window, i, i))
        self._spans = spans
        self._layer_loc = {}
        for si, (_, _, lo, hi) in enumerate(spans):
            for i in range(lo, hi + 1):
                self._layer_loc[i] = (si, i - lo)
        if cfg.hybrid is None:
            # homogeneous stack: params are already stacked (L, ...)
            self._span_params = [self.params["blocks"]]
        else:
            self._span_params = [
                _stack([self._layer_params(i) for i in range(lo, hi + 1)])
                for (_, _, lo, hi) in spans
            ]
        # span arenas in FLAT layout: the layer axis is folded into the slot
        # axis — leaves are (span_len * n_slots, ...) and layer k of a span
        # owns rows [k * n_slots, (k+1) * n_slots). Fused span scans thread
        # the arena through the scan carry (aliased in place) and address
        # layer k's batch rows as ``slots + k * n_slots`` — only the B live
        # rows are ever gathered/scattered, never a full layer slice.
        self._offs_cache: tuple = (None, None)    # n_slots -> per-span offs
        if cache_mode == "arena":
            self.arenas: List[object] = []
            for (kind, window, lo, hi) in spans:
                one = self.model._init_layer_cache(self.kinds[lo], n_slots,
                                                   max_len, window=None)
                span_len = hi - lo + 1
                self.arenas.append(jax.tree.map(
                    lambda l: jnp.zeros((span_len * l.shape[0],)
                                        + l.shape[1:], l.dtype), one))
        else:
            self.arenas = []
        # one-cycle decode lookahead (execute_run): the next cycle of a
        # steady batch, dispatched before the run's sync, as
        # (((rids, Bp), host positions it runs at), device tokens) or
        # None; only stacks whose arena leaves are all position-indexed
        # cache rows can run a cycle twice harmlessly
        self._ahead: Optional[tuple] = None
        self._lookahead = bool(self.arenas) and self.fused and all(
            _is_time_leaf(path) for span in self.arenas
            for path, _ in jax.tree_util.tree_leaves_with_path(span))
        self._full_cycle = ("decode", 0, len(self.kinds) - 1, True)
        self._san_lookahead_dispatched = 0
        self._san_lookahead_hits = 0

    # ------------------------------------------------------------------
    # Request registration / slot lifecycle
    # ------------------------------------------------------------------
    def register(self, req: Request, prompt_tokens: np.ndarray):
        self.states[req.rid] = EngineState(prompt_tokens)

    def prepare(self, model, req: Request, rng, prompt_tokens=None):
        """Backend-contract hook (ServingSession.submit): register the
        request's prompt — the supplied tokens, or a synthetic prompt of
        ``req.prompt_len`` sampled from ``rng`` (the session's seeded
        generator) when none is given. Idempotent for pre-registered
        requests (explicit ``register`` calls keep working)."""
        if req.rid in self.states:
            return
        if prompt_tokens is None:
            prompt_tokens = rng.integers(2, self.cfg.vocab_size,
                                         size=max(2, req.prompt_len))
        self.register(req, np.asarray(prompt_tokens))

    def token_count(self, model, req: Request) -> int:
        st = self.states.get(req.rid)
        return (len(st.generated) if st is not None
                else super().token_count(model, req))

    def tokens(self, model, req: Request):
        st = self.states.get(req.rid)
        return st.generated if st is not None else None

    def state(self, req: Request) -> EngineState:
        return self.states[req.rid]

    def slot_of(self, req: Request) -> int:
        """Arena slot owned by ``req`` (lazily assigned at first use)."""
        slot = self._slot.get(req.rid)
        if slot is None:
            if not self._free_slots:
                if not self._auto_grow:
                    # BackendOOMError subclasses RuntimeError: legacy
                    # catches keep working, fault-aware sessions can
                    # retry/fail the victims instead of crashing the loop
                    raise BackendOOMError(
                        f"cache arena exhausted: {self.n_slots} slots all "
                        f"held by live requests — raise "
                        f"JaxEngine(n_slots=...) above the policy's max "
                        f"concurrent batch size")
                self._grow_arena()
            slot = self._free_slots.popleft()
            self._slot[req.rid] = slot
        return slot

    def _grow_arena(self):
        """Widen the arena's slot capacity (rare; amortized O(1) per
        request — existing rows keep their slot ids, new rows are zero).
        Flat layout: unfold the layer axis, widen the slot axis, refold.
        Doubles, capped at ``max_slots``; at the cap, growth raises the
        same arena-exhausted error a pinned arena does (memory-aware
        admission is what keeps live requests under the cap)."""
        old = self.n_slots
        new = 2 * old if self.max_slots is None else min(2 * old,
                                                         self.max_slots)
        if new <= old:
            raise BackendOOMError(
                f"cache arena exhausted at its memory cap: all "
                f"{self.n_slots} slots (max_slots={self.max_slots}) held "
                f"by live requests — raise JaxEngine(max_slots=...) or "
                f"enable memory-aware admission so the scheduler defers "
                f"work instead of overcommitting device memory")
        # padded-row scatters use the _PAD_SLOT sentinel: growth must never
        # bring a real row index into the sentinel's range, or a padding
        # row's dropped scatter would silently alias a live slot
        if new >= _PAD_SLOT:
            raise RuntimeError(
                f"arena growth to {new} slots would reach the padded-row "
                f"sentinel (_PAD_SLOT={int(_PAD_SLOT)})")

        def grow(l):
            span_len = l.shape[0] // old
            r = l.reshape(span_len, old, *l.shape[1:])
            z = jnp.zeros((span_len, new - old) + l.shape[1:], l.dtype)
            return jnp.concatenate([r, z], axis=1).reshape(
                span_len * new, *l.shape[1:])

        self.arenas = [jax.tree.map(grow, span) for span in self.arenas]
        self._ahead = None
        self.n_slots = new
        self.n_grows += 1
        self._free_slots.extend(range(old, self.n_slots))

    def _maybe_shrink(self):
        """Reclaim arena memory when occupancy has dropped: compact live
        slots below the target watermark and slice the arena down to it.

        Fires only when capacity exceeds TWICE the target — the target
        itself keeps a doubling of headroom above the live set
        (``pow2(2 * live)``, floored at ``min_slots``) — so a stable
        working set never thrashes grow/shrink, while a drained burst
        returns capacity (and ``memory_stats().bytes_resident``) to within
        2x of steady-state occupancy."""
        if (not self._auto_shrink or self.cache_mode != "arena"
                or not self.arenas):
            return
        live = len(self._slot)
        target = max(_pow2(2 * live) if live else 1, self._min_slots)
        if target * 2 <= self.n_slots:
            self._shrink_arena(target)

    def _shrink_arena(self, target: int):
        """Compact live slots below ``target`` (relocating their rows in
        every span arena) and halve+ the arena down to ``target`` slots.

        Bit-exact by construction: relocation copies rows verbatim, the
        flat layout (layer k at ``slot + k * n_slots``) is re-folded at
        the new width, and every membership-keyed device cache holding
        slot ids is invalidated. Eager (unjitted) dispatch — reclamation
        is rare and off the decode hot path; the next fused dispatch
        retraces once for the new arena shape, exactly as growth does."""
        old = self.n_slots
        if not (target < old and len(self._slot) <= target):
            raise RuntimeError(
                f"_shrink_arena precondition violated: target={target} "
                f"must be < current {old} slots and hold all "
                f"{len(self._slot)} live slots")
        # host-side relocation plan: live slots >= target move into the
        # lowest free slots < target (enough exist: live <= target)
        moving = sorted(s for s in self._slot.values() if s >= target)
        free_low = sorted(s for s in self._free_slots if s < target)
        dst_of = dict(zip(moving, free_low))
        for rid, s in self._slot.items():
            if s in dst_of:
                self._slot[rid] = dst_of[s]
        src_np = np.fromiter(dst_of.keys(), np.int32, len(dst_of))
        dst_np = np.fromiter(dst_of.values(), np.int32, len(dst_of))
        for si, (_, _, lo, hi) in enumerate(self._spans):
            span_len = hi - lo + 1
            offs = np.arange(span_len, dtype=np.int32) * old
            src = (src_np[None, :] + offs[:, None]).ravel()
            dst = (dst_np[None, :] + offs[:, None]).ravel()

            def compact(l):
                if len(src):
                    l = l.at[dst].set(l[src])
                r = l.reshape(span_len, old, *l.shape[1:])
                return r[:, :target].reshape(span_len * target, *l.shape[1:])

            self.arenas[si] = jax.tree.map(compact, self.arenas[si])
        self.n_slots = target
        self.n_shrinks += 1
        self._ahead = None
        used = set(self._slot.values())
        self._free_slots = deque(s for s in range(target) if s not in used)
        # slot ids moved: the membership-keyed slot vector is stale (pos /
        # token vectors carry no slot ids and stay valid)
        self._slotbatch = None

    def _offs(self):
        """Per-span device vectors of layer row offsets (k * n_slots) in
        the flat arena layout; rebuilt only when the arena grows."""
        if self._offs_cache[0] != self.n_slots:
            self._offs_cache = (self.n_slots, [
                jnp.asarray(np.arange(hi - lo + 1, dtype=np.int32)
                            * self.n_slots)
                for (_, _, lo, hi) in self._spans
            ])
        return self._offs_cache[1]

    def release_slot(self, req: Request):
        """Return ``req``'s slot to the free pool (idempotent); reclaims
        arena capacity when occupancy has dropped far enough."""
        self._release_slots([req])

    def _release_slots(self, reqs: Sequence[Request]):
        """Release a whole batch of slots, then reclaim ONCE — a draining
        batch must not cascade through intermediate shrink sizes (each a
        full-arena copy that the next release would discard)."""
        released = False
        for r in reqs:
            slot = self._slot.pop(r.rid, None)
            if slot is not None:
                self._free_slots.append(slot)
                released = True
        if released:
            self._maybe_shrink()

    @property
    def slots_in_use(self) -> int:
        return len(self._slot)

    def memory_stats(self, model=None):
        """Arena accounting: slots live/free at current capacity plus the
        actual device-resident bytes (every span arena leaf). One engine
        is one pool — multi-tenant sessions see per-model pools through
        the :class:`~repro.serving.backend.MultiBackend` mux."""
        from .backend import MemoryStats
        total_bytes = sum(l.nbytes for span in self.arenas
                          for l in jax.tree.leaves(span))
        return MemoryStats(
            slots_total=self.n_slots,
            slots_live=len(self._slot),
            slots_free=len(self._free_slots),
            bytes_resident=int(total_bytes),
            bytes_per_slot=total_bytes / max(1, self.n_slots),
            max_slots=self.max_slots,
            pool=id(self))

    def sanitizer_stats(self, model=None):
        """Hot-path sanitizer snapshot: committed runs, run-boundary host
        sync events, and actual jit traces (= XLA compiles). Steady-state
        fused decode must show ``host_syncs`` growing at most one per run
        and ``retraces`` not growing at all — the dynamic counterpart of
        the ``sync-point`` / ``retrace-hazard`` static checkers."""
        from .backend import SanitizerStats
        return SanitizerStats(
            runs=self.runs_executed,
            host_syncs=self._san_host_syncs,
            retraces=self._san_retraces,
            max_syncs_per_run=self._san_max_syncs_per_run,
            lookahead_dispatched=self._san_lookahead_dispatched,
            lookahead_hits=self._san_lookahead_hits)

    def _note_trace(self):
        """Called from INSIDE jitted bodies: executes only at trace time,
        so each call is exactly one retrace/compile (and, when tracing,
        where the dispatch that compiles gets its compile span)."""
        self._san_retraces += 1
        if tracing.ON:
            tracing.compiling()

    def on_finished(self, model, reqs: Sequence[Request]) -> None:
        self._release_slots(reqs)

    def reset_request(self, model, req: Request) -> None:
        """Fault recovery: discard the request's device-side progress.

        The membership-keyed device caches are invalidated FIRST and
        without flushing — the in-flight activations/positions/tokens
        belong to the faulted (void) run, and an identical-rids batch
        re-forming after the retry must never read them back. Then the
        KV slot returns to the free pool (idempotent; survivors'
        slots are untouched) and the host-side EngineState rewinds to
        its post-``prepare`` point: prompt intact, caches/activations/
        generated tokens gone, so the retry replays prefill from node 0
        and regenerates the same tokens bit-exactly."""
        rid = req.rid
        if self._xbatch is not None and rid in self._xbatch[0]:
            self._xbatch = None
        if self._slotbatch is not None and rid in self._slotbatch[0]:
            self._slotbatch = None
        if self._posbatch is not None and rid in self._posbatch[0][0]:
            self._posbatch = None
        if self._tokbatch is not None and rid in self._tokbatch[0][0]:
            self._tokbatch = None
        self._release_slots([req])
        st = self.states.get(rid)
        if st is not None:
            st.x = None
            st.caches = {}
            st.generated = []
            st.next_token = int(st.prompt_np[-1])
            st.pos = st.prefill_len

    def release_request(self, model, req: Request) -> None:
        """Drop the request's host-side EngineState (prompt, generated
        tokens, activations) once the caller is done with its results —
        wired through ``ServingSession.release`` so long-lived online
        sessions don't accumulate per-request state forever."""
        self.release_slot(req)
        self.states.pop(req.rid, None)

    # ------------------------------------------------------------------
    # Batched-activation cache (arena mode)
    # ------------------------------------------------------------------
    def _flush_xbatch(self):
        if self._xbatch is not None:
            self._ahead = None
            rids, x = self._xbatch
            for bi, rid in enumerate(rids):
                st = self.states.get(rid)
                if st is not None:
                    st.x = x[bi]
            self._xbatch = None

    def _batched_x(self, reqs, sts, fresh=None):
        """(rids, (B, d) activations) for the current membership; ``fresh``
        (decode-cycle entry embeddings) bypasses both cache and stack."""
        rids = tuple(r.rid for r in reqs)
        if self._xbatch is not None and self._xbatch[0] != rids:
            self._flush_xbatch()                  # preserve ex-members' rows
        if fresh is not None:
            x = fresh
        elif self._xbatch is not None:
            x = self._xbatch[1]
        else:
            x = jnp.stack([st.x for st in sts])
        return rids, x

    def _batched_slots(self, reqs, rids, padded_to: Optional[int] = None):
        """(B,)-or-(Bp,) slot vector for the membership; padding rows get
        the out-of-bounds sentinel (scatters dropped, gathers clamped)."""
        Bp = padded_to or len(reqs)
        if self._slotbatch is None or self._slotbatch[0] != rids \
                or self._slotbatch[1] != Bp:
            slots = [self.slot_of(r) for r in reqs]
            slots += [_PAD_SLOT] * (Bp - len(slots))
            self._slotbatch = (rids, Bp, jnp.asarray(slots, jnp.int32))
        return self._slotbatch[2]

    # ------------------------------------------------------------------
    def _layer_params(self, i: int):
        cfg = self.cfg
        if cfg.hybrid is not None:
            pat = cfg.hybrid.block_pattern
            g, j = divmod(i, len(pat))
            if g < self.model.n_groups:
                return _index(self.params["blocks"], g)[f"b{j}_{pat[j]}"]
            return _index(self.params["tail"], i - self.model.n_groups * len(pat))
        return _index(self.params["blocks"], i)

    def _kind_window(self, i: int):
        cfg = self.cfg
        kind = self.kinds[i]
        if cfg.hybrid is not None:
            if kind == "attn":
                return "dense", cfg.hybrid.local_window
            return kind, None
        return ("dense" if kind == "attn" else kind), None

    def _node_meta(self, wl, node_id: str):
        """(phase, layer) for a node: NodeDesc metadata when present,
        engine node-id convention as fallback."""
        nd = wl.nodes.get(node_id) if wl is not None else None
        if nd is not None and getattr(nd, "phase", ""):
            return nd.phase, nd.layer
        if node_id == "emb":
            return "emb", -1
        if node_id == "head":
            return "head", -1
        if node_id[:1] in ("P", "D") and node_id[1:].isdigit():
            return ("prefill" if node_id[0] == "P" else "decode",
                    int(node_id[1:]))
        raise KeyError(f"unknown node {node_id!r}")

    # ------------------------------------------------------------------
    # Jitted node functions (single-node dispatch)
    # ------------------------------------------------------------------
    def _fn_prefill(self, i: int):
        key = ("prefill", i)
        if key not in self._jit_cache:
            kind, window = self._kind_window(i)

            def prefill(bp, x):
                self._note_trace()
                positions = jnp.arange(x.shape[1])[None, :]
                x, cache = self.model.apply_block_dense(
                    bp, x, kind, return_cache=True, window=window,
                    positions=positions)
                if isinstance(cache, tuple):      # moe: (kv_cache, aux)
                    cache = cache[0]
                return x, cache

            self._jit_cache[key] = jax.jit(prefill)
        return self._jit_cache[key]

    def _fn_prefill_arena(self, si: int):
        """Per-node prefill into arena span ``si``; the flat row index
        ``slot + k * n_slots`` is a traced scalar so all span layers share
        one compiled fn."""
        key = ("prefill_arena", si)
        if key not in self._jit_cache:
            kind, window, _, _ = self._spans[si]

            def prefill_arena(bp, arena, x, row):
                self._note_trace()
                positions = jnp.arange(x.shape[1])[None, :]
                x, cache = self.model.apply_block_dense(
                    bp, x, kind, return_cache=True, window=window,
                    positions=positions)
                if isinstance(cache, tuple):      # moe: (kv_cache, aux)
                    cache = cache[0]

                def write(path, a, c):
                    if c.ndim >= 1 and c.shape[0] == 1:
                        c = c[0]                  # drop the batch=1 dim
                    if _is_time_leaf(path):
                        pad_n = a.shape[1] - c.shape[0]
                        c = jnp.pad(c, [(0, pad_n)] + [(0, 0)] * (c.ndim - 1))
                    return a.at[row].set(c.astype(a.dtype))

                return x, jax.tree_util.tree_map_with_path(write, arena, cache)

            # the donated arena is updated in-place instead of copying all
            # rows per dispatch (backends without donation support fall
            # back to a copy with a warning)
            self._jit_cache[key] = jax.jit(prefill_arena,
                                           donate_argnums=(1,))
        return self._jit_cache[key]

    def _fn_decode(self, i: int):
        key = ("decode", i)
        if key not in self._jit_cache:
            kind, window = self._kind_window(i)

            def decode(bp, x, cache, pos):
                self._note_trace()
                return self.model.apply_block_decode(
                    bp, x, cache, pos, kind, window=window)

            self._jit_cache[key] = jax.jit(decode)
        return self._jit_cache[key]

    def _fn_decode_arena(self, si: int):
        """Per-node decode against arena span ``si``: the flat layout makes
        a layer dispatch identical to the PR-1 per-layer arena dispatch —
        gather/scatter B rows at ``slots + k * n_slots`` on the donated
        span arena, no layer slice materialized."""
        key = ("decode_arena", si)
        if key not in self._jit_cache:
            kind, window, _, _ = self._spans[si]

            def decode_arena(bp, arena, x, pos, slots, off):
                self._note_trace()
                return self.model.apply_block_decode(
                    bp, x, arena, pos, kind, window=window,
                    slots=slots + off)

            self._jit_cache[key] = jax.jit(decode_arena,
                                           donate_argnums=(1,))
        return self._jit_cache[key]

    def _fn_head(self):
        if "head" not in self._jit_cache:
            def head(params, x):
                self._note_trace()
                h = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
                logits = self.model.unembed(params, h)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            self._jit_cache["head"] = jax.jit(head)
        return self._jit_cache["head"]

    # ------------------------------------------------------------------
    # Jitted run functions (fused dispatch)
    # ------------------------------------------------------------------
    def _sub_span(self, si: int, a: int, b: int, span_params, offs):
        """Span ``si``'s stacked params + flat-arena row offsets restricted
        to layers [a, b] (static slices, resolved at trace time)."""
        _, _, lo, hi = self._spans[si]
        sp, off = span_params[si], offs[si]
        if a == lo and b == hi:
            return sp, off
        sl = slice(a - lo, b - lo + 1)
        return jax.tree.map(lambda l: l[sl], sp), off[sl]

    def _fn_mega(self, lo: int, hi: int, with_head: bool,
                 ctx: Optional[int] = None):
        """One fused decode dispatch for layers [lo, hi] (+ folded head).

        ``lo == 0``: the input is the (Bp,) token vector — the decode-cycle
        entry embedding happens inside the dispatch. ``lo == -1``: bare
        head (input is the (Bp, d) activation). Each overlapped span is one
        ``lax.scan`` over its stacked params with the flat span arena
        threaded through the carry; the whole arena list is donated as one
        pytree and returned updated in place. ``ctx`` (static power-of-two
        context bucket covering every member's position) bounds attention
        gathers/scores to actual context instead of arena capacity —
        bit-identical, and the reason fused decode beats per-node dispatch
        by more than just Python overhead.
        """
        key = ("mega", lo, hi, with_head, ctx)
        if key not in self._jit_cache:

            def decode_megastep(params, span_params, arenas, entry, pos,
                                slots, offs):
                self._note_trace()
                x = (self.model.embed(params, entry) if lo == 0 else entry)
                new_arenas = list(arenas)
                if lo >= 0:
                    for si, (kind, window, slo, shi) in enumerate(self._spans):
                        a, b = max(lo, slo), min(hi, shi)
                        if a > b:
                            continue
                        sub_bp, sub_off = self._sub_span(
                            si, a, b, span_params, offs)
                        x, new_arenas[si] = self.model.apply_span_decode(
                            sub_bp, x, new_arenas[si], pos, kind,
                            offs=sub_off, window=window, slots=slots,
                            ctx=ctx)
                if with_head:
                    h = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
                    out = jnp.argmax(self.model.unembed(params, h),
                                     axis=-1).astype(jnp.int32)
                else:
                    out = x
                return out, new_arenas

            self._jit_cache[key] = jax.jit(decode_megastep,
                                           donate_argnums=(2,))
        return self._jit_cache[key]

    def _fn_prefill_run(self, lo: int, hi: int, embed: bool):
        """One fused prefill dispatch for layers [lo, hi] over a (B, S)
        token bucket (``embed=True``) or a (B, S, d) activation batch.
        Every member's layer-k cache rows are written into its arena rows
        (``slots + k * n_slots``) inside the scan body (padding rows carry
        the OOB sentinel slot — their writes drop)."""
        key = ("prefill_run", lo, hi, embed)
        if key not in self._jit_cache:

            def prefill_run(params, span_params, arenas, entry, slots, offs):
                self._note_trace()
                x = self.model.embed(params, entry) if embed else entry
                positions = jnp.arange(x.shape[1])[None, :]

                def write(arena, cache, off):
                    row_idx = slots + off

                    def w(path, a, c):
                        if _is_time_leaf(path):
                            pad_n = a.shape[1] - c.shape[1]
                            c = jnp.pad(c, [(0, 0), (0, pad_n)]
                                        + [(0, 0)] * (c.ndim - 2))
                        return a.at[row_idx].set(c.astype(a.dtype),
                                                 mode="drop")
                    return jax.tree_util.tree_map_with_path(w, arena, cache)

                new_arenas = list(arenas)
                for si, (kind, window, slo, shi) in enumerate(self._spans):
                    a, b = max(lo, slo), min(hi, shi)
                    if a > b:
                        continue
                    sub_bp, sub_off = self._sub_span(
                        si, a, b, span_params, offs)
                    x, new_arenas[si] = self.model.apply_span_prefill(
                        sub_bp, new_arenas[si], x, kind, offs=sub_off,
                        window=window, positions=positions, write=write)
                return x, new_arenas

            self._jit_cache[key] = jax.jit(prefill_run, donate_argnums=(2,))
        return self._jit_cache[key]

    # ------------------------------------------------------------------
    # Fused run execution
    # ------------------------------------------------------------------
    def _chunk_run(self, wl, node_ids):
        """Split a committed run into fusable phase chunks:
        ("prefill", [(phase, layer), ...]) or ("decode", lo, hi, with_head)
        — a bare head is ("decode", -1, -1, True). Memoized per node-id
        tuple (decode cycles repeat the same run every token); the cache
        value pins the workload object so its id() cannot be recycled by
        a different workload while the entry lives."""
        ck = (id(wl), tuple(node_ids))
        cached = self._chunk_cache.get(ck)
        if cached is not None:
            return cached[1]
        metas = [self._node_meta(wl, nid) for nid in node_ids]
        chunks = []
        i = 0
        while i < len(metas):
            ph, layer = metas[i]
            if ph in ("emb", "prefill"):
                j = i
                while j < len(metas) and metas[j][0] in ("emb", "prefill"):
                    j += 1
                chunks.append(("prefill", metas[i:j]))
                i = j
            elif ph == "decode":
                lo = hi = layer
                j = i + 1
                while (j < len(metas) and metas[j][0] == "decode"
                       and metas[j][1] == hi + 1):
                    hi += 1
                    j += 1
                with_head = j < len(metas) and metas[j][0] == "head"
                if with_head:
                    j += 1
                chunks.append(("decode", lo, hi, with_head))
                i = j
            else:                                 # bare head
                chunks.append(("decode", -1, -1, True))
                i += 1
        self._chunk_cache[ck] = (wl, chunks)
        return chunks

    def _prefill_groups(self, reqs, sts):
        """Group sub-batch members for batched prefill.

        Attention-family stacks (dense/MLA) bucket by power-of-two padded
        prompt length (and pad the group's batch to a power of two):
        bounded recompiles, one dispatch per bucket. Other stacks (MoE
        routing, SSM/recurrent state scans don't tolerate tail padding)
        prefill per-request at exact length — still one fused dispatch per
        request instead of one per layer.
        """
        bucketable = set(self.kinds) <= {"dense", "mla"}
        groups: Dict[tuple, list] = {}
        for r, st in zip(reqs, sts):
            if bucketable:
                key = (min(_pow2(st.prefill_len), self.max_len),)
            else:
                key = (st.prefill_len, r.rid)
            groups.setdefault(key, []).append((r, st))
        return [(members, key[0]) for key, members in groups.items()]

    def _run_prefill_chunk(self, reqs, sts, metas):
        has_emb = metas[0][0] == "emb"
        layers = [l for ph, l in metas if ph == "prefill"]
        last = bool(layers) and layers[-1] == len(self.kinds) - 1
        if has_emb and not layers:
            for st in sts:                        # bare emb node
                st.x = self.model.embed(self.params,
                                        st.prompt[None, :st.prefill_len])
            return
        if has_emb:
            fn = self._fn_prefill_run(0, layers[-1], embed=True)
            for members, Lb in self._prefill_groups(reqs, sts):
                plan = tracing.ON and tracing.begin(tracing.ENGINE_PLAN)
                Bg = len(members)
                Bp = _pow2(Bg)
                toks = np.zeros((Bp, Lb), np.int32)
                slots = np.full((Bp,), _PAD_SLOT, np.int32)
                for bi, (r, st) in enumerate(members):
                    toks[bi, :st.prefill_len] = st.prompt_np[:st.prefill_len]
                    slots[bi] = self.slot_of(r)   # may grow the arena first
                entry, slots, offs = (jnp.asarray(toks), jnp.asarray(slots),
                                      self._offs())
                if plan:
                    tracing.end(plan)
                    tracing.count(tracing.PREFILL_TOKENS,
                                  sum(st.prefill_len for _, st in members))
                    tracing.count(tracing.PREFILL_POSITIONS, Bp * Lb)
                sp = tracing.ON and tracing.begin(tracing.ENGINE_DISPATCH,
                                                  program="prefill_run")
                x, self.arenas = fn(self.params, self._span_params,
                                    self.arenas, entry, slots, offs)
                if sp:
                    tracing.end(sp)
                for bi, (r, st) in enumerate(members):
                    st.x = (None if last
                            else x[bi:bi + 1, :st.prefill_len])
        else:
            # resumed mid-prefill (st.x in flight): per-request fused span
            fn = self._fn_prefill_run(layers[0], layers[-1], embed=False)
            for r, st in zip(reqs, sts):
                slots = jnp.asarray([self.slot_of(r)], jnp.int32)
                sp = tracing.ON and tracing.begin(tracing.ENGINE_DISPATCH,
                                                  program="prefill_run")
                if sp:
                    tracing.count(tracing.PREFILL_TOKENS, st.prefill_len)
                    tracing.count(tracing.PREFILL_POSITIONS, st.prefill_len)
                st.x, self.arenas = fn(self.params, self._span_params,
                                       self.arenas, st.x, slots,
                                       self._offs())
                if sp:
                    tracing.end(sp)
                if last:
                    st.x = None

    def execute_run(self, model, sb: SubBatch, node_ids: Sequence[str]):
        """Execute a committed run; returns ``(latency, None)`` — per-node
        latency is unobservable inside fused dispatches, by design."""
        if self.cache_mode != "arena" or not self.fused or len(node_ids) == 1:
            run = tracing.ON and tracing.begin(tracing.ENGINE_RUN)
            s0 = self._san_host_syncs
            out = super().execute_run(model, sb, node_ids)
            self._san_max_syncs_per_run = max(
                self._san_max_syncs_per_run, self._san_host_syncs - s0)
            if run:
                reqs = sb.live_requests
                wl = reqs[0].workload
                heads = sum(1 for n in node_ids
                            if self._node_meta(wl, n)[0] == "head")
                tracing.count(tracing.DECODE_CYCLES, heads)
                tracing.end(run, rids=[r.rid for r in reqs], rows=len(reqs),
                            padded=len(reqs), cycles=heads)
            JaxEngine._last_run_end = time.perf_counter()
            return out
        t0 = time.perf_counter()
        run = tracing.ON and tracing.begin(tracing.ENGINE_RUN)
        plan = tracing.ON and tracing.begin(tracing.ENGINE_PLAN)
        reqs = sb.live_requests
        wl = reqs[0].workload
        sts = [self.states[r.rid] for r in reqs]
        rids = tuple(r.rid for r in reqs)
        if self._xbatch is not None and self._xbatch[0] != rids:
            # another sub-batch is parked mid-cycle: its activations live
            # only in the batched cache — flush rows to per-request state
            # before this run's epilogue clobbers it
            self._flush_xbatch()
        ahead, self._ahead = self._ahead, None    # taken here, or discarded
        B = len(reqs)
        Bp = _pow2(B)
        pos0 = None
        slots = None
        toks_dev = None                           # device (Bp,) sampled toks
        x_dev = None                              # device (Bp, d) mid-cycle x
        head_toks: List[jax.Array] = []
        n_heads = 0
        chunks = self._chunk_run(wl, node_ids)
        # one static context bucket covers every decode chunk of the run.
        # A chunk preceded by h heads reads rows <= pos0 + h, so the
        # deepest read index is pos0 + n_heads - 1 when the run ends on a
        # head, and pos0 + n_heads when a trailing headless decode chunk
        # continues past the run's last head — ctx must exceed it
        n_cycles = sum(1 for ch in chunks if ch[0] == "decode" and ch[3])
        ctx = None
        if any(ch[0] == "decode" for ch in chunks):
            trailing = chunks[-1][0] == "decode" and not chunks[-1][3]
            deepest = (max(st.pos for st in sts) + n_cycles
                       + (1 if trailing else 0))
            ctx = min(_pow2(deepest), self.max_len)
        bkey = (rids, Bp)
        n = len(node_ids)
        finishing = [r for r in reqs if r.idx + n >= len(r.sequence)]
        # one full decode cycle with its head: the run lookahead repeats
        cycle = chunks == [self._full_cycle]
        hit = (cycle and ahead is not None
               and ahead[0] == (bkey, tuple(st.pos for st in sts)))
        if plan:
            tracing.end(plan)
        for ch in chunks:
            if ch[0] == "prefill":
                self._run_prefill_chunk(reqs, sts, ch[1])
                continue
            _, lo, hi, with_head = ch
            plan = tracing.ON and tracing.begin(tracing.ENGINE_PLAN)
            if slots is None:
                slots = self._batched_slots(reqs, rids, padded_to=Bp)
                if self._posbatch is not None and self._posbatch[0] == bkey:
                    pos0 = self._posbatch[1]      # device-carried positions
                else:
                    pos0 = jnp.asarray([st.pos for st in sts]
                                       + [0] * (Bp - B), jnp.int32)
            offs = self._offs()
            if hit:
                # dispatched by the previous run and run on the device
                # since the last run ended: its latency starts there, so
                # the session's clock keeps the device's time
                out, t0 = ahead[1], JaxEngine._last_run_end
                self._san_lookahead_hits += 1
                if plan:
                    tracing.end(plan)
                    tracing.count(tracing.LOOKAHEAD_HITS, 1)
            else:
                pos = pos0 if n_heads == 0 else pos0 + n_heads
                if lo == 0:
                    if toks_dev is None and self._tokbatch is not None \
                            and self._tokbatch[0] == bkey:
                        toks_dev = self._tokbatch[1]  # device-carried
                    entry = (toks_dev if toks_dev is not None else
                             jnp.asarray([st.next_token for st in sts]
                                         + [0] * (Bp - B), jnp.int32))
                else:
                    entry = x_dev if x_dev is not None \
                        else self._entry_x(reqs, sts, B, Bp)
                if plan:
                    tracing.end(plan)
                out = self._dispatch_mega(lo, hi, with_head, ctx, entry,
                                          pos, slots, offs)
            if with_head:
                head_toks.append(out)
                toks_dev = out
                x_dev = None
                n_heads += 1
            else:
                x_dev = out
        pos_next = pos0 + n_heads if n_heads and pos0 is not None else None
        nxt = None
        # ---- lookahead: the batch's next cycle, queued behind this one
        # at pos + 1 under the context bucket its own run would pick
        # (``deepest`` + 1), so the device is busy through the sync
        if (cycle and self._lookahead and not finishing
                and deepest < self.max_len):
            nxt = self._dispatch_mega(0, hi, True,
                                      min(_pow2(deepest + 1), self.max_len),
                                      toks_dev, pos_next, slots, offs)
            self._san_lookahead_dispatched += 1
            if tracing.ON:
                tracing.count(tracing.LOOKAHEAD_DISPATCHED, 1)
        # ---- run boundary: the ONLY sync point -----------------------
        sp = tracing.ON and tracing.begin(tracing.ENGINE_SYNC)
        # reprolint: disable=sync-point
        arrs = [np.asarray(t) for t in head_toks]
        if nxt is None:
            # (with a cycle queued the arenas are its output, and the
            # readback above already fenced this cycle's writes)
            jax.block_until_ready(self.arenas)  # reprolint: disable=sync-point
        # token readback + arena fence at ONE run boundary are a single
        # logical sync event: the one-sync-per-run contract
        self._san_host_syncs += 1
        self._san_max_syncs_per_run = max(self._san_max_syncs_per_run, 1)
        if sp:
            tracing.end(sp)
        sp = tracing.ON and tracing.begin(tracing.ENGINE_EPILOGUE)
        for arr in arrs:
            for bi, st in enumerate(sts):
                st.next_token = int(arr[bi])  # reprolint: disable=sync-point
                st.generated.append(st.next_token)
                st.pos += 1
        if pos_next is not None:
            self._posbatch = (bkey, pos_next)
            self._tokbatch = (bkey, toks_dev)
        if x_dev is not None:
            self._xbatch = (rids, x_dev[:B])      # run ended mid-cycle
        else:
            self._xbatch = None
        self.nodes_executed += n
        self.runs_executed += 1
        self._release_slots(finishing)            # their final node ran
        if sp:
            tracing.end(sp)
            tracing.count(tracing.DECODE_CYCLES, n_heads)
            tracing.end(run, rids=list(rids), rows=B, padded=Bp,
                        cycles=n_heads)
        if nxt is not None:
            self._ahead = ((bkey, tuple(st.pos for st in sts)), nxt)
        t1 = JaxEngine._last_run_end = time.perf_counter()
        return t1 - t0, None

    def _dispatch_mega(self, lo, hi, with_head, ctx, entry, pos, slots,
                       offs):
        """Dispatch one decode megastep chunk over the arenas; returns
        its output (head tokens, or the activations of a headless chunk)."""
        fn = self._fn_mega(lo, hi, with_head, ctx)
        sp = tracing.ON and tracing.begin(tracing.ENGINE_DISPATCH,
                                          program="decode_megastep")
        out, self.arenas = fn(self.params, self._span_params, self.arenas,
                              entry, pos, slots, offs)
        if sp:
            tracing.end(sp)
        return out

    def _entry_x(self, reqs, sts, B, Bp):
        rids, x = self._batched_x(reqs, sts)
        self._xbatch = (rids, x)
        if Bp > B:
            x = jnp.pad(x, [(0, Bp - B), (0, 0)])
        return x

    # ------------------------------------------------------------------
    # Single-node dispatch (degenerate run; bit-exactness reference)
    # ------------------------------------------------------------------
    def execute(self, model, sb: SubBatch, node_id: str) -> float:
        t0 = time.perf_counter()
        self._ahead = None
        reqs = sb.live_requests
        outs = []
        phase, i = self._node_meta(reqs[0].workload, node_id)
        disp = tracing.ON and tracing.begin(tracing.ENGINE_DISPATCH,
                                            program=phase)
        if phase == "emb":
            for r in reqs:
                st = self.state(r)
                st.x = self.model.embed(
                    self.params, st.prompt[None, :st.prefill_len])
                outs.append(st.x)
        elif phase == "prefill":
            bp = self._layer_params(i)
            last = (i == len(self.kinds) - 1)
            if self.cache_mode == "arena":
                si, k = self._layer_loc[i]
                fn = self._fn_prefill_arena(si)
                for r in reqs:
                    st = self.state(r)
                    slot = self.slot_of(r)    # may grow the arena: resolve
                    st.x, self.arenas[si] = fn(bp, self.arenas[si], st.x,
                                               slot + k * self.n_slots)
                    outs.append(st.x)
                    if last:                      # prefill done
                        st.x = None
            else:
                fn = self._fn_prefill(i)
                for r in reqs:
                    st = self.state(r)
                    st.x, cache = fn(bp, st.x)
                    st.caches[i] = self._pad_cache(cache, st.prefill_len)
                    outs.append(st.x)
                    if last:
                        st.x = None
        elif phase == "decode":
            bp = self._layer_params(i)
            sts = [self.state(r) for r in reqs]
            fresh = None
            if i == 0:
                toks = jnp.asarray([st.next_token for st in sts], jnp.int32)
                fresh = self.model.embed(self.params, toks)   # (B, d)
            pos = jnp.asarray([st.pos for st in sts], jnp.int32)
            if self.cache_mode == "arena":
                rids, x = self._batched_x(reqs, sts, fresh)
                si, k = self._layer_loc[i]
                fn = self._fn_decode_arena(si)
                slots = self._batched_slots(reqs, rids)
                x, self.arenas[si] = fn(bp, self.arenas[si], x, pos, slots,
                                        k * self.n_slots)
                self._xbatch = (rids, x)
            else:
                if fresh is not None:
                    for bi, st in enumerate(sts):
                        st.x = fresh[bi]
                x = jnp.stack([st.x for st in sts])           # (B, d)
                fn = self._fn_decode(i)
                cache = jax.tree.map(lambda *ls: jnp.stack(ls),
                                     *[st.caches[i] for st in sts])
                x, new_cache = fn(bp, x, cache, pos)
                for bi, st in enumerate(sts):
                    st.caches[i] = jax.tree.map(lambda l: l[bi], new_cache)
                    st.x = x[bi]
            outs.append(x)
        elif phase == "head":
            fn = self._fn_head()
            sts = [self.state(r) for r in reqs]
            if self.cache_mode == "arena":
                rids, x = self._batched_x(reqs, sts)
                self._xbatch = (rids, x)
            else:
                x = jnp.stack([st.x for st in sts])
            toks = fn(self.params, x)
            outs.append(toks)
            toks = np.asarray(toks)
            for bi, st in enumerate(sts):
                st.next_token = int(toks[bi])
                st.generated.append(st.next_token)
                st.pos += 1
            # single-node head advanced host state: the device-carried
            # run vectors are stale now
            self._posbatch = self._tokbatch = None
        else:
            raise KeyError(f"unknown node {node_id!r}")
        self.nodes_executed += 1
        if disp:
            tracing.end(disp)
        # per-node dispatch fences every node — one sync event per NODE,
        # which is exactly why fused runs beat it (their whole run is one)
        sp = tracing.ON and tracing.begin(tracing.ENGINE_SYNC)
        self._san_host_syncs += 1
        for o in outs:
            jax.block_until_ready(o)
        if sp:
            tracing.end(sp)
        # free arena slots of requests that just executed their final node
        # (on_finished() releases them too — both are idempotent — but this
        # covers direct engine driving without the server loop)
        self._release_slots([r for r in reqs
                             if r.idx == len(r.sequence) - 1])
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    def _pad_cache(self, cache, prefill_len: int):
        """Legacy mode: prefill returns time-axis caches sized to the
        prompt; pad them to ``max_len`` so merged decode batches share one
        cache shape. Only leaves named in ``_TIME_AXIS_KEYS`` (k/v/ckv/
        krope) have a time axis; recurrent state/conv leaves pass through
        untouched."""

        def pad(path, leaf):
            if not _is_time_leaf(path):
                return leaf
            if leaf.ndim >= 2 and leaf.shape[0] == 1:
                leaf = leaf[0]                    # drop the batch=1 dim
            pad_n = self.max_len - leaf.shape[0]
            if pad_n < 0:
                raise ValueError(
                    f"cache leaf time-dim {leaf.shape} exceeds engine "
                    f"max_len {self.max_len}")
            return jnp.pad(leaf, [(0, pad_n)] + [(0, 0)] * (leaf.ndim - 1))

        padded = jax.tree_util.tree_map_with_path(pad, cache)
        # non-time leaves still carry the batch=1 dim — drop it
        return jax.tree_util.tree_map_with_path(
            lambda p, l: (l[0] if not _is_time_leaf(p) and l.ndim >= 1
                          and l.shape[0] == 1 else l),
            padded)


def reference_generate(engine: JaxEngine, wl, prompt, n_tokens: int, *,
                       forced: Optional[List[int]] = None,
                       on_head: Optional[Callable] = None) -> List[int]:
    """Generate ``n_tokens`` for ``prompt`` in isolation through
    ``engine``'s single-node path (batch of 1, no preemption): the ground
    truth that batched serving must reproduce.

    ``forced`` teacher-forces the run: step i still computes its greedy
    pick, but feeds ``forced[i]`` onward, so the reference follows a given
    generation step by step. ``on_head(x)`` sees each step's final hidden
    state ``(1, d_model)`` before the head picks from it."""
    rng = np.random.default_rng(123)
    req = wl.sample_request(rng, 0.0)
    # rebuild the node sequence for this exact prompt/decode length
    seq, prefix_len, cycle_len = wl.build_sequence(len(prompt), n_tokens)
    req.sequence, req.prefix_len, req.cycle_len = seq, prefix_len, cycle_len
    req.prompt_len, req.decode_len = len(prompt), n_tokens
    engine.register(req, prompt)
    st = engine.state(req)
    sb = SubBatch([req])
    picks = []
    while not req.done:
        node_id = req.next_node_id
        head = node_id == "head"
        if head and on_head is not None:
            on_head(engine._batched_x([req], [st])[1])
        engine.execute("m", sb, node_id)
        if head:
            picks.append(st.generated[-1])
            if forced is not None:
                st.next_token = st.generated[-1] = int(forced[len(picks) - 1])
        sb.advance(0.0)
    return picks
