"""Spans and counters of the served path, on the host's wall clock.

Off by default. Every call site guards itself with the module flag::

    sp = tracing.ON and tracing.begin(tracing.ENGINE_PLAN)
    ...
    if sp:
        tracing.end(sp)

so with tracing off a site costs one check of ``ON``: nothing is
allocated, no name is formatted (names are the constants below, and
attributes are keyword arguments built only past the check) and no
profiler annotation is made.

:func:`enable` turns it on. Each span is then kept twice:

  * in memory, as a :class:`Span` ``(name, t0, t1, parent, attrs)`` on
    ``time.perf_counter``, ``parent`` being the index of the enclosing
    span in :func:`records` (-1 at the top); the buffer holds
    ``CAPACITY`` spans and counter events, and counts what it drops;
  * in the profiler's trace, as ``jax.profiler.TraceAnnotation(name)``,
    on the host plane of the same ``.xplane.pb`` and the same clock as
    the device's ops, so a device idle gap can be put down to the span
    that was open during it.

Spans nest on the one serving thread. :func:`end` also closes any span
still open inside the one it closes (left open by an exception).

This module is the audited wall-clock boundary of the served path
(``repro.analysis.base.WALLCLOCK_AUDITED_PREFIXES``): the session and the
engine call into it, and nothing it measures flows back into them. It
adds no host-device sync: device time comes from the trace.
"""
from __future__ import annotations

import time
from typing import List, NamedTuple, Tuple

# span names
SUBMIT = "serve.submit"                  # stamp: rid
STEP = "serve.step"
SCHED_ADMIT = "serve.sched.admit"
SCHED_POLICY = "serve.sched.policy"
SCHED_ARBITER = "serve.sched.arbiter"
SCHED_EPILOGUE = "serve.sched.epilogue"
ENGINE_RUN = "serve.engine.run"          # attrs: rids, rows, padded, cycles
ENGINE_PLAN = "serve.engine.plan"
ENGINE_DISPATCH = "serve.engine.dispatch"  # attr: program
ENGINE_COMPILE = "serve.engine.compile"    # attr: program
ENGINE_SYNC = "serve.engine.sync"
ENGINE_EPILOGUE = "serve.engine.epilogue"
# counter names
PREFILL_TOKENS = "prefill_tokens"        # real prompt tokens prefilled
PREFILL_POSITIONS = "prefill_positions"  # positions dispatched (Bp x Lb)
DECODE_CYCLES = "decode_cycles"          # heads run
LOOKAHEAD_DISPATCHED = "lookahead_dispatched"  # next cycles run ahead
LOOKAHEAD_HITS = "lookahead_hits"        # of those, taken by the next run

CAPACITY = 1 << 19

#: the one flag a call site checks
ON = False


class Span:
    __slots__ = ("name", "t0", "t1", "parent", "attrs")

    def __init__(self, name: str, t0: float, parent: int, attrs: dict):
        self.name, self.t0, self.t1 = name, t0, None
        self.parent, self.attrs = parent, attrs

    def __repr__(self):
        return (f"Span({self.name!r}, {self.t0}, {self.t1}, "
                f"parent={self.parent}, {self.attrs})")


class Records(NamedTuple):
    spans: List[Span]                        # in order of their start
    counters: List[Tuple[str, float, int]]   # (name, t, increment)
    dropped: int                             # spans and counts not kept


_spans: List[Span] = []
_counters: List[Tuple[str, float, int]] = []
_stack: list = []            # (span, its index or -1, annotation)
_dropped = 0
_annotation = None           # jax.profiler.TraceAnnotation, once enabled


def enable() -> None:
    """Start recording afresh, and annotating the profiler's trace."""
    global ON, _annotation
    from jax.profiler import TraceAnnotation
    reset()
    _annotation = TraceAnnotation
    ON = True


def disable() -> None:
    """Stop recording; what was recorded stays readable."""
    global ON
    ON = False
    while _stack:
        end(_stack[0][0])


def reset() -> None:
    global _dropped
    _spans.clear()
    _counters.clear()
    _stack.clear()
    _dropped = 0


def _room() -> bool:
    return len(_spans) + len(_counters) < CAPACITY


def records() -> Records:
    return Records(_spans, _counters, _dropped)


def begin(name: str, **attrs) -> Span:
    """Open span ``name`` inside the innermost open one."""
    global _dropped
    parent = _stack[-1][1] if _stack else -1
    sp = Span(name, time.perf_counter(), parent, attrs)
    if _room():
        idx = len(_spans)
        _spans.append(sp)
    else:
        idx = -1
        _dropped += 1
    ann = _annotation(name)
    ann.__enter__()
    _stack.append((sp, idx, ann))
    return sp


def end(span: Span, **attrs) -> None:
    """Close ``span`` and whatever is still open inside it."""
    if not any(e[0] is span for e in _stack):
        return
    if attrs:
        span.attrs.update(attrs)
    t = time.perf_counter()
    while True:
        sp, _, ann = _stack.pop()
        sp.t1 = t
        ann.__exit__(None, None, None)
        if sp is span:
            return


def stamp(name: str, **attrs) -> None:
    """A span of no length (an instant), e.g. a request's submission."""
    global _dropped
    t = time.perf_counter()
    if _room():
        sp = Span(name, t, _stack[-1][1] if _stack else -1, attrs)
        sp.t1 = t
        _spans.append(sp)
    else:
        _dropped += 1


def count(name: str, n: int) -> None:
    global _dropped
    if _room():
        _counters.append((name, time.perf_counter(), n))
    else:
        _dropped += 1


def compiling() -> None:
    """Called where a program is traced (so compiled, or loaded from the
    persistent cache): the open dispatch gets a ``serve.engine.compile``
    child, closed with it."""
    if _stack and _stack[-1][0].name == ENGINE_DISPATCH:
        begin(ENGINE_COMPILE, **_stack[-1][0].attrs)

