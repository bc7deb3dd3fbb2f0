"""The program's own spans and programs in a profiler trace.

With the program's recorder on (``repro.serving.tracing``), each
``serve.*`` span is also a host annotation in the ``.xplane.pb``, on the
clock of the device's ops. This module reads those spans, the device's
``XLA Ops`` (for busy and idle time) and its ``XLA Modules`` line, where a
jitted program shows as ``jit_<name>(<fingerprint>)``, all clipped to the
harness's ``bench.slice``. Device idle time is split between the spans by
intersecting each gap with the innermost ``serve.*`` span open at each
instant (not put whole to the span open at its midpoint, as
``bench.trace`` does with the ``bench.*`` spans).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from .trace import OPS_LINE, SLICE, _clip, union

MODULES_LINE = "XLA Modules"
SERVE = "serve."

Interval = Tuple[float, float]
Event = Tuple[str, float, float]


def module_name(event_name: str) -> str:
    """``jit_decode_megastep(8852587370456195745)`` ->
    ``jit_decode_megastep``."""
    return event_name.split("(", 1)[0]


@dataclass
class SpanTrace:
    window: Interval                   # the slice
    busy: Dict[str, List[Interval]]    # per device: union of its ops
    modules: Dict[str, List[Event]]    # per device: (program, start, end)
    spans: List[Event]                 # serve.* host spans

    @property
    def n_devices(self) -> int:
        return max(1, len(self.busy))

    def module_seconds(self, program: str) -> float:
        """Device time of ``program`` (e.g. ``jit_decode_megastep``) in
        the slice, averaged over the devices."""
        lo, hi = self.window
        return sum(b - a for mods in self.modules.values()
                   for n, a, b in _clip_events(mods, lo, hi)
                   if n == program) / self.n_devices

    def idle_by_span(self) -> Dict[Optional[str], float]:
        """Seconds of the slice with no device op, by the innermost
        ``serve.*`` span open then (None: no span open), averaged over
        the devices."""
        lo, hi = self.window
        pieces = innermost(self.spans, lo, hi)
        out: Dict[Optional[str], float] = defaultdict(float)
        for busy in self.busy.values():
            edges = [lo] + [x for ab in busy for x in ab] + [hi]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
            for name, s in _intersect(gaps, pieces).items():
                out[name] += s / self.n_devices
        return dict(out)

    def idle_share(self, names) -> float:
        """Percent of the slice with no device op while the innermost
        open span is one of ``names``."""
        idle = self.idle_by_span()
        lo, hi = self.window
        return 100.0 * sum(idle.get(n, 0.0) for n in names) / (hi - lo)


def _clip_events(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def innermost(spans: List[Event], lo: float,
              hi: float) -> List[Tuple[float, float, Optional[str]]]:
    """[lo, hi] cut into pieces, each with the innermost span open over
    it (the one opened last; None where none is)."""
    marks = []
    for i, (n, a, b) in enumerate(_clip_events(spans, lo, hi)):
        marks.append((a, 1, -(b - a), i, n))
        marks.append((b, 0, 0.0, i, n))
    marks.sort()
    out, stack, t = [], [], lo
    for x, kind, _, i, n in marks:
        if x > t:
            out.append((t, x, stack[-1][1] if stack else None))
            t = x
        if kind:
            stack.append((i, n))
        else:
            stack.remove(next(e for e in reversed(stack) if e[0] == i))
    if hi > t:
        out.append((t, hi, None))
    return out


def _intersect(gaps: List[Interval], pieces) -> Dict[Optional[str], float]:
    out: Dict[Optional[str], float] = defaultdict(float)
    j = 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            a, b, name = pieces[k]
            out[name] += min(b, g1) - max(a, g0)
            k += 1
    return out


def from_events(device_ops: Dict[str, List[Event]],
                device_modules: Dict[str, List[Event]],
                host_spans: List[Event]) -> SpanTrace:
    """``device_ops``/``device_modules``: per device, (name, start, end)
    in seconds; ``host_spans``: host spans, ``bench.slice`` among them."""
    slices = [(a, b) for n, a, b in host_spans if n == SLICE]
    if not slices:
        raise ValueError(f"trace holds no {SLICE!r} host span")
    lo, hi = min(a for a, _ in slices), max(b for _, b in slices)
    busy = {d: union(_clip([(a, b) for _, a, b in ops], lo, hi))
            for d, ops in device_ops.items()}
    mods = {d: [(module_name(n), a, b) for n, a, b in evs]
            for d, evs in device_modules.items()}
    spans = [e for e in host_spans if e[0].startswith(SERVE)]
    return SpanTrace((lo, hi), busy, mods, spans)


def read_xplane(path: str) -> SpanTrace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    mods: Dict[str, List[Event]] = {}
    host: List[Event] = []

    def events(line):
        return [(ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]

    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = events(line)
                elif line.name == MODULES_LINE:
                    mods[plane.name] = events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in events(line)
                            if e[0] == SLICE or e[0].startswith(SERVE))
    return from_events(ops, mods, host)
