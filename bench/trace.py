"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device busy time is the union of the intervals in which an operation ran
on a device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
within the traced slice; loop and branch ops, whose events enclose the
ops they run, count there but not in the per-op breakdown. The slice is the host span ``bench.slice`` that
the harness opens and closes between steps, on the same clock. Kernel
time is the sum of the durations of the operations whose name matches a
kernel's pattern. Idle gaps are the stretches of the slice with no device
operation, each put to the innermost ``bench.*`` host span running at its
midpoint (``idle`` where none is).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

SLICE = "bench.slice"
OPS_LINE = "XLA Ops"
# control-flow ops whose events enclose the ops they run: counted in busy
# time, left out of the per-op breakdown (their time is their children's)
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(event_name: str) -> str:
    """``%fusion.21 = bf16[...] fusion(...)`` -> ``fusion.21``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                     # averaged over the devices seen
    n_devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)
    gap_seconds: Dict[str, float] = field(default_factory=dict)

    def kernel_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds.items() if rx.search(n))

    def breakdown(self, k: int = 10) -> dict:
        top = lambda d: [[n, s] for n, s in sorted(
            d.items(), key=lambda kv: -kv[1])[:k]]
        return {"device_ops": top(self.op_seconds),
                "idle_gaps": top(self.gap_seconds)}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]]) -> Reduced:
    """``device_ops``: per device, (name, start, end) in seconds;
    ``host_spans``: (name, start, end) of the ``bench.*`` host spans,
    ``bench.slice`` among them."""
    slices = [(a, b) for n, a, b in host_spans if n == SLICE]
    if not slices:
        raise ValueError(f"trace holds no {SLICE!r} host span")
    lo, hi = min(a for a, _ in slices), max(b for _, b in slices)
    op_s: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    spans = sorted((a, b, n) for n, a, b in host_spans if n != SLICE)
    for ops in device_ops.values():
        iv = _clip([(a, b) for _, a, b in ops], lo, hi)
        for (name, a, b) in ops:
            c = _clip([(a, b)], lo, hi)
            if c and not CONTAINERS.match(name):
                op_s[name] += c[0][1] - c[0][0]
        busy = union(iv)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            inner = [(b - a, n) for a, b, n in spans if a <= mid <= b]
            gaps[min(inner)[1] if inner else "idle"] += g1 - g0
    n = max(1, len(device_ops))
    return Reduced(window_s=hi - lo, busy_s=busy_total / n,
                   n_devices=len(device_ops), op_seconds=dict(op_s),
                   gap_seconds=dict(gaps))


def read_xplane(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return reduce_events(device_ops, host)
