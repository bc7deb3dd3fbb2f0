"""The one traffic generator: reads a mix file and a cell file, and turns a
seed into an open-loop schedule of requests.

Every seed gets the same work, section by section. The schedule has three
sections: the lead-in, the measured window and the drain. The number of
arrivals in each section is fixed by the mix and the rate, and so are the
multisets of gaps, prompt lengths, output lengths and tiers within it
(drawn at evenly spaced quantiles of the stated distributions). The seed
only shuffles their order and pairing within a section, and only within
blocks: every run of ``BLOCK`` consecutive requests holds one value from
each of ``BLOCK`` quantile strata. So two seeds offer the window the same
requests, tokens and load in a different order, and a burst gets the same
mix of short and long requests whatever the seed.

Arrival shapes follow ``repro.serving.traffic`` (a Poisson process; a
two-state MMPP alternating a low and a high rate around the nominal one),
copied here so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due: float          # seconds after the schedule starts
    prompt_len: int
    output_len: int
    tier: str
    deadline: float     # seconds from due time to the last token
    in_window: bool     # due inside the measured window


BLOCK = 8


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def stratified_order(values: np.ndarray,
                     rng: np.random.Generator) -> np.ndarray:
    """``values`` in a seeded order in which every ``BLOCK`` consecutive
    entries take one value from each of ``BLOCK`` strata of the sorted
    values (stratum k: ranks k, k + BLOCK, ...; the strata's own orders
    and each block's order are shuffled)."""
    v = np.sort(values)
    n = len(v)
    strata = [v[k::BLOCK].copy() for k in range(BLOCK)]
    for s in strata:
        rng.shuffle(s)
    out = []
    for b in range(-(-n // BLOCK)):
        block = [s[b] for s in strata if b < len(s)]
        rng.shuffle(block)
        out.extend(block)
    return np.array(out, dtype=values.dtype)


def length_multiset(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``'s distribution,
    clipped to its bounds (sorted, the same for every seed)."""
    q = (np.arange(n) + 0.5) / n
    kind = spec["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _gaps(count: int, span: float, rng: np.random.Generator) -> np.ndarray:
    """``count`` arrival offsets in [0, span): exponential gaps at evenly
    spaced quantiles, shuffled by ``rng`` and scaled to fill ``span``."""
    q = (np.arange(count) + 0.5) / count
    g = stratified_order(-np.log1p(-q), rng)
    t = np.cumsum(g)
    return t / (t[-1] + g.mean()) * span


def arrival_times(arrivals: dict, rate: float, span: float,
                  rng: np.random.Generator, cuts=()) -> np.ndarray:
    """Arrival times over [0, span) at mean ``rate`` per second. Each
    stretch of constant rate is also split at ``cuts``, so the number of
    arrivals between two cuts is the same for every ``rng``."""
    kind = arrivals["kind"]
    if kind == "poisson":
        segments = [(0.0, span, rate)]
    elif kind == "mmpp2":
        # low and high states alternate every ``state_seconds``, starting
        # low: the repository's bursty shape (rate x 0.3, rate x 2.0)
        segments, t, high = [], 0.0, False
        while t < span:
            end = min(t + arrivals["state_seconds"], span)
            f = arrivals["high_factor"] if high else arrivals["low_factor"]
            segments.append((t, end, rate * f))
            t, high = end, not high
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    split = []
    for lo, hi, r in segments:
        edges = [lo] + sorted(c for c in cuts if lo < c < hi) + [hi]
        split.extend((a, b, r) for a, b in zip(edges, edges[1:]))
    out = []
    for lo, hi, r in split:
        count = int(round(r * (hi - lo)))
        if count:
            out.append(lo + _gaps(count, hi - lo, rng))
    return np.sort(np.concatenate(out)) if out else np.zeros(0)


def deadline(tier: dict, output_len: int) -> float:
    """The request's deadline from its due time: the tier's time to first
    token plus its time per output token for each later token."""
    return (tier["ttft_ms"] + tier["tpot_ms"] * (output_len - 1)) / 1e3


def schedule(mix: dict, cell: dict, seed: int,
             window_s: float) -> List[Arrival]:
    """The run's requests: due over lead-in + window + drain, the ones due
    inside the window marked. Same work for every seed in each section."""
    lead, drain = mix["lead_in_s"], mix["drain_s"]
    close = lead + window_s
    times = arrival_times(mix["arrivals"], cell["rate_per_s"],
                          close + drain, _rng(seed, 1), cuts=(lead, close))
    tiers = cell["tiers"]
    out = []
    for k, (lo, hi) in enumerate(((0.0, lead), (lead, close),
                                  (close, close + drain))):
        sec = times[(times >= lo) & (times < hi)]
        n = len(sec)
        if not n:
            continue
        prompts = stratified_order(length_multiset(mix["prompt"], n),
                                   _rng(seed, 10 + k))
        outputs = stratified_order(length_multiset(mix["output"], n),
                                   _rng(seed, 20 + k))
        counts = [int(math.floor(t["share"] * n)) for t in tiers]
        counts[0] += n - sum(counts)
        names = stratified_order(
            np.array(sum(([i] * c for i, c in enumerate(counts)), [])),
            _rng(seed, 30 + k))
        out.extend(Arrival(due=float(t), prompt_len=int(p),
                           output_len=int(o), tier=tiers[j]["name"],
                           deadline=deadline(tiers[j], int(o)),
                           in_window=k == 1)
                   for t, p, o, j in zip(sec, prompts, outputs, names))
    return out


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """Prompt token ids of request ``index``, drawn from the seed."""
    return _rng(seed, 1000 + index).integers(2, vocab, size=length,
                                             dtype=np.int64).astype(np.int32)
