"""The check that decides ``correct``: served greedy tokens against the
plain float32 reference.

Once the window has closed, a sample of the finished requests due in the
window, drawn from the seed, is replayed through the reference. It always
holds the one with the most output tokens and some that decoded in a
batch beside other requests, where a fault between a batch's rows would
show. Each is replayed as one forward pass over
each prompt followed by its served tokens. At every served position the
number compared is how far the served token's reference logit lies below
the reference's best logit there; the widest such gap over the sample is
held to the cell's limit. A correct bf16 program departs from the float32
reference only by rounding, so its gaps stay small; a wrong token, cache
or kernel puts the served token well down the reference's ranking.
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

@dataclass
class Sample:
    prompt: np.ndarray
    served: List[int]
    merged: bool = False


def draw_sample(finished: Sequence[Tuple[np.ndarray, List[int], bool]],
                seed: int, cell: dict) -> List[Sample]:
    """The longest finished request, then those that decoded beside other
    requests in a seeded order, then the rest in a seeded order, until the
    sample holds ``check_tokens`` served tokens and ``check_min_merged``
    such merged requests, or ``check_max_seqs`` requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i][1]), i))
    rest = np.array(order[1:], dtype=int)
    np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7]).shuffle(rest)
    rest = sorted(rest, key=lambda i: not finished[i][2])   # stable
    picked, n = [], 0
    for i in [order[0]] + rest:
        if len(picked) >= cell["check_max_seqs"] or (
                n >= cell["check_tokens"]
                and sum(p.merged for p in picked) >= cell["check_min_merged"]):
            break
        picked.append(Sample(*finished[i]))
        n += len(finished[i][1])
    return picked


def judge(gap: float, samples: Sequence[Sample], cell: dict):
    """(correct, checks): the widest gap against the cell's limit, and
    enough served tokens and merged requests compared. ``checks`` holds
    each number beside its limit."""
    n_tok = sum(len(s.served) for s in samples)
    n_merged = sum(s.merged for s in samples)
    checks = {"logit_gap": {"value": gap, "limit": cell["max_logit_gap"]},
              "tokens_compared": {"value": n_tok,
                                  "limit": cell["check_min_tokens"]},
              "merged_requests_compared": {
                  "value": n_merged, "limit": cell["check_min_merged"]}}
    correct = (math.isfinite(gap) and gap <= cell["max_logit_gap"]
               and n_tok >= cell["check_min_tokens"]
               and n_merged >= cell["check_min_merged"])
    return correct, checks


def _reference(config: dict):
    """The reference module the configuration names."""
    return importlib.import_module(f"bench.reference.{config['reference']}")


def logits(config: dict, seed: int, samples: Sequence[Sample],
           fp8: bool = False) -> List[np.ndarray]:
    """Reference (or fp8 control) logits at each served position: row i
    is the distribution the served token i was picked from."""
    import jax
    from bench.reference.common import forward_logits
    ref = _reference(config)
    seqs = [np.concatenate([s.prompt, np.asarray(s.served[:-1], np.int32)])
            for s in samples]
    want = [np.arange(len(s.prompt) - 1, len(s.prompt) - 1 + len(s.served))
            for s in samples]
    with jax.default_matmul_precision("highest"):
        return forward_logits(config, seed, seqs, want, ref.layer_weights,
                              ref.block, fp8=fp8)


def gaps(ref_logits: Sequence[np.ndarray],
         picks: Sequence[Sequence[int]]) -> np.ndarray:
    """Per position: reference best logit minus the picked token's."""
    out = [z.max(axis=-1) - z[np.arange(len(p)), np.asarray(p)]
           for z, p in zip(ref_logits, picks)]
    return np.concatenate(out) if out else np.zeros(0)


def widest(g: np.ndarray) -> float:
    return float(g.max()) if g.size else float("nan")


def served_gap(config: dict, seed: int, samples: Sequence[Sample]) -> float:
    """Widest gap of the served tokens."""
    z = logits(config, seed, samples)
    return widest(gaps(z, [s.served for s in samples]))


def control_gap(config: dict, seed: int, samples: Sequence[Sample],
                ref_logits=None) -> float:
    """Widest gap, under the float32 reference, of the tokens that the
    fp8 control puts first at the same positions."""
    z = ref_logits if ref_logits is not None else logits(config, seed,
                                                         samples)
    c = logits(config, seed, samples, fp8=True)
    return widest(gaps(z, [zc.argmax(axis=-1) for zc in c]))
