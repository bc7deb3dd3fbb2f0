"""The benchmark's traffic generator: seeded, the same work for every
seed, lengths inside their clips at the stated medians, burst rates and
tier shares as the mix states."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

BENCH = Path(__file__).resolve().parents[2] / "bench"
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
CELL = {"rate_per_s": 3.0,
        "tiers": [{"name": "interactive", "share": 0.7, "ttft_ms": 500,
                   "tpot_ms": 50},
                  {"name": "batch", "share": 0.3, "ttft_ms": 1500,
                   "tpot_ms": 150}]}


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    assert T.schedule(mix(name), CELL, 2**31 + 7, 30.0) == \
        T.schedule(mix(name), CELL, 2**31 + 7, 30.0)
    assert T.schedule(mix(name), CELL, 1, 30.0) != \
        T.schedule(mix(name), CELL, 2, 30.0)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("section", ["all", "window"])
def test_every_seed_gets_the_same_work(name, section):
    a, b = ([x for x in T.schedule(mix(name), CELL, s, 31.0)
             if section == "all" or x.in_window] for s in (3, 2**32 + 5))
    key = lambda s: sorted((x.prompt_len, x.output_len) for x in s)
    assert len(a) == len(b)
    assert sorted(x.prompt_len for x in a) == sorted(x.prompt_len for x in b)
    assert sorted(x.output_len for x in a) == sorted(x.output_len for x in b)
    assert Counter(x.tier for x in a) == Counter(x.tier for x in b)
    assert key(a) != key(b)          # pairing differs with the seed


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips_at_stated_median(name):
    m = mix(name)
    s = T.schedule(m, CELL, 11, 200.0)
    for key, attr in (("prompt", "prompt_len"), ("output", "output_len")):
        spec, vals = m[key], np.array([getattr(x, attr) for x in s])
        assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
        median = (spec["median"] if spec["kind"] == "lognormal"
                  else (spec["min"] + spec["max"]) / 2)
        assert abs(np.median(vals) - median) <= 0.02 * median + 1


def test_lognormal_multiset_is_its_quantiles():
    spec = {"kind": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
            "max": 10_000}
    v = T.length_multiset(spec, 1001)
    assert v[500] == 100
    assert np.all(np.diff(v) >= 0)
    # quartiles of a lognormal: median * exp(+-0.6745 sigma)
    assert abs(v[250] - 100 * np.exp(-0.6745 * 0.5)) <= 1
    assert abs(v[750] - 100 * np.exp(0.6745 * 0.5)) <= 1


def test_mmpp_state_rates_and_poisson_rate():
    arr = {"kind": "mmpp2", "low_factor": 0.3, "high_factor": 2.0,
           "state_seconds": 5.0}
    t = T.arrival_times(arr, 4.0, 40.0, np.random.default_rng(0))
    assert np.all(np.diff(t) >= 0) and t.min() >= 0 and t.max() < 40
    for k in range(8):
        n = np.sum((t >= 5 * k) & (t < 5 * k + 5))
        assert n == round(4.0 * (2.0 if k % 2 else 0.3) * 5)
    # a cut inside a state splits its arrivals by a fixed count
    for seed in range(5):
        c = T.arrival_times(arr, 4.0, 40.0, np.random.default_rng(seed),
                            cuts=(6.0,))
        assert np.sum((c >= 5) & (c < 6)) == round(8.0 * 1)
        assert np.sum((c >= 6) & (c < 10)) == round(8.0 * 4)
    p = T.arrival_times({"kind": "poisson"}, 4.0, 40.0,
                        np.random.default_rng(0))
    assert len(p) == 160
    gaps = np.diff(p)
    assert abs(gaps.mean() - 0.25) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1   # exponential


@pytest.mark.parametrize("name", MIXES)
def test_tier_shares_and_deadlines(name):
    s = T.schedule(mix(name), CELL, 5, 100.0)
    share = sum(x.tier == "interactive" for x in s) / len(s)
    assert abs(share - 0.7) <= 1 / len(s)
    for x in s:
        tier = CELL["tiers"][0 if x.tier == "interactive" else 1]
        assert x.deadline == pytest.approx(
            (tier["ttft_ms"] + tier["tpot_ms"] * (x.output_len - 1)) / 1e3)


@pytest.mark.parametrize("name", MIXES)
def test_window_marks_follow_lead_in(name):
    m = mix(name)
    s = T.schedule(m, CELL, 9, 30.0)
    for x in s:
        assert x.in_window == (m["lead_in_s"] <= x.due
                               < m["lead_in_s"] + 30.0)
    assert any(x.in_window for x in s)


def test_prompt_tokens_seeded_and_in_vocab():
    a = T.prompt_tokens(2**33 + 1, 4, 100, 512)
    assert np.array_equal(a, T.prompt_tokens(2**33 + 1, 4, 100, 512))
    assert not np.array_equal(a, T.prompt_tokens(2**33 + 1, 5, 100, 512))
    assert a.dtype == np.int32 and a.min() >= 2 and a.max() < 512


def test_every_block_holds_one_value_of_each_stratum():
    values = np.arange(100)
    out = T.stratified_order(values, np.random.default_rng(3))
    assert sorted(out) == list(values)
    for b in range(len(values) // T.BLOCK):
        block = out[b * T.BLOCK:(b + 1) * T.BLOCK]
        assert sorted(v % T.BLOCK for v in block) == list(range(T.BLOCK))
    assert not np.array_equal(out, T.stratified_order(
        values, np.random.default_rng(4)))
