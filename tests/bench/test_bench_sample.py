"""The correctness sample and the check that decides ``correct``: the
longest finished request is always in the sample, requests that decoded
beside others come before those that did not, and ``correct`` needs the
gap under its limit with enough tokens and merged requests compared."""
import numpy as np
import pytest

from bench import correctness as C

CELL = {"max_logit_gap": 0.25, "check_tokens": 100, "check_max_seqs": 6,
        "check_min_tokens": 50, "check_min_merged": 3}


def finished(lengths, merged):
    return [(np.arange(5, dtype=np.int32), list(range(n)), m)
            for n, m in zip(lengths, merged)]


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_sample_holds_the_longest_and_merged_requests(seed):
    f = finished([10, 400, 20, 30, 40, 50, 60],
                 [True, False, False, True, True, False, True])
    s = C.draw_sample(f, seed, CELL)
    assert len(s[0].served) == 400 and not s[0].merged
    # tokens are enough at once; three merged requests follow the longest
    assert len(s) == 4 and all(x.merged for x in s[1:])


def test_sample_stops_at_its_cap_and_on_nothing():
    f = finished([5] * 10, [False] * 10)
    assert len(C.draw_sample(f, 1, CELL)) == CELL["check_max_seqs"]
    assert C.draw_sample([], 1, CELL) == []


def test_judge_needs_gap_tokens_and_merged_requests():
    s = C.draw_sample(finished([60, 30, 30, 30], [True] * 4), 5, CELL)
    ok, checks = C.judge(0.1, s, CELL)
    assert ok and checks["merged_requests_compared"]["value"] == 3
    assert not C.judge(0.3, s, CELL)[0]
    assert not C.judge(float("nan"), s, CELL)[0]
    few = C.draw_sample(finished([60, 30, 30, 30], [True, True, False,
                                                    False]), 5, CELL)
    assert not C.judge(0.1, few, CELL)[0]
