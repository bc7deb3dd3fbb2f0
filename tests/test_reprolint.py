"""reprolint checker suite: each checker catches a seeded violation of
its invariant class and stays quiet on the clean twin, suppressions and
baselines behave, and the repo-wide run matches the committed baseline
EXACTLY (0 new findings, 0 stale entries) — so the suite fails loudly
the moment someone reintroduces a burned-down bug class OR fixes debt
without updating the baseline.

Fixture files are written under ``tmp_path/repro/...`` because path
scoping (hot-path checker only in ``serving/engine.py``, determinism
only in virtual-time modules) keys on the repo-relative suffix after
the last ``repro/`` marker — exactly how fingerprints stay stable
across checkouts.
"""
from pathlib import Path

import pytest

from repro.analysis.base import Finding, rel_path
from repro.analysis.lint import ALL_CHECKERS, run_lint
from repro.analysis import load_baseline

REPO = Path(__file__).resolve().parents[1]


def _write(tmp_path: Path, rel: str, text: str) -> Path:
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return p


def _lint(tmp_path, rel, text, checker=None):
    p = _write(tmp_path, rel, text)
    checkers = [c for c in ALL_CHECKERS if checker is None
                or c.name == checker]
    return run_lint([p], checkers=checkers)


def _names(res):
    return sorted(f.checker for f in res.new)


# ---------------------------------------------------------------------------
# sync-point
# ---------------------------------------------------------------------------

SYNC_VIOLATION = """
import numpy as np

class JaxEngine:
    def execute_run(self, model, sb, node_ids):
        for nid in node_ids:
            toks = self._dispatch(nid)
            val = toks.item()            # hidden per-node sync!
        return 0.0, None
"""

SYNC_CLEAN = """
import numpy as np

class JaxEngine:
    def execute_run(self, model, sb, node_ids):
        out = self._dispatch(node_ids)
        arr = np.asarray(out)  # reprolint: disable=sync-point
        return 0.0, None

    def debug_dump(self):
        # not a hot function: syncing here is fine
        return [np.asarray(a) for a in self.arenas]
"""


def test_sync_point_catches_hidden_sync_in_hot_path(tmp_path):
    res = _lint(tmp_path, "repro/serving/engine.py", SYNC_VIOLATION,
                checker="sync-point")
    assert _names(res) == ["sync-point"]
    assert "execute_run" in res.new[0].message


def test_sync_point_respects_suppression_and_cold_functions(tmp_path):
    res = _lint(tmp_path, "repro/serving/engine.py", SYNC_CLEAN,
                checker="sync-point")
    assert res.new == []


def test_sync_point_scoped_to_engine_module(tmp_path):
    # the same construct in a non-engine file is out of scope
    res = _lint(tmp_path, "repro/serving/metrics.py", SYNC_VIOLATION,
                checker="sync-point")
    assert res.new == []


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

RETRACE_VIOLATION = """
class JaxEngine:
    def execute_run(self, model, sb, node_ids):
        sts = [self.states[r.rid] for r in sb.live_requests]
        # unbucketed batch size in the jit-cache key: one compile per B
        fn = self._fn_mega(0, len(sts), True, sts[0].pos)
        return fn(self.params)
"""

RETRACE_CLEAN = """
class JaxEngine:
    def execute_run(self, model, sb, node_ids):
        sts = [self.states[r.rid] for r in sb.live_requests]
        fn = self._fn_mega(0, _pow2(len(sts)), True,
                           _pow2(sts[0].pos))
        return fn(self.params)
"""

JIT_OUTSIDE_GETTER = """
import jax

class JaxEngine:
    def execute_run(self, model, sb, node_ids):
        fn = jax.jit(lambda x: x + 1)    # uncached jit: retrace per call
        return fn(1.0)
"""


def test_retrace_catches_unbucketed_dynamic_scalars(tmp_path):
    res = _lint(tmp_path, "repro/serving/engine.py", RETRACE_VIOLATION,
                checker="retrace-hazard")
    assert len(res.new) >= 1
    assert all(f.checker == "retrace-hazard" for f in res.new)


def test_retrace_accepts_pow2_bucketed_args(tmp_path):
    res = _lint(tmp_path, "repro/serving/engine.py", RETRACE_CLEAN,
                checker="retrace-hazard")
    assert res.new == []


def test_retrace_flags_jit_outside_cached_getter(tmp_path):
    res = _lint(tmp_path, "repro/serving/engine.py", JIT_OUTSIDE_GETTER,
                checker="retrace-hazard")
    assert _names(res) == ["retrace-hazard"]


# ---------------------------------------------------------------------------
# bare-assert
# ---------------------------------------------------------------------------

def test_bare_assert_flags_runtime_invariant(tmp_path):
    res = _lint(tmp_path, "repro/serving/foo.py",
                "def f(x):\n    assert x > 0, 'bad'\n    return x\n",
                checker="bare-assert")
    assert _names(res) == ["bare-assert"]
    assert "python -O" in res.new[0].message


def test_bare_assert_suppression_on_preceding_line(tmp_path):
    res = _lint(tmp_path, "repro/serving/foo.py",
                "def f(x):\n"
                "    # reprolint: disable=bare-assert\n"
                "    assert x > 0\n",
                checker="bare-assert")
    assert res.new == []


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

DET_VIOLATIONS = """
import time
import random
import numpy as np

def schedule(queue):
    t = time.time()                      # wall clock in sim path
    rng = np.random.default_rng()        # unseeded
    jitter = random.random()             # global stdlib RNG
    pick = np.random.rand()              # numpy GLOBAL RNG
    best = min({q.name for q in queue}, key=lambda n: len(n))
    return t, rng, jitter, pick, best
"""

DET_CLEAN = """
import numpy as np

def schedule(queue, now, seed):
    rng = np.random.default_rng(seed)            # seeded: fine
    names = sorted({q.name for q in queue})      # key-less: total order
    return now + rng.exponential(1.0), names
"""


def test_determinism_catches_all_violation_kinds(tmp_path):
    res = _lint(tmp_path, "repro/core/sched.py", DET_VIOLATIONS,
                checker="determinism")
    assert len(res.new) == 4
    msgs = " ".join(f.message for f in res.new)
    assert "without a seed" in msgs
    assert "stdlib" in msgs
    assert "GLOBAL" in msgs
    assert "set iteration" in msgs
    # wall-clock reads moved to the interprocedural wallclock-taint
    # checker (see test_dataflow.py) — determinism must NOT double-report
    assert "wall-clock" not in msgs


def test_determinism_clean_patterns_pass(tmp_path):
    res = _lint(tmp_path, "repro/core/sched.py", DET_CLEAN,
                checker="determinism")
    assert res.new == []


def test_determinism_scoped_to_virtual_time_modules(tmp_path):
    # launch/train.py is NOT a virtual-time module: wall clock is fine
    res = _lint(tmp_path, "repro/launch/train.py", DET_VIOLATIONS,
                checker="determinism")
    assert res.new == []


# ---------------------------------------------------------------------------
# backend-contract
# ---------------------------------------------------------------------------

CONTRACT_VIOLATION = """
from repro.serving.backend import Backend

class DriftingBackend(Backend):
    def execute(self, sb, node_id):      # lost the model key!
        return 0.0

    def memory_stats(self, which=None):  # renamed the model key!
        return None
"""

CONTRACT_CLEAN = """
from repro.serving.backend import Backend

class GoodBackend(Backend):
    def execute(self, model, sb, node_id):
        return 0.0

    def helper(self, x):                 # non-contract method: free-form
        return x
"""

EXECUTOR_USE = """
from repro.serving.server import Executor

def build():
    return Executor()
"""


def test_contract_catches_signature_drift(tmp_path):
    res = _lint(tmp_path, "repro/serving/custom.py", CONTRACT_VIOLATION,
                checker="backend-contract")
    assert len(res.new) == 2
    assert all("model-keyed" in f.message for f in res.new)


def test_contract_accepts_conforming_subclass(tmp_path):
    res = _lint(tmp_path, "repro/serving/custom.py", CONTRACT_CLEAN,
                checker="backend-contract")
    assert res.new == []


def test_contract_flags_retired_executor_alias(tmp_path):
    res = _lint(tmp_path, "repro/serving/custom.py", EXECUTOR_USE,
                checker="backend-contract")
    assert len(res.new) >= 1
    assert all("Executor" in f.message for f in res.new)


# ---------------------------------------------------------------------------
# swallowed-exception
# ---------------------------------------------------------------------------

SWALLOW_VIOLATIONS = """
def fetch(x):
    try:
        return x.value()
    except:
        return None

def probe(x):
    try:
        x.poke()
    except Exception:
        pass
"""

SWALLOW_CLEAN = """
def fetch(x):
    try:
        return x.value()
    except KeyError:
        return None

def probe(x):
    try:
        x.poke()
    except Exception as e:
        record(e)                        # handled, not swallowed

def relay(x):
    try:
        return x.value()
    except:
        raise                            # bare but transparent
"""

def test_swallow_flags_bare_and_trivial_handlers(tmp_path):
    res = _lint(tmp_path, "repro/launch/foo.py", SWALLOW_VIOLATIONS,
                checker="swallowed-exception")
    assert len(res.new) == 2
    msgs = " ".join(f.message for f in res.new)
    assert "bare" in msgs and "black hole" in msgs


def test_swallow_accepts_specific_recorded_or_reraised(tmp_path):
    res = _lint(tmp_path, "repro/launch/foo.py", SWALLOW_CLEAN,
                checker="swallowed-exception")
    assert res.new == []


def test_swallow_no_longer_owns_the_slot_rule(tmp_path):
    # the syntactic slot rule (old rule B) is superseded by the
    # path-sensitive slot-leak checker (test_dataflow.py): the shape it
    # used to pattern-match is out of swallowed-exception's scope now
    leaky = ("class Engine:\n"
             "    def dispatch(self, model, req):\n"
             "        try:\n"
             "            slot = self.slot_of(req)\n"
             "            return self._run(slot)\n"
             "        except RuntimeError:\n"
             "            return None\n")
    res = _lint(tmp_path, "repro/serving/custom.py", leaky,
                checker="swallowed-exception")
    assert res.new == []


# ---------------------------------------------------------------------------
# fingerprints and baselines
# ---------------------------------------------------------------------------

def test_fingerprint_survives_unrelated_edits(tmp_path):
    before = "def f(x):\n    assert x > 0\n"
    after = "import os\n\n\ndef g():\n    pass\n\n\ndef f(x):\n    assert x > 0\n"
    f1 = _lint(tmp_path / "a", "repro/serving/foo.py", before,
               checker="bare-assert").new[0]
    f2 = _lint(tmp_path / "b", "repro/serving/foo.py", after,
               checker="bare-assert").new[0]
    assert f1.line != f2.line            # the site moved...
    assert f1.fingerprint == f2.fingerprint  # ...the identity did not


def test_baseline_splits_new_known_and_stale(tmp_path):
    two = "def f(x):\n    assert x > 0\n    assert x < 9\n"
    res = _lint(tmp_path, "repro/serving/foo.py", two,
                checker="bare-assert")
    baseline = [{"fingerprint": res.new[0].fingerprint,
                 "checker": "bare-assert", "path": res.new[0].path},
                {"fingerprint": "feedfacedeadbeef",
                 "checker": "bare-assert", "path": "repro/gone.py"}]
    p = tmp_path / "repro/serving/foo.py"
    res2 = run_lint([p], checkers=[c for c in ALL_CHECKERS
                                   if c.name == "bare-assert"],
                    baseline=baseline)
    assert len(res2.new) == 1            # the un-baselined assert
    assert len(res2.baselined) == 1      # the pinned one
    assert len(res2.stale) == 1          # the paid-down debt
    assert not res2.ok


# ---------------------------------------------------------------------------
# the repo itself
# ---------------------------------------------------------------------------

def test_repo_matches_committed_baseline_exactly():
    """The gate CI runs: linting ``src/``, ``tests/`` and
    ``benchmarks/`` with all nine checkers against the committed
    baseline yields zero NEW findings and zero STALE entries. If this
    fails you either introduced a violation (fix it) or fixed known
    debt (regenerate the baseline with --write-baseline and commit the
    shrunken file)."""
    baseline = load_baseline(REPO / "reprolint.baseline.json")
    res = run_lint([REPO / "src", REPO / "tests", REPO / "benchmarks"],
                   baseline=baseline)
    assert res.new == [], "\n".join(str(f) for f in res.new)
    assert res.stale == [], f"stale baseline entries: {res.stale}"
    # the debt is fully burned down: the baseline stays EMPTY
    assert res.baselined == [], \
        "the baseline must stay empty — fix the finding instead of " \
        "re-pinning it"


def test_rel_path_normalizes_across_checkouts():
    assert rel_path("/home/x/repo/src/repro/serving/engine.py") \
        == "repro/serving/engine.py"
    assert rel_path("/tmp/pytest-1/repro/serving/engine.py") \
        == "repro/serving/engine.py"
    assert rel_path("/home/x/repo/tests/test_session.py") \
        == "tests/test_session.py"
    assert rel_path("/home/x/repo/benchmarks/fig5_time_window.py") \
        == "benchmarks/fig5_time_window.py"


# ---------------------------------------------------------------------------
# scoped checker sets outside src/
# ---------------------------------------------------------------------------

def test_bare_assert_exempt_in_tests(tmp_path):
    # pytest asserts ARE the assertion mechanism in tests
    res = _lint(tmp_path, "tests/test_foo.py",
                "def test_x():\n    assert 1 + 1 == 2\n",
                checker="bare-assert")
    assert res.new == []


def test_bare_assert_still_active_in_benchmarks(tmp_path):
    res = _lint(tmp_path, "benchmarks/bench_foo.py",
                "def run(x):\n    assert x > 0\n",
                checker="bare-assert")
    assert _names(res) == ["bare-assert"]


def test_determinism_active_in_fig_benchmarks(tmp_path):
    # fig* benches ARE the paper's deterministic artifacts
    res = _lint(tmp_path, "benchmarks/fig5_time_window.py", DET_VIOLATIONS,
                checker="determinism")
    assert len(res.new) == 4


def test_determinism_inactive_in_wall_time_benchmarks(tmp_path):
    res = _lint(tmp_path, "benchmarks/engine_decode_bench.py",
                DET_VIOLATIONS, checker="determinism")
    assert res.new == []


def test_executor_reference_rule_exempt_in_tests(tmp_path):
    res = _lint(tmp_path, "tests/test_compat.py", EXECUTOR_USE,
                checker="backend-contract")
    assert res.new == []


def test_contract_requires_residency_pair(tmp_path):
    half = ("class SimBackend:\n"
            "    def reset_request(self, model, req):\n"
            "        pass\n")
    res = _lint(tmp_path, "repro/serving/custom.py", half,
                checker="backend-contract")
    assert len(res.new) == 1
    assert "release_request" in res.new[0].message
    both = half + ("\n    def release_request(self, model, req):\n"
                   "        pass\n")
    res2 = _lint(tmp_path / "b", "repro/serving/custom.py", both,
                 checker="backend-contract")
    assert res2.new == []


# ---------------------------------------------------------------------------
# the span recorder is an audited wall-clock boundary
# ---------------------------------------------------------------------------

RECORDER = """
import time


def begin(name):
    return (name, time.perf_counter())
"""

SESSION_CALLS_RECORDER = """
from repro.serving import tracing


class ServingSession:
    def step(self):
        sp = tracing.ON and tracing.begin("serve.step")
        return sp
"""

SESSION_READS_CLOCK = """
import time


class ServingSession:
    def step(self):
        return time.perf_counter()
"""


def test_recorder_is_audited_but_session_clock_reads_still_fire(tmp_path):
    from repro.analysis.lint import PROJECT_CHECKERS
    rec = _write(tmp_path, "src/repro/serving/tracing.py", RECORDER)
    calls = _write(tmp_path / "a", "src/repro/serving/session.py",
                   SESSION_CALLS_RECORDER)
    res = run_lint([rec, calls], checkers=[],
                   project_checkers=PROJECT_CHECKERS)
    assert res.new == []
    reads = _write(tmp_path / "b", "src/repro/serving/session.py",
                   SESSION_READS_CLOCK)
    res = run_lint([rec, reads], checkers=[],
                   project_checkers=PROJECT_CHECKERS)
    assert [(f.checker, f.path) for f in res.new] == \
        [("wallclock-taint", "repro/serving/session.py")]


# ---------------------------------------------------------------------------
# the --cache layer
# ---------------------------------------------------------------------------

def test_cache_reuses_results_and_keeps_project_facts(tmp_path):
    """Warm-cache runs must reproduce per-file findings AND still give
    the project checkers the full fact set (the wallclock-taint chain
    crosses a cached and a fresh file)."""
    helper = _write(tmp_path, "src/repro/launch/helper.py",
                    "import time\n\n\ndef stamp():\n"
                    "    return time.perf_counter()\n")
    sink = _write(tmp_path, "src/repro/core/sched.py",
                  "from repro.launch.helper import stamp\n\n\n"
                  "def schedule(queue):\n    return stamp()\n")
    cache = tmp_path / "cache.json"
    cold = run_lint([helper, sink], cache_path=cache)
    assert [f.checker for f in cold.new] == ["wallclock-taint"]
    assert cache.exists()
    warm = run_lint([helper, sink], cache_path=cache)
    assert [(f.checker, f.path, f.line, f.fingerprint) for f in warm.new] \
        == [(f.checker, f.path, f.line, f.fingerprint) for f in cold.new]


def test_stale_cache_version_is_recomputed_not_reused(tmp_path):
    """A cache written under an older CACHE_VERSION must be discarded
    wholesale: v1 facts lack the async effect summaries (is_async /
    awaited / suppressed_blocking) the async checkers read, so reusing
    them would silently blind blocking-in-async on unchanged files."""
    import json

    from repro.analysis import lint as lint_mod

    p = _write(tmp_path, "src/repro/serving/gateway/gw.py",
               "import time\n\n\nasync def handler():\n"
               "    time.sleep(1)\n")
    cache = tmp_path / "cache.json"
    cold = run_lint([p], cache_path=cache)
    assert "blocking-in-async" in _names(cold)
    # regress the on-disk cache to the previous schema version, with
    # entries a naive loader would happily reuse (hash matches because
    # we keep the v2 hashes — only the envelope version is old)
    doc = json.loads(cache.read_text())
    doc["version"] = lint_mod.CACHE_VERSION - 1
    for entry in doc["files"].values():
        for fn in entry["facts"]["functions"].values():
            fn.pop("is_async", None)         # v1 facts had no summaries
    cache.write_text(json.dumps(doc))
    warm = run_lint([p], cache_path=cache)
    assert _names(warm) == _names(cold)
    assert json.loads(cache.read_text())["version"] \
        == lint_mod.CACHE_VERSION


def test_cache_invalidated_by_content_change(tmp_path):
    p = _write(tmp_path, "src/repro/serving/foo.py",
               "def f(x):\n    assert x > 0\n")
    cache = tmp_path / "cache.json"
    first = run_lint([p], cache_path=cache)
    assert _names(first) == ["bare-assert"]
    p.write_text("def f(x):\n    if x <= 0:\n"
                 "        raise ValueError(x)\n")
    second = run_lint([p], cache_path=cache)
    assert second.new == []


# ---------------------------------------------------------------------------
# --format github
# ---------------------------------------------------------------------------

def test_github_format_emits_error_annotations(tmp_path, capsys):
    from repro.analysis.lint import main
    p = _write(tmp_path, "repro/serving/foo.py",
               "def f(x):\n    assert x > 0\n")
    empty = _write(tmp_path, "empty-baseline.json", '{"findings": []}')
    rc = main([str(p), "--format", "github", "--baseline", str(empty)])
    out = capsys.readouterr().out
    assert rc == 1
    line = next(l for l in out.splitlines() if l.startswith("::error"))
    assert f"file={p}" in line
    assert "line=2" in line
    assert "title=reprolint bare-assert" in line


def test_github_format_escapes_newlines_and_percent():
    from repro.analysis.lint import _escape_gha
    assert _escape_gha("a\nb%c") == "a%0Ab%25c"
