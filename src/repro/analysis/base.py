"""Shared lint infrastructure: findings, suppressions, baselines, scoping.

A :class:`Finding` is one rule violation at one source location. Its
*fingerprint* is deliberately line-number-free — ``(checker, repo-relative
path, normalized source snippet, occurrence index)`` — so unrelated edits
above a legacy finding never churn the committed baseline, while a second
identical violation in the same file IS a new finding (the occurrence
index disambiguates).

Suppressions are source comments::

    risky_line()            # reprolint: disable=sync-point
    # reprolint: disable=bare-assert,determinism   (applies to next line)

A suppression names the checker(s) it silences (or ``all``); it applies
to the finding's own line or the line directly above (multi-line
expressions report the line their AST node starts on).

The baseline file (``reprolint.baseline.json`` at the repo root) pins the
legacy findings the lint run tolerates: findings whose fingerprint is in
the baseline are *baselined* (reported, never failing), anything else is
*new* (fails), and baseline entries no findings match anymore are *stale*
(the debt was paid — remove the entry).
"""
from __future__ import annotations

import ast
import hashlib
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set

_SUPPRESS_RE = re.compile(r"#\s*reprolint:\s*disable=([\w,\s-]+)")


def rel_path(path) -> str:
    """Repo-stable identity for ``path``: the posix path from its last
    ``repro/`` package component down (``repro/serving/engine.py``), so
    fingerprints agree no matter where the tree is checked out or which
    directory the lint runs from. ``tests/`` and ``benchmarks/`` trees
    (now also linted) get the same treatment — ``tests/test_foo.py``,
    ``benchmarks/fig12_latency.py``. Anything else falls back to its
    posix form as given."""
    p = Path(path).as_posix()
    for root in ("repro", "tests", "benchmarks"):
        marker = f"/{root}/"
        i = p.rfind(marker)
        if i >= 0:
            return root + "/" + p[i + len(marker):]
        if p.startswith(root + "/"):
            return p
    return p


@dataclass
class Finding:
    checker: str
    path: str                # repo-stable rel path (see rel_path)
    line: int
    message: str
    snippet: str = ""        # the offending source line, stripped
    occurrence: int = 0      # index among same-(checker, path, snippet)
    file: str = ""           # real on-disk path (CI annotations only;
    #                          NOT part of the fingerprint)

    @property
    def fingerprint(self) -> str:
        key = "|".join((self.checker, self.path,
                        " ".join(self.snippet.split()),
                        str(self.occurrence)))
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def __str__(self):
        return (f"{self.path}:{self.line}: [{self.checker}] {self.message}"
                + (f"\n    {self.snippet}" if self.snippet else ""))


class SourceFile:
    """One parsed source file handed to every checker: raw text, line
    list, AST, and the per-line suppression table."""

    def __init__(self, path, text: Optional[str] = None):
        self.path = Path(path)
        self.rel = rel_path(path)
        self.text = self.path.read_text() if text is None else text
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text, filename=str(path))
        self.suppressions: Dict[int, Set[str]] = {}
        for lineno, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                names = {n.strip() for n in m.group(1).split(",") if n.strip()}
                self.suppressions[lineno] = names

    def line_at(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, checker: str, lineno: int) -> bool:
        for ln in (lineno, lineno - 1):
            names = self.suppressions.get(ln)
            if names and (checker in names or "all" in names):
                return True
        return False

    def finding(self, checker: str, node: ast.AST, message: str):
        """Build a Finding for ``node`` unless a suppression covers it."""
        lineno = getattr(node, "lineno", 1)
        if self.suppressed(checker, lineno):
            return None
        return Finding(checker=checker, path=self.rel, line=lineno,
                       message=message, snippet=self.line_at(lineno),
                       file=str(self.path))


class Checker:
    """One lint rule family. Subclasses set ``name`` and implement
    :meth:`check`; :meth:`applies_to` scopes which files are visited."""

    name = "abstract"
    description = ""

    def applies_to(self, sf: SourceFile) -> bool:
        return True

    def check(self, sf: SourceFile) -> Iterable[Finding]:
        raise NotImplementedError


class ProjectChecker:
    """A whole-project (interprocedural) rule family: runs ONCE over the
    per-file facts of every linted file (see :mod:`callgraph`) instead
    of per file — which is also what lets the ``--cache`` layer skip
    re-parsing unchanged files while interprocedural checks still see
    the whole tree."""

    name = "abstract-project"
    description = ""

    def check_project(self, facts: Dict[str, object],
                      graph) -> Iterable[Finding]:
        raise NotImplementedError


def assign_occurrences(findings: List[Finding]) -> List[Finding]:
    """Stamp each finding's occurrence index among its same-snippet twins
    (in (path, line) order) so fingerprints are unique and stable."""
    findings = sorted(findings, key=lambda f: (f.path, f.line, f.checker))
    seen: Dict[tuple, int] = {}
    for f in findings:
        key = (f.checker, f.path, " ".join(f.snippet.split()))
        f.occurrence = seen.get(key, 0)
        seen[key] = f.occurrence + 1
    return findings


@dataclass
class LintResult:
    findings: List[Finding] = field(default_factory=list)
    new: List[Finding] = field(default_factory=list)         # fail CI
    baselined: List[Finding] = field(default_factory=list)   # legacy debt
    stale: List[dict] = field(default_factory=list)          # paid-off debt

    @property
    def ok(self) -> bool:
        return not self.new


def split_against_baseline(findings: List[Finding],
                           baseline: List[dict]) -> LintResult:
    res = LintResult(findings=findings)
    known = {e["fingerprint"]: e for e in baseline}
    matched: Set[str] = set()
    for f in findings:
        fp = f.fingerprint
        if fp in known:
            matched.add(fp)
            res.baselined.append(f)
        else:
            res.new.append(f)
    res.stale = [e for e in baseline if e["fingerprint"] not in matched]
    return res


def load_baseline(path) -> List[dict]:
    doc = json.loads(Path(path).read_text())
    return doc.get("findings", [])


def write_baseline(path, findings: List[Finding]) -> None:
    doc = {
        "comment": ("reprolint legacy-finding baseline: every entry is "
                    "known debt to burn down, NOT an allowance for new "
                    "code. Remove entries as they are fixed; never add "
                    "one without a review saying why it cannot be fixed "
                    "now."),
        "findings": [{"fingerprint": f.fingerprint, "checker": f.checker,
                      "path": f.path, "line": f.line,
                      "message": f.message, "snippet": f.snippet}
                     for f in findings],
    }
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")


# ---------------------------------------------------------------------------
# Scope predicates shared by checkers
# ---------------------------------------------------------------------------

def is_engine_file(rel: str) -> bool:
    """The run-execution hot path lives here (sync/retrace checkers)."""
    return rel.endswith("repro/serving/engine.py") \
        or rel == "repro/serving/engine.py"


def is_test_file(rel: str) -> bool:
    """Pytest tree: asserts are the idiom there (bare-assert exempt),
    and tests deliberately poke deprecated shims (Executor-alias rule
    exempt)."""
    return rel.startswith("tests/") or "/tests/" in rel


def is_benchmark_file(rel: str) -> bool:
    return rel.startswith("benchmarks/") or "/benchmarks/" in rel


#: Modules whose notion of time is VIRTUAL (the discrete-event clock) or
#: that feed it: wall-clock reads and unseeded RNG here silently break
#: replay determinism and sim/JAX parity. ``launch/roofline.py`` and
#: ``launch/dryrun.py`` are included by audit decision — their wall-clock
#: probe timings are legitimate but must stay annotated so a new one is a
#: conscious choice.
VIRTUAL_TIME_SUFFIXES = (
    "repro/serving/server.py",
    "repro/serving/session.py",
    "repro/serving/metrics.py",
    "repro/serving/traffic.py",
    "repro/serving/workload.py",
    "repro/serving/registry.py",
    "repro/serving/backend.py",
    "repro/launch/roofline.py",
    "repro/launch/dryrun.py",
)


#: Audited wall-clock boundaries: modules whose *job* is to touch the
#: wall clock, reviewed as a unit rather than via per-line suppressions.
#: The serving gateway is the canonical case — it paces the virtual-time
#: session against real time (``SessionDriver``: ``target = (loop.time()
#: - t0) * time_scale``), serves SSE to real sockets, and enforces
#: wall-clock request timeouts. Per-line ``# reprolint:`` pragmas on
#: every ``loop.time()`` there would be pure noise and would train
#: readers to ignore suppressions; declaring the prefix keeps the audit
#: meaningful where it matters (the sim/replay path stays strict: a
#: clock read in ``serving/session.py`` et al. still fires, and taint
#: still propagates out of any NON-audited module into virtual-time
#: code). Adding a prefix here is a reviewed audit decision — the
#: boundary module must keep wall time out of SLA/latency arithmetic,
#: as ``gateway/bridge.py``'s module docstring spells out. The span and
#: counter recorder (``serving/tracing.py``) is the other: the session
#: and the engine call into it, and it returns them nothing they could
#: put on the session clock.
WALLCLOCK_AUDITED_PREFIXES = (
    "repro/serving/gateway/",
    "repro/serving/tracing.py",
)


def is_wallclock_audited(rel: str) -> bool:
    """True when ``rel`` lies inside a declared, audited wall-clock
    boundary (see :data:`WALLCLOCK_AUDITED_PREFIXES`)."""
    return rel.startswith(WALLCLOCK_AUDITED_PREFIXES)


def is_virtual_time_file(rel: str) -> bool:
    if "repro/core/" in rel:
        return True
    # paper-figure benchmarks drive the virtual-time simulator: their
    # reported latencies/SLAs must come from the event clock too
    if is_benchmark_file(rel) and Path(rel).name.startswith("fig"):
        return True
    return any(rel.endswith(sfx) for sfx in VIRTUAL_TIME_SUFFIXES)


# ---------------------------------------------------------------------------
# Small AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> str:
    """'np.random.default_rng' for nested Attribute/Name chains, '' when
    the expression is not a plain dotted chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def walk_calls(tree: ast.AST) -> Iterable[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node
