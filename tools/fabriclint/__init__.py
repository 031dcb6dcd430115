"""fabriclint — in-repo static analysis for the FFI boundary and hot path.

PRs 2, 4, and 5 moved the request hot path into concurrent C++
(src/tbnet) reached from Python through a hand-maintained ctypes table —
the exact seam where drift corrupts silently instead of failing loudly.
The reference codebase leans on compiler-enforced headers plus
gtest/sanitizer CI for this; fabriclint is that role for a
Python-driven native plane:

- **ffi** (ffi_check.py): the ``extern "C"`` declarations in
  src/tbutil/tbutil.h + src/tbnet/tbnet.h, parsed, cross-checked
  against ``native.SIGNATURES`` — names, arity, integer width and
  signedness, callback (CFUNCTYPE) layouts, and struct layouts
  (ctypes mirror AND the numpy drain dtype).
- **hotpath** (hotpath.py): functions marked ``# fabriclint: hotpath``
  must not acquire locks, log, do I/O, or run per-record Python loops
  (the vectorization regression class PR 4 fought).
- **registry** (registry_lint.py): every ``define_flag`` is read
  somewhere and carries help text; exposed bvar names are valid
  Prometheus identifiers and the ``native_*``/``mc_*`` families match
  docs/OBSERVABILITY.md; a ``.py``/``.md`` file that README.md or docs/
  names in backticks exists.
- **lifetime** (lifetime.py): every C callback registered from Python
  is held in a keepalive before crossing the FFI (the classic ctypes
  GC-of-live-callback crash), checked structurally.
- **errcheck** (errcheck.py): no ``LIB.tb_*`` call with an
  error-indicating return is silently discarded.

Run everything: ``python -m tools.fabriclint`` (or ``make lint``); the
same checks run inside tier-1 via tests/test_static_analysis.py.
Sanitizer wiring lives in san.py (``make san``).

Exemptions are inline and reasoned::

    # fabriclint: allow(<rule>) <non-empty reason>

on the violating line or the line above it.  An empty reason is itself
a violation (``bad-allow``) — the annotation documents *why* the rule
does not apply, not merely that someone silenced it.
"""

from __future__ import annotations

import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Rules owned by the sibling concurrency checker (tools/fabricverify).
# They are registered here because the two tools share ONE annotation
# grammar: a single ``# fabriclint: allow(<rule>) <reason>`` scanner must
# recognize every rule either tool can fire, or a legitimate fabricverify
# exemption would be reported as bad-allow by fabriclint and vice versa.
VERIFY_RULES = (
    "lock-cycle",       # cycle in the global lock-ordering graph
    "lock-unmodeled",   # a lock primitive the analyzer could not bind
    "lifecycle-borrow",     # SimpleDataPool.borrow with no give_back path
    "lifecycle-timer",      # TimerThread.schedule with no unschedule path
    "lifecycle-callback",   # hook registration with no teardown removal
    "model-stuck",          # reachable model state with no enabled action
    "model-unsafe",         # reachable state violating a safety property
    "model-unrevivable",    # state from which recovery is unreachable
)

# Rules owned by the C++-plane analyzer (tools/fabricscan) — registered
# here for the same reason as VERIFY_RULES: one annotation grammar, one
# scanner validating every allow() either tool can exempt.
SCAN_RULES = (
    "wire-bounds",      # tainted wire length reaches a sink unguarded
    "ownership",        # owned field touched from the wrong thread role
    "owner-missing",    # mutable shared C++ state with no declared owner
    "plane-parity",     # a mirrored constant drifted between the planes
    "scan-parse",       # C++ the model/extractors could not cover
)

RULES = (
    "ffi-missing",      # sigs entry with no header declaration
    "ffi-unbound",      # header function with no sigs entry
    "ffi-arity",        # argument count mismatch
    "ffi-type",         # width/signedness/kind mismatch
    "ffi-callback",     # CFUNCTYPE layout mismatch vs header typedef
    "ffi-struct",       # struct layout mismatch (ctypes or numpy dtype)
    "ffi-parse",        # declaration the header parser could not model
    "hotpath-lock",
    "hotpath-log",
    "hotpath-io",
    "hotpath-loop",
    "flag-dead",
    "flag-undocumented",
    "bvar-name",
    "bvar-undocumented",
    "doc-file-missing",
    "ffi-keepalive",
    "ffi-unchecked",
    "bad-allow",
) + VERIFY_RULES + SCAN_RULES


@dataclass
class Violation:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        rel = os.path.relpath(self.path, REPO_ROOT)
        return f"{rel}:{self.line}: [{self.rule}] {self.message}"


def to_records(violations: Iterable["Violation"]) -> List[Dict[str, object]]:
    """Violations as ``{rule, file, line, reason}`` records — the
    machine-readable report schema shared by ``--json`` on fabriclint and
    fabricverify, stable so CI tooling can diff violation sets across
    commits (files repo-relative, one record per violation)."""

    return [
        {
            "rule": v.rule,
            "file": os.path.relpath(v.path, REPO_ROOT),
            "line": v.line,
            "reason": v.message,
        }
        for v in violations
    ]


_ALLOW_RE = re.compile(
    r"(?:#|//)\s*fabriclint:\s*allow\(([a-z0-9-]+)\)\s*(.*)$"
)
_HOTPATH_RE = re.compile(r"#\s*fabriclint:\s*hotpath\b")


@dataclass
class Annotations:
    """Per-file fabriclint comment annotations."""

    # line -> list of (rule, reason)
    allows: Dict[int, List[Tuple[str, str]]] = field(default_factory=dict)
    hotpath_lines: List[int] = field(default_factory=list)
    bad: List[Violation] = field(default_factory=list)  # malformed allows


def scan_annotations(path: str, source: Optional[str] = None) -> Annotations:
    """Collect ``# fabriclint:`` comments with their line numbers.

    Works for Python (via tokenize, so strings containing the marker
    text don't count) and for C/C++ headers (line-regex fallback).
    """

    if source is None:
        with open(path, "r") as fh:
            source = fh.read()
    ann = Annotations()

    def _record(line_no: int, text: str) -> None:
        m = _ALLOW_RE.search(text)
        if m:
            rule, reason = m.group(1), m.group(2).strip()
            if rule not in RULES:
                ann.bad.append(
                    Violation(
                        "bad-allow", path, line_no,
                        f"allow() names unknown rule {rule!r}",
                    )
                )
            elif not reason:
                ann.bad.append(
                    Violation(
                        "bad-allow", path, line_no,
                        f"allow({rule}) has no reason — exemptions must "
                        "say why the rule does not apply",
                    )
                )
            else:
                ann.allows.setdefault(line_no, []).append((rule, reason))
            return
        if _HOTPATH_RE.search(text):
            ann.hotpath_lines.append(line_no)

    if path.endswith((".h", ".hh", ".hpp", ".c", ".cc", ".cpp")):
        for i, ln in enumerate(source.split("\n"), 1):
            if "fabriclint:" in ln:
                _record(i, ln)
        return ann
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT and "fabriclint:" in tok.string:
                _record(tok.start[0], tok.string)
    except (tokenize.TokenError, SyntaxError, IndentationError):
        pass
    return ann


def allowed(ann: Annotations, rule: str, line: int) -> bool:
    """An allow applies to its own line and the line directly below it
    (i.e. written inline or on the line above the violating statement)."""

    for ln in (line, line - 1):
        for r, _reason in ann.allows.get(ln, ()):  # reason checked at scan
            if r == rule:
                return True
    return False


def iter_py_files(
    roots: Iterable[str] = ("incubator_brpc_tpu", "tools", "examples"),
    include_tests: bool = False,
) -> List[str]:
    """Product-code Python files in lint scope, repo-relative roots."""

    out: List[str] = []
    roots = list(roots) + (["tests"] if include_tests else [])
    for root in roots:
        top = os.path.join(REPO_ROOT, root)
        if os.path.isfile(top) and top.endswith(".py"):
            out.append(top)
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [
                d for d in dirnames if d not in ("__pycache__", "build")
            ]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    out.append(os.path.join(dirpath, f))
    return out


def run_all() -> List[Violation]:
    """Run every checker over the repo; returns unexempted violations."""

    from tools.fabriclint import (
        errcheck,
        ffi_check,
        hotpath,
        lifetime,
        registry_lint,
    )

    out: List[Violation] = []
    out.extend(ffi_check.check())
    out.extend(hotpath.check())
    out.extend(registry_lint.check())
    out.extend(lifetime.check())
    out.extend(errcheck.check())
    # several passes scan the same files for annotations and each reports
    # malformed allows it sees — dedupe on identity
    seen = set()
    unique: List[Violation] = []
    for v in out:
        key = (v.rule, v.path, v.line, v.message)
        if key not in seen:
            seen.add(key)
            unique.append(v)
    return unique
