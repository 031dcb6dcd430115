"""Flag & bvar registry lint.

Flags (``define_flag``): every defined flag must be *read* somewhere in
product code (``get_flag``/``flag_registry.get`` with the literal name,
through any import alias) and must carry help text.  A flag nobody
reads is configuration theater — the operator flips it and nothing
changes (``flag-dead``); a flag without help is unusable from the
``/flags`` service (``flag-undocumented``).

Bvars: every name exposed into the metrics registry must be a valid
identifier for the Prometheus exposition (dots tolerated — the
exposition sanitizes them), and the ``native_*``/``mc_*`` families must
appear in docs/OBSERVABILITY.md — those two prefixes are this repo's
documented contract for the native plane and the multi-controller
plane (``bvar-name``/``bvar-undocumented``).  Names built from
f-strings or concatenation are checked by their literal prefix (the
part before the first runtime placeholder).  The device path's families,
``device_transport_*`` and ``device_link_*``, are held to the whole
name: the benchmark's per-layer readers find them by name, so a
per-link ``f"{pfx}_launch_us"`` has to be in the document as
``device_link_<n>_launch_us``, not only its prefix.

Documents (``doc-file-missing``): a ``.py`` or ``.md`` file that
README.md or a file of docs/ names in backticks has to be in the tree,
so a document cannot go on speaking for a script that was deleted.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from tools.fabriclint import (
    REPO_ROOT,
    Violation,
    allowed,
    iter_py_files,
    scan_annotations,
)

OBSERVABILITY_MD = os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md")

_BVAR_CTORS = {
    "Adder",
    "Maxer",
    "Miner",
    "IntRecorder",
    "LatencyRecorder",
    "PassiveStatus",
    "Status",
    "Window",
    "PerSecond",
}

_PLACEHOLDER = "\x00"
# families whose every name, not only its literal prefix, must be in the
# document (with <n> where the name has a runtime part)
_WHOLE_NAME_FAMILIES = ("device_transport_", "device_link_")
_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:.]*$")


def _str_template(node: ast.AST, local: Dict[str, str]) -> Optional[str]:
    """Resolve an expression to a string template where runtime parts
    become a placeholder byte; None when it is not string-shaped."""

    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, str) else None
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            elif (
                isinstance(v, ast.FormattedValue)
                and isinstance(v.value, ast.Name)
                and v.value.id in local
                and v.conversion == -1
                and v.format_spec is None
            ):
                # f"{pfx}_rtt_us" with pfx = f"device_link_{n}" resolves
                # through the local template
                parts.append(local[v.value.id])
            else:
                parts.append(_PLACEHOLDER)
        return "".join(parts)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _str_template(node.left, local)
        right = _str_template(node.right, local)
        if left is None and right is None:
            return None
        return (left or _PLACEHOLDER) + (right or _PLACEHOLDER)
    if isinstance(node, ast.Name):
        return local.get(node.id)
    if isinstance(node, ast.Call):
        # "x".format(...) / name.replace(...) — runtime content
        return _PLACEHOLDER
    return None


# ---------------------------------------------------------------------------
# flags
# ---------------------------------------------------------------------------


def _flag_aliases(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(names bound to get_flag, names bound to define_flag) in a file."""

    gets, defs = {"get_flag"}, {"define_flag"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module.endswith("utils.flags")
            or node.module.endswith("incubator_brpc_tpu.utils")
        ):
            for a in node.names:
                if a.name == "get_flag":
                    gets.add(a.asname or a.name)
                elif a.name == "define_flag":
                    defs.add(a.asname or a.name)
    return gets, defs


def _registry_method(node: ast.Call, method: str) -> bool:
    """True for ``flag_registry.<method>(...)`` specifically — a bare
    ``.get("name")``/``.define(...)`` on any other receiver is an
    ordinary dict/object call and must NOT count as a flag access
    (``sock.context.get("server")`` would otherwise mask a dead flag
    that happens to share a name with a dict key)."""

    return (
        isinstance(node.func, ast.Attribute)
        and node.func.attr == method
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id in ("flag_registry", "registry")
    )


def _call_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _first_str_arg(node: ast.Call) -> Optional[str]:
    if node.args and isinstance(node.args[0], ast.Constant) and isinstance(
        node.args[0].value, str
    ):
        return node.args[0].value
    return None


def check_flags(paths: Optional[List[str]] = None) -> List[Violation]:
    product = [
        p
        for p in (paths if paths is not None else iter_py_files())
        if os.sep + "tools" + os.sep + "fabriclint" not in p
    ]
    defined: Dict[str, Tuple[str, int, bool]] = {}
    read: Set[str] = set()
    anns = {}
    for path in product:
        with open(path, "r") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source)
        except SyntaxError:
            continue
        anns[path] = scan_annotations(path, source)
        gets, defs = _flag_aliases(tree)
        in_pkg = os.sep + "incubator_brpc_tpu" + os.sep in path
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            cname = _call_name(node)
            arg = _first_str_arg(node)
            if arg is None:
                continue
            if cname in defs or _registry_method(node, "define"):
                if in_pkg:  # flags are a framework-level registry
                    has_help = any(
                        k.arg == "help" for k in node.keywords
                    ) or (
                        len(node.args) > 2
                        and isinstance(node.args[2], ast.Constant)
                        and isinstance(node.args[2].value, str)
                        and node.args[2].value.strip() != ""
                    )
                    defined.setdefault(arg, (path, node.lineno, has_help))
            elif cname in gets or _registry_method(node, "get"):
                read.add(arg)
    out: List[Violation] = []
    for name, (path, line, has_help) in sorted(defined.items()):
        ann = anns.get(path)
        if name not in read:
            if ann is None or not allowed(ann, "flag-dead", line):
                out.append(
                    Violation(
                        "flag-dead", path, line,
                        f"flag {name!r} is defined but never read "
                        "(get_flag) anywhere in product code",
                    )
                )
        if not has_help:
            if ann is None or not allowed(ann, "flag-undocumented", line):
                out.append(
                    Violation(
                        "flag-undocumented", path, line,
                        f"flag {name!r} has no help text — it is "
                        "unreadable from the /flags service",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# bvars
# ---------------------------------------------------------------------------


def _collect_bvar_names(
    tree: ast.Module,
) -> List[Tuple[str, int]]:
    """(name template, line) for every statically-visible exposure."""

    out: List[Tuple[str, int]] = []
    # local single-assignment string templates, resolved per function so
    # `base = "native_method_" + ...; recorder.expose(base)` is checked
    for fn in [
        n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module))
    ]:
        local: Dict[str, str] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and (
                isinstance(node.targets[0], ast.Name)
            ):
                t = _str_template(node.value, local)
                if t is not None:
                    local[node.targets[0].id] = t
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            cname = _call_name(node)
            if cname in _BVAR_CTORS:
                for kw in node.keywords:
                    if kw.arg == "name":
                        t = _str_template(kw.value, local)
                        if t is not None:
                            out.append((t, node.lineno))
            elif cname == "expose" and node.args:
                t = _str_template(node.args[0], local)
                if t is not None:
                    out.append((t, node.lineno))
    # dedupe (module walk + function walks see nested nodes twice)
    return sorted(set(out), key=lambda x: x[1])


def check_bvars(paths: Optional[List[str]] = None) -> List[Violation]:
    with open(OBSERVABILITY_MD, "r") as fh:
        doc = fh.read()
    out: List[Violation] = []
    scope = [
        p
        for p in (paths if paths is not None else iter_py_files())
        if os.sep + "incubator_brpc_tpu" + os.sep in p
    ]
    for path in scope:
        with open(path, "r") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source)
        except SyntaxError:
            continue
        ann = scan_annotations(path, source)
        for template, line in _collect_bvar_names(tree):
            probe = template.replace(_PLACEHOLDER, "x0")
            if not _NAME_RE.match(probe):
                if not allowed(ann, "bvar-name", line):
                    out.append(
                        Violation(
                            "bvar-name", path, line,
                            f"bvar name {template.replace(_PLACEHOLDER, '{}')!r}"
                            " is not a valid metric identifier "
                            "([a-zA-Z_:][a-zA-Z0-9_:.]*)",
                        )
                    )
                continue
            prefix = template.split(_PLACEHOLDER, 1)[0]
            display = template.replace(_PLACEHOLDER, "{}")
            whole_name = prefix.startswith(_WHOLE_NAME_FAMILIES)
            if not (whole_name or prefix.startswith(("native_", "mc_"))):
                continue
            if _PLACEHOLDER not in template:
                documented = template in doc
                what = f"bvar {template!r}"
            elif whole_name:
                # the document writes the runtime part as <n>
                display = template.replace(_PLACEHOLDER, "<n>")
                documented = display in doc
                what = f"bvar family {display!r}"
            else:
                # templated family: the literal prefix is the contract
                documented = len(prefix) >= 8 and prefix in doc
                what = f"bvar family {display!r} (prefix {prefix!r})"
            if not documented and not allowed(ann, "bvar-undocumented", line):
                out.append(
                    Violation(
                        "bvar-undocumented", path, line,
                        f"{what} belongs to a documented family "
                        "(native_*, mc_*, device_transport_*, device_link_*) "
                        "but is not in docs/OBSERVABILITY.md",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# documents
# ---------------------------------------------------------------------------

_BACKTICKED = re.compile(r"`([^`\n]+)`")
_LINE_SUFFIX = re.compile(r":[\d,\-–]+$")
# a stem before the extension: a bare ".py" or a glob "*.py" names no file
_PY_OR_MD = re.compile(r"\w\.(?:py|md)$")
_NOT_THE_TREE = ("__pycache__", "build", "chiprun_out", "chip_scratch")


def doc_paths() -> List[str]:
    """README.md and every ``.md`` file of docs/."""

    docs = os.path.join(REPO_ROOT, "docs")
    return [os.path.join(REPO_ROOT, "README.md")] + [
        os.path.join(docs, f) for f in sorted(os.listdir(docs))
        if f.endswith(".md")
    ]


def _tree_files() -> Set[str]:
    """Root-relative paths of the checkout's files; what building and
    running leave behind (dot and cache directories, chip outputs, the
    parent's copy under chip_scratch/) is not the tree."""

    out: Set[str] = set()
    for dirpath, dirnames, filenames in os.walk(REPO_ROOT):
        dirnames[:] = [
            d for d in dirnames
            if not d.startswith(".") and d not in _NOT_THE_TREE
        ]
        rel = os.path.relpath(dirpath, REPO_ROOT)
        out.update(os.path.normpath(os.path.join(rel, f)) for f in filenames)
    return out


def check_doc_files(docs: Optional[List[str]] = None) -> List[Violation]:
    """A word in backticks that ends in ``.py`` or ``.md`` (a trailing
    ``:line`` dropped) names a file: by its path from the root, by its
    path under ``incubator_brpc_tpu/``, or by its base name."""

    files = _tree_files()
    pkg = "incubator_brpc_tpu/"
    known = (
        files
        | {f[len(pkg):] for f in files if f.startswith(pkg)}
        | {os.path.basename(f) for f in files}
    )
    out: List[Violation] = []
    for path in docs if docs is not None else doc_paths():
        with open(path, "r") as fh:
            for lineno, text in enumerate(fh, 1):
                for m in _BACKTICKED.finditer(text):
                    for word in m.group(1).split():
                        token = _LINE_SUFFIX.sub("", word)
                        if _PY_OR_MD.search(token) and token not in known:
                            out.append(
                                Violation(
                                    "doc-file-missing", path, lineno,
                                    f"`{token}` is not a file of this tree: "
                                    "by its path from the root, under "
                                    "incubator_brpc_tpu/, or by its base name",
                                )
                            )
    return out


def check(paths: Optional[List[str]] = None) -> List[Violation]:
    return check_flags(paths) + check_bvars(paths) + check_doc_files()
