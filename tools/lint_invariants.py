"""Python-side AST lint for repo invariants the HLO auditor can only see
AFTER lowering (ISSUE 10) — run in CI next to ruff, so a seam escape is
flagged at the source line that writes it, before it ever compiles.

Rules (each exercised by a fixture test in
tests/test_lint_invariants.py):

  naked-collective   no ``jax.lax.{all_to_all, psum_scatter, all_gather,
                     ppermute, ragged_all_to_all}`` call outside
                     ``ops/wire.py`` — every
                     exchange collective lives behind the wire seam, the
                     static source-side twin of the wire-seam HLO pass.
  hot-params-access  no ``params["hot"]`` subscript outside
                     ``layers/dist_model_parallel.py`` /
                     ``ops/sparse_update.py`` — the replicated hot shard
                     has exactly two owners (the forward split and the
                     dense hot update); anything else touching it
                     bypasses the sync_hot_rows consistency seam.
  wallclock-in-jit   no ``time.time()`` / ``datetime.now()`` in
                     jitted-code modules (ops/, layers/, parallel/,
                     schedule/) — a wall clock read inside a traced
                     function freezes ONE timestamp into the compiled
                     program; host-side timing belongs in utils/, obs/
                     or the drivers.
  shadow-metric      no direct ``LatencyHistogram`` / ``Counter`` /
                     ``Gauge`` construction (the obs.registry metric
                     classes) outside ``obs/`` — ONE metric namespace
                     (ISSUE 11): components obtain instruments through
                     a `MetricRegistry` (``registry.histogram(...)``),
                     never by hand-rolling a private histogram the
                     snapshot/SLO layer cannot see. Import-tracked, so
                     ``from ...utils.metrics import LatencyHistogram``
                     aliases and module-attribute forms cannot evade —
                     and ``collections.Counter`` stays untouched (only
                     names imported from the metric modules count).
  upward-import      no module under ``ops/``, ``parallel/`` or
                     ``layers/`` imports a subsystem package
                     (``serving``, ``store``, ``vocab``, ``fleet``,
                     ``schedule``, ``faults``, ``analysis``), nor from
                     ``obs/`` anything but ``obs.stages`` (the scope
                     names the step is traced under) and ``obs.spans``
                     (the host spans a layer's host-side methods are
                     timed under: ``init``, ``get_weights``) — what a
                     cell runs
                     is read from the step down, never through a
                     package above it. Standing exceptions are named
                     debts, listed in ``UPWARD_EXCEPTIONS`` by file,
                     function and module.

Escapes: append ``# lint: allow(<rule>)`` to the offending line (or the
line directly above). Escapes are themselves greppable, which is the
point — an allowed violation is a reviewed decision, not an accident.

Usage:
  python tools/lint_invariants.py            # lint the package, exit 1
                                             # on findings
  python tools/lint_invariants.py --json     # machine-readable findings
  python tools/lint_invariants.py PATH...    # lint specific files
"""

import argparse
import ast
import json
import os
import re
import sys
from typing import List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "distributed_embeddings_tpu"

COLLECTIVES = ("all_to_all", "psum_scatter", "all_gather", "ppermute",
               "ragged_all_to_all")
COLLECTIVE_ALLOWED = (os.path.join("ops", "wire.py"),)
HOT_ALLOWED = (os.path.join("layers", "dist_model_parallel.py"),
               os.path.join("ops", "sparse_update.py"))
# modules whose code runs under jit traces: a wall-clock call here is
# either traced (frozen constant) or a host sync hazard
JIT_MODULE_DIRS = ("ops", "layers", "parallel", "schedule")

# obs.registry metric classes: construction belongs to the registry
# (obs/ is the whole allowed subtree — registry.py constructs, spans.py
# and instrument.py are the instrumentation home)
METRIC_CLASSES = ("LatencyHistogram", "Counter", "Gauge")
METRIC_MODULES = (
    "distributed_embeddings_tpu.obs.registry",
    "distributed_embeddings_tpu.obs",
    "distributed_embeddings_tpu.utils.metrics",   # the re-export
)
METRIC_ALLOWED_DIR = "obs"

# the hot path's packages, and what they may not import
LOW_DIRS = ("ops", "parallel", "layers")
SUBSYSTEMS = ("serving", "store", "vocab", "fleet", "schedule", "faults",
              "analysis")
OBS_ALLOWED = ("obs.stages", "obs.spans")
# (file, enclosing function, imported module): each a debt ROADMAP names
UPWARD_EXCEPTIONS = (
    # ROADMAP D5, the host-apply ladder: the quantized arm of
    # host_bucket_apply counts its rows in the default registry
    # (layers/dist_model_parallel.py:3647 today)
    (os.path.join("layers", "dist_model_parallel.py"),
     "_host_quantized_touched_apply", "obs.registry"),
)

_ALLOW_RE = re.compile(
    r'#.*?lint:\s*allow\(([\w-]+(?:\s*,\s*[\w-]+)*)\)')


class Finding:
    def __init__(self, rule: str, path: str, line: int, message: str):
        self.rule, self.path, self.line, self.message = \
            rule, path, line, message

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed_rules(src_lines: List[str], lineno: int) -> set:
    """Rules escaped at `lineno` (1-based): an allow comment on the line
    itself or on the line directly above."""
    out = set()
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(src_lines):
            m = _ALLOW_RE.search(src_lines[ln - 1])
            if m:
                out.update(r.strip() for r in m.group(1).split(","))
    return out


def _attr_chain(node: ast.AST) -> Optional[str]:
    """'jax.lax.all_to_all' for nested Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _rel(path: str) -> str:
    return os.path.relpath(path, REPO_ROOT)


def _imports_in_scope(node: ast.AST, fn: Optional[str] = None):
    """(import node, innermost enclosing function's name or None)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.Import, ast.ImportFrom)):
            yield child, fn
        elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield from _imports_in_scope(child, child.name)
        else:
            yield from _imports_in_scope(child, fn)


def _package_modules(node: ast.AST, pkg_rel: str) -> List[str]:
    """What an import statement of a package module names, each relative
    to the package root ('obs.registry.default_registry', 'store');
    imports from outside the package give nothing."""
    if isinstance(node, ast.Import):
        full = [a.name for a in node.names]
    else:
        base = node.module or ""
        if node.level:
            here = [PACKAGE] + pkg_rel.split(os.sep)[:-1]
            base = ".".join(here[:len(here) - node.level + 1]
                            + ([base] if base else []))
        full = [f"{base}.{a.name}" for a in node.names]
    return [m[len(PACKAGE) + 1:] for m in full
            if m.startswith(PACKAGE + ".")]


def _under(mod: str, prefix: str) -> bool:
    """`mod` is the module `prefix` or something inside it."""
    return mod == prefix or mod.startswith(prefix + ".")


def lint_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    """Lint one file. ``rel`` overrides the repo-relative path the
    path-scoped rules key on (fixture tests lint tmp files AS IF they
    lived at a package path)."""
    if rel is None:
        rel = _rel(path)
    in_package = rel.startswith(PACKAGE + os.sep)
    pkg_rel = rel[len(PACKAGE) + 1:] if in_package else rel
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("parse-error", rel, e.lineno or 0, str(e))]
    lines = src.splitlines()
    findings: List[Finding] = []

    def emit(rule: str, node: ast.AST, message: str):
        if rule not in _allowed_rules(lines, node.lineno):
            findings.append(Finding(rule, rel, node.lineno, message))

    check_collectives = pkg_rel not in COLLECTIVE_ALLOWED
    check_hot = pkg_rel not in HOT_ALLOWED
    check_clock = in_package and pkg_rel.split(os.sep)[0] in \
        JIT_MODULE_DIRS
    check_metric = pkg_rel.split(os.sep)[0] != METRIC_ALLOWED_DIR

    if in_package and pkg_rel.split(os.sep)[0] in LOW_DIRS:
        for node, fn in _imports_in_scope(tree):
            for mod in _package_modules(node, pkg_rel):
                top = mod.split(".")[0]
                upward = top in SUBSYSTEMS or (
                    top == "obs" and not any(_under(mod, ok)
                                             for ok in OBS_ALLOWED))
                listed = any(
                    pkg_rel == exc_path and fn == func and _under(mod, m)
                    for exc_path, func, m in UPWARD_EXCEPTIONS)
                if upward and not listed:
                    emit("upward-import", node,
                         f"{pkg_rel} imports {mod} — ops/, parallel/ and "
                         "layers/ import nothing above themselves "
                         f"(of obs/ only {', '.join(OBS_ALLOWED)})")

    # ---- import tracking, so from-imports and aliases cannot evade the
    # rules: `from jax.lax import all_to_all`, `import jax.lax as jl`,
    # `from time import time`, `from datetime import datetime as dt`
    lax_names = {}        # local name -> collective leaf name
    lax_modules = {"lax", "jax.lax"}   # names that mean the lax module
    clock_names = {}      # local name -> canonical 'time.time' chain
    clock_modules = {}    # local module alias -> 'time' | 'datetime'
    metric_names = {}     # local name -> metric class name
    metric_modules = set()  # local aliases that mean a metric module
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module in METRIC_MODULES:
                for a in node.names:
                    if a.name in METRIC_CLASSES:
                        metric_names[a.asname or a.name] = a.name
                    elif a.name in ("registry", "metrics"):
                        metric_modules.add(a.asname or a.name)
            elif node.module in ("distributed_embeddings_tpu.utils",
                                 "distributed_embeddings_tpu.obs"):
                for a in node.names:
                    if a.name in ("metrics", "registry"):
                        metric_modules.add(a.asname or a.name)
            elif node.module == "distributed_embeddings_tpu":
                for a in node.names:
                    if a.name == "obs":
                        metric_modules.add(a.asname or "obs")
            if node.module == "jax.lax":
                for a in node.names:
                    if a.name in COLLECTIVES:
                        lax_names[a.asname or a.name] = a.name
            elif node.module == "jax":
                for a in node.names:
                    if a.name == "lax":
                        lax_modules.add(a.asname or "lax")
            elif node.module == "time":
                for a in node.names:
                    if a.name == "time":
                        clock_names[a.asname or "time"] = "time.time"
            elif node.module == "datetime":
                for a in node.names:
                    if a.name == "datetime":
                        clock_modules[a.asname or "datetime"] = \
                            "datetime"
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.lax" and a.asname:
                    lax_modules.add(a.asname)
                elif a.name in ("time", "datetime"):
                    clock_modules[a.asname or a.name] = a.name
                elif a.name in METRIC_MODULES:
                    # `import ...obs.registry as r` -> r.Counter(...);
                    # unaliased deep imports resolve through the chain's
                    # last segment below
                    metric_modules.add(a.asname or a.name.rsplit(
                        ".", 1)[-1])

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain is None:
                continue
            leaf = chain.rsplit(".", 1)[-1]
            base = chain.rsplit(".", 1)[0] if "." in chain else ""
            naked = (leaf in COLLECTIVES
                     and (base.split(".")[-1] in lax_modules
                          or base in lax_modules)) or \
                (chain in lax_names)
            if check_collectives and naked:
                emit("naked-collective", node,
                     f"{chain}(...) outside ops/wire.py — route the "
                     "exchange through the wire seam "
                     "(wire_all_to_all / wire_id_all_to_all / "
                     "wire_all_gather / wire_psum_scatter)")
            clock = chain in clock_names or (
                "." in chain
                and clock_modules.get(chain.split(".")[0]) is not None
                and (chain.endswith(".time")
                     if clock_modules.get(chain.split(".")[0]) == "time"
                     else chain.endswith(".now")))
            if check_clock and clock:
                emit("wallclock-in-jit", node,
                     f"{chain}() in a jitted-code module — a traced "
                     "wall-clock read freezes one timestamp into the "
                     "compiled program; time at the driver layer")
            shadow = (chain in metric_names) or (
                leaf in METRIC_CLASSES and base
                and base.split(".")[-1] in metric_modules)
            if check_metric and shadow:
                emit("shadow-metric", node,
                     f"{chain}(...) outside obs/ — metric instruments "
                     "come from a MetricRegistry "
                     "(registry.histogram/counter/gauge), one namespace "
                     "the snapshot/SLO layer can see; no shadow "
                     "accounting")
        elif isinstance(node, ast.Subscript) and check_hot:
            sl = node.slice
            if isinstance(sl, ast.Constant) and sl.value == "hot":
                emit("hot-params-access", node,
                     '["hot"] subscript outside dist_model_parallel/'
                     "sparse_update — the replicated hot shard's only "
                     "owners; go through sync_hot_rows/get_weights")
    return findings


def default_files() -> List[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(REPO_ROOT, PACKAGE)):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        out.extend(os.path.join(dirpath, f) for f in filenames
                   if f.endswith(".py"))
    return sorted(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("paths", nargs="*",
                   help="files to lint (default: the package)")
    p.add_argument("--json", action="store_true",
                   help="print findings as one JSON document")
    args = p.parse_args(argv)
    files = args.paths or default_files()
    findings: List[Finding] = []
    for path in files:
        findings.extend(lint_file(path))
    if args.json:
        print(json.dumps([f.to_dict() for f in findings], indent=1))
    else:
        for f in findings:
            print(f)
        print(f"lint_invariants: {len(findings)} finding(s) over "
              f"{len(files)} file(s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
