"""AST-family lint rules (stdlib ``ast``, DESIGN.md §16.4).

Four rules over the source tree — no imports of the linted modules, so
they run in milliseconds and catch violations before anything traces:

  * **dissat-signature** — every ``dissat_fn`` produced by a factory
    annotated ``-> DissatFn`` (the Protocol of ``core/refine.py``) has
    exactly the canonical 9 parameters, in order, with the canonical
    names; every ``dissat_fn(...)`` call site passes exactly 9
    positionals.  The rule anchors on the Protocol annotation, not on a
    magic arity, so unrelated 9-arg functions are never dragged in.
  * **theta-single-site** — the Eq.-4 net-of-price subtraction
    ``dissat - theta`` happens in exactly ONE jnp function
    (``costs.dissatisfaction_from_cost``); the two Pallas kernels that
    mirror it inside fused reductions are a fixed, documented allowlist
    (they are bitwise-compared against the jnp path by the kernel
    tests).  Any new subtraction site is a finding.
  * **trace-unsafe** — inside jitted bodies: no ``np.random``, no
    ``float()``/``int()`` host casts of dynamic arguments, no ``if``
    statements on dynamic (tracer) arguments.  ``is None`` tests and
    tests over ``static_argnames`` parameters are trace-time constants
    and exempt.
  * **dispatch-coverage** — rebuild the dense/sparse × runtime ×
    kernel dispatch matrix from the ``isinstance(..., SparseProblem)``
    arms; missing cells are findings (today exactly
    ``sparse-distributed`` — ROADMAP item 5 — absorbed by the
    baseline), and removing any registered arm uncovers a cell.
"""
from __future__ import annotations

import ast

from .registry import AnalysisContext, Finding, rule

__all__ = ["CANONICAL_DISSAT_PARAMS", "dissat_signature_findings",
           "theta_site_findings", "trace_unsafe_findings",
           "dispatch_matrix", "dispatch_findings"]

CANONICAL_DISSAT_PARAMS = (
    "aggregate", "assignment", "node_weights", "loads", "speeds", "mu",
    "framework", "total_weight", "theta")

_SRC_DIR = "src/repro"


def _walk_functions(tree: ast.Module):
    """Yield ``(qualname, node)`` for every (async) function def, with
    class / enclosing-function qualification."""
    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                yield qual, child
                yield from walk(child, qual + ".")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
            else:
                yield from walk(child, prefix)
    yield from walk(tree, "")


def _param_names(fn: ast.FunctionDef) -> tuple[str, ...]:
    a = fn.args
    return tuple(p.arg for p in (*a.posonlyargs, *a.args))


# -- rule: dissat-signature ------------------------------------------------

def _mentions(node: ast.AST | None, name: str) -> bool:
    return node is not None and name in ast.unparse(node)


def dissat_signature_findings(ctx: AnalysisContext) -> list[Finding]:
    findings: list[Finding] = []
    factories = 0
    for path in ctx.py_files(_SRC_DIR):
        tree = ctx.tree(path)
        for qual, fn in _walk_functions(tree):
            # Protocol itself: DissatFn.__call__ pins the canonical names
            if qual.endswith("DissatFn.__call__"):
                params = _param_names(fn)[1:]        # drop self
                if params != CANONICAL_DISSAT_PARAMS:
                    findings.append(Finding(
                        rule="dissat-signature", key=f"protocol:{path}",
                        file=path, line=fn.lineno,
                        message=f"DissatFn.__call__ params {params} != "
                                f"canonical {CANONICAL_DISSAT_PARAMS}"))
                continue
            if not _mentions(fn.returns, "DissatFn"):
                continue
            factories += 1
            for inner_qual, inner in _walk_functions(
                    ast.Module(body=fn.body, type_ignores=[])):
                if inner.args.vararg is not None:
                    continue   # pass-through wrapper (*args, **kwargs)
                params = _param_names(inner)
                if params != CANONICAL_DISSAT_PARAMS:
                    findings.append(Finding(
                        rule="dissat-signature",
                        key=f"def:{path}::{qual}.{inner_qual}",
                        file=path, line=inner.lineno,
                        message=f"dissat_fn factory {qual!r} returns a "
                                f"function with params {params}; the "
                                f"canonical convention is "
                                f"{CANONICAL_DISSAT_PARAMS} "
                                f"(repro.core.refine)"))
        # call sites: dissat_fn(...) must pass exactly 9 positionals
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = func.id if isinstance(func, ast.Name) else (
                func.attr if isinstance(func, ast.Attribute) else None)
            if name != "dissat_fn":
                continue
            if any(isinstance(a, ast.Starred) for a in node.args):
                continue   # pass-through wrapper
            if len(node.args) != len(CANONICAL_DISSAT_PARAMS) or \
                    node.keywords:
                findings.append(Finding(
                    rule="dissat-signature",
                    key=f"call:{path}:{node.lineno}",
                    file=path, line=node.lineno,
                    message=f"dissat_fn call passes {len(node.args)} "
                            f"positional + {len(node.keywords)} keyword "
                            f"args; the convention is exactly "
                            f"{len(CANONICAL_DISSAT_PARAMS)} positionals"))
    if factories == 0:
        findings.append(Finding(
            rule="dissat-signature", key="no-factories",
            message="no `-> DissatFn`-annotated factory found under src/ "
                    "— the lint anchor (core.refine.DissatFn) is gone"))
    return findings


@rule("dissat-signature", "ast")
def _rule_dissat_signature(ctx: AnalysisContext) -> list[Finding]:
    """Canonical 9-arg dissat_fn signature at every def/call site."""
    return dissat_signature_findings(ctx)


# -- rule: theta-single-site -----------------------------------------------

_THETA_CANONICAL = ("src/repro/core/costs.py", "dissatisfaction_from_cost")
# Pallas kernels mirroring the subtraction inside fused reductions; each
# is bitwise-pinned against the jnp path by tests/test_kernels.py
_THETA_MIRRORS = frozenset({
    ("src/repro/kernels/dissatisfaction.py", "reduce_dissat_tile"),
    ("src/repro/kernels/dissatisfaction.py", "_dissat_kernel_batched"),
})


def _is_theta_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id.startswith("theta")
    if isinstance(node, ast.Subscript):
        return _is_theta_expr(node.value)
    if isinstance(node, ast.Attribute):
        return node.attr.startswith("theta")
    return False


def theta_site_findings(ctx: AnalysisContext) -> list[Finding]:
    findings: list[Finding] = []
    sites: set[tuple[str, str]] = set()
    lines: dict[tuple[str, str], int] = {}
    for path in ctx.py_files(_SRC_DIR):
        for qual, fn in _walk_functions(ctx.tree(path)):
            for node in ast.walk(fn):
                if isinstance(node, ast.BinOp) and \
                        isinstance(node.op, ast.Sub) and \
                        _is_theta_expr(node.right):
                    sites.add((path, qual))
                    lines.setdefault((path, qual), node.lineno)
    for site in sorted(sites):
        if site == _THETA_CANONICAL or site in _THETA_MIRRORS:
            continue
        findings.append(Finding(
            rule="theta-single-site", key=f"{site[0]}::{site[1]}",
            file=site[0], line=lines[site],
            message=f"theta is subtracted in {site[1]!r} ({site[0]}); "
                    f"the Eq.-4 net-of-price subtraction must happen "
                    f"ONLY in costs.dissatisfaction_from_cost (plus the "
                    f"two pinned Pallas mirrors) — DESIGN.md §11"))
    if _THETA_CANONICAL not in sites:
        findings.append(Finding(
            rule="theta-single-site", key="canonical-missing",
            file=_THETA_CANONICAL[0],
            message="the canonical theta-subtraction site "
                    "costs.dissatisfaction_from_cost no longer subtracts "
                    "theta — the hysteresis contract moved or vanished"))
    return findings


@rule("theta-single-site", "ast")
def _rule_theta_site(ctx: AnalysisContext) -> list[Finding]:
    """Eq.-4 theta subtraction occurs in exactly one jnp function."""
    return theta_site_findings(ctx)


# -- rule: trace-unsafe ----------------------------------------------------

def _jit_static_names(fn: ast.FunctionDef) -> tuple[bool, set[str]]:
    """(is_jitted, static_argnames) from the decorator list."""
    for deco in fn.decorator_list:
        text = ast.unparse(deco)
        if "jit" not in text.split("(")[0] and ".jit" not in text:
            continue
        statics: set[str] = set()
        if isinstance(deco, ast.Call):
            for kw in deco.keywords:
                if kw.arg in ("static_argnames", "static_argnums"):
                    for node in ast.walk(kw.value):
                        if isinstance(node, ast.Constant) and \
                                isinstance(node.value, str):
                            statics.add(node.value)
        return True, statics
    return False, set()


def _is_none_test(test: ast.AST) -> bool:
    """True for tests that are pure `x is (not) None` (possibly and/or
    combined, possibly negated) — trace-time constants for optional
    operands."""
    if isinstance(test, ast.BoolOp):
        return all(_is_none_test(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_none_test(test.operand)
    return (isinstance(test, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops))


def trace_unsafe_findings(ctx: AnalysisContext) -> list[Finding]:
    findings: list[Finding] = []
    for path in ctx.py_files(_SRC_DIR):
        for qual, fn in _walk_functions(ctx.tree(path)):
            jitted, statics = _jit_static_names(fn)
            if not jitted:
                continue
            dynamic = set(_param_names(fn)) - statics
            for node in ast.walk(fn):
                if isinstance(node, ast.Attribute) and \
                        node.attr == "random" and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id in ("np", "numpy"):
                    findings.append(Finding(
                        rule="trace-unsafe",
                        key=f"np-random:{path}:{node.lineno}",
                        file=path, line=node.lineno,
                        message=f"np.random inside jitted {qual!r}: host "
                                f"randomness is drawn once at trace time "
                                f"and baked into the program"))
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id in ("float", "int", "bool") and \
                        any(isinstance(a, ast.Name) and a.id in dynamic
                            for a in node.args):
                    findings.append(Finding(
                        rule="trace-unsafe",
                        key=f"host-cast:{path}:{node.lineno}",
                        file=path, line=node.lineno,
                        message=f"{node.func.id}() on a dynamic argument "
                                f"inside jitted {qual!r}: forces a trace-"
                                f"time concretization (TracerError at "
                                f"best, silent staleness at worst)"))
                elif isinstance(node, ast.If) and \
                        not _is_none_test(node.test):
                    names = {n.id for n in ast.walk(node.test)
                             if isinstance(n, ast.Name)}
                    hit = sorted(names & dynamic)
                    if hit:
                        findings.append(Finding(
                            rule="trace-unsafe",
                            key=f"if-tracer:{path}:{node.lineno}",
                            file=path, line=node.lineno,
                            message=f"`if` on dynamic argument(s) {hit} "
                                    f"inside jitted {qual!r}: branch is "
                                    f"resolved at trace time, not per "
                                    f"call — use lax.cond/jnp.where or "
                                    f"mark the arg static"))
    return findings


@rule("trace-unsafe", "ast")
def _rule_trace_unsafe(ctx: AnalysisContext) -> list[Finding]:
    """No np.random / host casts / tracer `if`s inside jitted bodies."""
    return trace_unsafe_findings(ctx)


# -- rule: dispatch-coverage -----------------------------------------------

# every isinstance(..., SparseProblem) dispatch arm must be registered
# here; the cells below declare which arms make each matrix cell covered
_REGISTERED_ARMS = frozenset({
    ("src/repro/core/costs.py", "problem_aggregate"),
    ("src/repro/core/costs.py", "problem_cut"),
    ("src/repro/core/costs.py", "global_cost_c0"),
    ("src/repro/core/aggregate.py", "apply_move"),
    ("src/repro/core/aggregate.py", "apply_sweep"),
    ("src/repro/core/aggregate.py", "apply_moves"),
    ("src/repro/core/aggregate.py", "apply_cluster_move"),
    ("src/repro/core/refine.py", "_refine_sweeps"),
    ("src/repro/core/cluster.py", "h_hop_mask"),
    ("src/repro/core/batch.py", "problem_shape_key"),
    ("src/repro/core/reference.py", "host_aggregate"),
})

_CORE_SPARSE_ARMS = frozenset(a for a in _REGISTERED_ARMS
                              if a[0] != "src/repro/core/batch.py")

# (file, function) definitions whose presence covers the dense cells
_DENSE_DEFS = {
    "dense-controller": (("src/repro/core/refine.py", "refine"),
                         ("src/repro/core/refine.py", "refine_traced"),
                         ("src/repro/core/refine.py", "refine_simultaneous")),
    "dense-batched": (("src/repro/core/batch.py", "refine_batched"),
                      ("src/repro/core/batch.py", "refine_traced_batched"),
                      ("src/repro/core/batch.py",
                       "refine_simultaneous_batched")),
    "dense-distributed": (
        ("src/repro/distributed/runtime.py", "_refine_distributed"),
        ("src/repro/distributed/runtime.py", "_refine_distributed_traced"),
        ("src/repro/distributed/runtime.py",
         "_refine_distributed_simultaneous"),
        ("src/repro/distributed/runtime.py",
         "refine_distributed_shard_map")),
    "dense-kernel": (("src/repro/kernels/ops.py",
                      "make_aggregate_dissat_fn"),),
    "sparse-kernel": (("src/repro/kernels/ops.py", "make_edge_dissat_fn"),),
}

CELL_ORDER = ("dense-controller", "dense-batched", "dense-distributed",
              "dense-kernel", "sparse-controller", "sparse-batched",
              "sparse-distributed", "sparse-kernel")


def _sparse_isinstance_sites(ctx: AnalysisContext) -> set[tuple[str, str]]:
    sites: set[tuple[str, str]] = set()
    for path in ctx.py_files(_SRC_DIR):
        for qual, fn in _walk_functions(ctx.tree(path)):
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id == "isinstance" and \
                        len(node.args) == 2 and \
                        "SparseProblem" in ast.unparse(node.args[1]):
                    sites.add((path, qual))
    return sites


def _defined_functions(ctx: AnalysisContext, path: str) -> set[str]:
    try:
        tree = ctx.tree(path)
    except FileNotFoundError:
        return set()
    return {qual for qual, _ in _walk_functions(tree)}


def dispatch_matrix(ctx: AnalysisContext) -> dict[str, dict]:
    """cell -> {"covered": bool, "missing": [what would cover it]}."""
    sites = _sparse_isinstance_sites(ctx)
    matrix: dict[str, dict] = {}
    for cell, defs in _DENSE_DEFS.items():
        missing = [f"{p}::{name}" for p, name in defs
                   if name not in _defined_functions(ctx, p)]
        matrix[cell] = {"covered": not missing, "missing": missing}
    core_missing = sorted(f"{p}::{f}" for p, f in _CORE_SPARSE_ARMS
                          if (p, f) not in sites)
    matrix["sparse-controller"] = {"covered": not core_missing,
                                   "missing": core_missing}
    batch_arm = ("src/repro/core/batch.py", "problem_shape_key")
    batched_missing = core_missing + (
        [] if batch_arm in sites else ["::".join(batch_arm)])
    matrix["sparse-batched"] = {"covered": not batched_missing,
                                "missing": sorted(batched_missing)}
    dist_sites = sorted(f"{p}::{f}" for p, f in sites
                        if p.startswith("src/repro/distributed/"))
    matrix["sparse-distributed"] = {
        "covered": bool(dist_sites),
        "missing": [] if dist_sites else
        ["an isinstance(problem, SparseProblem) dispatch arm anywhere "
         "under src/repro/distributed/ (ROADMAP item 5)"]}
    return {cell: matrix[cell] for cell in CELL_ORDER}


def dispatch_findings(ctx: AnalysisContext) -> list[Finding]:
    matrix = dispatch_matrix(ctx)
    ctx.reports["dispatch-coverage"] = {"cells": matrix}
    findings = []
    for cell, info in matrix.items():
        if not info["covered"]:
            findings.append(Finding(
                rule="dispatch-coverage", key=cell,
                message=f"dispatch matrix cell {cell!r} is uncovered; "
                        f"missing: {info['missing']}"))
    for path, qual in sorted(_sparse_isinstance_sites(ctx)):
        if (path, qual) not in _REGISTERED_ARMS and \
                not path.startswith("src/repro/distributed/"):
            findings.append(Finding(
                rule="dispatch-coverage", key=f"arm:{path}::{qual}",
                file=path,
                message=f"unregistered SparseProblem dispatch arm in "
                        f"{qual!r} — register it in "
                        f"repro.analysis.ast_rules._REGISTERED_ARMS so "
                        f"the matrix stays authoritative"))
    return findings


@rule("dispatch-coverage", "ast")
def _rule_dispatch(ctx: AnalysisContext) -> list[Finding]:
    """dense/sparse × runtime dispatch matrix has no unknown holes."""
    return dispatch_findings(ctx)
