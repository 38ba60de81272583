"""The contract linter (repro.analysis, DESIGN.md §16).

Two halves:

  * the repo is CLEAN: every rule family runs over the real tree and
    reports nothing beyond the committed baseline (exactly the missing
    sparse×distributed dispatch cell);
  * every rule family FIRES: for each analyzer a deliberately seeded
    violation — a callback in a disabled path, an 8-arg dissat_fn, a
    second θ-subtraction site, an f64 leak, an N-dependent wire term, a
    removed dispatch arm — produces the expected finding.  Seeding uses
    ``AnalysisContext(source_overrides=...)`` (AST rules), injectable
    callables (wire rules) and hand-built jaxprs (jaxpr rules), so the
    tree on disk is never touched.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (AnalysisContext, Finding, load_baseline,
                            registered_rules, run_rules, split_findings)
from repro.analysis import ast_rules, jaxpr_rules, wire_rules
from repro.analysis.entrypoints import (registered_entry_points,
                                        trace_entry_point)

REPO = pathlib.Path(__file__).resolve().parent.parent


def _ctx(**kwargs) -> AnalysisContext:
    return AnalysisContext(repo_root=REPO, **kwargs)


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------

def test_registry_families_populated():
    rules = registered_rules()
    fams = {r.family for r in rules}
    assert fams == {"jaxpr", "ast", "wire", "docs", "complexity"}
    assert len(rules) >= 10


def test_finding_ids_and_baseline_split():
    f1 = Finding(rule="r", key="a", message="m")
    f2 = Finding(rule="r", key="b", message="m")
    new, known, stale = split_findings([f1, f2], {"r:a", "r:gone"})
    assert [f.id for f in new] == ["r:b"]
    assert [f.id for f in known] == ["r:a"]
    assert stale == {"r:gone"}


# ---------------------------------------------------------------------------
# entry-point registry + jaxpr analyzers over ALL of them
# ---------------------------------------------------------------------------

def test_entry_point_registry_covers_every_runtime():
    eps = registered_entry_points()
    assert len(eps) >= 10
    assert {ep.runtime for ep in eps} == \
        {"controller", "batched", "distributed", "des"}
    names = {ep.name for ep in eps}
    # the drivers the tentpole names explicitly
    for required in ("refine", "refine_traced", "refine_simultaneous",
                     "distributed.refine", "distributed.refine_traced",
                     "distributed.refine_simultaneous",
                     "distributed.shard_map", "des.tick", "batch.refine",
                     "refine.kernel"):
        assert required in names, required


def test_all_entry_points_zero_callbacks_and_f32_only():
    for ep in registered_entry_points():
        jaxpr = trace_entry_point(ep.name)
        assert jaxpr_rules.callback_primitives(jaxpr) == [], ep.name
        assert jaxpr_rules.dtype_drift(jaxpr) == [], ep.name


def test_seeded_callback_fires():
    def leaky(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    jaxpr = jax.make_jaxpr(leaky)(jnp.float32(1.0))
    prims = jaxpr_rules.callback_primitives(jaxpr)
    assert prims and all("callback" in p for p in prims)


def test_seeded_callback_inside_scan_body_fires():
    # the walker must recurse into sub-jaxprs, not just top-level eqns
    def leaky_scan(x):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c + 1, c
        return jax.lax.scan(body, x, None, length=3)

    jaxpr = jax.make_jaxpr(leaky_scan)(jnp.float32(0.0))
    assert jaxpr_rules.callback_primitives(jaxpr)


def test_seeded_f64_leak_fires():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: jnp.cumsum(x * 2.0))(
            np.ones(4, np.float64))
    drift = jaxpr_rules.dtype_drift(jaxpr)
    assert any(dtype == "float64" for dtype, _ in drift)


def test_seeded_f16_truncation_fires():
    jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float16) * 2)(
        jnp.ones(4, jnp.float32))
    assert any(dtype == "float16"
               for dtype, _ in jaxpr_rules.dtype_drift(jaxpr))


# ---------------------------------------------------------------------------
# compile-cache audit
# ---------------------------------------------------------------------------

def test_sweep_compile_audit_clean_on_canonical_grid():
    findings, report = jaxpr_rules.group_signature_findings(
        jaxpr_rules.canonical_sweep_cases())
    assert findings == []
    assert report["groups"] == 12 and report["cases"] == 16


def test_seeded_dtype_mismatch_breaks_group():
    from repro.core.problem import make_problem
    from repro.graphs.generators import random_degree_graph, random_weights
    from repro.sweeps.runtime import SweepCase

    adj = random_degree_graph(16, seed=3)
    b, c = random_weights(adj, seed=4, mean=5.0)
    p32 = make_problem(c, b, np.ones(3) / 3, mu=8.0)
    p16 = make_problem(c, b, np.ones(3) / 3, mu=8.0, dtype=jnp.float16)
    r0 = jnp.asarray(np.arange(16) % 3, jnp.int32)
    cases = [SweepCase(problem=p, assignment=r0, framework="c",
                       label=str(p.node_weights.dtype)) for p in (p32, p16)]
    findings, _ = jaxpr_rules.group_signature_findings(cases)
    assert findings and "distinct jit signatures" in findings[0].message


# ---------------------------------------------------------------------------
# AST rules: dissat signature
# ---------------------------------------------------------------------------

def test_repo_dissat_signatures_clean():
    assert ast_rules.dissat_signature_findings(_ctx()) == []


_BAD_FACTORY = textwrap.dedent("""\
    from repro.core.refine import DissatFn


    def make_bad_dissat_fn() -> DissatFn:
        def fn(aggregate, assignment, node_weights, loads, speeds, mu,
               framework, total_weight):
            return None, None
        return fn
    """)


def test_seeded_eight_arg_dissat_fn_fires():
    ctx = _ctx(source_overrides={
        "src/repro/kernels/_seeded.py": _BAD_FACTORY})
    findings = ast_rules.dissat_signature_findings(ctx)
    assert len(findings) == 1
    f = findings[0]
    assert f.key.startswith("def:src/repro/kernels/_seeded.py")
    assert "canonical convention" in f.message


def test_seeded_bad_call_site_fires():
    src = "def caller(dissat_fn, agg):\n    return dissat_fn(agg)\n"
    ctx = _ctx(source_overrides={"src/repro/core/_seeded.py": src})
    findings = ast_rules.dissat_signature_findings(ctx)
    assert len(findings) == 1 and findings[0].key.startswith("call:")


def test_varargs_wrappers_are_exempt():
    src = textwrap.dedent("""\
        from repro.core.refine import DissatFn


        def make_wrapper(inner) -> DissatFn:
            def fn(*args, **kwargs):
                return inner(*args, **kwargs)
            return fn
        """)
    ctx = _ctx(source_overrides={"src/repro/kernels/_seeded.py": src})
    assert ast_rules.dissat_signature_findings(ctx) == []


# ---------------------------------------------------------------------------
# AST rules: single theta-subtraction site
# ---------------------------------------------------------------------------

def test_repo_theta_single_site_clean():
    assert ast_rules.theta_site_findings(_ctx()) == []


def test_seeded_second_theta_subtraction_fires():
    src = ("def sneaky_netting(dissat, theta):\n"
           "    return dissat - theta\n")
    ctx = _ctx(source_overrides={"src/repro/core/_seeded.py": src})
    findings = ast_rules.theta_site_findings(ctx)
    assert len(findings) == 1
    assert findings[0].key == "src/repro/core/_seeded.py::sneaky_netting"
    assert "ONLY in costs.dissatisfaction_from_cost" in findings[0].message


def test_removing_canonical_theta_site_fires():
    costs_src = (REPO / "src/repro/core/costs.py").read_text()
    patched = costs_src.replace("dissat = dissat - theta",
                                "dissat = dissat")
    assert patched != costs_src
    ctx = _ctx(source_overrides={"src/repro/core/costs.py": patched})
    findings = ast_rules.theta_site_findings(ctx)
    assert any(f.key == "canonical-missing" for f in findings)


# ---------------------------------------------------------------------------
# AST rules: trace-unsafe patterns
# ---------------------------------------------------------------------------

def test_repo_trace_unsafe_clean():
    assert ast_rules.trace_unsafe_findings(_ctx()) == []


_TRACE_UNSAFE = textwrap.dedent("""\
    from functools import partial

    import numpy as np
    import jax


    @partial(jax.jit, static_argnames=("flag",))
    def bad(x, flag):
        noise = np.random.rand()
        if x > 0:
            return float(x) + noise
        if flag:
            return x
        return x - 1
    """)


def test_seeded_trace_unsafe_patterns_fire():
    ctx = _ctx(source_overrides={
        "src/repro/core/_seeded.py": _TRACE_UNSAFE})
    findings = ast_rules.trace_unsafe_findings(ctx)
    kinds = {f.key.split(":")[0] for f in findings}
    # np.random, the `if x > 0` tracer branch, and float(x); the
    # `if flag` static branch must NOT fire
    assert kinds == {"np-random", "if-tracer", "host-cast"}
    assert not any("if flag" in f.message for f in findings)


def test_is_none_tests_are_exempt():
    src = textwrap.dedent("""\
        import jax


        @jax.jit
        def fine(x, maybe):
            if maybe is None:
                return x
            return x + maybe
        """)
    ctx = _ctx(source_overrides={"src/repro/core/_seeded.py": src})
    assert ast_rules.trace_unsafe_findings(ctx) == []


# ---------------------------------------------------------------------------
# AST rules: dispatch-coverage matrix
# ---------------------------------------------------------------------------

def test_dispatch_matrix_missing_exactly_sparse_distributed():
    matrix = ast_rules.dispatch_matrix(_ctx())
    missing = [cell for cell, info in matrix.items() if not info["covered"]]
    assert missing == ["sparse-distributed"]


def test_repo_dispatch_findings_match_baseline_exactly():
    findings = ast_rules.dispatch_findings(_ctx())
    assert [f.id for f in findings] == \
        ["dispatch-coverage:sparse-distributed"]
    assert load_baseline() == {"dispatch-coverage:sparse-distributed"}


@pytest.mark.parametrize("arm", ["problem_aggregate", "problem_cut",
                                 "global_cost_c0"])
def test_removing_costs_isinstance_arm_uncovers_cells(arm):
    costs_src = (REPO / "src/repro/core/costs.py").read_text()
    # neutralize exactly the isinstance test inside the chosen function
    lines = costs_src.splitlines(keepends=True)
    out, in_fn, patched = [], False, False
    for line in lines:
        if line.startswith(f"def {arm}("):
            in_fn = True
        elif line.startswith("def "):
            in_fn = False
        if in_fn and not patched and \
                "isinstance(problem, SparseProblem)" in line:
            line = line.replace("isinstance(problem, SparseProblem)",
                                "False")
            patched = True
        out.append(line)
    assert patched, f"no isinstance arm found in {arm}"
    ctx = _ctx(source_overrides={"src/repro/core/costs.py": "".join(out)})
    findings = ast_rules.dispatch_findings(ctx)
    ids = {f.id for f in findings}
    assert "dispatch-coverage:sparse-controller" in ids
    assert "dispatch-coverage:sparse-batched" in ids
    # and these are NEW relative to the baseline -> --check would fail
    new, _, _ = split_findings(findings, load_baseline())
    assert any(f.key == "sparse-controller" for f in new)


def test_unregistered_dispatch_arm_fires():
    src = textwrap.dedent("""\
        from repro.core.sparse import SparseProblem


        def rogue_dispatch(problem):
            if isinstance(problem, SparseProblem):
                return 1
            return 0
        """)
    ctx = _ctx(source_overrides={"src/repro/core/_seeded.py": src})
    findings = ast_rules.dispatch_findings(ctx)
    assert any(f.key == "arm:src/repro/core/_seeded.py::rogue_dispatch"
               for f in findings)


def test_sparse_distributed_arm_would_close_the_gap():
    # adding ANY SparseProblem dispatch under distributed/ covers the cell
    src = ("from ..core.sparse import SparseProblem\n\n\n"
           "def dispatch(problem):\n"
           "    return isinstance(problem, SparseProblem)\n")
    ctx = _ctx(source_overrides={
        "src/repro/distributed/_seeded.py": src})
    matrix = ast_rules.dispatch_matrix(ctx)
    assert matrix["sparse-distributed"]["covered"]


# ---------------------------------------------------------------------------
# wire rules
# ---------------------------------------------------------------------------

def test_repo_wire_contracts_clean():
    assert wire_rules.candidate_findings() == []
    assert wire_rules.ledger_findings() == []


def test_symbolic_sizes_match_measured_constants():
    from repro.distributed import protocol
    for n in wire_rules.N_GRID:
        cand, _ = wire_rules.symbolic_candidate_bytes(n, 4)
        assert cand == protocol.CANDIDATE_BYTES == 16
        assert wire_rules.symbolic_delta_bytes(n, 4) == \
            protocol.TRACE_PARTIAL_BYTES == 8
    assert wire_rules.symbolic_load_partial_bytes(256, 7) == 4 * 7


def test_seeded_n_dependent_candidate_fires():
    from repro.distributed import protocol

    def fat_candidate(agg, b, ids, valid, r, loads, speeds, mu, total_b,
                      m, framework, with_deltas=False):
        # ships the whole per-row gain vector: O(Ns) on the wire
        cand = protocol.Candidate(gain=b, node=ids, dest=ids,
                                  weight=b)
        if with_deltas:
            return cand, b[0], b[0]
        return cand

    findings = wire_rules.candidate_findings(candidate_fn=fat_candidate)
    assert any(f.key.startswith("candidate-n-dep") for f in findings)
    assert any("O(K) wire contract" in f.message for f in findings)


def test_seeded_n_dependent_ledger_fires():
    from repro.distributed import accounting

    def bad_ledger(stats, k, rounds, **flags):
        led = accounting.ledger_for_run(stats, k, rounds, **flags)
        # a per-round term proportional to N — the classic broadcast bug
        return dataclasses.replace(
            led, candidate_bytes=led.candidate_bytes
            + rounds * 4 * stats.num_nodes)

    findings = wire_rules.ledger_findings(ledger_fn=bad_ledger)
    assert findings
    assert all("depend on N" in f.message for f in findings)


# ---------------------------------------------------------------------------
# full run + CLI
# ---------------------------------------------------------------------------

def test_full_run_has_only_baselined_findings():
    findings = run_rules(_ctx(complexity_grid="quick"))
    new, known, stale = split_findings(findings, load_baseline())
    assert new == [], [f.id for f in new]
    assert [f.id for f in known] == ["dispatch-coverage:sparse-distributed"]
    assert stale == set()


def test_cli_check_passes_and_writes_json(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "findings.json"
    assert main(["--check", "--json", str(out),
                 "--complexity-grid", "quick"]) == 0
    report = json.loads(out.read_text())
    assert report["new"] == []
    assert report["baselined"] == ["dispatch-coverage:sparse-distributed"]
    cells = report["reports"]["dispatch-coverage"]["cells"]
    assert not cells["sparse-distributed"]["covered"]
    assert len(report["reports"]["jaxpr-zero-callback"]["entry_points"]) >= 10
    text = capsys.readouterr().out
    assert "sparse-distributed" in text and "MISSING" in text


def test_cli_check_fails_on_new_finding(tmp_path, capsys):
    from repro.analysis.__main__ import main

    empty = tmp_path / "empty_baseline.json"
    empty.write_text('{"findings": []}\n')
    # with an empty baseline the known sparse-distributed gap is NEW
    assert main(["--check", "--baseline", str(empty),
                 "--families", "ast"]) == 2
    assert "FAIL" in capsys.readouterr().out
