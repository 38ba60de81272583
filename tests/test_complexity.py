"""Tests for the complexity family (DESIGN.md §18): real-profile sanity
on the quick grid, every rule proven to fire on a seeded violation
(mirroring test_contracts.py), the expectation-table lifecycle, the CLI
exit-code matrix incl. --prune-stale, deterministic provenance-stamped
JSON, and the BENCH payload schema gate."""
from __future__ import annotations

import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import complexity_rules as cx
from repro.analysis import entrypoints
from repro.analysis.registry import AnalysisContext
from repro.core.sparse import SPARSE_COMPLEXITY
from repro.distributed import protocol

REPO = pathlib.Path(__file__).resolve().parent.parent
QUICK = cx.GRIDS["quick"]


def _quick_profiles():
    return cx.all_profiles("quick")


# ---------------------------------------------------------------------------
# real-profile sanity: the repo's own paths obey their budgets
# ---------------------------------------------------------------------------

def test_every_entry_point_has_declared_budget():
    eps = entrypoints.registered_entry_points()
    assert len(eps) >= 21
    for ep in eps:
        budget = cx.declared_budget(ep)
        assert budget is not None, ep.name
        assert set(budget) == {"mem", "ops", "collectives"}


def test_sparse_paths_have_linear_memory():
    profs = _quick_profiles()
    for name in ("refine.sparse", "refine_traced.sparse",
                 "refine.sparse.edge_kernel",
                 "refine_sweeps.sparse.unbounded"):
        fits = profs[name]["fits"]
        assert fits["mem"]["n"] <= 1.0 + cx.EXPONENT_TOL, (name, fits)
        assert fits["mem"]["e"] <= 1.0 + cx.EXPONENT_TOL, (name, fits)


def test_dense_paths_sit_at_the_quadratic_floor():
    profs = _quick_profiles()
    assert abs(profs["refine"]["fits"]["mem"]["n"] - 2.0) < 0.1
    assert profs["refine"]["peak_shape"] == (256, 256)


def test_shard_map_collectives_match_ledger():
    coll = _quick_profiles()["distributed.shard_map"]["collectives"]
    assert coll["n_independent"]
    assert coll["recurring_bytes"] == protocol.CANDIDATE_BYTES
    assert coll["setup_bytes"] == 0
    # one CandidateMsg per round: 4 scalar all_gathers
    gathers = [c for c in coll["schedule"] if "all_gather" in c[0]]
    assert len(gathers) == 4
    assert all(ph == "recurring" for _, ph, _ in gathers)


def test_emulated_drivers_stage_zero_collectives():
    profs = _quick_profiles()
    for name in ("distributed.refine", "distributed.refine_traced",
                 "distributed.refine_simultaneous"):
        assert profs[name]["collectives"]["schedule"] == ()


def test_no_findings_on_the_real_tree():
    ctx = AnalysisContext(repo_root=REPO, complexity_grid="quick")
    from repro.analysis.registry import run_rules
    findings = run_rules(ctx, families=["complexity"])
    assert findings == [], [f.id for f in findings]
    report = ctx.reports["complexity"]
    assert report["grid"] == "quick"
    assert len(report["entry_points"]) >= 21


def test_fit_exponent_recovers_power_laws():
    ns = (32, 64, 128, 256)
    assert abs(cx.fit_exponent(ns, [n * n for n in ns]) - 2.0) < 1e-9
    assert abs(cx.fit_exponent(ns, [7 * n for n in ns]) - 1.0) < 1e-9
    assert abs(cx.fit_exponent(ns, [5, 5, 5, 5])) < 1e-9
    assert cx.fit_exponent((4,), (16,)) == 0.0


# ---------------------------------------------------------------------------
# seeded violations: every rule fires (ISSUE satellite — the fixture
# materializes senders[:, None] == receivers[None, :])
# ---------------------------------------------------------------------------

def _dense_mask_trace(n, k, degree):
    """A 'sparse' fixture that secretly materializes a dense (E, E)
    mask — the exact regression the mem rule exists to catch."""
    sp = entrypoints.canonical_sparse_degree(n, k, degree or 8)

    def fn(r):
        mask = sp.senders[:, None] == sp.receivers[None, :]
        return jnp.sum(jnp.where(mask, 1.0, 0.0)) + jnp.sum(r)

    return jax.make_jaxpr(fn)(entrypoints.canonical_assignment(n, k))


def test_seeded_dense_materialization_fails_mem_budget():
    prof = cx.profile_trace(_dense_mask_trace, QUICK, sparse=True)
    assert prof["fits"]["mem"]["n"] > 1.8           # quadratic in N
    findings = cx.exponent_findings("seeded.densemask", prof,
                                    SPARSE_COMPLEXITY | {"collectives": {}},
                                    "mem")
    keys = {f.key for f in findings}
    assert "seeded.densemask:n" in keys             # O(N^2) memory finding
    assert "seeded.densemask:e" in keys             # quadratic in E too
    assert all(f.rule == "complexity-mem-budget" for f in findings)
    n_msg = next(f.message for f in findings
                 if f.key == "seeded.densemask:n")
    assert "peak intermediate" in n_msg             # names the (E, E) aval
    # and the op count blows the budget as well
    ops = cx.exponent_findings("seeded.densemask", prof,
                               SPARSE_COMPLEXITY | {"collectives": {}},
                               "ops")
    assert any(f.key == "seeded.densemask:n" for f in ops)


def _psum_trace(n, k, degree):
    """An injected per-shard psum of an (N,) operand inside the round
    loop — the collective audit must reject it twice over: the schedule
    depends on N, and the recurring bytes are not the ledger constant."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))

    def spmd(x):
        def step(_, acc):
            return acc + jax.lax.psum(x, "shards")
        return jax.lax.fori_loop(0, 3, step, jnp.zeros_like(x))

    f = jax.shard_map(spmd, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    return jax.make_jaxpr(f)(jnp.ones((n,), jnp.float32))


def test_seeded_wide_psum_fails_collective_audit():
    prof = cx.profile_trace(_psum_trace, QUICK)
    coll = prof["collectives"]
    assert not coll["n_independent"]
    assert coll["recurring_bytes"] == 4 * QUICK.n[-1]
    findings = cx.collective_findings(
        "seeded.psum", coll, {"recurring_bytes": 0, "setup_bytes": 0})
    keys = {f.key for f in findings}
    assert "seeded.psum:n-dependent" in keys
    assert "seeded.psum:recurring-bytes" in keys
    assert all(f.rule == "complexity-collectives" for f in findings)


def test_missing_budget_fires():
    eps = entrypoints.registered_entry_points()
    findings = cx.budget_findings(eps, lookup=lambda ep: None)
    assert len(findings) == len(eps)
    assert all(f.rule == "complexity-budget-declared" for f in findings)
    assert cx.budget_findings(eps) == []            # the real tree declares all


# ---------------------------------------------------------------------------
# expectation table lifecycle
# ---------------------------------------------------------------------------

def test_expectation_table_missing_grid_and_drift_and_stale(tmp_path):
    profiles = {"refine": cx.profile_entry_point("refine", "quick")}

    missing = cx.expectation_findings(profiles, {}, "quick")
    assert [f.key for f in missing] == ["table:quick"]

    table = {"grids": {"quick": {
        "refine": cx.build_table_entry(profiles["refine"]),
        "ghost.entry": cx.build_table_entry(profiles["refine"]),
    }}}
    findings = cx.expectation_findings(profiles, table, "quick")
    assert [f.key for f in findings] == ["stale:ghost.entry"]

    drifted = json.loads(json.dumps(table))
    drifted["grids"]["quick"]["refine"]["fits"]["mem"]["n"] += 0.5
    del drifted["grids"]["quick"]["ghost.entry"]
    findings = cx.expectation_findings(profiles, drifted, "quick")
    assert [f.key for f in findings] == ["refine:mem.n"]


def test_checked_in_table_agrees_with_quick_refit():
    table = cx.load_table()
    findings = cx.expectation_findings(_quick_profiles(), table, "quick")
    assert findings == [], [f.id for f in findings]


def test_update_table_roundtrip(tmp_path):
    path = tmp_path / "complexity.json"
    cx.update_table("quick", path)
    table = cx.load_table(path)
    assert set(table["grids"]) == {"quick"}
    assert len(table["grids"]["quick"]) >= 21
    # regenerating is idempotent (fits are exact shape arithmetic)
    before = path.read_text()
    cx.update_table("quick", path)
    assert path.read_text() == before


# ---------------------------------------------------------------------------
# CLI: complexity wiring, exit-code matrix, --prune-stale, JSON shape
# ---------------------------------------------------------------------------

def _main(argv):
    from repro.analysis.__main__ import main
    return main(argv)


def test_cli_complexity_family_check_passes(tmp_path):
    out = tmp_path / "findings.json"
    rc = _main(["--check", "--families", "complexity",
                "--complexity-grid", "quick", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["reports"]["complexity"]["grid"] == "quick"
    shard = report["reports"]["complexity"]["entry_points"][
        "distributed.shard_map"]
    assert shard["collectives"]["recurring_bytes"] == protocol.CANDIDATE_BYTES


def test_cli_update_complexity_writes_table(tmp_path, capsys):
    path = tmp_path / "table.json"
    rc = _main(["--update-complexity", "--complexity-grid", "quick",
                "--complexity-table", str(path)])
    assert rc == 0
    assert "21" in capsys.readouterr().out
    assert "quick" in json.loads(path.read_text())["grids"]


_KNOWN = {"rule": "dispatch-coverage", "key": "sparse-distributed"}


def _baseline_file(tmp_path, entries):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"findings": entries}, indent=2) + "\n")
    return p


def test_cli_exit_codes_known_new_stale(tmp_path):
    # known-only: exit 0
    b = _baseline_file(tmp_path, [_KNOWN])
    assert _main(["--check", "--families", "ast",
                  "--baseline", str(b)]) == 0
    # empty baseline: the known gap is NEW -> exit 2
    b = _baseline_file(tmp_path, [])
    assert _main(["--check", "--families", "ast",
                  "--baseline", str(b)]) == 2
    # stale extra entry: never fatal, file untouched without --prune-stale
    b = _baseline_file(tmp_path, [_KNOWN, {"rule": "ghost", "key": "x"}])
    before = b.read_text()
    assert _main(["--check", "--families", "ast",
                  "--baseline", str(b)]) == 0
    assert b.read_text() == before


def test_cli_prune_stale_rewrites_baseline(tmp_path):
    b = _baseline_file(tmp_path, [_KNOWN, {"rule": "ghost", "key": "x"}])
    assert _main(["--check", "--prune-stale", "--families", "ast",
                  "--baseline", str(b)]) == 0
    data = json.loads(b.read_text())
    assert data["findings"] == [_KNOWN]
    # stale AND new at once: prune still happens, check still fails
    b = _baseline_file(tmp_path, [{"rule": "ghost", "key": "x"}])
    assert _main(["--check", "--prune-stale", "--families", "ast",
                  "--baseline", str(b)]) == 2
    assert json.loads(b.read_text())["findings"] == []


def test_cli_update_baseline_prunes_and_dedupes(tmp_path):
    b = _baseline_file(tmp_path, [{"rule": "ghost", "key": "x"},
                                  _KNOWN, _KNOWN])
    assert _main(["--update-baseline", "--families", "ast",
                  "--baseline", str(b)]) == 0
    assert json.loads(b.read_text())["findings"] == [_KNOWN]


def test_cli_json_is_deterministic_and_stamped(tmp_path):
    out1, out2 = tmp_path / "a.json", tmp_path / "b.json"
    assert _main(["--families", "ast", "--json", str(out1)]) == 0
    assert _main(["--families", "ast", "--json", str(out2)]) == 0
    r1, r2 = json.loads(out1.read_text()), json.loads(out2.read_text())
    for r in (r1, r2):
        # same provenance block the benchmarks stamp (DESIGN.md §14.5)
        assert {"git_sha", "jax", "jaxlib", "backend",
                "device_kind"} <= set(r["provenance"])
        ids = [f["id"] for f in r["findings"]]
        assert ids == sorted(ids)
    for k in ("rules", "findings", "new", "baselined", "stale_baseline",
              "reports"):
        assert r1[k] == r2[k]


# ---------------------------------------------------------------------------
# benchmarks/common.py payload schema gate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench_common():
    spec = importlib.util.spec_from_file_location(
        "bench_common", REPO / "benchmarks" / "common.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_validate_bench_payload(bench_common):
    good = {"provenance": bench_common.provenance(),
            "results": {"rows": [{"n": 64, "seconds": 0.5}]}}
    bench_common.validate_bench_payload(good)    # no raise

    with pytest.raises(bench_common.BenchPayloadError, match="provenance"):
        bench_common.validate_bench_payload({"results": {}})
    with pytest.raises(bench_common.BenchPayloadError, match="missing keys"):
        bench_common.validate_bench_payload({"provenance": {"jax": "x"}})
    bad = dict(good, results={"v": float("nan")})
    with pytest.raises(bench_common.BenchPayloadError, match="non-finite"):
        bench_common.validate_bench_payload(bad)
    bad = dict(good, results={"v": [1.0, float("inf")]})
    with pytest.raises(bench_common.BenchPayloadError, match="non-finite"):
        bench_common.validate_bench_payload(bad)
    bad = dict(good, results={"v": object()})
    with pytest.raises(bench_common.BenchPayloadError, match="non-JSON"):
        bench_common.validate_bench_payload(bad)


def test_write_bench_json_refuses_bad_payload(bench_common, tmp_path,
                                              monkeypatch):
    monkeypatch.setattr(bench_common, "REPO_ROOT", str(tmp_path))
    with pytest.raises(bench_common.BenchPayloadError):
        bench_common.write_bench_json("seeded", {"v": float("nan")})
    assert not (tmp_path / "BENCH_seeded.json").exists()

    path = bench_common.write_bench_json("seeded", {"v": 1.5})
    doc = json.loads(pathlib.Path(path).read_text())
    assert doc["results"] == {"v": 1.5}
    assert doc["provenance"]["jax"]
