"""Static-analysis suite tests: seeded-violation fixtures must fire,
clean programs must not, the allowlist loader must reject unreviewed
suppressions, and the dispatch fallback must be loud at the boundary."""
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.analysis import collectives_pass, lint, overflow_pass, vmem
from repro.analysis.findings import (AllowEntry, Allowlist, Finding,
                                     Report)
from repro.analysis.fixtures import (fixture_collective_mismatch,
                                     fixture_lint, fixture_overflow,
                                     fixture_vmem)


def rules(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------------
# negative tests: the seeded fixtures must fire their pass
# ---------------------------------------------------------------------------

def test_collective_fixture_fires_mismatch_and_unchecked():
    # P=1 is enough: the branch-signature mismatch and the
    # check=False staging are structural, not device-count-bound
    report = Report()
    collectives_pass.run(fixture_collective_mismatch.captured(1), report)
    got = rules(report)
    assert "SPMD002" in got, got   # cond branches diverge on psum
    assert "SPMD003" in got, got   # check=False, not allowlisted


def test_overflow_fixture_fires_on_sum_form():
    report = Report()
    overflow_pass.run(fixture_overflow.captured(), report)
    assert rules(report) == ["OFL001"], rules(report)
    (f,) = report.findings
    assert f.function == "admit"
    assert "fixture_overflow" in f.file


def test_overflow_guard_form_is_clean():
    # the sanctioned `w <= budget - c` rewrite of the same check
    import jax
    import jax.numpy as jnp

    def admit(cluster_w, vweights, labels, budget):
        cw = cluster_w[labels]
        return cw <= budget - vweights

    n = 8
    args = (jnp.ones((n,), jnp.int32), jnp.ones((n,), jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.full((n,), 100, jnp.int32))
    report = Report()
    overflow_pass.run([("guarded", jax.make_jaxpr(admit)(*args))], report)
    assert report.findings == []


def test_lint_fixture_fires_all_three_rules():
    report = Report()
    lint.check_file(fixture_lint.__file__, report, serve_hot=True)
    got = rules(report)
    assert got.count("LNT001") == 2, got  # np.random + random.random
    assert "LNT002" in got, got           # shard_map w/o check=
    assert "LNT003" in got, got           # .item() in serve hot path


def test_vmem_fixture_fires_divergence():
    report = Report()
    vmem.run(report, static_fn=fixture_vmem.static_bytes)
    got = rules(report)
    assert "VMEM001" in got, got


def test_vmem_static_matches_runtime_gate():
    # the real inventories must agree with the runtime planning
    # formulas at every grid point (the 5% budget is headroom, not
    # slack we actually use)
    report = Report()
    points = vmem.run(report)
    assert points > 100
    assert report.findings == [], rules(report)


# ---------------------------------------------------------------------------
# allowlist semantics
# ---------------------------------------------------------------------------

def test_allowlist_rejects_missing_reason(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[overflow]]\nfile = "src/x.py"\n')
    with pytest.raises(ValueError, match="reason"):
        Allowlist.load(str(p))


def test_allowlist_rejects_unknown_table(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[typo]]\nfile = "src/x.py"\nreason = "r"\n')
    with pytest.raises(ValueError, match="unknown table"):
        Allowlist.load(str(p))


def test_allowlist_suppresses_only_matching_kind():
    allow = Allowlist([AllowEntry(kind="overflow", file="src/x.py",
                                  function="f", reason="bounded")])
    report = Report(allow)
    report.add(Finding(rule="OFL001", pass_name="overflow", message="m",
                       file="src/x.py", function="f"))
    report.add(Finding(rule="SPMD003", pass_name="collectives",
                       message="m", file="src/x.py", function="f"))
    assert len(report.suppressed) == 1
    assert rules(report) == ["SPMD003"]


def test_repo_allowlist_loads_and_every_entry_has_reason():
    allow = Allowlist.load()
    assert allow.entries, "repo allowlist is empty"
    assert all(e.reason for e in allow.entries)


# ---------------------------------------------------------------------------
# dispatch fallback observability (satellite: no more silent fallback)
# ---------------------------------------------------------------------------

def _dedup_inputs():
    csrc = np.array([0, 1, 1, 2, 0], dtype=np.int64)
    cdst = np.array([1, 0, 2, 1, 1], dtype=np.int64)
    w = np.ones(csrc.size, dtype=np.int64)
    return csrc, cdst, w


def _fallback_events(trace):
    return [t for t in trace if t.get("event") == "kernel-fallback"]


def test_fallback_boundary_exact_budget_stays_fused(monkeypatch):
    from repro import spans
    from repro.core import contraction
    from repro.kernels import dispatch
    from repro.kernels.seg_merge import ops as seg_ops
    from repro.kernels.seg_merge.seg_merge import seg_merge_vmem_bytes

    csrc, cdst, w = _dedup_inputs()
    est = seg_merge_vmem_bytes(csrc.size)
    # ops modules freeze the budget at import: patch the frozen copy
    monkeypatch.setattr(seg_ops, "VMEM_BUDGET_BYTES", est)
    dispatch.reset_fallback_warnings()
    trace = []
    with warnings.catch_warnings(), spans.recording(trace):
        warnings.simplefilter("error")  # any fallback warning -> fail
        out = contraction.dedup_arcs(csrc, cdst, w, kernel="fused")
    assert _fallback_events(trace) == []
    assert [t["span"] for t in trace] == ["level.h2d"] * 3 + \
        ["wait"] * 4 + ["level.dedup"]
    want = contraction.dedup_arcs(csrc, cdst, w, kernel="composed")
    assert all(np.array_equal(a, b) for a, b in zip(out, want))


def test_fallback_one_past_budget_warns_once_and_records(monkeypatch):
    from repro import spans
    from repro.core import contraction
    from repro.kernels import dispatch
    from repro.kernels.seg_merge import ops as seg_ops
    from repro.kernels.seg_merge.seg_merge import seg_merge_vmem_bytes

    csrc, cdst, w = _dedup_inputs()
    est = seg_merge_vmem_bytes(csrc.size)
    monkeypatch.setattr(seg_ops, "VMEM_BUDGET_BYTES", est - 1)
    dispatch.reset_fallback_warnings()
    trace = []
    with spans.recording(trace):
        with pytest.warns(UserWarning, match="seg_merge"):
            out = contraction.dedup_arcs(csrc, cdst, w, kernel="fused")
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # one-shot: second time silent
            contraction.dedup_arcs(csrc, cdst, w, kernel="fused")
    records = _fallback_events(trace)
    assert len(records) == 2  # every decision recorded, warned once
    assert records[0] == {"event": "kernel-fallback", "kernel": "seg_merge",
                          "estimated_bytes": est,
                          "budget_bytes": dispatch.VMEM_BUDGET_BYTES,
                          "detail": "dedup_arcs (int32/VMEM envelope)"}
    # outside a recorder a decision is only warned about: no global
    # list keeps it for whichever request drains next
    contraction.dedup_arcs(csrc, cdst, w, kernel="fused")
    assert len(_fallback_events(trace)) == 2
    assert not hasattr(dispatch, "drain_fallback_records")
    want = contraction.dedup_arcs(csrc, cdst, w, kernel="composed")
    assert all(np.array_equal(a, b) for a, b in zip(out, want))


def test_fallback_records_drain_into_partition_trace(monkeypatch):
    from repro.core import deep_mgp
    from repro.graphs import generators
    from repro.kernels import dispatch
    from repro.kernels.bal_round import ops as bal_ops
    from repro.kernels.lp_move import ops as move_ops
    from repro.kernels.seg_merge import ops as seg_ops

    # force every fused path over budget: the whole run falls back to
    # the composed kernels and each decision lands in the trace of the
    # partition that made it (also keeps this test fast — no
    # interpret-mode Pallas)
    monkeypatch.setattr(move_ops, "VMEM_BUDGET_BYTES", 0)
    monkeypatch.setattr(bal_ops, "VMEM_BUDGET_BYTES", 0)
    monkeypatch.setattr(seg_ops, "VMEM_BUDGET_BYTES", 0)
    dispatch.reset_fallback_warnings()
    g = generators.make("rgg2d", 300, 6.0, seed=2)
    cfg = deep_mgp.PartitionerConfig(contraction_limit=64,
                                     ip_repetitions=1, num_chunks=2,
                                     kernel="fused")
    traces = [[], []]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        for trace in traces:
            deep_mgp.partition(g, 2, cfg, trace=trace)
    events = [_fallback_events(t) for t in traces]
    assert events[0], traces[0]
    assert {"lp_move", "seg_merge"} <= {t["kernel"] for t in events[0]}
    # the second partition gets its own decisions, none left over
    assert events[1] == events[0]
    builds = [t for t in traces[0] if t.get("span") == "level.ell_build"]
    assert builds and not any(t["attrs"]["used"] for t in builds)


# ---------------------------------------------------------------------------
# end-to-end CLI directions (subprocess; slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_repo_clean_and_fixtures_fire():
    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", *extra],
            capture_output=True, text=True)

    proc = run()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for fx in ("collective", "overflow", "lint", "vmem"):
        proc = run("--fixture", fx)
        assert proc.returncode == 1, (fx, proc.stdout + proc.stderr)
