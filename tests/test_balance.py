"""Balancer correctness tests (paper §4, Balancing).

Host-side property tests: feasibility from adversarial starts, the
early-return fast path, the int32 boundary behavior (clear errors
instead of silent wraps), the padded-block fallback regression, the
shared ejection rule, and the uncoarsening seed derivation. The
distributed balancer itself is exercised in subprocesses via
``repro.launch.selftest --test balance`` (see test_distributed.py).
"""
import numpy as np
import pytest

from repro.core import metrics
from repro.core.balance import rebalance
from repro.core.coarsening import (ejection_candidates,
                                   enforce_cluster_weights)
from repro.core.deep_mgp import uncoarsen_seed
from repro.core.refinement import pad_blocks
from repro.graphs import generators
from repro.graphs.format import from_coo


def ring(n, vweights=None):
    src = np.arange(n)
    dst = (src + 1) % n
    return from_coo(n, src, dst, vweights=vweights)


def assert_feasible(g, part, l_max_vec):
    k = int(l_max_vec.shape[0])
    assert part.min() >= 0 and part.max() < k, (part.min(), part.max(), k)
    bw = metrics.block_weights(g, part, k)
    assert np.all(bw <= l_max_vec), (bw, l_max_vec)


# ---------------------------------------------------------------------------
# feasibility from adversarial starts
# ---------------------------------------------------------------------------

def test_rebalance_all_in_one_block():
    g = generators.make("rgg2d", 1200, 8.0, seed=3)
    k = 16
    lmax = np.full(k, metrics.l_max(g.total_vweight, k, 0.03,
                                    int(g.vweights.max())), dtype=np.int64)
    part = np.zeros(g.n, dtype=np.int64)
    fixed = rebalance(g, part, lmax, seed=1)
    assert_feasible(g, fixed, lmax)


def test_rebalance_k_close_to_n():
    g = ring(80)
    k = 64
    lmax = np.full(k, metrics.l_max(g.total_vweight, k, 0.03,
                                    int(g.vweights.max())), dtype=np.int64)
    part = np.zeros(g.n, dtype=np.int64)
    fixed = rebalance(g, part, lmax, seed=2)
    assert_feasible(g, fixed, lmax)


def test_rebalance_heterogeneous_lmax():
    g = generators.make("rgg2d", 800, 8.0, seed=4)
    k = 8
    base = metrics.l_max(g.total_vweight, k, 0.03, int(g.vweights.max()))
    lvec = (base * (1 + (np.arange(k) % 3))).astype(np.int64)
    rng = np.random.default_rng(0)
    part = rng.integers(0, k, g.n).astype(np.int64)
    part[rng.random(g.n) < 0.7] = 0
    fixed = rebalance(g, part, lvec, seed=5)
    assert_feasible(g, fixed, lvec)


# ---------------------------------------------------------------------------
# early return: feasible inputs never touch the O(m) chunk build
# ---------------------------------------------------------------------------

def test_rebalance_feasible_early_return(monkeypatch):
    g = generators.make("rgg2d", 500, 8.0, seed=6)
    k = 4
    # round-robin start is feasible for generous budgets
    part = (np.arange(g.n) % k).astype(np.int64)
    lmax = np.full(k, int(g.total_vweight), dtype=np.int64)

    from repro.core import lp

    def boom(*a, **kw):
        raise AssertionError("feasible input must not build chunks")

    monkeypatch.setattr(lp, "build_chunks", boom)
    stats = {}
    out = rebalance(g, part, lmax, seed=0, stats=stats)
    assert np.array_equal(out, part)
    assert out is not part and not np.shares_memory(out, part)
    assert stats["rounds"] == 0 and stats["gather_bytes"] == 0


@pytest.mark.parametrize("parent", [None, "siblings"])
def test_rebalance_builds_no_refused_ell(monkeypatch, parent):
    """The fused balancer's gate reads the ELL's shape: with every ELL
    over its budget none is built, the fallback is recorded, and the
    answer is the composed balancer's."""
    import warnings

    from repro import spans
    from repro.kernels import dispatch
    from repro.kernels.bal_round import ops as bal_ops

    g = generators.make("rgg2d", 600, 8.0, seed=7)
    k = 8
    lmax = np.full(k, metrics.l_max(g.total_vweight, k, 0.03,
                                    int(g.vweights.max())), dtype=np.int64)
    part = np.zeros(g.n, dtype=np.int64)
    par = None if parent is None else np.arange(k) // 2
    if par is not None:
        part[g.n // 2:] = 2
    want = rebalance(g, part, lmax, parent=par, seed=3, kernel="composed")

    def refused(*a, **kw):
        raise AssertionError("a refused ELL was built")

    monkeypatch.setattr(bal_ops, "VMEM_BUDGET_BYTES", 0)
    monkeypatch.setattr(bal_ops, "build_balance_ell", refused)
    dispatch.reset_fallback_warnings()
    trace = []
    with warnings.catch_warnings(), spans.recording(trace):
        warnings.simplefilter("ignore", UserWarning)
        got = rebalance(g, part, lmax, parent=par, seed=3, kernel="fused")
    assert np.array_equal(got, want)
    (build,) = [r for r in trace if r.get("span") == "level.ell_build"]
    n_pad = next(r for r in trace
                 if r.get("span") == "level.balance")["attrs"]["n_pad"]
    assert build["attrs"]["used"] is False
    assert (build["attrs"]["rows"], build["attrs"]["lanes"]) == \
        bal_ops.balance_ell_shape(g, n_pad)
    assert [r["kernel"] for r in trace if r.get("event")] == ["bal_round"]


def test_balance_ell_shape_is_the_built_shape():
    from repro.kernels.bal_round import ops as bal_ops
    g = generators.make("rgg2d", 700, 9.0, seed=8)
    for n_pad in (700, 1023, 1024, 4096):
        idx, w = bal_ops.build_balance_ell(g, n_pad)
        assert idx.shape == w.shape == bal_ops.balance_ell_shape(g, n_pad)


# ---------------------------------------------------------------------------
# int32 boundary: exact at 2^31 - 1, clear error at 2^31
# ---------------------------------------------------------------------------

def test_rebalance_at_int32_boundary():
    # total vertex weight == 2^31 - 1 exactly; the balancer must detect the
    # overload and reach feasibility without any comparison wrapping
    w = np.array([2**29, 2**29, 2**29, 2**31 - 1 - 3 * 2**29],
                 dtype=np.int64)
    g = ring(4, vweights=w)
    assert g.total_vweight == 2**31 - 1
    lmax = np.full(2, 2**30 + 2**29 + 16, dtype=np.int64)
    part = np.zeros(4, dtype=np.int64)
    fixed = rebalance(g, part, lmax, seed=0)
    assert_feasible(g, fixed, lmax)


def test_rebalance_overweight_total_raises():
    w = np.full(4, 2**29, dtype=np.int64)   # total == 2^31
    g = ring(4, vweights=w)
    assert g.total_vweight == 2**31
    lmax = np.full(2, 2**30, dtype=np.int64)   # infeasible -> no early out
    with pytest.raises(ValueError, match="2\\^31"):
        rebalance(g, np.zeros(4, dtype=np.int64), lmax, seed=0)


def test_pad_blocks_raises_on_overflow():
    with pytest.raises(ValueError, match="int32"):
        pad_blocks(np.array([2**31, 5], dtype=np.int64),
                   np.array([10, 10], dtype=np.int64), None)


def test_pad_blocks_dummies_never_lightest():
    # dummy blocks must carry the maximal weight so the argmin fallback
    # can never pick one (the historical 2^30 filler could win)
    bw, lv, _, k = pad_blocks(np.array([2**30 + 7], dtype=np.int64),
                              np.array([2**29], dtype=np.int64), None)
    assert k == 1 and bw.shape[0] >= 64
    assert int(np.argmin(bw)) == 0          # the real block stays lightest
    assert np.all(bw[1:] == 2**31 - 1)


def test_rebalance_never_emits_padded_block_ids():
    # regression: an infeasible k=1 instance whose only block exceeds 2^30
    # used to leak moves into the padded dummy blocks (labels >= k)
    n = 600
    w = np.full(n, 2**21, dtype=np.int64)
    g = ring(n, vweights=w)
    assert g.total_vweight > 2**30
    lmax = np.full(1, 2**29, dtype=np.int64)   # unsatisfiable: k == 1
    out = rebalance(g, np.zeros(n, dtype=np.int64), lmax, seed=0,
                    max_rounds=2)
    assert np.all(out == 0)                    # never a dummy block id


# ---------------------------------------------------------------------------
# shared ejection rule (host sweep; the sharded sweep must match it)
# ---------------------------------------------------------------------------

def test_ejection_candidates_postconditions():
    rng = np.random.default_rng(1)
    n = 400
    labels = rng.integers(0, 12, n).astype(np.int64)
    vweights = rng.integers(1, 9, n).astype(np.int64)
    W = 40
    ej = ejection_candidates(labels, vweights, W)
    out = enforce_cluster_weights(labels.copy(), vweights, W)
    # exactly the ejection candidates changed cluster
    assert np.array_equal(np.sort(np.flatnonzero(out != labels)),
                          np.sort(ej))
    # every multi-member cluster now fits W
    cw = np.zeros(n, dtype=np.int64)
    np.add.at(cw, out, vweights)
    members = np.bincount(out, minlength=n)
    assert np.all(cw[members > 1] <= W)
    # the heaviest member of every original cluster is never ejected
    for c in np.unique(labels):
        mem = np.flatnonzero(labels == c)
        heaviest = mem[np.lexsort((mem, -vweights[mem]))][0]
        assert heaviest not in ej


# ---------------------------------------------------------------------------
# uncoarsening seeds: level-derived, never colliding on equal n
# ---------------------------------------------------------------------------

def test_uncoarsen_seed_distinct_per_level():
    # distinct across levels AND across the two uncoarsening streams
    # (the distributed loop and the base case it delegates to both
    # count levels from 0)
    seeds = {uncoarsen_seed(42, lvl, stream=s)
             for lvl in range(64) for s in (0, 1)}
    assert len(seeds) == 128
    # the historical formula collided whenever two levels had equal n
    old = lambda s, n: s + n % 1000003
    assert old(42, 5000) == old(42, 5000)
    assert uncoarsen_seed(42, 0) != uncoarsen_seed(42, 1)
