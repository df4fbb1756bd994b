"""The benchmark's copied generators, CSR build and cut arithmetic agree
with the program's on small graphs; the reference partition is
balanced and its skewed control is not."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import cell, reference, registry  # noqa: E402

sys.path.insert(0, str(registry.ROOT / "src"))

from repro.core import metrics  # noqa: E402
from repro.graphs import format as gformat  # noqa: E402
from repro.graphs import generators  # noqa: E402


def _csr(g):
    return g.indptr, g.adjncy, g.eweights


@pytest.mark.parametrize("family,n,seed", [
    ("rgg2d", 3000, 0), ("rgg2d", 1000, 7), ("rgg3d", 3000, 0),
    ("rgg3d", 2000, 5)])
def test_generator_and_csr_match_program(family, n, seed):
    pts, src, dst = registry.graph_family(family).generate(n, 8.0, seed)
    assert pts.shape == (n, 2 if family == "rgg2d" else 3)
    mine = reference.csr_from_pairs(n, src, dst)
    theirs = generators.make(family, n, 8.0, seed)
    for a, b in zip(mine, _csr(theirs)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_relabel_matches_program_permute():
    g = generators.rgg2d(1500, 8.0, 3)
    perm = np.random.default_rng(1).permutation(g.n)
    mine = reference.relabel(_csr(g), perm)
    theirs, _ = gformat.permute(g, perm)
    for a, b in zip(mine, _csr(theirs)):
        np.testing.assert_array_equal(a, b)


def test_seeded_input_is_a_relabelling():
    seed = 2**40 + 9
    cfg = {"graph": {"family": "rgg2d", "n": 800, "avg_deg": 8.0,
                     "seed": 3}}
    _, base = cell.generate(cfg)
    _, again = cell.generate(cfg)
    np.testing.assert_array_equal(base[1], again[1])
    one, two = cell.make_input(base, seed, 1), cell.make_input(base, seed, 2)
    assert not np.array_equal(one[1], two[1])
    perm = np.random.default_rng(cell.seed_sequence(seed, 1)).permutation(800)
    part = np.arange(800) % 4
    renamed = np.empty_like(part)
    renamed[perm] = part
    assert reference.edge_cut(one, renamed) == reference.edge_cut(base, part)


@pytest.mark.parametrize("k,eps", [(2, 0.03), (16, 0.03), (7, 0.1)])
def test_cut_and_feasibility_match_program(k, eps):
    g = generators.rgg3d(2500, 8.0, 4)
    rng = np.random.default_rng(k)
    for part in (rng.integers(0, k, g.n), np.arange(g.n) % k):
        assert reference.edge_cut(_csr(g), part) == metrics.edge_cut(g, part)
        lim = reference.l_max(g.n, k, eps)
        assert lim == metrics.l_max(g.total_vweight, k, eps, 1)
        assert (np.bincount(part, minlength=k).max() <= lim) == \
            metrics.is_feasible(g, part, k, eps)


@pytest.mark.parametrize("k", [2, 16, 5, 64])
def test_rcb_balanced_and_control_not(k):
    rcb = registry.quality_reference("rcb").partition
    pts = np.random.default_rng(k).random((4096, 2))
    sizes = np.bincount(rcb(pts, None, k), minlength=k)
    assert sizes.max() - sizes.min() <= 2
    lim = reference.l_max(4096, k, 0.03)
    assert sizes.max() <= lim
    skewed = np.bincount(rcb(pts, None, k, skew=0.12), minlength=k)
    assert skewed.max() > lim


def test_check_partition_numbers():
    g = generators.rgg2d(2000, 8.0, 1)
    csr = _csr(g)
    part = registry.quality_reference("rcb").partition(
        np.random.default_rng(0).random((2000, 2)), csr, 8)
    cut = reference.edge_cut(csr, part)
    nums = reference.check_partition(csr, part, 8, 0.03, cut, True, cut)
    assert nums["bad_labels"] == 0 and nums["cut_gap"] == 0
    assert nums["flag_gap"] == 0 and nums["cut_over_ref"] == 0.0
    assert nums["slack_used"] <= 1.0
    bad = part.copy()
    bad[:3] = 8
    assert reference.check_partition(csr, bad, 8, 0.03, cut, True,
                                     cut)["bad_labels"] == 3
    assert reference.check_partition(csr, part, 8, 0.03, cut + 1, False,
                                     cut)["cut_gap"] == 1
    short = reference.check_partition(csr, part[:-1], 8, 0.03, cut, True,
                                      cut)
    assert short["bad_labels"] == 2000
