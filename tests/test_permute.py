"""``format.permute`` relabels by gathering rows; it must return exactly
what sorting every arc by (new tail, new head) returns."""
import numpy as np
import pytest

from repro.core import lp
from repro.graphs import generators
from repro.graphs.format import Graph, degree_bucket_order, from_coo, permute


def _lexsort_permute(g, perm):
    """Reference relabel: sort all arcs by (new tail, new head), stably."""
    inv = np.empty_like(perm)
    inv[perm] = np.arange(g.n, dtype=perm.dtype)
    new_src = perm[g.arc_tails()]
    new_dst = perm[g.adjncy]
    order = np.lexsort((new_dst, new_src))
    indptr = np.zeros(g.n + 1, dtype=np.int64)
    np.add.at(indptr, new_src + 1, 1)
    return Graph(indptr=np.cumsum(indptr),
                 adjncy=new_dst[order].astype(g.adjncy.dtype),
                 eweights=g.eweights[order],
                 vweights=g.vweights[inv]), inv


def _arrays(g, inv):
    return (g.indptr, g.adjncy, g.eweights, g.vweights, inv)


def _assert_same(got, want):
    for a, b in zip(_arrays(*got), _arrays(*want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def _random_arcs(rng, n, m):
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    return src, dst, rng.integers(1, 9, m)


def _symmetric(rng):
    return generators.rgg2d(600, 8.0, int(rng.integers(100)))


def _one_directional(rng):
    src, dst, w = _random_arcs(rng, 300, 1500)
    return from_coo(300, src, dst, w, symmetrize=False)


def _parallel_arcs(rng):
    # few vertices and many arcs: most rows hold repeated heads, each
    # copy with its own weight, so the tie order shows in ``eweights``
    src, dst, w = _random_arcs(rng, 40, 2000)
    return from_coo(40, src, dst, w, symmetrize=False, dedup=False)


def _symmetric_parallel(rng):
    src, dst, w = _random_arcs(rng, 50, 400)
    return from_coo(50, src, dst, w, dedup=False)


def _isolated_vertices(rng):
    # arcs only among the first 100 of 400 vertices; weighted vertices
    src, dst, w = _random_arcs(rng, 100, 600)
    return from_coo(400, src, dst, w,
                    vweights=rng.integers(1, 5, 400))


def _single_vertex(rng):
    return from_coo(1, np.zeros(0), np.zeros(0))


def _no_arcs(rng):
    return from_coo(7, np.zeros(0), np.zeros(0),
                    vweights=rng.integers(1, 5, 7))


def _int64_adjncy(rng):
    g = _parallel_arcs(rng)
    return Graph(g.indptr, g.adjncy.astype(np.int64), g.eweights,
                 g.vweights)


GRAPHS = [_symmetric, _one_directional, _parallel_arcs,
          _symmetric_parallel, _isolated_vertices, _single_vertex,
          _no_arcs, _int64_adjncy]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("make", GRAPHS, ids=lambda f: f.__name__[1:])
def test_permute_matches_lexsort(make, seed):
    rng = np.random.default_rng(seed)
    g = make(rng)
    for perm in (rng.permutation(g.n),
                 rng.permutation(g.n).astype(np.int32),
                 np.arange(g.n)):
        _assert_same(permute(g, perm), _lexsort_permute(g, perm))


@pytest.mark.parametrize("seed", [3, 2**40 + 9])
def test_reorder_is_degree_buckets_then_relabel(seed):
    g = generators.make("rhg", 800, 8.0, seed=2)
    perm, got = lp.reorder(g, seed)
    order = degree_bucket_order(g, np.random.default_rng(seed))
    want_perm = np.empty(g.n, dtype=np.int64)
    want_perm[order] = np.arange(g.n)
    np.testing.assert_array_equal(perm, want_perm)
    want, want_inv = _lexsort_permute(g, want_perm)
    _assert_same((got, want_inv), (want, want_inv))
