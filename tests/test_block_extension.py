"""Block extension at large k (``deep_mgp.extend_partition``), the path
that partitioning a 3-D mesh into many blocks runs: recording its spans
changes no result, the counters of ``extend.bipartition`` say how much
graph the serial bipartition loop was handed, the grow loop of every
bipartition takes the steps of its per-element transcription, and the
partition keeps its guarantees by the chip benchmark's own arithmetic."""
import heapq
import os
import sys

import numpy as np
import pytest

from repro import spans
from repro.api import PartitionRequest, Partitioner
from repro.core import deep_mgp, initial_partition, metrics
from repro.core.deep_mgp import PartitionerConfig
from repro.core.refinement import block_bucket
from repro.graphs import generators
from repro.graphs.format import Graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from chipbench import reference  # noqa: E402

N, K, EPS = 2**13, 256, 0.03


@pytest.fixture(scope="module")
def g():
    return generators.make("rgg3d", N, 8.0, seed=1)


@pytest.fixture(scope="module")
def runs(g):
    """One partition with records on, then the same input with them off."""
    engine = Partitioner()
    return tuple(engine.run(PartitionRequest(
        graph=g, k=K, epsilon=EPS, preset="fast", seed=0, backend="single",
        kernel="auto", collect_trace=on)) for on in (True, False))


def records(trace, name):
    return [r for r in trace if r.get("span") == name]


def test_records_change_no_assignment(runs):
    on, off = runs
    assert records(on.trace, "extend.bipartition") and off.trace == ()
    assert np.array_equal(on.assignment, off.assignment)
    assert on.cut == off.cut


def test_bipartition_counters_cover_every_level_graph(runs):
    """k is a power of two, so every block of every round is split: each
    round hands the whole level graph to the bipartition loop."""
    trace = runs[0].trace
    subgraphs = records(trace, "extend.subgraphs")
    splits = records(trace, "extend.bipartition")
    refines = records(trace, "extend.refine")
    assert len(subgraphs) == len(splits) == len(refines) >= 7
    for sub, split, ref in zip(subgraphs, splits, refines):
        c = split["counters"]
        assert c["bipartitions"] == split["attrs"]["blocks"]
        assert c["vertices"] == sub["attrs"]["n"]
        assert 0 < c["arcs"] <= sub["attrs"]["m"]
        assert ref["attrs"]["blocks"] == 2 * split["attrs"]["blocks"]
        assert ref["attrs"]["k_pad"] == block_bucket(ref["attrs"]["blocks"])
    assert sum(s["counters"]["bipartitions"] for s in splits) == K - 2
    assert max(r["attrs"]["k_pad"] for r in refines) == K


def test_vertex_counter_matches_extracted_subgraphs(g):
    """Blocks that cannot split (one block's share) are handed on, not
    bipartitioned, and are not counted."""
    part = (np.arange(N) * 4) // N
    block_k = np.array([1, 3, 2, 1])
    graphs, _ = deep_mgp.extract_block_subgraphs(g, part, 4)
    trace = []
    with spans.recording(trace):
        new_part, new_k = deep_mgp.extend_partition(
            g, part, block_k, 7, metrics.l_max(N, 7, EPS, 1),
            PartitionerConfig(), np.random.default_rng(0), target_blocks=6)
    assert new_k.tolist() == [1, *deep_mgp.split_count(3), 1, 1, 1]
    assert np.unique(new_part).tolist() == list(range(6))
    (split,) = records(trace, "extend.bipartition")
    assert {c: split["counters"][c]
            for c in ("bipartitions", "vertices", "arcs")} == {
        "bipartitions": 2,
        "vertices": graphs[1].n + graphs[2].n,
        "arcs": graphs[1].m + graphs[2].m}
    (ref,) = records(trace, "extend.refine")
    assert ref["attrs"]["k_pad"] == 64


def test_partition_keeps_the_guarantees(g, runs):
    res = runs[0]
    csr = (g.indptr, g.adjncy, g.eweights)
    nums = reference.check_partition(csr, res.assignment, K, EPS, res.cut,
                                     res.feasible, ref_cut=1)
    assert res.feasible
    assert nums["bad_labels"] == nums["cut_gap"] == nums["flag_gap"] == 0
    assert nums["slack_used"] <= 1.0
    counts = np.bincount(res.assignment, minlength=K)
    assert counts.shape == (K,) and counts.min() > 0
    assert counts.max() <= reference.l_max(N, K, EPS)


def ggg_per_element(g, target1, lmax0, lmax1, rng):
    """Greedy graph growing with its state in numpy arrays, one element
    at a time: what ``initial_partition.ggg_bipartition`` must match,
    draw for draw."""
    n = g.n
    part = np.zeros(n, dtype=np.int64)
    if n == 0:
        return part
    vw = g.vweights
    min_w1 = max(0, int(vw.sum()) - lmax0)
    wdeg = np.zeros(n, dtype=np.int64)
    np.add.at(wdeg, g.arc_tails(), g.eweights)
    gain = -wdeg
    in1 = np.zeros(n, dtype=bool)
    heap = []
    seed = int(rng.integers(n))
    heapq.heappush(heap, (0, seed))
    gain[seed] = 0
    w1 = 0
    budget = 8 * n + 64
    while w1 < target1 or w1 < min_w1:
        budget -= 1
        if budget <= 0:
            break
        if not heap:
            rest = np.flatnonzero(~in1)
            if rest.size == 0:
                break
            fits = rest[vw[rest] + w1 <= lmax1]
            if fits.size == 0:
                break
            v = int(rng.choice(fits))
            heapq.heappush(heap, (-int(gain[v]), v))
            continue
        negg, v = heapq.heappop(heap)
        if in1[v] or -negg != gain[v]:
            continue
        if w1 + int(vw[v]) > lmax1:
            continue
        in1[v] = True
        w1 += int(vw[v])
        a0, a1 = int(g.indptr[v]), int(g.indptr[v + 1])
        nbr, nw = g.adjncy[a0:a1], g.eweights[a0:a1]
        upd, uw = nbr[~in1[nbr]], nw[~in1[nbr]]
        gain[upd] += 2 * uw
        for u in upd.tolist():
            heapq.heappush(heap, (-int(gain[u]), u))
    part[in1] = 1
    return part


def _weighted(g, seed):
    rng = np.random.default_rng(seed)
    return Graph(indptr=g.indptr, adjncy=g.adjncy,
                 eweights=rng.integers(1, 5, g.adjncy.size),
                 vweights=rng.integers(1, 4, g.n))


def _blocks(g, k):
    """Subgraphs of k blocks of ids: disconnected pieces, so the grow
    loop restarts from random vertices."""
    graphs, _ = deep_mgp.extract_block_subgraphs(
        g, (np.arange(g.n) * k) // g.n, k)
    return graphs


def _multigraph(seed):
    """A mesh with a third of its arcs repeated at other weights, and
    self loops: numpy's ``+=`` keeps the last arc to a neighbour."""
    g = generators.make("rgg2d", 400, 6.0, seed=seed)
    rng = np.random.default_rng(seed)
    src, dst = g.arc_tails(), g.adjncy
    keep = src < dst
    src, dst, w = src[keep], dst[keep], g.eweights[keep]
    extra = rng.random(src.size) < 0.33
    loops = rng.choice(g.n, 20, replace=False)
    src = np.concatenate([src, src[extra], loops])
    dst = np.concatenate([dst, dst[extra], loops])
    w = np.concatenate([w, rng.integers(2, 9, int(extra.sum())),
                        np.full(20, 3)])
    tails = np.concatenate([src, dst])
    heads = np.concatenate([dst, src])
    ws = np.concatenate([w, w])
    order = np.argsort(tails, kind="stable")
    indptr = np.zeros(g.n + 1, dtype=np.int64)
    np.cumsum(np.bincount(tails, minlength=g.n), out=indptr[1:])
    return Graph(indptr=indptr, adjncy=heads[order], eweights=ws[order],
                 vweights=np.ones(g.n, dtype=np.int64))


@pytest.mark.parametrize("make", [
    lambda: generators.make("rgg3d", 2000, 8.0, seed=3),
    lambda: _weighted(generators.make("rgg2d", 1500, 6.0, seed=4), 0),
    lambda: _blocks(generators.make("rgg3d", 3000, 8.0, seed=5), 5)[2],
    lambda: _multigraph(6),
], ids=["rgg3d", "weighted", "disconnected", "multigraph"])
def test_grow_loop_takes_the_per_element_steps(make):
    g = make()
    total = int(g.vweights.sum())
    for s in range(3):
        for t1, l0, l1 in ((total // 2, total * 52 // 100 + 1,
                            total * 52 // 100 + 1),
                           (total // 3, total, total // 3 + 2),
                           (total, total // 4, total // 4)):
            ra, rb = np.random.default_rng(s), np.random.default_rng(s)
            got = initial_partition.ggg_bipartition(g, t1, l0, l1, ra)
            want = ggg_per_element(g, t1, l0, l1, rb)
            assert got.dtype == want.dtype
            assert np.array_equal(got, want)
            # the same draws taken
            assert ra.integers(2**40) == rb.integers(2**40)
