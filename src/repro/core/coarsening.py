"""Coarsening via size-constrained label propagation clustering (paper §4).

Host driver: degree-bucket reorder -> chunked LP iterations (jitted) ->
exact max-cluster-weight enforcement (the paper's "unwind contractions that
lead to overweight clusters", applied as a final eject-to-singleton sweep;
multi-member clusters are always reducible below W, singletons heavier than
W are tolerated exactly as in the paper — the balance constraint absorbs
them via the ``+ max_v c(v)`` term).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import spans
from ..graphs.format import Graph
from ..kernels import dispatch
from ..kernels.lp_move import ops as move_ops
from . import lp


def ejection_candidates(labels: np.ndarray, vweights: np.ndarray,
                        max_weight: int) -> np.ndarray:
    """Vertices that must leave their overweight cluster, under the
    deterministic keep-heaviest-first-prefix rule: members sort by
    (cluster, -weight, id) and a member is ejected once the cumulative
    kept weight including it exceeds ``max_weight`` — except each
    cluster's first (heaviest) member, since singletons may legitimately
    exceed W. This is the shared decision rule: the sharded enforcement
    (``dist.dist_balance.dist_enforce_cluster_weights``) runs the same
    sort owner-side and must eject the identical vertex set."""
    n = labels.shape[0]
    cw = np.zeros(n, dtype=np.int64)
    np.add.at(cw, labels, vweights)
    over = cw > max_weight
    if not over.any():
        return np.empty(0, dtype=np.int64)
    members = np.flatnonzero(over[labels])
    # keep heaviest-first prefix per cluster (fewest ejections)
    order = np.lexsort((members, -vweights[members], labels[members]))
    sid = labels[members][order]
    sw = vweights[members][order]
    csum = np.cumsum(sw)
    starts = np.concatenate([[True], sid[1:] != sid[:-1]])
    gidx = np.cumsum(starts) - 1
    gstart = np.flatnonzero(starts)
    base = (csum[gstart] - sw[gstart])[gidx]
    within = csum - base
    eject = (within > max_weight) & ~starts
    return members[order][eject].astype(np.int64)


def enforce_cluster_weights(labels: np.ndarray, vweights: np.ndarray,
                            max_weight: int) -> np.ndarray:
    """Eject members of overweight clusters into fresh singleton clusters
    until every multi-member cluster fits. One exact pass."""
    n = labels.shape[0]
    ej = ejection_candidates(labels, vweights, max_weight)
    if ej.size == 0:
        return labels
    used = np.zeros(n, dtype=bool)
    keep_members = np.setdiff1d(np.arange(n), ej, assume_unique=False)
    used[labels[keep_members]] = True
    free = np.flatnonzero(~used)
    assert free.size >= ej.size, "no free cluster ids for ejection"
    out = labels.copy()
    out[ej] = free[:ej.size]
    return out


def cluster_prepare(g: Graph, num_chunks: int, seed: int,
                    kernel: str = "composed"):
    """Host-side setup shared by the solo and stacked clustering paths:
    seeded degree-bucket reorder, permuted graph, padded chunk slabs.
    Returns ``(perm, g2, chunks)``. Kept per-request even when requests
    are batched — the reorder draws from a per-request RNG, so any
    batch-level change here would break solo bit-identity.

    ``kernel="fused"`` builds ELL slabs for the Pallas move kernel
    instead of arc slabs (falling back to arc slabs when the chunk
    working set would not fit the kernel's VMEM budget); both describe
    identical vertex ranges (``lp.chunk_bounds``)."""
    perm, g2 = lp.reorder(g, seed)
    if kernel == "fused":
        with spans.span("level.ell_build", kernel="lp_move") as sp:
            chunks = move_ops.build_move_chunks(g2, num_chunks)
            used = move_ops.move_chunks_fit_vmem(chunks)
            _, R, D = chunks.shape
            sp.set(used=used, rows=R, lanes=D)
        if used:
            return perm, g2, chunks
        dispatch.report_fallback(
            "lp_move",
            move_ops.lp_move_vmem_bytes(R, D, move_ops.ROW_TILE),
            detail="cluster_prepare")
    with spans.span("level.slab_build"):
        chunks = lp.build_chunks(g2, num_chunks)
    return perm, g2, chunks


def cluster_seed(seed: int, iteration: int) -> np.uint32:
    """The jit-side salt stream for LP-clustering iteration ``it``."""
    return np.uint32((seed * 1000003 + iteration) % (2**32))


def cluster_finish(labels_pad: np.ndarray, g2: Graph, perm: np.ndarray,
                   max_cluster_weight: int) -> np.ndarray:
    """Shared epilogue: slice the padded label vector to the real
    vertices, exactly enforce the cluster-weight bound, and map the
    labels back to the input graph's vertex numbering."""
    n = g2.n
    lab2 = spans.fetch(labels_pad)[:n].astype(np.int64)
    with spans.span("level.enforce_weights"):
        lab2 = enforce_cluster_weights(lab2, np.asarray(g2.vweights),
                                       int(max_cluster_weight))
    return lab2[perm]


def cluster(g: Graph,
            max_cluster_weight: int,
            num_iterations: int = 3,
            num_chunks: int = 8,
            seed: int = 0,
            kernel: str = "auto") -> np.ndarray:
    """Size-constrained LP clustering. Returns cluster labels (n,) in the
    input graph's vertex numbering; label values are arbitrary ids.

    ``kernel`` selects the chunk-move implementation (see
    ``kernels.dispatch``); "fused" and "composed" produce bit-identical
    labels."""
    n = g.n
    if n <= 1:
        return np.zeros(n, dtype=np.int64)
    mode = dispatch.resolve_kernel_mode(kernel)
    with spans.span("level.cluster", n=n, m=g.m) as sp:
        perm, g2, chunks = cluster_prepare(g, num_chunks, seed, kernel=mode)
        np_pad = chunks.n_pad
        fused = isinstance(chunks, move_ops.MoveChunks)
        sp.set(n_pad=np_pad, kernel="fused" if fused else "composed")
        if not fused:
            sp.set(m_pad=chunks.src.shape[1])
        labels = jnp.arange(np_pad + 1, dtype=jnp.int32)
        vw = np.zeros(np_pad + 1, dtype=np.int32)
        vw[:n] = g2.vweights
        vw = spans.upload(vw)
        cluster_w = vw
        W = jnp.int32(max(1, max_cluster_weight))
        with spans.span("level.iterate"):
            if fused:
                idx, cw_slab = (spans.upload(chunks.idx),
                                spans.upload(chunks.w))
                v0s = spans.upload(chunks.v0)
                interp = dispatch.kernel_interpret()
                for it in range(num_iterations):
                    labels, cluster_w = move_ops.cluster_iteration_fused(
                        labels, cluster_w, idx, cw_slab, v0s, vw, W,
                        jnp.uint32(cluster_seed(seed, it)), n=np_pad,
                        interpret=interp)
            else:
                for it in range(num_iterations):
                    labels, cluster_w = lp.cluster_iteration(
                        labels, cluster_w, spans.upload(chunks.src),
                        spans.upload(chunks.dst), spans.upload(chunks.w),
                        vw, W, jnp.uint32(cluster_seed(seed, it)),
                        n=np_pad)
        return cluster_finish(labels, g2, perm, int(W))
