"""Cluster contraction (paper §5, Graph Contraction) — host side.

Deduplicates inter-cluster arcs and accumulates vertex/edge weights. The
distributed version (dist/dist_contraction.py) adds the cluster->PE
assignment and the all-to-all edge exchange; ``dedup_arcs`` below is the
sequential kernel shared by both (the host contraction here, the per-PE
local pre-contraction and owner-side accumulation there)."""
from __future__ import annotations

from typing import Tuple

import numpy as np

from .. import spans
from ..graphs.format import Graph, from_coo
from ..kernels import dispatch


def dedup_arcs(csrc: np.ndarray, cdst: np.ndarray, w: np.ndarray,
               kernel: str = "composed"
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Drop self loops and merge parallel arcs (summing weights).

    Returns (src, dst, w) int64 arrays sorted by (src, dst). This is the
    local contraction kernel: ``contract`` runs it over the whole arc
    set, the distributed path runs it per PE before and after the edge
    exchange. ``kernel="fused"`` routes through the seg_merge Pallas
    kernel (bit-identical; keeps numpy when the records exceed the
    kernel's int32/VMEM envelope, reported via
    ``dispatch.report_fallback``).
    """
    with spans.span("level.dedup", arcs=int(csrc.size)):
        return _dedup_arcs(csrc, cdst, w, kernel)


def _dedup_arcs(csrc, cdst, w, kernel):
    if dispatch.resolve_kernel_mode(kernel) == "fused":
        from ..kernels.seg_merge import ops as seg_ops
        if seg_ops.dedup_fits(csrc, cdst, w):
            return seg_ops.dedup_arcs_fused(
                csrc, cdst, w, interpret=dispatch.kernel_interpret())
        if csrc.size:
            from ..kernels.seg_merge.seg_merge import seg_merge_vmem_bytes
            dispatch.report_fallback(
                "seg_merge", seg_merge_vmem_bytes(
                    seg_ops.dedup_records(csrc, cdst)),
                detail="dedup_arcs (int32/VMEM envelope)")
    keep = csrc != cdst
    csrc, cdst, w = csrc[keep], cdst[keep], w[keep]
    if csrc.size == 0:
        return (csrc.astype(np.int64), cdst.astype(np.int64),
                w.astype(np.int64))
    order = np.lexsort((cdst, csrc))
    csrc, cdst, w = csrc[order], cdst[order], w[order]
    first = np.concatenate(
        [[True], (csrc[1:] != csrc[:-1]) | (cdst[1:] != cdst[:-1])])
    seg = np.cumsum(first) - 1
    merged = np.zeros(int(seg[-1]) + 1, dtype=np.int64)
    np.add.at(merged, seg, w)
    return (csrc[first].astype(np.int64), cdst[first].astype(np.int64),
            merged)


def contract(g: Graph, labels: np.ndarray,
             kernel: str = "composed") -> Tuple[Graph, np.ndarray]:
    """Contract clustering ``labels`` (arbitrary ids). Returns
    (coarse_graph, fine_to_coarse) with fine_to_coarse[v] in [0, n_c)."""
    with spans.span("level.contract", n=g.n, m=g.m) as sp:
        uniq, cl = np.unique(labels, return_inverse=True)
        nc = int(uniq.size)
        cvw = np.zeros(nc, dtype=np.int64)
        np.add.at(cvw, cl, g.vweights)
        src = g.arc_tails()
        csrc, cdst, w = dedup_arcs(cl[src], cl[g.adjncy], g.eweights,
                                   kernel=kernel)
        gc = from_coo(nc, csrc, cdst, eweights=w, vweights=cvw,
                      symmetrize=False, dedup=False)
        sp.set(coarse_n=nc, coarse_m=gc.m)
    return gc, cl.astype(np.int64)
