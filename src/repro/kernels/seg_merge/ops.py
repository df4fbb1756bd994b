"""Host wrapper: fused duplicate-arc merge backing ``dedup_arcs``.

``core.contraction.dedup_arcs`` is int64 numpy (lexsort + ``np.add.at``).
The fused path runs the seg_merge Pallas kernel instead when the record
ids and weight totals fit int32 and the slab fits the kernel's VMEM
budget; otherwise it reports "doesn't apply" and the caller keeps the
numpy kernel. Results are identical: same (src, dst)-sorted unique arcs,
same summed weights.
"""
from __future__ import annotations

import numpy as np

from ... import spans
from .seg_merge import I32_MAX, padded_lanes, seg_merge, seg_merge_vmem_bytes
from ..dispatch import VMEM_BUDGET_BYTES


def dedup_records(csrc: np.ndarray, cdst: np.ndarray) -> int:
    """Records the kernel sorts: self loops are dropped before it."""
    return int(np.count_nonzero(csrc != cdst))


def dedup_fits(csrc: np.ndarray, cdst: np.ndarray, w: np.ndarray) -> bool:
    """int32-exactness + VMEM guard for the fused dedup path."""
    if csrc.size == 0:
        return False
    if int(csrc.max(initial=0)) >= int(I32_MAX) or \
            int(cdst.max(initial=0)) >= int(I32_MAX):
        return False
    if int(np.abs(w).astype(np.int64).sum()) >= 2**31:
        return False
    return seg_merge_vmem_bytes(dedup_records(csrc, cdst)) <= \
        VMEM_BUDGET_BYTES


def dedup_arcs_fused(csrc: np.ndarray, cdst: np.ndarray, w: np.ndarray,
                     interpret: bool = True):
    """Fused twin of ``core.contraction.dedup_arcs`` (same contract:
    drop self loops, merge parallel arcs, return int64 sorted by
    (src, dst)). Caller must have checked ``dedup_fits``."""
    keep = csrc != cdst
    csrc, cdst, w = csrc[keep], cdst[keep], w[keep]
    if csrc.size == 0:
        return (csrc.astype(np.int64), cdst.astype(np.int64),
                w.astype(np.int64))
    L = padded_lanes(csrc.size)
    pad = L - csrc.size
    src32 = np.concatenate([csrc.astype(np.int32),
                            np.full(pad, I32_MAX, np.int32)])
    dst32 = np.concatenate([cdst.astype(np.int32),
                            np.full(pad, I32_MAX, np.int32)])
    w32 = np.concatenate([w.astype(np.int32), np.zeros(pad, np.int32)])
    s_src, s_dst, tot, first = (spans.fetch(x) for x in seg_merge(
        spans.upload(src32), spans.upload(dst32), spans.upload(w32),
        interpret=interpret))
    take = (s_src < int(I32_MAX)) & (first != 0)
    return (s_src[take].astype(np.int64), s_dst[take].astype(np.int64),
            tot[take].astype(np.int64))
