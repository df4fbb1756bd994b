"""Plain reference arithmetic, independent of the program under test.

Graphs are CSR arrays ``(indptr, adjncy, eweights)`` with unit vertex
weights; every undirected edge is stored as two arcs. The definitions
follow arXiv 2303.01417 §2: the cut is the weight of the edges between
blocks, and a partition is feasible when no block is heavier than
L_max = max{(1 + eps) c(V) / k, ceil(c(V) / k) + max_v c(v)}.

The quality reference, the partition whose cut ``cut_over_ref`` is
measured against, is named by the configuration and found under
``references/`` (``rcb`` for graphs with coordinates).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Csr = Tuple[np.ndarray, np.ndarray, np.ndarray]


def csr_from_pairs(n: int, src: np.ndarray, dst: np.ndarray) -> Csr:
    """Undirected unit-weight edges -> CSR. Self loops are dropped and
    parallel edges merged by summing their weights; rows are sorted."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    tails = np.concatenate([src[keep], dst[keep]])
    heads = np.concatenate([dst[keep], src[keep]])
    keys, weights = np.unique(tails * n + heads, return_counts=True)
    tails, heads = keys // n, keys % n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(tails, minlength=n), out=indptr[1:])
    return (indptr, heads.astype(np.int32 if n < 2**31 else np.int64),
            weights.astype(np.int64))


def arc_tails(indptr: np.ndarray) -> np.ndarray:
    n = indptr.shape[0] - 1
    return np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))


def relabel(csr: Csr, perm: np.ndarray) -> Csr:
    """The same graph with vertex v renamed perm[v]; rows sorted."""
    indptr, adjncy, eweights = csr
    n = indptr.shape[0] - 1
    tails = perm[arc_tails(indptr)]
    heads = perm[adjncy]
    order = np.argsort(tails * n + heads)
    new_indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(tails, minlength=n), out=new_indptr[1:])
    return new_indptr, heads[order].astype(adjncy.dtype), eweights[order]


def edge_cut(csr: Csr, part: np.ndarray) -> int:
    indptr, adjncy, eweights = csr
    cut_arcs = part[arc_tails(indptr)] != part[adjncy]
    return int(eweights[cut_arcs].sum()) // 2


def l_max(total: int, k: int, eps: float, max_vweight: int = 1) -> int:
    return max(int(np.floor((1.0 + eps) * total / k)),
               -(-total // k) + max_vweight)


def check_partition(csr: Csr, part, k: int, eps: float,
                    reported_cut: int, reported_feasible: bool,
                    ref_cut: int) -> Dict[str, float]:
    """Numbers compared for one answer. ``ref_cut`` is the cut of the
    reference partition of the same graph."""
    n = csr[0].shape[0] - 1
    part = np.asarray(part)
    if part.shape != (n,) or not np.issubdtype(part.dtype, np.integer):
        return {"bad_labels": n, "cut_gap": float("inf"),
                "flag_gap": 1, "slack_used": float("inf"),
                "cut_over_ref": float("inf")}
    bad = int(np.count_nonzero((part < 0) | (part >= k)))
    part = np.clip(part, 0, k - 1)
    cut = edge_cut(csr, part)
    weights = np.bincount(part, minlength=k)
    lim = l_max(n, k, eps)
    feasible = bool(weights.max() <= lim)
    return {"bad_labels": bad,
            "cut_gap": abs(int(reported_cut) - cut),
            "flag_gap": int(bool(reported_feasible) != feasible),
            "slack_used": float((weights.max() - n / k) / (lim - n / k)),
            "cut_over_ref": cut / max(1, ref_cut) - 1.0,
            "cut": cut, "feasible": feasible}
