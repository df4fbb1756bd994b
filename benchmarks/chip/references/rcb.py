"""rcb: recursive coordinate bisection of the generator's points, a plain
geometric partition of the same graph, for graph families with
coordinates. Its cut is what a configuration's ``cut_over_ref`` is
measured against.

Each step splits a point set across its widest axis, k1 = k // 2
blocks' share to one side. ``skew`` makes the first split give the first
k1 blocks (1 + skew) times their share, so that each of them is
(1 + skew) times the average: the control that breaks balance.
"""
import numpy as np


def partition(points, csr, k: int, skew: float = 0.0) -> np.ndarray:
    n = points.shape[0]
    part = np.empty(n, dtype=np.int64)
    stack = [(np.arange(n), 0, k, skew)]
    while stack:
        idx, first, kk, sk = stack.pop()
        if kk == 1:
            part[idx] = first
            continue
        k1 = kk // 2
        pts = points[idx]
        axis = int(np.argmax(pts.max(axis=0) - pts.min(axis=0)))
        n1 = min(len(idx), int(round(len(idx) * k1 / kk * (1.0 + sk))))
        if 0 < n1 < len(idx):
            order = np.argpartition(pts[:, axis], n1)
        else:
            order = np.arange(len(idx))
        stack.append((idx[order[:n1]], first, k1, 0.0))
        stack.append((idx[order[n1:]], first + k1, kk - k1, 0.0))
    return part
