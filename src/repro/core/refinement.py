"""k-way refinement drivers (paper §4 + the unconstrained tier).

``balance_and_refine`` is the per-level entry point: restore
feasibility, improve, re-restore. The improvement pass is selected by
the ``refine`` knob — ``"lp"`` (default) is the paper's size-constrained
LP; ``"unconstrained"`` is the Jet-style penalty-weighted search of
``core.unconstrained`` whose trailing rebalance acts as the feasibility
*afterburner* (docs/REFINEMENT.md). Either way the function never
returns an infeasible partition.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import spans
from ..graphs.format import Graph
from . import balance as bal
from . import lp

_BIG_L = np.int32(2**31 - 1)


def block_bucket(k: int, min_bucket: int = 64) -> int:
    """The padded block count (``k_pad``) of ``k`` blocks: the power of
    two at or above ``k``, and at least ``min_bucket``."""
    return max(min_bucket, 1 << max(0, (int(k) - 1)).bit_length())


def pad_blocks(block_w: np.ndarray, l_max_vec: np.ndarray,
               parent: Optional[np.ndarray], min_bucket: int = 64
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Pad the block count to a power-of-two bucket (>= min_bucket) with
    unreachable dummy blocks so jitted programs are shared across k:
    dummies carry the maximal int32 weight (so ``argmin`` never picks one
    as the balancer's lightest-block fallback — with the historical 2^30
    filler a dummy *could* win once every real block exceeded 2^30, and
    the balancer then emitted block ids >= k), have the same maximal
    budget (never overloaded, never a fitting target) and are adjacent to
    no vertex (never an adjacency target).

    Block weights must fit int32 — the jit tables are int32 throughout —
    so overlarge totals raise a ``ValueError`` instead of silently
    wrapping (the historical cast inverted the ``block_w > l_max``
    overload test)."""
    k = int(block_w.shape[0])
    if np.any(block_w.astype(np.int64) > int(_BIG_L)) or \
            np.any(block_w.astype(np.int64) < 0):
        raise ValueError(
            "pad_blocks: block weights must fit int32 (max "
            f"{int(block_w.max())}); totals >= 2^31 are not supported by "
            "the int32 jit path")
    k_pad = block_bucket(k, min_bucket)
    if k_pad == k:
        p = parent if parent is not None else np.arange(k)
        return (block_w.astype(np.int32),
                np.minimum(l_max_vec, _BIG_L).astype(np.int32),
                p.astype(np.int32), k)
    bw = np.full(k_pad, _BIG_L, dtype=np.int32)
    bw[:k] = block_w
    lv = np.full(k_pad, _BIG_L, dtype=np.int32)
    lv[:k] = np.minimum(l_max_vec, _BIG_L)
    pr = np.arange(k_pad, dtype=np.int32)
    if parent is not None:
        pr[:k] = parent
    else:
        pr[:k] = np.arange(k)
    return bw, lv, pr, k


def lp_refine(g: Graph,
              part: np.ndarray,
              l_max_vec: np.ndarray,
              parent: Optional[np.ndarray] = None,
              num_iterations: int = 2,
              num_chunks: int = 8,
              seed: int = 0) -> np.ndarray:
    """Chunked size-constrained LP refinement (jitted inner loops)."""
    n = g.n
    k = int(l_max_vec.shape[0])
    if n == 0 or k <= 1:
        return part
    with spans.span("level.refine", n=n, m=g.m, k=k) as sp:
        perm, g2 = lp.reorder(g, seed)
        part2 = np.empty(n, dtype=np.int64)
        part2[perm] = part  # part2[new_id] = part[old_id]
        with spans.span("level.slab_build"):
            chunks = lp.build_chunks(g2, num_chunks)
        n_pad = chunks.n_pad
        labels = np.zeros(n_pad + 1, dtype=np.int32)
        labels[:n] = part2
        vw = np.zeros(n_pad + 1, dtype=np.int32)
        vw[:n] = g2.vweights
        block_w = np.zeros(k, dtype=np.int64)
        np.add.at(block_w, part, g.vweights)
        bw_p, lv_p, pr_p, _ = pad_blocks(block_w, l_max_vec, parent)
        sp.set(n_pad=n_pad, m_pad=chunks.src.shape[1], k_pad=bw_p.shape[0])
        labels = spans.upload(labels)
        vw_j = spans.upload(vw)
        block_w = spans.upload(bw_p)
        l_max_j = spans.upload(lv_p)
        parent_j = spans.upload(pr_p)
        restricted = parent is not None
        with spans.span("level.iterate"):
            for it in range(num_iterations):
                labels, block_w = lp.refine_iteration(
                    labels, block_w, l_max_j, parent_j,
                    spans.upload(chunks.src), spans.upload(chunks.dst),
                    spans.upload(chunks.w), vw_j,
                    jnp.uint32((seed * 2654435761 + it) % (2**32)),
                    n=n_pad, restricted=restricted)
        out2 = spans.fetch(labels)[:n].astype(np.int64)
    return out2[perm]  # back to original ids: part[old] = out2[perm[old]]


REFINE_MODES = ("lp", "unconstrained")


def check_refine_mode(refine: str) -> str:
    if refine not in REFINE_MODES:
        raise ValueError(f"unknown refine mode {refine!r}; expected one "
                         f"of {REFINE_MODES}")
    return refine


def balance_and_refine(g: Graph,
                       part: np.ndarray,
                       l_max_vec: np.ndarray,
                       parent: Optional[np.ndarray] = None,
                       num_iterations: int = 2,
                       num_chunks: int = 8,
                       seed: int = 0,
                       kernel: str = "auto",
                       refine: str = "lp",
                       stats: Optional[Dict] = None) -> np.ndarray:
    """Paper's BalanceAndRefine: restore feasibility, improve, re-restore.

    ``refine="unconstrained"`` swaps the improvement pass for the
    penalty-weighted unconstrained search; the trailing rebalance then
    acts as the feasibility afterburner, so the result satisfies the
    budgets under either mode. ``stats`` (unconstrained mode only)
    receives the ``penalty`` schedule and the afterburner's
    ``repair_rounds``."""
    check_refine_mode(refine)
    part = bal.rebalance(g, part, l_max_vec, parent=parent, seed=seed,
                         kernel=kernel)
    if refine == "unconstrained":
        from .unconstrained import unconstrained_refine
        part = unconstrained_refine(g, part, l_max_vec, parent=parent,
                                    num_iterations=num_iterations,
                                    num_chunks=num_chunks, seed=seed,
                                    stats=stats)
        repair: Dict = {}
        part = bal.rebalance(g, part, l_max_vec, parent=parent,
                             seed=seed + 1, kernel=kernel, stats=repair)
        if stats is not None:
            stats["repair_rounds"] = repair.get("rounds")
        return part
    part = lp_refine(g, part, l_max_vec, parent=parent,
                     num_iterations=num_iterations,
                     num_chunks=num_chunks, seed=seed)
    part = bal.rebalance(g, part, l_max_vec, parent=parent, seed=seed + 1,
                         kernel=kernel)
    return part
