"""Unconstrained (Jet-style) k-way refinement with penalty-weighted gains.

Second refinement tier behind ``PartitionerConfig(refine="unconstrained")``
(arXiv 2406.03169, same authors as the source paper): moves may violate
the balance constraint during the pass, so the search escapes the local
optima that the size-constrained LP rule (``core.lp._refine_chunk``)
gets pinned against when every improving move targets a full block.
Feasibility is restored afterwards by the balancer acting as an
*afterburner* (``core.balance.rebalance`` /
``dist.dist_balance.dist_rebalance``) — callers through
``refinement.balance_and_refine`` never observe an infeasible result.

The move rule replaces the hard budget mask with a **penalty-weighted
gain**: a move whose target block would exceed its budget is charged

    pen = (own_connection // R) * r          (round r of R, integer math)

so round 0 is fully unconstrained (pure gain-greedy) and later rounds
escalate the required gain for overloading moves toward ~2x the own
connection, herding the partition back toward feasibility before the
repair pass. The penalty is integer-only and overflow-safe:
``pen <= own_connection < 2^31``. Everything else — the chunked arc
slabs, the 4-stage argmax tie-break, the zero-gain-into-lighter-block
rule, the salt streams — reuses ``core.lp`` verbatim, so the tier costs
no new kernel machinery. The distributed twin lives in
``dist.dist_lp.dist_ulp_refine``. See docs/REFINEMENT.md.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..graphs.format import Graph
from . import lp
from .lp import I32_MAX, _argmax_target, _group_conns, _own_connection


def penalty_schedule(num_iterations: int) -> list:
    """The escalating per-round penalty fractions ``r / R`` (round 0 is
    fully unconstrained) — recorded in trace records and docs."""
    R = max(1, int(num_iterations))
    return [round(r / R, 4) for r in range(R)]


def _urefine_chunk(labels, block_w, l_max, parent, chunk_src, chunk_dst,
                   chunk_w, vweights, salt, pen_num, pen_den, n,
                   restricted):
    """One chunk of unconstrained LP refinement over k blocks.

    Identical to ``lp._refine_chunk`` except the budget mask: instead of
    rejecting moves into full blocks, candidates whose target would end
    up over budget pay ``(own_conn // pen_den) * pen_num`` off their
    connection before the argmax, and the block-weight tables track the
    (possibly overloaded) truth. ``restricted`` keeps the
    sibling-confinement semantics of the extension pass."""
    lab_dst = labels[chunk_dst]
    s_src, s_lab, s_w = jax.lax.sort(
        (chunk_src, lab_dst, chunk_w), num_keys=2)
    conn = _group_conns(s_src, s_lab, s_w)
    own_lab = labels[s_src]
    staying = s_lab == own_lab
    own_conn = _own_connection(s_src, s_lab, s_w, labels, n)
    # would the target overflow its budget after taking this vertex?
    # (``w > budget - c`` form: exact at the int32 boundary)
    over_after = block_w[s_lab] > l_max[s_lab] - vweights[s_src]
    pen = jnp.where(over_after,
                    (own_conn[s_src] // pen_den) * pen_num, 0)
    ok = ~staying
    if restricted:
        ok &= parent[s_lab] == parent[own_lab]
    # clamping to -1 loses nothing: a candidate with penalized score < 0
    # can never pass the move rule (it would need score >= own_conn >= 0)
    score = jnp.where(ok, jnp.maximum(conn - pen, -1), -1)
    best, target = _argmax_target(s_src, s_lab, score,
                                  block_w[s_lab], salt, n)
    gain = best - own_conn
    tgt_safe = jnp.where(target < I32_MAX, target, 0)
    lighter = block_w[tgt_safe] < block_w[labels] - vweights
    move = (target < I32_MAX) & (best >= 0) & \
        ((gain > 0) | ((gain == 0) & lighter))
    move = move.at[n].set(False)
    new_labels = jnp.where(move, tgt_safe, labels)
    vw_moved = jnp.where(move, vweights, 0)
    k = block_w.shape[0]
    d_in = jax.ops.segment_sum(vw_moved, jnp.where(move, tgt_safe, 0),
                               num_segments=k)
    d_out = jax.ops.segment_sum(vw_moved, jnp.where(move, labels, 0),
                                num_segments=k)
    return new_labels, block_w + d_in - d_out


@functools.partial(jax.jit, static_argnames=("n", "restricted"))
def urefine_iteration(labels, block_w, l_max, parent, chunks_src,
                      chunks_dst, chunks_w, vweights, seed, pen_num,
                      pen_den, *, n, restricted=False):
    """One unconstrained refinement pass over all chunks. ``pen_num`` /
    ``pen_den`` are traced int32 scalars so every round of the schedule
    shares one compiled program."""
    B = chunks_src.shape[0]

    def body(carry, xs):
        labels, block_w = carry
        c_src, c_dst, c_w, salt = xs
        labels, block_w = _urefine_chunk(
            labels, block_w, l_max, parent, c_src, c_dst, c_w, vweights,
            salt, pen_num, pen_den, n, restricted)
        return (labels, block_w), ()

    salts = (jnp.arange(B, dtype=jnp.uint32) * np.uint32(0xC2B2AE35)
             + seed.astype(jnp.uint32))
    (labels, block_w), _ = jax.lax.scan(
        body, (labels, block_w), (chunks_src, chunks_dst, chunks_w, salts))
    return labels, block_w


def unconstrained_refine(g: Graph,
                         part: np.ndarray,
                         l_max_vec: np.ndarray,
                         parent: Optional[np.ndarray] = None,
                         num_iterations: int = 2,
                         num_chunks: int = 8,
                         seed: int = 0,
                         stats: Optional[Dict] = None) -> np.ndarray:
    """Host driver: chunked unconstrained refinement (jitted inner loops).

    Same skeleton as ``refinement.lp_refine`` — degree-bucket reorder,
    padded arc slabs, one ``urefine_iteration`` per round — but the
    result may violate the per-block budgets; callers must follow with
    ``balance.rebalance`` (``balance_and_refine`` does). ``stats``,
    when given, receives the ``penalty`` schedule actually applied."""
    n = g.n
    k = int(l_max_vec.shape[0])
    if stats is not None:
        stats["penalty"] = penalty_schedule(num_iterations)
    if n == 0 or k <= 1 or num_iterations < 1:
        return part
    with spans.span("level.refine", n=n, m=g.m, k=k,
                    mode="unconstrained") as sp:
        perm, g2 = lp.reorder(g, seed)
        part2 = np.empty(n, dtype=np.int64)
        part2[perm] = part
        with spans.span("level.slab_build"):
            chunks = lp.build_chunks(g2, num_chunks)
        n_pad = chunks.n_pad
        labels = np.zeros(n_pad + 1, dtype=np.int32)
        labels[:n] = part2
        vw = np.zeros(n_pad + 1, dtype=np.int32)
        vw[:n] = g2.vweights
        block_w = np.zeros(k, dtype=np.int64)
        np.add.at(block_w, part, g.vweights)
        from .refinement import pad_blocks  # deferred: refinement imports us
        bw_p, lv_p, pr_p, _ = pad_blocks(block_w, l_max_vec, parent)
        sp.set(n_pad=n_pad, m_pad=chunks.src.shape[1], k_pad=bw_p.shape[0])
        labels = spans.upload(labels)
        vw_j = spans.upload(vw)
        block_w = spans.upload(bw_p)
        l_max_j = spans.upload(lv_p)
        parent_j = spans.upload(pr_p)
        restricted = parent is not None
        pen_den = jnp.int32(num_iterations)
        with spans.span("level.iterate"):
            for it in range(num_iterations):
                labels, block_w = urefine_iteration(
                    labels, block_w, l_max_j, parent_j,
                    spans.upload(chunks.src), spans.upload(chunks.dst),
                    spans.upload(chunks.w), vw_j,
                    jnp.uint32((seed * 2654435761 + it) % (2**32)),
                    jnp.int32(it), pen_den, n=n_pad, restricted=restricted)
        out2 = spans.fetch(labels)[:n].astype(np.int64)
    return out2[perm]
