"""Greedy global balancing (paper §4, Balancing).

TPU-native adaptation of the PQ + binary-tree-reduction scheme:

  * per-PE priority queues        ->  ``lax.top_k`` over relative gains
    (a PQ is only ever popped from the top; top-k is the array equivalent
    and the queue-size invariant is the pool size ``top_m``)
  * binary tree reduction + root  ->  gather of per-shard top lists + the
    decides + broadcast               same deterministic greedy selection
                                      executed redundantly everywhere
  * "update gains of neighbors"   ->  gains recomputed per round (rounds
                                      are few; the paper assumes few moves
                                      suffice, so recompute is cheap)

Relative gain (paper): g·c(v) if g >= 0 else g/c(v) where g is the best
cut reduction over targets that would not become overloaded. Moving to any
*non-adjacent* block has g = -own_connection; the lightest such block is
always a valid fallback because L_max >= c(V)/k + max_v c(v), which is what
guarantees termination (feasibility is always reachable).

The round is factored into two kernels shared with the distributed
balancer (``dist.dist_balance``): ``balance_gains`` (per-vertex relative
gains + targets over an arc slab — each PE runs it over its own shard)
and ``greedy_select`` (the deterministic greedy application of a ranked
candidate pool — run redundantly on every PE so no root/broadcast step
is needed). Two historical host edge cases are fixed here: padded
vertices can no longer enter the candidate pool (their zero relative
gain used to displace real negative-gain candidates), and feasibility
comparisons are arranged as ``w <= budget - c`` so they cannot wrap at
the int32 boundary.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..graphs.format import Graph
from ..kernels import dispatch
from . import lp
from .lp import I32_MAX, _argmax_target, _group_conns, _own_connection

NEG_INF = np.float32(-np.inf)


def balance_gains(lab_src_tab, s_src, s_lab, s_w, block_w, l_max, parent,
                  vw_pad, salt, n, valid, restricted=False):
    """Per-vertex relative gains + targets for one balancing round.

    ``(s_src, s_lab, s_w)`` is the arc slab sorted by (src, label[dst]);
    ``lab_src_tab``/``vw_pad``/``valid`` live over the (n+1,) src space
    (slot n is the sentinel). ``valid`` masks real vertices — padded
    slots must never enter the candidate pool. Returns ``(rel, tgt)``:
    the paper's relative gain (NEG_INF where the vertex must not move)
    and the chosen target block.

    All weight comparisons are written ``w <= budget - c`` so that they
    stay exact for totals at the int32 boundary (``w + c`` could wrap).
    """
    k = block_w.shape[0]
    over = block_w > l_max
    conn = _group_conns(s_src, s_lab, s_w)
    own_lab = lab_src_tab[s_src]
    # target must not become overloaded (fits) and differ from own block
    fits = block_w[s_lab] <= l_max[s_lab] - vw_pad[s_src]
    ok = fits & (s_lab != own_lab)
    if restricted:
        ok &= parent[s_lab] == parent[own_lab]
    score = jnp.where(ok, conn, -1)
    best, target = _argmax_target(s_src, s_lab, score, block_w[s_lab],
                                  salt, n)
    own_conn = _own_connection(s_src, s_lab, s_w, lab_src_tab, n)

    has_adj = (best >= 0) & (target < I32_MAX)
    tgt_adj = jnp.where(has_adj, target, 0)
    gain_adj = best - own_conn

    if restricted:
        # fallback target: the lightest sibling within the own parent group
        # (O(k) via segment-min over blocks grouped by parent)
        grp_min = jax.ops.segment_min(block_w, parent, num_segments=k)
        is_min = block_w == grp_min[parent]
        bid = jnp.where(is_min, jnp.arange(k, dtype=jnp.int32), I32_MAX)
        grp_argmin = jax.ops.segment_min(bid, parent, num_segments=k)
        fb_t = grp_argmin[parent[lab_src_tab]]
    else:
        fb_t = jnp.full((n + 1,), jnp.argmin(block_w).astype(jnp.int32))
    fb_ok = (block_w[fb_t] <= l_max[fb_t] - vw_pad) & (fb_t != lab_src_tab)
    gain_fb = -own_conn

    tgt = jnp.where(has_adj, tgt_adj, fb_t)
    g = jnp.where(has_adj, gain_adj, gain_fb)
    movable = over[lab_src_tab] & (has_adj | fb_ok) & valid

    gf = g.astype(jnp.float32)
    cv = jnp.maximum(vw_pad.astype(jnp.float32), 1.0)
    rel = jnp.where(g >= 0, gf * cv, gf / cv)
    rel = jnp.where(movable, rel, NEG_INF)
    return rel, tgt


def greedy_select(vals, tgt_blk, src_blk, cand_w, block_w, l_max):
    """Deterministic greedy application of a ranked candidate pool.

    The pool arrays must already be ordered by descending relative gain
    (ties by ascending vertex id); every PE of the distributed balancer
    runs this redundantly over the identical gathered pool, so accept
    decisions agree everywhere without a root/broadcast step. Returns
    ``(accept, block_w)``.
    """
    m = vals.shape[0]

    def body(i, carry):
        block_w, accept = carry
        t = tgt_blk[i]
        b = src_blk[i]
        cw = cand_w[i]
        ok = (vals[i] > NEG_INF) & (block_w[b] > l_max[b]) & \
             (block_w[t] <= l_max[t] - cw) & (t != b)
        cwd = jnp.where(ok, cw, 0)
        block_w = block_w.at[b].add(-cwd).at[t].add(cwd)
        accept = accept.at[i].set(ok)
        return block_w, accept

    block_w, accept = jax.lax.fori_loop(
        0, m, body, (block_w, jnp.zeros_like(vals, dtype=jnp.bool_)))
    return accept, block_w


@functools.partial(jax.jit, static_argnames=("n", "top_m", "restricted"))
def balance_round(labels, block_w, l_max, parent, src, dst, w, vweights,
                  valid, salt, *, n, top_m, restricted=False):
    """One global balancing round. Returns (labels, block_w, still_overloaded).

    All arrays over vertices have size n+1 (sentinel slot n); ``valid``
    marks the real vertices among them."""
    lab_dst = labels[dst]
    s_src, s_lab, s_w = jax.lax.sort((src, lab_dst, w), num_keys=2)
    rel, tgt = balance_gains(labels, s_src, s_lab, s_w, block_w, l_max,
                             parent, vweights, salt, n, valid,
                             restricted=restricted)
    vals, vidx = jax.lax.top_k(rel, top_m)
    accept, block_w = greedy_select(vals, tgt[vidx], labels[vidx],
                                    vweights[vidx], block_w, l_max)
    labels = labels.at[vidx].set(
        jnp.where(accept, tgt[vidx], labels[vidx]))
    return labels, block_w, jnp.any(block_w > l_max)


def rebalance(g: Graph,
              part: np.ndarray,
              l_max_vec: np.ndarray,
              parent: Optional[np.ndarray] = None,
              top_m: int = 128,
              max_rounds: int = 200,
              seed: int = 0,
              kernel: str = "auto",
              stats: Optional[Dict] = None) -> np.ndarray:
    """Host driver: run balance rounds until feasible. ``part`` is (n,) block
    ids; ``l_max_vec`` is (k,) per-block budgets.

    Already-feasible partitions return immediately without building the
    O(m) chunk slabs or touching a device. ``kernel="fused"`` runs the
    round through the ``kernels.bal_round`` Pallas pair (bit-identical;
    keeps the composed round when the ELL slab exceeds the VMEM budget,
    reporting the fallback via ``dispatch.report_fallback``). ``stats``,
    when given, receives ``rounds`` / ``time_s`` / ``gather_bytes`` for
    benchmarks.
    """
    n = g.n
    k = int(l_max_vec.shape[0])
    t_start = time.perf_counter()
    with spans.span("level.balance", n=n, m=g.m, k=k) as sp:
        with spans.span("level.feasibility"):
            from . import metrics
            block_w = metrics.block_weights(g, part, k)
            feasible = not bool(np.any(block_w > l_max_vec))
        if feasible:
            sp.add("rounds", 0)
            if stats is not None:
                stats.update(rounds=0, gather_bytes=0,
                             time_s=time.perf_counter() - t_start)
            return np.array(part, dtype=np.int64)  # fresh, never a view
        # build_chunks raises a clear ValueError for totals >= 2^31 (the
        # int32 jit path would wrap)
        with spans.span("level.slab_build"):
            chunks = lp.build_chunks(g, 1)
        n_pad = chunks.n_pad
        top_m = min(top_m, n_pad + 1)
        labels = np.zeros(n_pad + 1, dtype=np.int32)
        labels[:n] = part
        vw = np.zeros(n_pad + 1, dtype=np.int32)
        vw[:n] = g.vweights
        from .refinement import pad_blocks
        bw_p, lv_p, pr_p, _ = pad_blocks(block_w, l_max_vec, parent)
        sp.set(n_pad=n_pad, m_pad=chunks.src.shape[1], k_pad=bw_p.shape[0])
        labels = spans.upload(labels)
        vw_j = spans.upload(vw)
        block_w = spans.upload(bw_p)
        l_max_j = spans.upload(lv_p)
        parent_j = spans.upload(pr_p)
        valid = spans.upload(np.arange(n_pad + 1) < n)
        restricted = parent is not None
        fused_ell = None
        if dispatch.resolve_kernel_mode(kernel) == "fused":
            from ..kernels.bal_round import ops as bal_ops
            # the gate reads the ELL's shape: a refused ELL is not built
            rows, lanes = bal_ops.balance_ell_shape(g, n_pad)
            used = bal_ops.balance_ell_fits(rows, lanes,
                                            restricted=restricted)
            with spans.span("level.ell_build", kernel="bal_round",
                            used=used, rows=rows, lanes=lanes):
                if used:
                    idx, ew = bal_ops.build_balance_ell(g, n_pad)
            if used:
                fused_ell = (spans.upload(idx), spans.upload(ew))
            else:
                dispatch.report_fallback(
                    "bal_round",
                    bal_ops.bal_scores_vmem_bytes(
                        rows, lanes, bal_ops.ROW_TILE,
                        restricted=restricted),
                    detail="rebalance")
        if fused_ell is None:
            src = spans.upload(chunks.src[0])
            dst = spans.upload(chunks.dst[0])
            w = spans.upload(chunks.w[0])
        rounds = 0
        with spans.span("level.iterate"):
            for r in range(max_rounds):
                salt = jnp.uint32((seed * 7919 + r) % (2**32))
                if fused_ell is not None:
                    labels, block_w, overloaded = \
                        bal_ops.balance_round_fused(
                            labels, block_w, l_max_j, parent_j,
                            fused_ell[0], fused_ell[1], vw_j, valid, salt,
                            n=n_pad, top_m=top_m, restricted=restricted,
                            interpret=dispatch.kernel_interpret())
                else:
                    labels, block_w, overloaded = balance_round(
                        labels, block_w, l_max_j, parent_j, src, dst, w,
                        vw_j, valid, salt, n=n_pad, top_m=top_m,
                        restricted=restricted)
                rounds = r + 1
                if not bool(spans.fetch(overloaded)):
                    break
        sp.add("rounds", rounds)
        if stats is not None:
            # the host balancer pays one O(m) single-chunk gather up front
            stats.update(rounds=rounds,
                         gather_bytes=int(chunks.src.nbytes
                                          + chunks.dst.nbytes
                                          + chunks.w.nbytes),
                         time_s=time.perf_counter() - t_start)
        return spans.fetch(labels)[:n].astype(np.int64)
