"""Host-side ELL construction + jitted driver for the fused balance round.

``core.balance.rebalance`` feeds the composed round a single-chunk arc
slab (the whole graph, sorted per round inside the jit). The fused round
wants the graph in ELL form once — one row per vertex, D padded neighbor
lanes — so the per-round work is gathers (XLA, inside the same jit
program) plus the two Pallas kernels. Rows are the label-table space
``0 .. n_pad`` (+ tile padding): the sentinel and padded rows carry no
arcs and are masked by the ``valid`` column exactly like the composed
path masks them, so (labels, block_w) trajectories are bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .bal_round import (I32_MAX, bal_scores, bal_scores_vmem_bytes,
                        greedy_pick)
from ..dispatch import VMEM_BUDGET_BYTES
from ..lp_move.ops import LANE, ROW_TILE, _round_up, ell_from_csr


def balance_ell_shape(g, n_pad: int):
    """(R, D) of ``build_balance_ell(g, n_pad)``, without building it."""
    deg = np.diff(g.indptr)
    return (_round_up(n_pad + 1, ROW_TILE),
            _round_up(int(deg.max()) if deg.size else 1, LANE))


def build_balance_ell(g, n_pad: int):
    """(R, D) neighbor-id / weight ELL over the (n_pad + 1) label-table
    row space (tile-padded); -1 / 0 padding."""
    R, D = balance_ell_shape(g, n_pad)
    idx = np.full((R, D), -1, dtype=np.int32)
    w = np.zeros((R, D), dtype=np.int32)
    idx_full, w_full = ell_from_csr(np.asarray(g.indptr),
                                    np.asarray(g.adjncy, dtype=np.int64),
                                    np.asarray(g.eweights), D)
    idx[:g.n] = idx_full
    w[:g.n] = w_full
    return idx, w


def balance_ell_fits(R: int, D: int, restricted: bool = False) -> bool:
    return bal_scores_vmem_bytes(R, D, ROW_TILE,
                                 restricted=restricted) <= VMEM_BUDGET_BYTES


def build_balance_ell_dist(shards):
    """Per-PE ELL of the local arc shards: rows are local vertices
    (+ sentinel + tile padding), lanes hold *dst table indices* into the
    PE's (local + ghost + sentinel) label table. Sentinel arcs
    (src == n_loc) are dropped — arc-less rows never move."""
    P, n_loc = shards.P, shards.n_loc
    D_true = 1
    for p in range(P):
        sv = shards.arc_src[p][shards.arc_src[p] < n_loc]
        if sv.size:
            D_true = max(D_true, int(np.bincount(sv).max()))
    D = _round_up(D_true, LANE)
    R = _round_up(n_loc + 1, ROW_TILE)
    idx = np.full((P, R, D), -1, dtype=np.int32)
    w = np.zeros((P, R, D), dtype=np.int32)
    for p in range(P):
        real = shards.arc_src[p] < n_loc
        sv = shards.arc_src[p][real].astype(np.int64)
        order = np.argsort(sv, kind="stable")
        sv = sv[order]
        pos = np.arange(sv.shape[0]) - np.searchsorted(sv, sv, side="left")
        idx[p, sv, pos] = shards.arc_dst_idx[p][real][order]
        w[p, sv, pos] = shards.arc_w[p][real][order]
    return idx, w


def _col(x, R, fill=0):
    """(num,) -> (R, 1) column, padded rows carry ``fill``."""
    pad = R - x.shape[0]
    return jnp.concatenate(
        [x, jnp.full((pad,), fill, x.dtype)])[:, None]


def fused_round_scores(tab, lab_src, bw, l_max, parent, ell_idx, ell_w,
                       vw_pad, vld, salt, *, restricted, interpret):
    """Gather ELL operands + run ``bal_scores``. ``tab`` is the label
    table ELL lanes index into (host path: == ``lab_src``; dist path:
    local + ghost + sentinel); ``lab_src``/``vw_pad``/``vld`` live over
    the row space whose ``(rel, tgt)`` the caller consumes. Fallback
    target / feasibility columns are composed exactly as
    ``core.balance.balance_gains`` composes them."""
    R, _ = ell_idx.shape
    num = lab_src.shape[0]
    k = bw.shape[0]
    valid_l = ell_idx >= 0
    nlab = jnp.where(valid_l, tab[jnp.where(valid_l, ell_idx, 0)], -1)
    nl = jnp.where(valid_l, nlab, 0)
    nbw = bw[nl]
    nlm = l_max[nl]
    over_own = bw[lab_src] > l_max[lab_src]
    if restricted:
        grp_min = jax.ops.segment_min(bw, parent, num_segments=k)
        is_min = bw == grp_min[parent]
        bid = jnp.where(is_min, jnp.arange(k, dtype=jnp.int32), I32_MAX)
        grp_argmin = jax.ops.segment_min(bid, parent, num_segments=k)
        fb_t = grp_argmin[parent[lab_src]]
    else:
        fb_t = jnp.full((num,), jnp.argmin(bw).astype(jnp.int32))
    fb_ok = (bw[fb_t] <= l_max[fb_t] - vw_pad) & (fb_t != lab_src)
    kw = {}
    if restricted:
        kw = dict(npar=parent[nl], opar=_col(parent[lab_src], R))
    rel, tgt = bal_scores(
        nlab, ell_w, nbw, nlm, _col(lab_src, R), _col(vw_pad, R),
        _col(over_own.astype(jnp.int32), R), _col(vld.astype(jnp.int32), R),
        _col(fb_t, R), _col(fb_ok.astype(jnp.int32), R),
        jnp.reshape(salt, (1, 1)), restricted=restricted,
        row_tile=ROW_TILE, interpret=interpret, **kw)
    return rel[:num, 0], tgt[:num, 0]


@functools.partial(jax.jit, static_argnames=("n", "top_m", "restricted",
                                             "interpret"))
def balance_round_fused(labels, block_w, l_max, parent, ell_idx, ell_w,
                        vweights, valid, salt, *, n, top_m,
                        restricted=False, interpret=True):
    """Fused twin of ``core.balance.balance_round`` — same pool ranking,
    same accept rule, bit-identical (labels, block_w) trajectory."""
    rel, tgt = fused_round_scores(
        labels, labels, block_w, l_max, parent, ell_idx, ell_w,
        vweights, valid, salt, restricted=restricted, interpret=interpret)
    vals, vidx = lax.top_k(rel, top_m)
    accept, block_w = greedy_pick(vals, tgt[vidx], labels[vidx],
                                  vweights[vidx], block_w, l_max,
                                  interpret=interpret)
    labels = labels.at[vidx].set(
        jnp.where(accept, tgt[vidx], labels[vidx]))
    return labels, block_w, jnp.any(block_w > l_max)
