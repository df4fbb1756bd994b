"""Pallas TPU kernels: one fused balancing round (paper §4, Balancing).

``core.balance.balance_round`` composes each round out of a lexicographic
sort of the arc slab, two segment-sum passes, the four-stage tie-broken
argmax, and a ``fori_loop`` of dense-table reads for the greedy pool
application — every stage re-reading an O(m) or O(top_m * k) operand from
HBM. The two kernels here fuse those stages:

  * ``bal_scores`` — per-vertex relative gains + targets over the ELL
    slab (rows = vertices, D padded neighbor lanes) resident in VMEM:
    connection weights via the row-tile label-equality cube (the same
    sort-free contraction as ``kernels.lp_move``), the composed argmax
    tie chain (max score, lightest target block, min ``hash32(label,
    salt)``, min label) as masked row reductions, then the paper's
    relative gain ``g*c(v)`` / ``g/c(v)`` in the identical f32 op order.
    Per-neighbor block weights/budgets (``nbw``/``nlm``) and the O(k)
    fallback-target columns (``fb_t``/``fb_ok`` — lightest feasible
    block, composed outside the kernel exactly as the reference) are
    pre-gathered: the kernel keeps the O(m) part single-pass.
  * ``greedy_pick`` — the deterministic greedy application of the ranked
    candidate pool: a ``fori_loop`` over pool entries with the block
    weight table carried in registers/VMEM instead of re-reading it from
    HBM each step. One-hot lane reductions replace the composed path's
    dynamic gathers; the accept rule and integer updates are identical.

Inputs of ``bal_scores`` (R rows, D lanes, all i32 unless noted):
  nlab (R, D)  neighbor block labels (sentinel -1 on padding)
  nw   (R, D)  arc weights (0 on padding)
  nbw  (R, D)  block weight of the neighbor's block
  nlm  (R, D)  budget of the neighbor's block
  npar (R, D)  parent group of the neighbor's block (restricted only)
  own/opar/vw/ovr/vld/fb_t/fb_ok (R, 1) per-row columns: own block (+ its
  parent group, restricted only), vertex weight, overloaded / valid /
  fallback-feasible flags, fallback target
  salt (1, 1) u32
Outputs: rel (R, 1) f32 relative gain (NEG_INF = must not move),
  tgt (R, 1) i32 chosen target block.

Bit-identical to ``core.balance.balance_gains`` / ``greedy_select``
(enforced by tests/test_fused_kernels.py): integer arithmetic matches op
for op, and the single f32 multiply/divide happens on identical operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..dispatch import tiled_bytes
from ..lp_move.lp_move import _h32

I32_MAX = np.int32(np.iinfo(np.int32).max)
NEG_INF = np.float32(-np.inf)


def _scores_kernel(*refs, R, D, TA, restricted):
    if restricted:
        (salt_ref, nlab_ref, nw_ref, nbw_ref, nlm_ref, npar_ref, own_ref,
         opar_ref, vw_ref, ovr_ref, vld_ref, fbt_ref, fbok_ref,
         rel_ref, tgt_ref) = refs
    else:
        (salt_ref, nlab_ref, nw_ref, nbw_ref, nlm_ref, own_ref, vw_ref,
         ovr_ref, vld_ref, fbt_ref, fbok_ref, rel_ref, tgt_ref) = refs
        npar_ref = opar_ref = None
    salt = salt_ref[0, 0]

    def tile(t, _):
        rows = (pl.ds(t * TA, TA), slice(None))
        nlab = nlab_ref[rows]  # (TA, D)
        nw = nw_ref[rows]
        nbw = nbw_ref[rows]
        nlm = nlm_ref[rows]
        own = own_ref[rows]  # (TA, 1)
        vw = vw_ref[rows]
        validn = nlab >= 0
        # target must fit (w <= budget - c, exact at the int32 boundary)
        # and differ from the own block
        ok = (nbw <= (nlm - vw)) & (nlab != own) & validn
        if restricted:
            ok &= npar_ref[rows] == opar_ref[rows]
        # conn[r, j] = sum_i w[r, i] * [lab[r, i] == lab[r, j]]
        eq = nlab[:, :, None] == nlab[:, None, :]    # (TA, D, D)
        conn = jnp.sum(jnp.where(eq, nw[:, :, None], 0), axis=1)
        score = jnp.where(ok, conn, -1)
        best = jnp.max(score, axis=1, keepdims=True)
        is_best = score == best
        wk = jnp.where(is_best, nbw, I32_MAX)
        light = jnp.min(wk, axis=1, keepdims=True)
        is_best &= nbw == light
        h = _h32(nlab, salt)
        hk = jnp.where(is_best, h, I32_MAX)
        hbest = jnp.min(hk, axis=1, keepdims=True)
        is_best &= h == hbest
        tgt_adj = jnp.min(jnp.where(is_best, nlab, I32_MAX), axis=1,
                          keepdims=True)
        own_conn = jnp.sum(jnp.where((nlab == own) & validn, nw, 0),
                           axis=1, keepdims=True)
        has_adj = best >= 0
        g = jnp.where(has_adj, best - own_conn, -own_conn)
        tgt = jnp.where(has_adj, tgt_adj, fbt_ref[rows])
        movable = (ovr_ref[rows] != 0) & \
            (has_adj | (fbok_ref[rows] != 0)) & \
            (vld_ref[rows] != 0)
        gf = g.astype(jnp.float32)
        cv = jnp.maximum(vw.astype(jnp.float32), 1.0)
        rel = jnp.where(g >= 0, gf * cv, gf / cv)
        rel = jnp.where(movable, rel, NEG_INF)
        rel_ref[rows] = rel
        tgt_ref[rows] = tgt
        return 0

    lax.fori_loop(0, R // TA, tile, 0)


@functools.partial(jax.jit, static_argnames=("restricted", "row_tile",
                                             "interpret"))
def bal_scores(nlab, nw, nbw, nlm, own, vw, ovr, vld, fb_t, fb_ok, salt,
               npar=None, opar=None, *, restricted: bool = False,
               row_tile: int = 8, interpret: bool = True):
    """Fused per-vertex relative gains + targets. Returns ``(rel, tgt)``
    of shapes ``(R, 1)`` f32 / i32."""
    R, D = nlab.shape
    assert R % row_tile == 0, (R, row_tile)
    assert restricted == (npar is not None) == (opar is not None)
    out_shapes = (
        jax.ShapeDtypeStruct((R, 1), jnp.float32),
        jax.ShapeDtypeStruct((R, 1), jnp.int32),
    )
    kernel = functools.partial(_scores_kernel, R=R, D=D, TA=row_tile,
                               restricted=restricted)
    inputs = [salt, nlab, nw, nbw, nlm]
    if restricted:
        inputs += [npar, own, opar]
    else:
        inputs.append(own)
    inputs += [vw, ovr, vld, fb_t, fb_ok]
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs)


def _pick_kernel(vals_ref, tgt_ref, blk_ref, cw_ref, bw_ref, lm_ref,
                 acc_ref, bwout_ref, *, M, K):
    vals = vals_ref[...]                              # (1, M) f32
    tgt = tgt_ref[...]
    blk = blk_ref[...]
    cw = cw_ref[...]
    lm = lm_ref[...]                                  # (1, K)
    iota_m = lax.broadcasted_iota(jnp.int32, (1, M), 1)
    iota_k = lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def body(i, carry):
        bw, acc = carry
        sel = iota_m == i
        v = jnp.max(jnp.where(sel, vals, NEG_INF))
        t = jnp.sum(jnp.where(sel, tgt, 0))
        b = jnp.sum(jnp.where(sel, blk, 0))
        c = jnp.sum(jnp.where(sel, cw, 0))
        bw_b = jnp.sum(jnp.where(iota_k == b, bw, 0))
        lm_b = jnp.sum(jnp.where(iota_k == b, lm, 0))
        bw_t = jnp.sum(jnp.where(iota_k == t, bw, 0))
        lm_t = jnp.sum(jnp.where(iota_k == t, lm, 0))
        ok = (v > NEG_INF) & (bw_b > lm_b) & (bw_t <= lm_t - c) & (t != b)
        cwd = jnp.where(ok, c, 0)
        bw = bw - jnp.where(iota_k == b, cwd, 0) \
                + jnp.where(iota_k == t, cwd, 0)
        # int32, not bool: Mosaic cannot carry an i1 vector through a loop
        acc = jnp.where(sel & ok, 1, acc)
        return bw, acc

    bw, acc = lax.fori_loop(
        0, M, body, (bw_ref[...], jnp.zeros((1, M), jnp.int32)))
    acc_ref[...] = acc
    bwout_ref[...] = bw


@functools.partial(jax.jit, static_argnames=("interpret",))
def greedy_pick(vals, tgt_blk, src_blk, cand_w, block_w, l_max, *,
                interpret: bool = True):
    """Fused greedy application of a ranked pool. ``vals`` is (M,) f32
    (descending), the rest (M,) / (K,) i32. Returns ``(accept, block_w)``
    — (M,) bool and the updated (K,) table, bit-identical to
    ``core.balance.greedy_select``."""
    (M,) = vals.shape
    (K,) = block_w.shape
    acc, bw = pl.pallas_call(
        functools.partial(_pick_kernel, M=M, K=K),
        out_shape=(jax.ShapeDtypeStruct((1, M), jnp.int32),
                   jax.ShapeDtypeStruct((1, K), jnp.int32)),
        interpret=interpret,
    )(vals[None], tgt_blk[None], src_blk[None], cand_w[None],
      block_w[None], l_max[None])
    return acc[0] != 0, bw[0]


def bal_scores_vmem_bytes(R: int, D: int, row_tile: int = 8,
                          restricted: bool = False) -> int:
    """Planning estimate of the scores kernel's VMEM working set, each
    array at its tiled size (``dispatch.tiled_bytes``)."""
    slabs = (5 if restricted else 4) * tiled_bytes(R, D)
    cols = (9 if restricted else 8) * tiled_bytes(R, 1)
    cube = tiled_bytes(row_tile, D, D)
    return slabs + cols + cube
