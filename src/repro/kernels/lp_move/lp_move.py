"""Pallas TPU kernel: one fused LP clustering chunk step (paper §4).

``core.lp._cluster_chunk`` composes the chunk move out of a sort, two
segment-sum passes, a four-stage tie-broken argmax, and a second sorted
cumulative-sum pass for the overweight revert — eight XLA ops each
re-reading the arc slab from HBM. This kernel performs the whole step —

    gather -> gain -> argmax -> budget check -> hash-ordered revert

— in a single pass over the chunk's ELL slab resident in VMEM.

Reformulation (sort-free, docs/KERNELS.md):

  * gains: per row the DxD label-equality matrix contracted with the
    weight vector, ``conn[j] = sum_i w[i] * [lab[i] == lab[j]]`` —
    MXU-shaped; computed in int32 (exact, same arithmetic as the
    composed ``segment_sum``).
  * argmax: the composed tie-break chain (max score, then lightest
    target cluster, then min ``hash32(label, salt)``, then min label)
    becomes four masked row reductions.
  * revert: the composed path sorts candidate movers by (cluster,
    hash32(vertex, salt')) and reverts the cumulative-weight suffix that
    exceeds the budget. Sort-free pairwise form over the chunk rows:

      d_in[v]     = sum_u move_u · c(u) · [tgt_u == tgt_v]
      d_out[v]    = sum_u move_u · c(u) · [lab_u == tgt_v]
      new_cw[v]   = cw[tgt_v] + d_in[v] - d_out[v]
      cand_v      = move_v & (new_cw[v] > W)
      moved_in[v] = sum_u cand_u · c(u) · [tgt_u == tgt_v]
      within[v]   = sum_u cand_u · c(u) · [tgt_u == tgt_v]
                                        · [(rk_u, u) <= (rk_v, v)]
      revert_v    = cand_v & (within[v] > max(W - (new_cw[v]
                                                   - moved_in[v]), 0))

    ``(rk, index)`` is exactly the composed sort order (lax.sort is
    stable), so the reverted set is bit-identical. ``cw[tgt_v]`` needs no
    extra gather: the argmax's lightest-cluster tie stage already pinned
    it (``light``).

Layout: the whole chunk stays resident (one grid step); row tiles are
walked with ``fori_loop`` so the (tile, D, D) equality cube and the
(tile, R) pairwise masks bound the VMEM high-water mark. All arithmetic
is int32 in the composed op order — labels are bit-identical to
``core.lp.cluster_iteration`` (enforced by tests/test_fused_kernels.py).

Inputs (R rows = chunk vertices ``v0 .. v0+R-1``, D padded neighbors):
  nlab  (R, D) i32   neighbor labels (sentinel -1 on padding)
  nw    (R, D) i32   arc weights (0 on padding)
  ncw   (R, D) i32   cluster weight of each neighbor's label
  nbud  (R, D) i32   per-label budget (diff fit form only)
  own   (R, 1) i32   current label of the row vertex
  vw    (R, 1) i32   row vertex weight
  W/v0  (1, 2) i32   scalar budget + first row's vertex id
  salt  (1, 1) u32   chunk salt (same stream as the composed path)
Outputs:
  moved (R, 1) i32   1 where the vertex moves (post-revert)
  tgt   (R, 1) i32   its target label (== own where not moved)

``fit_sum=True`` uses the host clustering admission form
``cw + c(v) <= W`` (no ``nbud`` operand); ``fit_sum=False`` the
distributed ``cw <= bud - c(v)`` form. Both match their composed twins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import tiled_bytes

I32_MAX = np.int32(np.iinfo(np.int32).max)


def _h32(x: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """int32 mix hash — must match core.lp._hash32 bit for bit."""
    h = (x.astype(jnp.uint32) * np.uint32(2654435761)) ^ salt
    h = h ^ (h >> 15)
    return (h & np.uint32(0x7FFFFFFF)).astype(jnp.int32)


def _kernel(*refs, R, D, TA, TB, fit_sum):
    if fit_sum:
        (scal_ref, salt_ref, nlab_ref, nw_ref, ncw_ref, own_ref, vw_ref,
         moved_ref, tgt_ref, pmove_ref, light_ref, cand_ref,
         newcw_ref) = refs
        nbud_ref = None
    else:
        (scal_ref, salt_ref, nlab_ref, nw_ref, ncw_ref, nbud_ref, own_ref,
         vw_ref, moved_ref, tgt_ref, pmove_ref, light_ref, cand_ref,
         newcw_ref) = refs
    W = scal_ref[0, 0]
    v0 = scal_ref[0, 1]
    salt = salt_ref[0, 0]

    # ---- phase A: gain + argmax + admission per row tile ---------------
    def phase_a(t, _):
        r0 = t * TA
        rows = (pl.ds(r0, TA), slice(None))
        nlab = nlab_ref[rows]  # (TA, D)
        nw = nw_ref[rows]
        ncw = ncw_ref[rows]
        own = own_ref[rows]  # (TA, 1)
        vw = vw_ref[rows]
        validn = nlab >= 0
        staying = nlab == own
        if fit_sum:
            fits = ((ncw + vw) <= W) | staying
        else:
            nbud = nbud_ref[rows]
            fits = (ncw <= (nbud - vw)) | staying
        fits = fits & validn
        # conn[r, j] = sum_i w[r, i] * [lab[r, i] == lab[r, j]]
        eq = nlab[:, :, None] == nlab[:, None, :]    # (TA, D, D)
        conn = jnp.sum(jnp.where(eq, nw[:, :, None], 0), axis=1)
        score = jnp.where(fits, conn, -1)
        best = jnp.max(score, axis=1, keepdims=True)
        is_best = score == best
        wk = jnp.where(is_best, ncw, I32_MAX)
        light = jnp.min(wk, axis=1, keepdims=True)
        is_best &= ncw == light
        h = _h32(nlab, salt)
        hk = jnp.where(is_best, h, I32_MAX)
        hbest = jnp.min(hk, axis=1, keepdims=True)
        is_best &= h == hbest
        tgt = jnp.min(jnp.where(is_best, nlab, I32_MAX), axis=1,
                      keepdims=True)
        own_conn = jnp.sum(jnp.where(staying & validn, nw, 0), axis=1,
                           keepdims=True)
        mv = (best > own_conn) & (tgt != own) & (tgt < I32_MAX) & (best > 0)
        tgt_ref[rows] = jnp.where(mv, tgt, own)
        pmove_ref[rows] = mv.astype(jnp.int32)
        light_ref[rows] = light
        return 0

    lax.fori_loop(0, R // TA, phase_a, 0)

    # ---- phase B1: per-mover updated target-cluster weight -------------
    tgt_u = jnp.reshape(tgt_ref[...], (1, R))
    own_u = jnp.reshape(own_ref[...], (1, R))
    vw_u = jnp.reshape(vw_ref[...], (1, R))
    mvw_u = jnp.reshape(pmove_ref[...], (1, R)) * vw_u

    def phase_b1(t, _):
        r0 = t * TB
        rows = (pl.ds(r0, TB), slice(None))
        tgt_v = tgt_ref[rows]  # (TB, 1)
        light_v = light_ref[rows]
        pmove_v = pmove_ref[rows]
        d_in = jnp.sum(jnp.where(tgt_u == tgt_v, mvw_u, 0), axis=1,
                       keepdims=True)
        d_out = jnp.sum(jnp.where(own_u == tgt_v, mvw_u, 0), axis=1,
                        keepdims=True)
        new_cw = light_v + d_in - d_out
        cand = (pmove_v != 0) & (new_cw > W)
        newcw_ref[rows] = new_cw
        cand_ref[rows] = cand.astype(jnp.int32)
        return 0

    lax.fori_loop(0, R // TB, phase_b1, 0)

    # ---- phase B2: hash-ordered within-budget revert --------------------
    salt2 = salt ^ np.uint32(0x9E3779B9)
    iota_u = lax.broadcasted_iota(jnp.int32, (1, R), 1)
    rk_u = _h32(v0 + iota_u, salt2)
    cvw_u = jnp.reshape(cand_ref[...], (1, R)) * vw_u

    def phase_b2(t, _):
        r0 = t * TB
        rows = (pl.ds(r0, TB), slice(None))
        tgt_v = tgt_ref[rows]
        cand_v = cand_ref[rows] != 0
        pmove_v = pmove_ref[rows] != 0
        new_cw = newcw_ref[rows]
        iota_v = r0 + lax.broadcasted_iota(jnp.int32, (TB, 1), 0)
        rk_v = _h32(v0 + iota_v, salt2)
        same = tgt_u == tgt_v                        # (TB, R)
        moved_in = jnp.sum(jnp.where(same, cvw_u, 0), axis=1,
                           keepdims=True)
        # composed order: stable sort by (cluster, rk) => (rk, index)
        prior = (rk_u < rk_v) | ((rk_u == rk_v) & (iota_u <= iota_v))
        within = jnp.sum(jnp.where(same & prior, cvw_u, 0), axis=1,
                         keepdims=True)
        allowed = jnp.maximum(W - (new_cw - moved_in), 0)
        revert = cand_v & (within > allowed)
        moved_ref[rows] = (pmove_v & ~revert).astype(jnp.int32)
        return 0

    lax.fori_loop(0, R // TB, phase_b2, 0)


@functools.partial(jax.jit, static_argnames=("fit_sum", "row_tile",
                                             "interpret"))
def lp_move_chunk(nlab, nw, ncw, own, vw, scal, salt, nbud=None, *,
                  fit_sum: bool = True, row_tile: int = 8,
                  interpret: bool = True):
    """Run the fused chunk step. ``scal`` is ``[[W, v0]]`` int32, ``salt``
    ``[[salt]]`` uint32. Returns ``(moved, tgt)`` int32 ``(R, 1)``."""
    R, D = nlab.shape
    assert R % row_tile == 0, (R, row_tile)
    assert fit_sum == (nbud is None), "nbud goes with fit_sum=False only"
    out_shapes = (
        jax.ShapeDtypeStruct((R, 1), jnp.int32),
        jax.ShapeDtypeStruct((R, 1), jnp.int32),
    )
    kernel = functools.partial(_kernel, R=R, D=D, TA=row_tile, TB=row_tile,
                               fit_sum=fit_sum)
    inputs = [scal, salt, nlab, nw, ncw]
    if not fit_sum:
        inputs.append(nbud)
    inputs += [own, vw]
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.int32),   # pre-revert move flags
            pltpu.VMEM((R, 1), jnp.int32),   # cw[target] at chunk start
            pltpu.VMEM((R, 1), jnp.int32),   # revert candidates
            pltpu.VMEM((R, 1), jnp.int32),   # updated target weights
        ],
        interpret=interpret,
    )(*inputs)


def lp_move_vmem_bytes(R: int, D: int, row_tile: int = 8,
                       fit_sum: bool = True) -> int:
    """Planning estimate of the kernel's VMEM working set (operands +
    scratch + the (TA, D, D) equality cube and (TB, R) pairwise masks),
    each array at its tiled size (``dispatch.tiled_bytes``)."""
    slabs = (3 if fit_sum else 4) * tiled_bytes(R, D)
    cols = 8 * tiled_bytes(R, 1)          # own/vw/outputs/scratch columns
    cube = tiled_bytes(row_tile, D, D)
    pairwise = 4 * tiled_bytes(row_tile, R)
    return slabs + cols + cube + pairwise
