"""Pallas TPU kernel: segmented sort + duplicate-arc merge (paper §5).

Contraction's inner loop deduplicates coarse arcs: sort (src, dst, w)
records lexicographically by (src, dst), flag the first record of every
equal-key run, and sum each run's weights. The composed path is a
``lax.sort`` (or host lexsort) followed by a cumsum-based segment-sum —
multiple passes over the record slab. This kernel keeps the whole slab
resident in VMEM and does all three stages in one ``pallas_call``:

  * **sort** — a bitonic network over the lane axis ((1, L) layout,
    L a power of two, at least one 128-lane vreg). Each
    compare-exchange stage pairs lane ``i`` with ``i ^ j``: two lane
    rotations (``pltpu.roll`` by ``j`` and ``L - j``) bring lanes
    ``i - j`` and ``i + j`` to lane ``i``, and a select keeps the
    partner. Keys compare lexicographically on (src, dst), the weight
    rides as payload. Bitonic networks are not stable, but equal keys
    are exactly the records that merge, so every output of this kernel
    is invariant to their order.
  * **run flags** — ``first[i] = (i == 0) | key[i] != key[i-1]``.
  * **run totals** — forward + backward segmented Hillis-Steele scans
    (log L rounds each) give every lane its run's total weight:
    ``tot = fwd_incl + bwd_incl - w``.

Invalid records (self loops, padding beyond the true record count)
carry key ``src = dst = I32_MAX`` / ``w = 0``: they sort to the tail and
callers drop them with ``(s_src < I32_MAX) & first``.

Outputs are bit-identical to the composed owner-side merge in
``dist.dist_contraction._build_exchange_fn`` and to the host
``core.contraction.dedup_arcs`` after that filter (int32 range).
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
LANES = 128


def _kernel(src_ref, dst_ref, w_ref, osrc_ref, odst_ref, tot_ref,
            first_ref, *, L):
    s = src_ref[...]                                  # (1, L)
    d = dst_ref[...]
    w = w_ref[...]
    iota = lax.broadcasted_iota(jnp.int32, (1, L), 1)
    # ``fwd`` (1 on all lanes or 0 on all lanes): whether a rotation by
    # ``t`` brings lane ``i - t`` to lane ``i``. Deriving it from a
    # rotated iota keeps the kernel independent of the rotate
    # primitive's direction convention. Lane bits and masks stay int32
    # where they meet each other: Mosaic has no i1 vector compares.
    fwd = jnp.where(pltpu.roll(iota, 1, 1) == ((iota - 1) & (L - 1)), 1, 0)

    def rot(x, t):
        """Rotate the lanes by ``t`` in [0, L] (static or traced)."""
        return pltpu.roll(x, t % L, 1)

    def shr(x, t):
        """Lanes shifted right by ``t`` (lane i gets i - t), zero fill."""
        return jnp.where(iota >= t,
                         jnp.where(fwd == 1, rot(x, t), rot(x, L - t)), 0)

    def shl(x, t):
        """Lanes shifted left by ``t`` (lane i gets i + t), zero fill."""
        return jnp.where(iota < L - t,
                         jnp.where(fwd == 1, rot(x, L - t), rot(x, t)), 0)

    # ---- bitonic sort by (src, dst), w as payload -----------------------
    # The merge stages j = k/2, k/4, .., 1 of each block size k run as a
    # loop with a traced rotation amount, so the program grows as
    # O(log L) stage bodies, not O(log^2 L).
    def stage(t, carry, k):
        s, d, w = carry
        j = jnp.right_shift(jnp.int32(k // 2), t)
        bit_j = jnp.minimum(iota & j, 1)
        # partner lane i ^ j: i + j where bit j is 0, i - j where 1
        take_rot = bit_j == fwd

        def xchg(x):
            return jnp.where(take_rot, rot(x, j), rot(x, L - j))

        sp, dp, wp = xchg(s), xchg(d), xchg(w)
        want_min = jnp.minimum(iota & k, 1) == bit_j
        gt = (s > sp) | ((s == sp) & (d > dp))
        lt = (s < sp) | ((s == sp) & (d < dp))
        take = (want_min & gt) | (~want_min & lt)
        return (jnp.where(take, sp, s), jnp.where(take, dp, d),
                jnp.where(take, wp, w))

    k = 2
    while k <= L:
        s, d, w = lax.fori_loop(0, k.bit_length() - 1,
                                functools.partial(stage, k=k), (s, d, w))
        k *= 2

    # ---- run-start flags (lane 0 is forced first, so the shifted-in
    # zero on the left never matters); int32 0/1, since Mosaic rotates
    # no i1 vectors ----------------------------------------------------------
    first = ((iota == 0) | (s != shr(s, 1)) | (d != shr(d, 1))) \
        .astype(jnp.int32)

    # ---- run totals: forward + backward segmented scans ------------------
    # (log L rounds each, the shift 2^t traced like the merge stages)
    def scan(shift):
        def round_(t, carry):
            acc, flag = carry
            step = jnp.left_shift(jnp.int32(1), t)
            return (acc + jnp.where(flag == 0, shift(acc, step), 0),
                    flag | shift(flag, step))
        return round_

    rounds = L.bit_length() - 1
    fsum, _ = lax.fori_loop(0, rounds, scan(shr), (w, first))
    is_end = shl(first, 1) | (iota == L - 1).astype(jnp.int32)
    bsum, _ = lax.fori_loop(0, rounds, scan(shl), (w, is_end))

    osrc_ref[...] = s
    odst_ref[...] = d
    tot_ref[...] = fsum + bsum - w
    first_ref[...] = first


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1)).bit_length()


def padded_lanes(L: int) -> int:
    """Lane count the kernel runs at for ``L`` records."""
    return max(LANES, _next_pow2(L))


@functools.partial(jax.jit, static_argnames=("interpret",))
def seg_merge(src, dst, w, *, interpret: bool = True):
    """Sort + merge (L,) int32 arc records. Returns
    ``(s_src, s_dst, tot, first)`` — sorted keys, per-lane run totals,
    int32 run-start flags. Pads to a power of two of at least one vreg
    row (padding carries the same I32_MAX invalid key callers already
    filter)."""
    (L,) = src.shape
    Lp = padded_lanes(L)
    pad = Lp - L
    if pad:
        src = jnp.concatenate([src, jnp.full((pad,), I32_MAX, jnp.int32)])
        dst = jnp.concatenate([dst, jnp.full((pad,), I32_MAX, jnp.int32)])
        w = jnp.concatenate([w, jnp.zeros((pad,), jnp.int32)])
    out_shapes = tuple(jax.ShapeDtypeStruct((1, Lp), jnp.int32)
                       for _ in range(4))
    s_src, s_dst, tot, first = pl.pallas_call(
        functools.partial(_kernel, L=Lp),
        out_shape=out_shapes,
        interpret=interpret,
    )(src[None], dst[None], w[None])
    return s_src[0, :L], s_dst[0, :L], tot[0, :L], first[0, :L]


def seg_merge_vmem_bytes(L: int) -> int:
    """Planning estimate: ~10 live (1, L) i32 lanesets during the sort
    and scan stages (inputs, partners, flags, outputs), each at its
    tiled size (``dispatch.tiled_bytes``: 32 B per lane)."""
    return 10 * tiled_bytes(1, padded_lanes(L))
