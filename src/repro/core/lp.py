"""Size-constrained label propagation, vectorized for XLA.

This is the paper's workhorse (coarsening clustering *and* k-way refinement).
The MPI original iterates vertices sequentially inside batches; the TPU-native
adaptation processes a *chunk* of vertices at once:

  gains:   sort arcs by (src, label[dst])  ->  per-(src,label) run lengths
           -> segment_sum of arc weights   ->  per-src argmax with tie-breaks
  races:   optimistic moves + the paper's own overweight-revert mechanism
           absorb intra-chunk weight races (Section 4, Coarsening).

Chunks are *contiguous vertex ranges* of the degree-bucket-reordered graph
(paper Section 4 iteration order), balanced by arc count so every chunk's
padded arc slab has the same static shape — one jitted program per level.

All jit-side integers are int32; the host driver guarantees total vertex /
edge weight < 2**31 (asserted at build).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..graphs.format import Graph, degree_bucket_order, permute

I32_MAX = np.int32(np.iinfo(np.int32).max)


# ---------------------------------------------------------------------------
# Host-side chunk construction
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LPChunks:
    """Padded per-chunk arc slabs. Sentinel arcs: src = dst = n_pad, w = 0.

    ``n_pad`` and ``m_pad`` are rounded to powers of two so that the jitted
    per-level programs hit a small cache of shape buckets instead of
    recompiling for every hierarchy level.
    """
    src: np.ndarray   # (B, m_pad) int32
    dst: np.ndarray   # (B, m_pad) int32
    w: np.ndarray     # (B, m_pad) int32
    n: int            # true vertex count
    n_pad: int        # padded (power-of-two) vertex count == sentinel id
    num_chunks: int


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def chunk_bounds(g: Graph, num_chunks: int) -> list:
    """Chunk boundaries: contiguous vertex ranges with ~equal arc counts.
    Returns ``B + 1`` vertex ids; chunk ``b`` covers ``[bounds[b],
    bounds[b+1])``. Shared by the arc-slab (composed) and ELL (fused
    Pallas) chunk builders so both paths walk identical vertex ranges."""
    n, m = g.n, g.m
    B = max(1, min(num_chunks, max(1, n)))
    target = (m + B - 1) // max(B, 1) if m else 1
    bounds = [0]
    for b in range(1, B):
        v = int(np.searchsorted(g.indptr, b * target, side="left"))
        bounds.append(min(max(v, bounds[-1]), n))
    bounds.append(n)
    return bounds


def reorder(g: Graph, seed: int):
    """Seeded degree-bucket iteration order (paper §4): ``(perm, g2)``,
    ``g2`` the graph relabelled so that vertex ``v`` becomes ``perm[v]``."""
    with spans.span("level.reorder"):
        rng = np.random.default_rng(seed)
        order = degree_bucket_order(g, rng)
        perm = np.empty(g.n, dtype=np.int64)
        perm[order] = np.arange(g.n)
        g2, _ = permute(g, perm)
    return perm, g2


def build_chunks(g: Graph, num_chunks: int, pad_shapes: bool = True) -> LPChunks:
    if g.total_eweight >= 2**31 or g.total_vweight >= 2**31:
        # a real error, not an assert: asserts vanish under ``python -O``
        # and the int32 tables would then silently wrap
        raise ValueError(
            f"build_chunks: total vertex/edge weight ({g.total_vweight}/"
            f"{g.total_eweight}) must be < 2^31 for the int32 jit path")
    n, m = g.n, g.m
    n_pad = _next_pow2(n) if pad_shapes else n
    bounds = chunk_bounds(g, num_chunks)
    B = len(bounds) - 1
    src = g.arc_tails().astype(np.int64)
    m_pad = 1
    for b in range(B):
        a0, a1 = int(g.indptr[bounds[b]]), int(g.indptr[bounds[b + 1]])
        m_pad = max(m_pad, a1 - a0)
    if pad_shapes:
        m_pad = _next_pow2(m_pad)
    slabs = []
    for b in range(B):
        a0, a1 = int(g.indptr[bounds[b]]), int(g.indptr[bounds[b + 1]])
        cnt = a1 - a0
        s = np.full(m_pad, n_pad, dtype=np.int32)
        d = np.full(m_pad, n_pad, dtype=np.int32)
        ww = np.zeros(m_pad, dtype=np.int32)
        s[:cnt] = src[a0:a1]
        d[:cnt] = g.adjncy[a0:a1]
        ww[:cnt] = g.eweights[a0:a1]
        slabs.append((s, d, ww))
    return LPChunks(src=np.stack([x[0] for x in slabs]),
                    dst=np.stack([x[1] for x in slabs]),
                    w=np.stack([x[2] for x in slabs]),
                    n=n, n_pad=n_pad, num_chunks=B)


# ---------------------------------------------------------------------------
# jit-side gain machinery
# ---------------------------------------------------------------------------

def _hash32(x: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    h = (x.astype(jnp.uint32) * np.uint32(2654435761)) ^ salt.astype(jnp.uint32)
    h = h ^ (h >> 15)
    return (h & np.uint32(0x7FFFFFFF)).astype(jnp.int32)


def _group_conns(s_src: jnp.ndarray, s_lab: jnp.ndarray, s_w: jnp.ndarray
                 ) -> jnp.ndarray:
    """Per-arc connection weight of the (src, label) group the arc belongs to.

    Inputs must be sorted by (src, label)."""
    newgrp = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (s_src[1:] != s_src[:-1]) | (s_lab[1:] != s_lab[:-1])])
    gid = jnp.cumsum(newgrp.astype(jnp.int32)) - 1
    conn_g = jax.ops.segment_sum(s_w, gid, num_segments=s_w.shape[0],
                                 indices_are_sorted=True)
    return conn_g[gid]


def _argmax_target(s_src, s_lab, score, weight_key, salt, n):
    """Per-src argmax of ``score`` with ties broken by (lighter weight_key,
    then hash). Returns (best_score, target_label) arrays of size n+1.
    ``score`` must be >= 0 for real candidates and < 0 for masked ones."""
    num = n + 1
    best = jax.ops.segment_max(score, s_src, num_segments=num,
                               indices_are_sorted=True)
    is_best = score == best[s_src]
    wk = jnp.where(is_best, weight_key, I32_MAX)
    light = jax.ops.segment_min(wk, s_src, num_segments=num,
                                indices_are_sorted=True)
    is_best &= weight_key == light[s_src]
    h = _hash32(s_lab, salt)
    hk = jnp.where(is_best, h, I32_MAX)
    hbest = jax.ops.segment_min(hk, s_src, num_segments=num,
                                indices_are_sorted=True)
    is_best &= h == hbest[s_src]
    lk = jnp.where(is_best, s_lab, I32_MAX)
    target = jax.ops.segment_min(lk, s_src, num_segments=num,
                                 indices_are_sorted=True)
    return best, target


def _own_connection(s_src, s_lab, s_w, labels, n):
    own = jax.ops.segment_sum(
        jnp.where(s_lab == labels[s_src], s_w, 0), s_src,
        num_segments=n + 1, indices_are_sorted=True)
    return own


# ---------------------------------------------------------------------------
# Clustering (coarsening) chunk step
# ---------------------------------------------------------------------------

def _cluster_chunk(labels, cluster_w, chunk_src, chunk_dst, chunk_w,
                   vweights, max_cluster_weight, salt, n):
    """One chunk of size-constrained LP clustering. Returns updated
    (labels, cluster_w)."""
    lab_dst = labels[chunk_dst]
    s_src, s_lab, s_w = jax.lax.sort(
        (chunk_src, lab_dst, chunk_w), num_keys=2)
    conn = _group_conns(s_src, s_lab, s_w)
    own_lab = labels[s_src]
    staying = s_lab == own_lab
    fits = (cluster_w[s_lab] + vweights[s_src] <= max_cluster_weight) | staying
    score = jnp.where(fits, conn, -1)
    best, target = _argmax_target(s_src, s_lab, score,
                                  cluster_w[s_lab], salt, n)
    own_conn = _own_connection(s_src, s_lab, s_w, labels, n)
    move = (best > own_conn) & (target != labels) & (target < I32_MAX) & (best > 0)
    move = move.at[n].set(False)
    new_labels = jnp.where(move, target, labels)
    # weight update
    vw_moved = jnp.where(move, vweights, 0)
    num = n + 1
    d_in = jax.ops.segment_sum(vw_moved, new_labels, num_segments=num)
    d_out = jax.ops.segment_sum(vw_moved, labels, num_segments=num)
    new_cw = cluster_w + d_in - d_out

    # --- overweight revert (paper Section 4, Coarsening) -------------------
    # For each cluster that exceeded W this chunk, undo the most recently
    # proposed moves (random order within the chunk) until it fits again.
    over = new_cw > max_cluster_weight
    cand = move & over[new_labels]
    rk = _hash32(jnp.arange(num, dtype=jnp.int32), salt ^ np.uint32(0x9E3779B9))
    sort_lab = jnp.where(cand, new_labels, jnp.int32(num))
    o_lab, o_rk, o_v = jax.lax.sort(
        (sort_lab, rk, jnp.arange(num, dtype=jnp.int32)), num_keys=2)
    o_vw = jnp.where(o_lab < num, vweights[o_v], 0)
    csum = jnp.cumsum(o_vw)
    grp_start = jnp.concatenate([
        jnp.ones((1,), jnp.bool_), o_lab[1:] != o_lab[:-1]])
    gid = jnp.cumsum(grp_start.astype(jnp.int32)) - 1
    base = jax.ops.segment_min(jnp.where(grp_start, csum - o_vw, I32_MAX),
                               gid, num_segments=num)
    within = csum - base[gid]             # cumulative moved-in weight incl self
    lab_safe = jnp.where(o_lab < num, o_lab, 0)
    pre_w = new_cw[lab_safe] - (d_in - d_out)[lab_safe] \
        + jnp.zeros_like(csum)            # weight before this chunk's moves
    # moved-out weight also changed pre->new; allowed extra for moved-in:
    allowed = jnp.maximum(max_cluster_weight - (new_cw[lab_safe] -
                          jax.ops.segment_sum(o_vw, gid, num_segments=num)[gid]),
                          0)
    del pre_w
    revert = (o_lab < num) & (within > allowed)
    rv = jnp.zeros(num, dtype=jnp.bool_).at[o_v].set(revert, mode="drop")
    rv &= move
    final_labels = jnp.where(rv, labels, new_labels)
    vw_rv = jnp.where(rv, vweights, 0)
    r_in = jax.ops.segment_sum(vw_rv, labels, num_segments=num)
    r_out = jax.ops.segment_sum(vw_rv, new_labels, num_segments=num)
    final_cw = new_cw + r_in - r_out
    return final_labels, final_cw


def _cluster_iteration_impl(labels, cluster_w, chunks_src, chunks_dst,
                            chunks_w, vweights, max_cluster_weight, seed, n):
    """One full LP-clustering iteration over all chunks (traceable body
    shared by the solo jit and the stacked vmap entry points)."""
    B = chunks_src.shape[0]

    def body(carry, xs):
        labels, cluster_w = carry
        c_src, c_dst, c_w, salt = xs
        labels, cluster_w = _cluster_chunk(
            labels, cluster_w, c_src, c_dst, c_w, vweights,
            max_cluster_weight, salt, n)
        return (labels, cluster_w), ()

    salts = (jnp.arange(B, dtype=jnp.uint32) * np.uint32(0x85EBCA6B)
             + seed.astype(jnp.uint32))
    (labels, cluster_w), _ = jax.lax.scan(
        body, (labels, cluster_w), (chunks_src, chunks_dst, chunks_w, salts))
    return labels, cluster_w


@functools.partial(jax.jit, static_argnames=("n",))
def cluster_iteration(labels, cluster_w, chunks_src, chunks_dst, chunks_w,
                      vweights, max_cluster_weight, seed, *, n):
    """One full LP-clustering iteration over all chunks."""
    return _cluster_iteration_impl(labels, cluster_w, chunks_src, chunks_dst,
                                   chunks_w, vweights, max_cluster_weight,
                                   seed, n)


@functools.partial(jax.jit, static_argnames=("n",))
def cluster_iteration_stacked(labels, cluster_w, chunks_src, chunks_dst,
                              chunks_w, vweights, max_cluster_weight, seed,
                              *, n):
    """``cluster_iteration`` with a leading request axis: every operand
    carries an extra dim R and requests run as one vmapped program.

    Per-row results are bit-identical to the solo entry point at the
    same padded shape: the body is integer-only, vmap of integer ops is
    exactly semantics-preserving, and padded rows/columns are inert
    (weight-0 singleton vertices with sentinel arcs never move and are
    never adopted as targets — see ``repro.serve.batching``)."""
    return jax.vmap(
        lambda la, cw, cs, cd, cww, vw, mw, sd: _cluster_iteration_impl(
            la, cw, cs, cd, cww, vw, mw, sd, n)
    )(labels, cluster_w, chunks_src, chunks_dst, chunks_w, vweights,
      max_cluster_weight, seed)


# ---------------------------------------------------------------------------
# k-way refinement chunk step
# ---------------------------------------------------------------------------

def _refine_chunk(labels, block_w, l_max, parent, chunk_src, chunk_dst,
                  chunk_w, vweights, salt, n, restricted):
    """One chunk of size-constrained LP refinement over k blocks.

    ``l_max`` is a per-block budget vector (k,) — deep MGP refines
    intermediate partitions whose blocks represent different numbers of
    final blocks. With ``restricted=True`` moves are confined to blocks
    sharing a parent (the partition-extension step: each block of the
    previous partition was split and refinement may only shuffle vertices
    between siblings).
    """
    lab_dst = labels[chunk_dst]
    s_src, s_lab, s_w = jax.lax.sort(
        (chunk_src, lab_dst, chunk_w), num_keys=2)
    conn = _group_conns(s_src, s_lab, s_w)
    own_lab = labels[s_src]
    staying = s_lab == own_lab
    # weight comparisons arranged as ``w <= budget - c`` so they cannot
    # wrap when the totals approach the int32 boundary
    fits = (block_w[s_lab] <= l_max[s_lab] - vweights[s_src]) & ~staying
    if restricted:
        fits &= parent[s_lab] == parent[own_lab]
    score = jnp.where(fits, conn, -1)
    best, target = _argmax_target(s_src, s_lab, score,
                                  block_w[s_lab], salt, n)
    own_conn = _own_connection(s_src, s_lab, s_w, labels, n)
    gain = best - own_conn
    tgt_safe = jnp.where(target < I32_MAX, target, 0)
    # move on strict gain; zero-gain moves only if they strictly improve
    # balance (paper: ties broken in favor of the lighter block)
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
def refine_iteration(labels, block_w, l_max, parent, chunks_src, chunks_dst,
                     chunks_w, vweights, seed, *, n, restricted=False):
    B = chunks_src.shape[0]

    def body(carry, xs):
        labels, block_w = carry
        c_src, c_dst, c_w, salt = xs
        labels, block_w = _refine_chunk(
            labels, block_w, l_max, parent, c_src, c_dst, c_w, vweights,
            salt, n, restricted)
        return (labels, block_w), ()

    salts = (jnp.arange(B, dtype=jnp.uint32) * np.uint32(0xC2B2AE35)
             + seed.astype(jnp.uint32))
    (labels, block_w), _ = jax.lax.scan(
        body, (labels, block_w), (chunks_src, chunks_dst, chunks_w, salts))
    return labels, block_w
