"""Distributed size-constrained label propagation (paper §4).

shard_map port of the chunked LP kernels in ``core/lp.py`` over the
``GraphShards`` layout of ``graphs/distribute.py``. Each PE owns a
contiguous vertex range; labels are *global* ids, and ghost labels are
refreshed through the static halo schedule after every chunk.

Cluster/block weight tables come in two layouts, selected by the
``weights`` argument:

  * ``"replicated"`` — every PE carries the full (n+1,)/(k+1,) table,
    synchronized by psum after each chunk. Simple and fast at test
    scale, but O(n) persistent state per PE.
  * ``"owner"`` — each PE persistently holds only its ~(n/P,) shard of
    the table (uniform block distribution of the label space). Movers
    *request* current weights via ``all_gather_1d`` at the top of each
    chunk and *commit* their deltas via ``psum_scatter_1d``; the
    overweight check runs on the owner's authoritative shard before the
    flags are gathered back for the bounce. Persistent per-PE state
    drops to O(n/P + k); the dense view exists only transiently inside
    the chunk body (XLA's static shapes rule out sparse messages).

Both layouts apply identical integer arithmetic in the same order, so
they produce bit-identical labels.

Weight constraint handling follows the paper's two tiers:

  * intra-PE races within a chunk use the exact hash-ordered revert of
    ``core.lp._cluster_chunk`` against the PE's local view;
  * cross-PE races are only detected after the commit — overweight
    clusters then *bounce* this chunk's incoming moves back (approximate
    revert, §4 Coarsening). Exact enforcement happens on the host before
    contraction (``core.coarsening.enforce_cluster_weights``).

The bounce decision depends only on reduction results, never on message
routing, so grid and direct runs produce identical labels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as PS

from .. import spans
from ..core.lp import (I32_MAX, _argmax_target, _group_conns, _hash32,
                       _own_connection)
from ..graphs.distribute import GraphShards, chunk_local_arcs
from ..kernels import dispatch
from ..kernels.lp_move import ops as move_ops
from ..kernels.lp_move.lp_move import lp_move_chunk, lp_move_vmem_bytes
from .collectives import all_gather_1d, halo_exchange, psum_scatter_1d
from .compat import shard_map

_BIG = np.int32(2**30)

WEIGHT_MODES = ("replicated", "owner")


def _check_weights_mode(weights: str) -> bool:
    if weights not in WEIGHT_MODES:
        raise ValueError(f"unknown weights mode {weights!r}; expected one "
                         f"of {WEIGHT_MODES}")
    return weights == "owner"


def owner_table_width(num_labels: int, P: int) -> int:
    """Per-PE owner-shard width: uniform block distribution of the label
    space, padded so P shards tile the dense table exactly."""
    return -(-num_labels // P)


def _check_int32_weights(shards: GraphShards) -> None:
    """Same guard as core.lp.build_chunks: the replicated int32 weight
    tables (psum-accumulated) must never wrap. A real error, not an
    assert — asserts vanish under ``python -O``."""
    tot_v = int(shards.vweights.astype(np.int64).sum())
    tot_e = int(shards.arc_w.astype(np.int64).sum())
    if tot_v >= 2**31 or tot_e >= 2**31:
        raise ValueError(
            f"dist_lp: total vertex/edge weight ({tot_v}/{tot_e}) must "
            "be < 2^31 for the int32 jit path")


def make_mesh_1d(P: int) -> Mesh:
    """1D 'pe' mesh over the first P devices."""
    devs = jax.devices()
    assert len(devs) >= P, (len(devs), P)
    return Mesh(np.array(devs[:P]), ("pe",))


def _resolve_mesh(mesh, P: int) -> Mesh:
    """Accept a caller-provided 1D 'pe' mesh (serving sessions build one
    and reuse it across requests) or build a fresh one."""
    if mesh is None:
        return make_mesh_1d(P)
    assert mesh.axis_names == ("pe",) and mesh.devices.size == P, \
        (mesh.axis_names, mesh.devices.size, P)
    return mesh


# ---------------------------------------------------------------------------
# per-PE chunk step (jit-side)
# ---------------------------------------------------------------------------

def _local_moves(lab_src_tab, tab, cw_like, budget_like, vw_pad,
                 c_src, c_dst, c_w, salt, n_loc, cluster_mode):
    """Shared gain/argmax stage. Returns (move, target, lab_cur) over the
    (n_loc+1,) src space. ``cw_like``/``budget_like`` are indexed by label
    value; in cluster mode budget is the scalar W broadcast."""
    lab_dst = tab[c_dst]
    s_src, s_lab, s_w = lax.sort((c_src, lab_dst, c_w), num_keys=2)
    conn = _group_conns(s_src, s_lab, s_w)
    own_lab = lab_src_tab[s_src]
    staying = s_lab == own_lab
    # ``w <= budget - c`` form: exact at the int32 boundary (w + c wraps)
    fits = cw_like[s_lab] <= budget_like[s_lab] - vw_pad[s_src]
    if cluster_mode:
        fits = fits | staying
    else:
        fits = fits & ~staying
    score = jnp.where(fits, conn, -1)
    best, target = _argmax_target(s_src, s_lab, score, cw_like[s_lab],
                                  salt, n_loc)
    own_conn = _own_connection(s_src, s_lab, s_w, lab_src_tab, n_loc)
    lab_cur = lab_src_tab
    tgt_safe = jnp.where(target < I32_MAX, target, lab_cur)
    if cluster_mode:
        move = (best > own_conn) & (tgt_safe != lab_cur) & \
            (target < I32_MAX) & (best > 0)
    else:
        gain = best - own_conn
        lighter = cw_like[tgt_safe] < cw_like[lab_cur] - vw_pad
        move = (target < I32_MAX) & (best >= 0) & \
            ((gain > 0) | ((gain == 0) & lighter))
    move = move.at[n_loc].set(False)
    return move, tgt_safe, lab_cur


def _penalized_moves(lab_src_tab, tab, bw_like, budget_like, vw_pad,
                     c_src, c_dst, c_w, salt, pen_num, pen_den, n_loc):
    """Unconstrained (Jet-style) gain/argmax stage: the budget mask of
    ``_local_moves`` is replaced by a penalty-weighted score. A move
    whose target block would exceed its budget pays
    ``(own_conn // pen_den) * pen_num`` off its connection (integer-only,
    ``pen <= own_conn < 2^31``), so round 0 is pure gain-greedy and later
    rounds escalate the bar for overloading moves. No bounce follows —
    feasibility is repaired by the trailing balancer (afterburner). Same
    tie-breaks and move rule as the constrained stage otherwise, so the
    two stages differ only in admission. See docs/REFINEMENT.md."""
    lab_dst = tab[c_dst]
    s_src, s_lab, s_w = lax.sort((c_src, lab_dst, c_w), num_keys=2)
    conn = _group_conns(s_src, s_lab, s_w)
    own_lab = lab_src_tab[s_src]
    staying = s_lab == own_lab
    own_conn = _own_connection(s_src, s_lab, s_w, lab_src_tab, n_loc)
    # ``w > budget - c`` form: exact at the int32 boundary (w + c wraps)
    over_after = bw_like[s_lab] > budget_like[s_lab] - vw_pad[s_src]
    pen = jnp.where(over_after,
                    (own_conn[s_src] // pen_den) * pen_num, 0)
    # clamping to -1 loses nothing: a score < 0 can never pass the move
    # rule (it would need score >= own_conn >= 0)
    score = jnp.where(~staying, jnp.maximum(conn - pen, -1), -1)
    best, target = _argmax_target(s_src, s_lab, score, bw_like[s_lab],
                                  salt, n_loc)
    lab_cur = lab_src_tab
    tgt_safe = jnp.where(target < I32_MAX, target, lab_cur)
    gain = best - own_conn
    lighter = bw_like[tgt_safe] < bw_like[lab_cur] - vw_pad
    move = (target < I32_MAX) & (best >= 0) & \
        ((gain > 0) | ((gain == 0) & lighter))
    move = move.at[n_loc].set(False)
    return move, tgt_safe, lab_cur


def _intra_pe_revert(move, tgt, lab_cur, vw_pad, cw, d_in, d_out,
                     salt, n_loc, num_labels, W):
    """Exact hash-ordered revert of this PE's chunk moves against its local
    weight view (port of core.lp._cluster_chunk's revert block)."""
    new_cw = cw + d_in - d_out
    new_lab = jnp.where(move, tgt, lab_cur)
    over = new_cw > W
    cand = move & over[new_lab]
    num = n_loc + 1
    rk = _hash32(jnp.arange(num, dtype=jnp.int32),
                 salt ^ np.uint32(0x9E3779B9))
    sort_lab = jnp.where(cand, new_lab, jnp.int32(num_labels))
    o_lab, _, o_v = lax.sort(
        (sort_lab, rk, jnp.arange(num, dtype=jnp.int32)), num_keys=2)
    o_vw = jnp.where(o_lab < num_labels, vw_pad[o_v], 0)
    csum = jnp.cumsum(o_vw)
    grp_start = jnp.concatenate([
        jnp.ones((1,), jnp.bool_), o_lab[1:] != o_lab[:-1]])
    gid = jnp.cumsum(grp_start.astype(jnp.int32)) - 1
    base = jax.ops.segment_min(
        jnp.where(grp_start, csum - o_vw, I32_MAX), gid, num_segments=num)
    within = csum - base[gid]
    lab_safe = jnp.where(o_lab < num_labels, o_lab, 0)
    moved_in = jax.ops.segment_sum(o_vw, gid, num_segments=num)[gid]
    allowed = jnp.maximum(W - (new_cw[lab_safe] - moved_in), 0)
    revert = (o_lab < num_labels) & (within > allowed)
    rv = jnp.zeros(num, dtype=jnp.bool_).at[o_v].set(revert, mode="drop")
    return move & ~rv


def _apply_and_sync(move, tgt, lab_cur, vw_pad, cw, num_labels):
    """Scatter move deltas into the replicated label-weight table and psum.
    Returns the updated weight table."""
    vw_m = jnp.where(move, vw_pad, 0)
    d_in = jnp.zeros((num_labels,), jnp.int32).at[tgt].add(vw_m,
                                                           mode="drop")
    d_out = jnp.zeros((num_labels,), jnp.int32).at[lab_cur].add(vw_m,
                                                                mode="drop")
    delta = lax.psum(d_in - d_out, "pe")
    return cw + delta


def _bounce_back(move, tgt, lab_cur, vw_pad, cw, budget_like, num_labels):
    """Approximate cross-PE revert: labels that exceeded their budget after
    the psum bounce this chunk's incoming moves back everywhere."""
    over = cw > budget_like
    bounce = move & over[tgt]
    vw_b = jnp.where(bounce, vw_pad, 0)
    b_in = jnp.zeros((num_labels,), jnp.int32).at[lab_cur].add(vw_b,
                                                               mode="drop")
    b_out = jnp.zeros((num_labels,), jnp.int32).at[tgt].add(vw_b,
                                                            mode="drop")
    cw = cw + lax.psum(b_in - b_out, "pe")
    return move & ~bounce, cw


# --- owner-sharded weight-table protocol (weights="owner") -----------------

def _commit_to_owners(move, tgt, lab_cur, vw_pad, cw_own, L, P, use_grid):
    """Owner-mode apply: scatter this chunk's move deltas into a transient
    dense table and reduce-scatter them onto the owners' authoritative
    shards. Returns the updated (L/P,) owner shard."""
    vw_m = jnp.where(move, vw_pad, 0)
    d_in = jnp.zeros((L,), jnp.int32).at[tgt].add(vw_m, mode="drop")
    d_out = jnp.zeros((L,), jnp.int32).at[lab_cur].add(vw_m, mode="drop")
    return cw_own + psum_scatter_1d(d_in - d_out, "pe", P,
                                    use_grid=use_grid)


def _bounce_back_owner(move, tgt, lab_cur, vw_pad, cw_own, budget_own, L,
                       P, use_grid):
    """Approximate cross-PE revert, owner-authoritative: each owner checks
    *its shard* against its budget slice, the overweight flags are
    gathered back, and bounced moves return their weight via a second
    commit. Same flags as the replicated check, O(L/P) persistent state."""
    over = all_gather_1d(cw_own > budget_own, "pe", P, use_grid=use_grid)
    bounce = move & over[tgt]
    vw_b = jnp.where(bounce, vw_pad, 0)
    b_in = jnp.zeros((L,), jnp.int32).at[lab_cur].add(vw_b, mode="drop")
    b_out = jnp.zeros((L,), jnp.int32).at[tgt].add(vw_b, mode="drop")
    cw_own = cw_own + psum_scatter_1d(b_in - b_out, "pe", P,
                                      use_grid=use_grid)
    return move & ~bounce, cw_own


def _fused_chunk_move(lab_src_tab, tab, cw, bud, vw_pad, c_idx, c_w, v0,
                      salt, n_loc, W, interpret):
    """Fused twin of ``_local_moves`` + ``_intra_pe_revert``: gather the
    chunk's ELL operands from the live tables and run the Pallas move
    kernel (diff-form admission, same salts/hash order — bit-identical).
    Returns ``(move, tgt)`` over the (n_loc+1,) src space."""
    R, _ = c_idx.shape
    rows = v0 + jnp.arange(R, dtype=jnp.int32)
    own = lab_src_tab[rows][:, None]         # clamp-gather: dup rows inert
    vwr = vw_pad[rows][:, None]
    valid = c_idx >= 0
    nlab = jnp.where(valid, tab[jnp.where(valid, c_idx, 0)], -1)
    safe_lab = jnp.where(valid, nlab, 0)
    ncw = jnp.where(valid, cw[safe_lab], I32_MAX)
    nbud = jnp.where(valid, bud[safe_lab], 0)
    scal = jnp.concatenate([
        jnp.reshape(W.astype(jnp.int32), (1, 1)),
        jnp.reshape(v0.astype(jnp.int32), (1, 1))], axis=1)
    moved, tgt = lp_move_chunk(nlab, c_w, ncw, own, vwr, scal,
                               jnp.reshape(salt, (1, 1)), nbud=nbud,
                               fit_sum=False, row_tile=move_ops.ROW_TILE,
                               interpret=interpret)
    move = jnp.zeros((n_loc + 1,), jnp.bool_).at[rows].set(
        moved[:, 0] != 0, mode="drop")
    tgt_full = lab_src_tab.at[rows].set(tgt[:, 0], mode="drop")
    return move, tgt_full


# ---------------------------------------------------------------------------
# distributed clustering
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_cluster_fn(mesh, P, n, n_loc, n_ghost, B, num_iterations,
                      use_grid, owner=False, fused=False, interpret=True):
    num_labels = n + 1           # label values are global vertex ids
    S_w = owner_table_width(num_labels, P)
    # owner mode pads the dense *transient* view so P shards tile it;
    # only the (S_w,) shard persists across chunks
    L = P * S_w if owner else num_labels

    def per_pe(slab_a, slab_b, slab_c, vw_loc, lgid, ggid, send_idx,
               recv_slot, salts, W):
        # slabs are (src, dst, w) arc chunks, or (idx, w, v0) ELL chunks
        # when the fused Pallas move kernel is active
        slab_a, slab_b, slab_c = slab_a[0], slab_b[0], slab_c[0]
        vw_loc, lgid, ggid = vw_loc[0], lgid[0], ggid[0]
        send_idx, recv_slot = send_idx[0], recv_slot[0]
        vw_pad = jnp.concatenate([vw_loc, jnp.zeros((1,), jnp.int32)])
        # global per-cluster weights: every vertex starts as a singleton
        # so cw == scattered vertex weights
        dense0 = jnp.zeros((L,), jnp.int32).at[lgid].add(vw_loc,
                                                         mode="drop")
        if owner:
            cw_state = psum_scatter_1d(dense0, "pe", P, use_grid=use_grid)
            gidx = lax.axis_index("pe") * S_w + \
                jnp.arange(S_w, dtype=jnp.int32)
            cw_state = jnp.where(gidx == n, _BIG, cw_state)
            budget_own = jnp.where(gidx == n, -_BIG, W).astype(jnp.int32)
        else:
            cw_state = lax.psum(dense0, "pe")
            cw_state = cw_state.at[n].set(_BIG)  # sentinel never a target
            budget = jnp.full((L,), W, jnp.int32).at[n].set(-_BIG)
        lab_loc = lgid.astype(jnp.int32)     # own global id = own cluster
        lab_ghost = ggid.astype(jnp.int32)

        def chunk_body(carry, xs):
            lab_loc, lab_ghost, cw_state = carry
            # owner mode: request current weights from the owners (the
            # dense views live only inside this chunk body)
            if owner:
                cw = all_gather_1d(cw_state, "pe", P, use_grid=use_grid)
                bud = jnp.full((L,), W, jnp.int32).at[n].set(-_BIG)
            else:
                cw, bud = cw_state, budget
            tab = jnp.concatenate(
                [lab_loc, lab_ghost, jnp.full((1,), n, jnp.int32)])
            lab_src_tab = jnp.concatenate(
                [lab_loc, jnp.full((1,), n, jnp.int32)])
            if fused:
                c_idx, c_w, v0, salt = xs
                move, tgt = _fused_chunk_move(
                    lab_src_tab, tab, cw, bud, vw_pad, c_idx, c_w, v0,
                    salt, n_loc, W, interpret)
                lab_cur = lab_src_tab
            else:
                c_src, c_dst, c_w, salt = xs
                move, tgt, lab_cur = _local_moves(
                    lab_src_tab, tab, cw, bud, vw_pad, c_src, c_dst, c_w,
                    salt, n_loc, cluster_mode=True)
                vw_m = jnp.where(move, vw_pad, 0)
                d_in = jnp.zeros((L,), jnp.int32).at[tgt].add(
                    vw_m, mode="drop")
                d_out = jnp.zeros((L,), jnp.int32).at[lab_cur].add(
                    vw_m, mode="drop")
                move = _intra_pe_revert(move, tgt, lab_cur, vw_pad, cw,
                                        d_in, d_out, salt, n_loc, L, W)
            if owner:
                cw_state = _commit_to_owners(move, tgt, lab_cur, vw_pad,
                                             cw_state, L, P, use_grid)
                move, cw_state = _bounce_back_owner(
                    move, tgt, lab_cur, vw_pad, cw_state, budget_own, L,
                    P, use_grid)
            else:
                cw_state = _apply_and_sync(move, tgt, lab_cur, vw_pad,
                                           cw_state, L)
                move, cw_state = _bounce_back(move, tgt, lab_cur, vw_pad,
                                              cw_state, bud, L)
            lab_loc = jnp.where(move[:n_loc], tgt[:n_loc], lab_loc)
            lab_ghost = halo_exchange(lab_loc, send_idx, recv_slot,
                                      n_ghost, "pe", P, use_grid=use_grid)
            return (lab_loc, lab_ghost, cw_state), ()

        for it in range(num_iterations):
            (lab_loc, lab_ghost, cw_state), _ = lax.scan(
                chunk_body, (lab_loc, lab_ghost, cw_state),
                (slab_a, slab_b, slab_c, salts[it]))
        return lab_loc[None]

    pe = PS("pe")
    rep = PS()
    # check: pallas_call has no replication rule under shard_map
    fn = shard_map(per_pe, mesh=mesh,
                   in_specs=(pe, pe, pe, pe, pe, pe, pe, pe, rep, rep),
                   out_specs=pe, check=not fused)
    return jax.jit(fn)


def dist_cluster(shards: GraphShards,
                 max_cluster_weight: int,
                 num_iterations: int = 3,
                 num_chunks: int = 8,
                 seed: int = 0,
                 use_grid: bool = True,
                 mesh: Mesh = None,
                 weights: str = "replicated",
                 kernel: str = "auto") -> np.ndarray:
    """Distributed size-constrained LP clustering over graph shards.

    Returns (n,) int64 global cluster labels (label values are vertex
    ids). Cluster weights respect ``max_cluster_weight`` up to cross-PE
    race tolerance; callers contract only after exact host-side
    enforcement. ``weights`` picks the table layout (module docstring)
    and ``kernel`` the chunk-move implementation (``kernels.dispatch``);
    every combination returns bit-identical labels.
    """
    P, n = shards.P, shards.n
    owner = _check_weights_mode(weights)
    _check_int32_weights(shards)
    mesh = _resolve_mesh(mesh, P)
    fused = dispatch.resolve_kernel_mode(kernel) == "fused"
    if fused:
        idx, ws_ell, v0s = move_ops.build_move_chunks_dist(
            shards, num_chunks)
        _, B, R, D = idx.shape
        est = lp_move_vmem_bytes(R, D, move_ops.ROW_TILE, fit_sum=False)
        if est > dispatch.VMEM_BUDGET_BYTES:
            dispatch.report_fallback("lp_move", est,
                                     detail="dist_cluster")
            fused = False
        else:
            slabs = (spans.upload(idx), spans.upload(ws_ell),
                     spans.upload(v0s))
    if not fused:
        srcs, dsts, ws = chunk_local_arcs(shards, num_chunks)
        B = srcs.shape[1]
        slabs = (spans.upload(srcs), spans.upload(dsts), spans.upload(ws))
    fn = _build_cluster_fn(mesh, P, n, shards.n_loc, shards.n_ghost, B,
                           num_iterations, use_grid, owner, fused=fused,
                           interpret=dispatch.kernel_interpret())
    salts = (np.arange(num_iterations * B, dtype=np.uint64).reshape(
        num_iterations, B) * 0x85EBCA6B + seed * 1000003) % (2**32)
    lab = fn(*slabs,
             spans.upload(shards.vweights), spans.upload(shards.local_gid),
             spans.upload(shards.ghost_gid), spans.upload(shards.send_idx),
             spans.upload(shards.recv_slot),
             spans.upload(salts.astype(np.uint32)),
             jnp.int32(max(1, min(int(max_cluster_weight), int(_BIG)))))
    lab = spans.fetch(lab)
    out = np.empty(n, dtype=np.int64)
    valid = shards.local_gid < n
    out[shards.local_gid[valid]] = lab[valid]
    return out


# ---------------------------------------------------------------------------
# distributed k-way refinement
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_refine_fn(mesh, P, k, n_loc, n_ghost, B, num_iterations,
                     use_grid, owner=False):
    kk = k + 1                   # sentinel block k
    S_k = owner_table_width(kk, P)
    L = P * S_k if owner else kk

    def per_pe(src, dst, w, vw_loc, part_loc, part_ghost, send_idx,
               recv_slot, salts, l_max):
        src, dst, w = src[0], dst[0], w[0]
        vw_loc, part_loc, part_ghost = vw_loc[0], part_loc[0], part_ghost[0]
        send_idx, recv_slot = send_idx[0], recv_slot[0]
        vw_pad = jnp.concatenate([vw_loc, jnp.zeros((1,), jnp.int32)])
        dense0 = jnp.zeros((L,), jnp.int32).at[part_loc].add(vw_loc,
                                                             mode="drop")
        budget = jnp.concatenate([l_max.astype(jnp.int32),
                                  jnp.full((L - k,), -_BIG, jnp.int32)])
        if owner:
            bw_state = psum_scatter_1d(dense0, "pe", P, use_grid=use_grid)
            gidx = lax.axis_index("pe") * S_k + \
                jnp.arange(S_k, dtype=jnp.int32)
            bw_state = jnp.where(gidx == k, _BIG, bw_state)
            budget_own = lax.dynamic_slice(
                budget, (lax.axis_index("pe") * S_k,), (S_k,))
        else:
            bw_state = lax.psum(dense0, "pe")
            bw_state = bw_state.at[k].set(_BIG)

        def chunk_body(carry, xs):
            lab_loc, lab_ghost, bw_state = carry
            c_src, c_dst, c_w, salt = xs
            bw = all_gather_1d(bw_state, "pe", P, use_grid=use_grid) \
                if owner else bw_state
            tab = jnp.concatenate(
                [lab_loc, lab_ghost, jnp.full((1,), k, jnp.int32)])
            lab_src_tab = jnp.concatenate(
                [lab_loc, jnp.full((1,), k, jnp.int32)])
            move, tgt, lab_cur = _local_moves(
                lab_src_tab, tab, bw, budget, vw_pad, c_src, c_dst, c_w,
                salt, n_loc, cluster_mode=False)
            if owner:
                bw_state = _commit_to_owners(move, tgt, lab_cur, vw_pad,
                                             bw_state, L, P, use_grid)
                move, bw_state = _bounce_back_owner(
                    move, tgt, lab_cur, vw_pad, bw_state, budget_own, L,
                    P, use_grid)
            else:
                bw_state = _apply_and_sync(move, tgt, lab_cur, vw_pad,
                                           bw_state, L)
                move, bw_state = _bounce_back(move, tgt, lab_cur, vw_pad,
                                              bw_state, budget, L)
            lab_loc = jnp.where(move[:n_loc], tgt[:n_loc], lab_loc)
            lab_ghost = halo_exchange(lab_loc, send_idx, recv_slot,
                                      n_ghost, "pe", P, use_grid=use_grid)
            return (lab_loc, lab_ghost, bw_state), ()

        lab_loc = part_loc
        lab_ghost = part_ghost
        for it in range(num_iterations):
            (lab_loc, lab_ghost, bw_state), _ = lax.scan(
                chunk_body, (lab_loc, lab_ghost, bw_state),
                (src, dst, w, salts[it]))
        return lab_loc[None]

    pe = PS("pe")
    rep = PS()
    fn = shard_map(per_pe, mesh=mesh,
                   in_specs=(pe, pe, pe, pe, pe, pe, pe, pe, rep, rep),
                   out_specs=pe, check=True)
    return jax.jit(fn)


def dist_lp_refine(shards: GraphShards,
                   part: np.ndarray,
                   l_max_vec: np.ndarray,
                   num_iterations: int = 2,
                   num_chunks: int = 8,
                   seed: int = 0,
                   use_grid: bool = True,
                   mesh: Mesh = None,
                   weights: str = "replicated") -> np.ndarray:
    """Distributed chunked LP refinement of a k-way partition.

    Same move rule as ``core.lp._refine_chunk`` (positive gain, or zero
    gain into the lighter block); block weights either replicated and
    psum-synced per chunk or owner-sharded (``weights``, module
    docstring), overweight blocks bouncing racing moves back either way.
    May leave the partition slightly infeasible; pair with a balancing
    pass.
    """
    P, n = shards.P, shards.n
    owner = _check_weights_mode(weights)
    _check_int32_weights(shards)
    k = int(l_max_vec.shape[0])
    mesh = _resolve_mesh(mesh, P)
    srcs, dsts, ws = chunk_local_arcs(shards, num_chunks)
    B = srcs.shape[1]
    fn = _build_refine_fn(mesh, P, k, shards.n_loc, shards.n_ghost, B,
                          num_iterations, use_grid, owner)
    part_pad = np.concatenate([part.astype(np.int64), [k]])  # sentinel gid=n
    part_loc = part_pad[np.minimum(shards.local_gid, n)].astype(np.int32)
    part_ghost = part_pad[np.minimum(shards.ghost_gid, n)].astype(np.int32)
    salts = (np.arange(num_iterations * B, dtype=np.uint64).reshape(
        num_iterations, B) * 0xC2B2AE35 + seed * 2654435761) % (2**32)
    lmax32 = np.minimum(l_max_vec, int(_BIG)).astype(np.int32)
    lab = fn(spans.upload(srcs), spans.upload(dsts), spans.upload(ws),
             spans.upload(shards.vweights), spans.upload(part_loc),
             spans.upload(part_ghost), spans.upload(shards.send_idx),
             spans.upload(shards.recv_slot),
             spans.upload(salts.astype(np.uint32)), spans.upload(lmax32))
    lab = spans.fetch(lab)
    out = np.empty(n, dtype=np.int64)
    valid = shards.local_gid < n
    out[shards.local_gid[valid]] = lab[valid]
    return out


# ---------------------------------------------------------------------------
# distributed unconstrained (Jet-style) refinement
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_urefine_fn(mesh, P, k, n_loc, n_ghost, B, num_iterations,
                      use_grid, owner=False):
    """shard_map program for one unconstrained refinement call: the
    ``_build_refine_fn`` skeleton with the penalized gain stage and *no*
    bounce-back — moves commit even when they overload the target, the
    weight tables track the overloaded truth, and the per-round penalty
    (a Python constant of the unrolled iteration loop) escalates
    ``it / num_iterations``. Both weight layouts stay bit-identical:
    they present the same dense table at the top of each chunk and
    commit the same deltas."""
    kk = k + 1                   # sentinel block k
    S_k = owner_table_width(kk, P)
    L = P * S_k if owner else kk

    def per_pe(src, dst, w, vw_loc, part_loc, part_ghost, send_idx,
               recv_slot, salts, l_max):
        src, dst, w = src[0], dst[0], w[0]
        vw_loc, part_loc, part_ghost = vw_loc[0], part_loc[0], part_ghost[0]
        send_idx, recv_slot = send_idx[0], recv_slot[0]
        vw_pad = jnp.concatenate([vw_loc, jnp.zeros((1,), jnp.int32)])
        dense0 = jnp.zeros((L,), jnp.int32).at[part_loc].add(vw_loc,
                                                             mode="drop")
        budget = jnp.concatenate([l_max.astype(jnp.int32),
                                  jnp.full((L - k,), -_BIG, jnp.int32)])
        if owner:
            bw_state = psum_scatter_1d(dense0, "pe", P, use_grid=use_grid)
            gidx = lax.axis_index("pe") * S_k + \
                jnp.arange(S_k, dtype=jnp.int32)
            bw_state = jnp.where(gidx == k, _BIG, bw_state)
        else:
            bw_state = lax.psum(dense0, "pe")
            bw_state = bw_state.at[k].set(_BIG)
        pen_den = jnp.int32(num_iterations)

        def make_chunk_body(pen_num):
            def chunk_body(carry, xs):
                lab_loc, lab_ghost, bw_state = carry
                c_src, c_dst, c_w, salt = xs
                bw = all_gather_1d(bw_state, "pe", P, use_grid=use_grid) \
                    if owner else bw_state
                tab = jnp.concatenate(
                    [lab_loc, lab_ghost, jnp.full((1,), k, jnp.int32)])
                lab_src_tab = jnp.concatenate(
                    [lab_loc, jnp.full((1,), k, jnp.int32)])
                move, tgt, lab_cur = _penalized_moves(
                    lab_src_tab, tab, bw, budget, vw_pad, c_src, c_dst,
                    c_w, salt, pen_num, pen_den, n_loc)
                if owner:
                    bw_state = _commit_to_owners(move, tgt, lab_cur,
                                                 vw_pad, bw_state, L, P,
                                                 use_grid)
                else:
                    bw_state = _apply_and_sync(move, tgt, lab_cur, vw_pad,
                                               bw_state, L)
                lab_loc = jnp.where(move[:n_loc], tgt[:n_loc], lab_loc)
                lab_ghost = halo_exchange(lab_loc, send_idx, recv_slot,
                                          n_ghost, "pe", P,
                                          use_grid=use_grid)
                return (lab_loc, lab_ghost, bw_state), ()
            return chunk_body

        lab_loc = part_loc
        lab_ghost = part_ghost
        for it in range(num_iterations):
            (lab_loc, lab_ghost, bw_state), _ = lax.scan(
                make_chunk_body(jnp.int32(it)),
                (lab_loc, lab_ghost, bw_state), (src, dst, w, salts[it]))
        return lab_loc[None]

    pe = PS("pe")
    rep = PS()
    fn = shard_map(per_pe, mesh=mesh,
                   in_specs=(pe, pe, pe, pe, pe, pe, pe, pe, rep, rep),
                   out_specs=pe, check=True)
    return jax.jit(fn)


def dist_ulp_refine(shards: GraphShards,
                    part: np.ndarray,
                    l_max_vec: np.ndarray,
                    num_iterations: int = 2,
                    num_chunks: int = 8,
                    seed: int = 0,
                    use_grid: bool = True,
                    mesh: Mesh = None,
                    weights: str = "replicated") -> np.ndarray:
    """Distributed unconstrained (Jet-style) refinement of a k-way
    partition: penalty-weighted gains instead of the budget mask, no
    bounce-back. The result may overload blocks by design — callers MUST
    follow with ``rebalance`` / ``dist_rebalance`` (the afterburner;
    ``dist_partitioner.dist_refine_and_balance`` does). Block weight
    tables replicated or owner-sharded per ``weights``, bit-identical
    either way. Same chunking/salt streams as ``dist_lp_refine``."""
    P, n = shards.P, shards.n
    owner = _check_weights_mode(weights)
    _check_int32_weights(shards)
    k = int(l_max_vec.shape[0])
    mesh = _resolve_mesh(mesh, P)
    srcs, dsts, ws = chunk_local_arcs(shards, num_chunks)
    B = srcs.shape[1]
    fn = _build_urefine_fn(mesh, P, k, shards.n_loc, shards.n_ghost, B,
                           num_iterations, use_grid, owner)
    part_pad = np.concatenate([part.astype(np.int64), [k]])  # sentinel
    part_loc = part_pad[np.minimum(shards.local_gid, n)].astype(np.int32)
    part_ghost = part_pad[np.minimum(shards.ghost_gid, n)].astype(np.int32)
    salts = (np.arange(num_iterations * B, dtype=np.uint64).reshape(
        num_iterations, B) * 0xC2B2AE35 + seed * 2654435761) % (2**32)
    lmax32 = np.minimum(l_max_vec, int(_BIG)).astype(np.int32)
    lab = fn(spans.upload(srcs), spans.upload(dsts), spans.upload(ws),
             spans.upload(shards.vweights), spans.upload(part_loc),
             spans.upload(part_ghost), spans.upload(shards.send_idx),
             spans.upload(shards.recv_slot),
             spans.upload(salts.astype(np.uint32)), spans.upload(lmax32))
    lab = spans.fetch(lab)
    out = np.empty(n, dtype=np.int64)
    valid = shards.local_gid < n
    out[shards.local_gid[valid]] = lab[valid]
    return out
