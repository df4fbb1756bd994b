"""Distributed greedy balancing (paper §4, Balancing).

shard_map port of the host balancer in ``core/balance.py`` over the
``GraphShards`` layout. The two round kernels are shared with the host
path — each PE runs ``core.balance.balance_gains`` over its own arc
shard and pools its ``top_m`` candidates; the pools are combined with
``collectives.all_gather_1d`` (direct or two-level grid routing — the
array analogue of the paper's binary-tree reduction), and
``core.balance.greedy_select`` then applies the ranked pool redundantly
on every PE, so all PEs agree on the accepted moves without a root /
broadcast step.

Per round, each PE therefore exchanges O(P · top_m) candidate records
plus one halo refresh — never the O(m) arc gather the host balancer
pays (``core.balance.rebalance`` builds a single-chunk arc slab of the
whole graph). Block weight tables come in the same two layouts as
``dist_lp``:

  * ``"replicated"`` — every PE carries the dense (k+1,) table across
    rounds. Selection is deterministic and redundant, so no psum is
    needed to keep the copies identical.
  * ``"owner"`` — each PE persistently holds its (ceil((k+1)/P),) shard
    and requests the dense view via ``all_gather_1d`` at the top of
    each round; after selection it keeps only its slice (the commit is
    a slice, not a reduce-scatter, exactly because every PE computed
    the identical updated table).

Both layouts apply identical integer arithmetic in the same order and
produce bit-identical labels; at P=1 the whole balancer is bit-identical
to ``core.balance.rebalance``.

``dist_enforce_cluster_weights`` is the coarsening-side half of paper
§4's balancing: the exact eject-to-singleton sweep of
``core.coarsening.enforce_cluster_weights``, run owner-side. Member
records (cluster, weight, vertex) are routed to the cluster's owner PE
through one all-to-all, the owner applies the shared
keep-heaviest-first-prefix rule (``core.coarsening.ejection_candidates``
semantics) over the members it alone sees in full, and the eject flags
ride the reverse all-to-all back. Ejected vertices move to cluster id
``n + vertex_gid`` — guaranteed unused since LP labels are vertex ids
< n — so decisions match the host sweep exactly and the resulting
clustering is identical up to a relabeling of the fresh singletons
(contraction renumbers labels anyway).

Transients: the gathered pool is O(P · top_m) and the enforcement slab
O(n_loc · P) per PE — the same transient class as the dense weight
views of ``dist_lp``; persistent state stays O(n/P + k).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as PS

from .. import spans
from ..core.balance import balance_gains, greedy_select
from ..core.lp import I32_MAX
from ..graphs.distribute import GraphShards
from ..kernels import dispatch
from ..kernels.bal_round import ops as bal_ops
from ..kernels.bal_round.bal_round import greedy_pick
from .collectives import all_gather_1d, all_to_all, halo_exchange
from .compat import shard_map
from .dist_lp import (_check_int32_weights, _check_weights_mode,
                      _resolve_mesh, owner_table_width)

# bytes per pooled candidate record: 4 int32 fields + 1 f32 gain
_POOL_RECORD_BYTES = 20


# ---------------------------------------------------------------------------
# distributed balancing rounds
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_balance_round_fn(mesh, P, k, n, n_loc, n_ghost, top_m, use_grid,
                            owner, fused=False, interpret=True):
    kk = k + 1                    # sentinel block k
    S_k = owner_table_width(kk, P)

    def per_pe(*args):
        if fused:
            (lab_loc, lab_ghost, bw_state, ell_idx, ell_w, vw_loc, lgid,
             send_idx, recv_slot, offsets, l_max, salt) = args
            ell_idx, ell_w = ell_idx[0], ell_w[0]
        else:
            (lab_loc, lab_ghost, bw_state, src, dst, w, vw_loc, lgid,
             send_idx, recv_slot, offsets, l_max, salt) = args
            src, dst, w = src[0], dst[0], w[0]
        lab_loc, lab_ghost, bw_state = lab_loc[0], lab_ghost[0], bw_state[0]
        vw_loc, lgid = vw_loc[0], lgid[0]
        send_idx, recv_slot = send_idx[0], recv_slot[0]

        # dense block-weight view for this round (owner mode: request)
        bw = all_gather_1d(bw_state, "pe", P, use_grid=use_grid) if owner \
            else bw_state
        vw_pad = jnp.concatenate([vw_loc, jnp.zeros((1,), jnp.int32)])
        gid_pad = jnp.concatenate([lgid, jnp.full((1,), n, jnp.int32)])
        tab = jnp.concatenate(
            [lab_loc, lab_ghost, jnp.full((1,), k, jnp.int32)])
        lab_src_tab = jnp.concatenate(
            [lab_loc, jnp.full((1,), k, jnp.int32)])

        # per-shard gains: shared host kernel (composed) or Pallas pair
        if fused:
            rel, tgt = bal_ops.fused_round_scores(
                tab, lab_src_tab, bw, l_max, None, ell_idx, ell_w,
                vw_pad, gid_pad < n, salt, restricted=False,
                interpret=interpret)
        else:
            lab_dst = tab[dst]
            s_src, s_lab, s_w = lax.sort((src, lab_dst, w), num_keys=2)
            rel, tgt = balance_gains(lab_src_tab, s_src, s_lab, s_w, bw,
                                     l_max, None, vw_pad, salt, n_loc,
                                     valid=gid_pad < n, restricted=False)

        # local top-m pool -> gathered (P*top_m,) pool on every PE
        vals, vidx = lax.top_k(rel, top_m)
        pool = jnp.stack([gid_pad[vidx], tgt[vidx], lab_src_tab[vidx],
                          vw_pad[vidx]], axis=1)            # (top_m, 4)
        pool = all_gather_1d(pool, "pe", P, use_grid=use_grid)
        pvals = all_gather_1d(vals, "pe", P, use_grid=use_grid)

        # deterministic ranking: descending gain, ties by vertex id
        # (matches lax.top_k's lower-index-first tie-break at P=1)
        o_neg, o_gid, o_tgt, o_blk, o_w = lax.sort(
            (-pvals, pool[:, 0], pool[:, 1], pool[:, 2], pool[:, 3]),
            num_keys=2)
        if fused:
            accept, bw = greedy_pick(-o_neg, o_tgt, o_blk, o_w, bw, l_max,
                                     interpret=interpret)
        else:
            accept, bw = greedy_select(-o_neg, o_tgt, o_blk, o_w, bw,
                                       l_max)

        # apply accepted moves to the locally-owned vertices
        pid = lax.axis_index("pe")
        v0, v1 = offsets[pid], offsets[pid + 1]
        mine = accept & (o_gid >= v0) & (o_gid < v1)
        idx = jnp.where(mine, o_gid - v0, jnp.int32(n_loc))
        lab_loc = lab_loc.at[idx].set(o_tgt, mode="drop")
        lab_ghost = halo_exchange(lab_loc, send_idx, recv_slot, n_ghost,
                                  "pe", P, use_grid=use_grid)

        overloaded = jnp.any(bw[:k] > l_max[:k])
        if owner:   # commit: keep only this PE's authoritative slice
            bw_state = lax.dynamic_slice(bw, (pid * S_k,), (S_k,))
        else:
            bw_state = bw
        return (lab_loc[None], lab_ghost[None], bw_state[None],
                overloaded[None])

    pe = PS("pe")
    rep = PS()
    n_pe = 9 if fused else 10
    fn = shard_map(per_pe, mesh=mesh,
                   in_specs=(pe,) * n_pe + (rep, rep, rep),
                   out_specs=(pe, pe, pe, pe), check=not fused)
    return jax.jit(fn)


def dist_rebalance(shards: GraphShards,
                   part: np.ndarray,
                   l_max_vec: np.ndarray,
                   top_m: int = 128,
                   max_rounds: int = 200,
                   seed: int = 0,
                   use_grid: bool = True,
                   mesh=None,
                   weights: str = "replicated",
                   kernel: str = "auto",
                   stats: Optional[Dict] = None) -> np.ndarray:
    """Distributed exact balancer: rounds of pooled greedy moves until
    every block fits its budget.

    Bit-identical to ``core.balance.rebalance(g, part, l_max_vec)`` at
    P=1 (same gains, same pool ordering, same salt schedule, same
    early-return); at P>1 each PE contributes its own ``top_m``
    candidates per round, so a round can apply up to ``P * top_m``
    moves. ``weights`` picks the block-table layout (module docstring);
    both produce bit-identical labels, as does ``kernel="fused"`` (the
    ``kernels.bal_round`` Pallas pair; falls back to composed when the
    per-PE ELL slab exceeds the VMEM budget). ``stats``, when given,
    receives ``rounds`` / ``pool_bytes`` / ``halo_bytes`` / ``time_s``.
    """
    P, n = shards.P, shards.n
    owner = _check_weights_mode(weights)
    k = int(l_max_vec.shape[0])
    part = np.asarray(part, dtype=np.int64)
    l_max_vec = np.asarray(l_max_vec, dtype=np.int64)
    t_start = time.perf_counter()

    valid = shards.local_gid < n
    vw_glob = np.zeros(n, dtype=np.int64)
    vw_glob[shards.local_gid[valid]] = shards.vweights[valid]
    bw0 = np.zeros(k, dtype=np.int64)
    np.add.at(bw0, part, vw_glob)
    if not bool(np.any(bw0 > l_max_vec)):   # already feasible: no device work
        if stats is not None:
            stats.update(rounds=0, pool_bytes=0, halo_bytes=0,
                         time_s=time.perf_counter() - t_start)
        return part.copy()

    _check_int32_weights(shards)
    mesh = _resolve_mesh(mesh, P)
    kk = k + 1
    S_k = owner_table_width(kk, P)
    L = P * S_k if owner else kk
    # sentinel / pad blocks: maximal weight and budget — never overloaded,
    # never a fitting target, never the argmin fallback (same fix as
    # core.refinement.pad_blocks)
    bw_dense = np.full(L, I32_MAX, dtype=np.int32)
    bw_dense[:k] = bw0
    lmax_dense = np.full(L, I32_MAX, dtype=np.int32)
    lmax_dense[:k] = np.minimum(l_max_vec, int(I32_MAX))
    bw_state = bw_dense.reshape(P, S_k) if owner \
        else np.broadcast_to(bw_dense, (P, kk)).copy()

    top_m_loc = min(top_m, shards.n_loc + 1)
    part_pad = np.concatenate([part, [k]])   # sentinel gid n -> block k
    lab_loc = part_pad[np.minimum(shards.local_gid, n)].astype(np.int32)
    lab_ghost = part_pad[np.minimum(shards.ghost_gid, n)].astype(np.int32)

    fused = dispatch.resolve_kernel_mode(kernel) == "fused"
    if fused:
        ell_idx, ell_w = bal_ops.build_balance_ell_dist(shards)
        if not bal_ops.balance_ell_fits(ell_idx.shape[1],
                                        ell_idx.shape[2]):
            dispatch.report_fallback(
                "bal_round",
                bal_ops.bal_scores_vmem_bytes(
                    ell_idx.shape[1], ell_idx.shape[2],
                    bal_ops.ROW_TILE),
                detail="dist_rebalance")
            fused = False
    fn = _build_balance_round_fn(mesh, P, k, n, shards.n_loc,
                                 shards.n_ghost, top_m_loc, use_grid,
                                 owner, fused=fused,
                                 interpret=dispatch.kernel_interpret())
    lab_loc = spans.upload(lab_loc)
    lab_ghost = spans.upload(lab_ghost)
    bw_state = spans.upload(bw_state)
    slab_args = (spans.upload(ell_idx), spans.upload(ell_w)) if fused else \
        (spans.upload(shards.arc_src),
         spans.upload(shards.arc_dst_idx),
         spans.upload(shards.arc_w))
    graph_args = slab_args + (spans.upload(shards.vweights),
                  spans.upload(shards.local_gid),
                  spans.upload(shards.send_idx),
                  spans.upload(shards.recv_slot),
                  spans.upload(shards.offsets.astype(np.int32)),
                  spans.upload(lmax_dense))
    rounds = 0
    for r in range(max_rounds):
        lab_loc, lab_ghost, bw_state, overloaded = fn(
            lab_loc, lab_ghost, bw_state, *graph_args,
            jnp.uint32((seed * 7919 + r) % (2**32)))
        rounds = r + 1
        if not bool(np.any(spans.fetch(overloaded))):
            break

    lab = spans.fetch(lab_loc)
    out = np.empty(n, dtype=np.int64)
    out[shards.local_gid[valid]] = lab[valid]
    if stats is not None:
        stats.update(
            rounds=rounds,
            # per-PE gathered pool volume + ghost refresh, per run
            pool_bytes=rounds * P * top_m_loc * _POOL_RECORD_BYTES,
            halo_bytes=rounds * shards.comm_bytes_per_halo(),
            time_s=time.perf_counter() - t_start)
    return out


# ---------------------------------------------------------------------------
# sharded exact cluster-weight enforcement (coarsening-side balancing)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_enforce_fn(mesh, P, n, n_loc, use_grid):
    S_w = owner_table_width(n + 1, P)   # cluster id c is owned by c // S_w
    R = P * n_loc                       # owner-side member rows

    def per_pe(lab_loc, vw_loc, lgid, W):
        lab_loc, vw_loc, lgid = lab_loc[0], vw_loc[0], lgid[0]
        iota = jnp.arange(n_loc, dtype=jnp.int32)
        valid = lgid < n
        dest = jnp.where(valid, lab_loc // S_w, P)   # P == drop

        # pack member records into per-owner segments of the send slab
        o_dest, _, o_lab, o_vw, o_gid, o_idx = lax.sort(
            (dest, lgid, lab_loc, vw_loc, lgid, iota), num_keys=2)
        runs = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), o_dest[1:] != o_dest[:-1]])
        rid = jnp.cumsum(runs.astype(jnp.int32)) - 1
        run0 = jax.ops.segment_min(jnp.where(runs, iota, I32_MAX), rid,
                                   num_segments=n_loc)
        pos = iota - run0[rid]
        fidx = jnp.where(o_dest < P, o_dest * n_loc + pos, R)
        slab = jnp.stack([
            jnp.full((R,), n, jnp.int32).at[fidx].set(o_lab, mode="drop"),
            jnp.zeros((R,), jnp.int32).at[fidx].set(o_vw, mode="drop"),
            jnp.full((R,), n, jnp.int32).at[fidx].set(o_gid, mode="drop"),
        ], axis=-1).reshape(P, n_loc, 3)

        # owners see every member of their clusters
        recv = all_to_all(slab, "pe", P, use_grid=use_grid)
        r_lab = recv[:, :, 0].reshape(R)
        r_vw = recv[:, :, 1].reshape(R)
        r_gid = recv[:, :, 2].reshape(R)

        # shared decision rule: sort by (cluster, -weight, id), eject when
        # the cumulative kept weight exceeds W — never the first member
        riota = jnp.arange(R, dtype=jnp.int32)
        s_lab, s_nvw, s_gid, s_j = lax.sort(
            (r_lab, -r_vw, r_gid, riota), num_keys=3)
        s_vw = jnp.where(s_lab < n, -s_nvw, 0)
        starts = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), s_lab[1:] != s_lab[:-1]])
        grp = jnp.cumsum(starts.astype(jnp.int32)) - 1
        csum = jnp.cumsum(s_vw)
        base = jax.ops.segment_min(
            jnp.where(starts, csum - s_vw, I32_MAX), grp, num_segments=R)
        within = csum - base[grp]
        eject = (s_lab < n) & (within > W) & ~starts

        # eject flags ride the reverse exchange back to the member's PE
        flags = jnp.zeros((R,), jnp.bool_).at[s_j].set(eject)
        back = all_to_all(flags.reshape(P, n_loc), "pe", P,
                          use_grid=use_grid).reshape(R)
        fl = jnp.where(o_dest < P, back[jnp.minimum(fidx, R - 1)], False)
        ej_loc = jnp.zeros((n_loc,), jnp.bool_).at[o_idx].set(fl)

        # fresh singleton id n + gid: unused, since LP labels are ids < n
        lab_out = jnp.where(ej_loc & valid, n + lgid, lab_loc)
        return lab_out[None], jnp.sum(ej_loc)[None]

    pe = PS("pe")
    fn = shard_map(per_pe, mesh=mesh, in_specs=(pe, pe, pe, PS()),
                   out_specs=(pe, pe), check=True)
    return jax.jit(fn)


def dist_enforce_cluster_weights(shards: GraphShards,
                                 labels: np.ndarray,
                                 max_weight: int,
                                 use_grid: bool = True,
                                 mesh=None,
                                 stats: Optional[Dict] = None
                                 ) -> np.ndarray:
    """Sharded exact max-cluster-weight enforcement.

    Ejects the identical vertex set as the host sweep
    (``core.coarsening.enforce_cluster_weights`` /
    ``ejection_candidates``) — owners apply the same deterministic
    (cluster, -weight, id) prefix rule over all members of their
    clusters — but assigns ejected vertices the fresh singleton id
    ``n + vertex_gid`` instead of recycling host-side free ids, so the
    result matches the host sweep up to a relabeling of the fresh
    singletons. ``labels`` must be LP cluster labels (values are vertex
    ids < n).
    """
    P, n = shards.P, shards.n
    if n >= 2**30:
        raise ValueError(
            f"dist_enforce_cluster_weights: n = {n} >= 2^30 would "
            "overflow the int32 fresh-singleton id space (n + gid)")
    labels = np.asarray(labels, dtype=np.int64)
    if labels.shape != (n,) or (n and labels.max() >= n):
        raise ValueError(
            "dist_enforce_cluster_weights expects (n,) LP labels with "
            f"values < n, got shape {labels.shape}")
    _check_int32_weights(shards)   # the owner-side cumsum is int32
    mesh = _resolve_mesh(mesh, P)
    t0 = time.perf_counter()
    lab_pad = np.concatenate([labels, [n]])
    lab_loc = lab_pad[np.minimum(shards.local_gid, n)].astype(np.int32)
    fn = _build_enforce_fn(mesh, P, n, shards.n_loc, use_grid)
    out_loc, ejected = fn(
        spans.upload(lab_loc), spans.upload(shards.vweights),
        spans.upload(shards.local_gid),
        jnp.int32(max(1, min(int(max_weight), int(I32_MAX)))))
    out_loc = spans.fetch(out_loc)
    valid = shards.local_gid < n
    out = np.empty(n, dtype=np.int64)
    out[shards.local_gid[valid]] = out_loc[valid]
    if stats is not None:
        stats.update(ejected=int(spans.fetch(ejected).sum()),
                     slab_bytes_per_pe=int(P * shards.n_loc * 12),
                     time_s=time.perf_counter() - t0)
    return out
