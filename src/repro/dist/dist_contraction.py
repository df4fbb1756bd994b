"""Distributed cluster contraction (paper §5, Graph Contraction).

The host-side ``core.contraction.contract`` gathers the whole fine graph
to one process; here each level stays sharded:

  1. **cluster → PE ownership** — clusters are assigned to PEs by a
     multiplicative hash of the cluster id (the paper's load-spreading
     assignment) and renumbered so each owner holds a contiguous coarse
     id range (the layout every downstream shard_map kernel expects).
  2. **local pre-contraction** — every PE maps its own arc slab through
     the cluster mapping and runs the shared sequential kernel
     (``core.contraction.dedup_arcs``) over its local arcs only, so the
     exchange ships deduplicated coarse arcs instead of raw fine arcs.
  3. **segmented all-to-all edge exchange** — pre-contracted arcs are
     routed to the owner of their coarse tail through
     ``collectives.exchange_segments`` (direct or two-level grid), with
     the owner-side duplicate merge running inside the same jitted
     program (sort + segment-sum, mirroring the kernel of step 2).
  4. **owner-side assembly** — owners hold the final coarse arc and
     vertex-weight shards; ``graphs.distribute.assemble_shards`` turns
     them into the next level's ``GraphShards`` without re-sharding.

Segment sizes are exact (the host knows the cluster assignment when it
pads the exchange slab), so the padded slab is ~m/P per PE rather than a
worst-case bound. The coarse graph's host view is assembled only for the
phases that are host-side by design (the single-process base case and
the exact balancer); no PE's device state ever exceeds O(n/P + k).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as PS

from .. import spans
from ..core.contraction import dedup_arcs
from ..core.lp import I32_MAX
from ..graphs.distribute import GraphShards, assemble_shards
from ..graphs.format import Graph, from_coo
from ..kernels import dispatch
from ..kernels.seg_merge.seg_merge import seg_merge, seg_merge_vmem_bytes
from .collectives import exchange_segments
from .compat import shard_map
from .dist_lp import _check_int32_weights, _resolve_mesh


@dataclasses.dataclass(frozen=True)
class DistContraction:
    """Result of one sharded contraction level."""
    shards: GraphShards      # coarse graph, contiguous per-owner ranges
    graph: Graph             # host view (base case / exact balancer only)
    mapping: np.ndarray      # (n_fine,) int64 fine gid -> coarse gid
    stats: Dict              # exchange payload / timing for benchmarks


def cluster_owners(cluster_ids: np.ndarray, P: int) -> np.ndarray:
    """Hash-based cluster → PE assignment (paper §5): spreads ownership
    independently of the id distribution the clustering produced."""
    h = (cluster_ids.astype(np.uint64) * np.uint64(2654435761)) \
        & np.uint64(0xFFFFFFFF)
    h ^= np.uint64(0x9E3779B9)
    h ^= h >> np.uint64(15)
    return (h % np.uint64(max(1, P))).astype(np.int64)


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1)).bit_length()


@functools.lru_cache(maxsize=32)
def _build_exchange_fn(mesh, P: int, S_e: int, use_grid: bool,
                       fused: bool = False, interpret: bool = True):
    """Jitted program: segmented all-to-all of (src, dst, w) coarse-arc
    records followed by the owner-side duplicate merge (sort by arc key,
    segment-sum the weights — or the fused seg_merge Pallas kernel,
    bit-identical)."""
    L = P * S_e

    def per_pe(slab, counts):
        slab, counts = slab[0], counts[0]
        recv, rcounts = exchange_segments(slab, counts, "pe", P,
                                          use_grid=use_grid)
        valid = jnp.arange(S_e, dtype=jnp.int32)[None, :] < \
            rcounts[:, None]                                  # (P, S_e)
        src = jnp.where(valid, recv[:, :, 0], I32_MAX).reshape(L)
        dst = jnp.where(valid, recv[:, :, 1], I32_MAX).reshape(L)
        w = jnp.where(valid, recv[:, :, 2], 0).reshape(L)
        if fused:
            s_src, s_dst, tot, first32 = seg_merge(src, dst, w,
                                                   interpret=interpret)
            return (s_src[None], s_dst[None], tot[None],
                    (first32 != 0)[None])
        s_src, s_dst, s_w = lax.sort((src, dst, w), num_keys=2)
        first = jnp.concatenate([
            jnp.ones((1,), jnp.bool_),
            (s_src[1:] != s_src[:-1]) | (s_dst[1:] != s_dst[:-1])])
        gid = jnp.cumsum(first.astype(jnp.int32)) - 1
        tot = jax.ops.segment_sum(s_w, gid, num_segments=L,
                                  indices_are_sorted=True)
        return (s_src[None], s_dst[None], tot[gid][None],
                first[None])

    pe = PS("pe")
    fn = shard_map(per_pe, mesh=mesh, in_specs=(pe, pe),
                   out_specs=(pe, pe, pe, pe), check=not fused)
    return jax.jit(fn)


def _global_vweights(shards: GraphShards) -> np.ndarray:
    vw = np.zeros(shards.n, dtype=np.int64)
    valid = shards.local_gid < shards.n
    vw[shards.local_gid[valid]] = shards.vweights[valid]
    return vw


def dist_contract(shards: GraphShards,
                  labels: np.ndarray,
                  use_grid: bool = False,
                  mesh=None,
                  kernel: str = "auto") -> DistContraction:
    """Contract clustering ``labels`` over graph shards without gathering
    the fine graph. Returns the coarse graph both as shards (fed straight
    into the next level's distributed clustering) and as a host view
    (consumed only by the host-side base case / exact balancer), plus the
    fine→coarse mapping used for uncoarsening projection.
    """
    P, n = shards.P, shards.n
    labels = np.asarray(labels, dtype=np.int64)
    assert labels.shape == (n,), (labels.shape, n)
    _check_int32_weights(shards)   # the exchange slab is int32
    mesh = _resolve_mesh(mesh, P)

    # ---- ownership + owner-contiguous renumbering ----------------------
    uniq, inv = np.unique(labels, return_inverse=True)
    nc = int(uniq.size)
    owner = cluster_owners(uniq, P)
    order = np.lexsort((uniq, owner))       # group clusters by owner PE
    rank = np.empty(nc, dtype=np.int64)
    rank[order] = np.arange(nc)
    mapping = rank[inv]
    coff = np.concatenate(
        [[0], np.cumsum(np.bincount(owner, minlength=P))]).astype(np.int64)

    # coarse vertex weights, accumulated into owner slices
    cvw = np.zeros(nc, dtype=np.int64)
    np.add.at(cvw, mapping, _global_vweights(shards))

    # ---- per-PE local pre-contraction (shared sequential kernel) -------
    kmode = dispatch.resolve_kernel_mode(kernel)
    t0 = time.perf_counter()
    pre_parts = []
    seg_counts = np.zeros((P, P), dtype=np.int32)
    for p in range(P):
        valid = shards.arc_src[p] < shards.n_loc
        src_g = shards.local_gid[p][shards.arc_src[p][valid]]
        tab_g = np.concatenate([shards.local_gid[p], shards.ghost_gid[p]])
        dst_g = tab_g[shards.arc_dst_idx[p][valid]]
        cs, cd, cw = dedup_arcs(mapping[src_g], mapping[dst_g],
                                shards.arc_w[p][valid].astype(np.int64),
                                kernel=kmode)
        # dedup_arcs sorts by coarse tail; owner ranges are contiguous in
        # coarse-id space, so destination segments are already contiguous
        dest = np.searchsorted(coff, cs, side="right") - 1
        seg_counts[p] = np.bincount(dest, minlength=P)
        pre_parts.append((cs, cd, cw))
    pre_s = time.perf_counter() - t0

    # ---- segmented all-to-all + owner-side merge (jit) -----------------
    S_e = _next_pow2(max(1, int(seg_counts.max())))
    slab = np.zeros((P, P, S_e, 3), dtype=np.int32)
    for p in range(P):
        cs, cd, cw = pre_parts[p]
        ends = np.cumsum(seg_counts[p])
        starts = ends - seg_counts[p]
        for q in range(P):
            s0, s1 = int(starts[q]), int(ends[q])
            slab[p, q, :s1 - s0, 0] = cs[s0:s1]
            slab[p, q, :s1 - s0, 1] = cd[s0:s1]
            slab[p, q, :s1 - s0, 2] = cw[s0:s1]
    t0 = time.perf_counter()
    est = seg_merge_vmem_bytes(P * S_e)
    fused = kmode == "fused" and est <= dispatch.VMEM_BUDGET_BYTES
    if kmode == "fused" and not fused:
        dispatch.report_fallback("seg_merge", est,
                                 detail="dist_contract")
    fn = _build_exchange_fn(mesh, P, S_e, use_grid, fused=fused,
                            interpret=dispatch.kernel_interpret())
    s_src, s_dst, wsum, first = (spans.fetch(x) for x in fn(
        spans.upload(slab), spans.upload(seg_counts)))
    exchange_s = time.perf_counter() - t0

    # ---- owner-side coarse shards + host view --------------------------
    arc_parts = []
    for p in range(P):
        take = (s_src[p] < int(I32_MAX)) & first[p]
        arc_parts.append((s_src[p][take].astype(np.int64),
                          s_dst[p][take].astype(np.int64),
                          wsum[p][take].astype(np.int64)))
    vw_parts = [cvw[coff[p]:coff[p + 1]] for p in range(P)]
    coarse_shards = assemble_shards(nc, coff, arc_parts, vw_parts)
    # arc parts are sorted by coarse tail within each PE and owner ranges
    # ascend with p, so the concatenation is already in CSR order
    graph = from_coo(nc,
                     np.concatenate([a[0] for a in arc_parts]),
                     np.concatenate([a[1] for a in arc_parts]),
                     eweights=np.concatenate([a[2] for a in arc_parts]),
                     vweights=cvw, symmetrize=False, dedup=False)
    stats = {
        "nc": nc,
        "payload_bytes": int(seg_counts.astype(np.int64).sum()) * 12,
        "slab_bytes_per_pe": int(P * S_e * 3 * 4),
        "precontract_s": round(pre_s, 6),
        "exchange_s": round(exchange_s, 6),
    }
    return DistContraction(shards=coarse_shards, graph=graph,
                           mapping=mapping, stats=stats)
