"""Distributed deep multilevel graph partitioning driver (paper Alg. 1).

Mirrors ``core/deep_mgp.py``: while the graph is large it coarsens with
*distributed* LP clustering over graph shards; once the graph fits one
PE's budget it delegates to the single-process deep-MGP path (the paper's
own base case: after log P contractions the coarse graph is gathered and
partitioned on fewer PEs). Uncoarsening projects through the contraction
maps and runs distributed refinement + balancing per level, reusing the
shards built during coarsening — each level is distributed exactly once.

Two ``PartitionerConfig`` knobs select the distributed memory model
(docs/DIST.md): ``contraction`` ("host" gathers each level and contracts
via ``core.contraction``; "sharded" contracts in place via the paper-§5
cluster→PE assignment + all-to-all edge exchange of
``dist_contraction``) and ``weights`` ("replicated" psum-synced tables
vs "owner"-sharded authoritative tables in ``dist_lp``). The defaults
("host"/"replicated") reproduce the original pipeline bit-for-bit.

The public surface is ``repro.api`` (backend names ``"dist"`` /
``"dist-grid"``), which calls ``dist_partition_impl`` and can reuse one
mesh across requests; the old ``dist_partition`` shim is gone.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import spans
from ..core import metrics
from ..core.balance import rebalance
from ..core.coarsening import enforce_cluster_weights
from ..core.contraction import contract
from ..core.deep_mgp import (PartitionerConfig, check_k,
                             partition as sp_partition, trace_cut,
                             trace_event, uncoarsen_seed)
from ..graphs.distribute import GraphShards, distribute_graph
from ..graphs.format import Graph
from .dist_balance import dist_enforce_cluster_weights, dist_rebalance
from .dist_contraction import dist_contract
from .dist_lp import dist_cluster, dist_lp_refine, dist_ulp_refine


def dist_refine_and_balance(g: Graph,
                            part: np.ndarray,
                            l_max_vec: np.ndarray,
                            P: int,
                            num_iterations: int = 2,
                            num_chunks: int = 8,
                            seed: int = 0,
                            use_grid: bool = True,
                            mesh=None,
                            shards: Optional[GraphShards] = None,
                            weights: str = "replicated",
                            balance: str = "host",
                            kernel: str = "auto",
                            refine: str = "lp",
                            balance_stats: Optional[Dict] = None
                            ) -> np.ndarray:
    """Distributed BalanceAndRefine: sharded refinement (block weights
    replicated or owner-sharded per ``weights``) followed by the exact
    global balancer so the result always satisfies the per-block
    budgets. ``shards`` lets the driver pass the level's existing
    distribution instead of re-sharding ``g``.

    ``refine`` picks the improvement pass: ``"lp"`` (default) is the
    size-constrained LP with races bounced, ``"unconstrained"`` the
    Jet-style penalty-weighted search of ``dist_ulp_refine`` whose
    overloads the trailing balancer repairs (the afterburner —
    docs/REFINEMENT.md). ``balance`` picks where that exact balancer
    runs: ``"host"`` gathers the level into
    ``core.balance.rebalance``'s single-chunk arc slab (one O(m) gather
    per call), ``"dist"`` runs ``dist_balance.dist_rebalance`` over the
    same shards the refinement used — no host gather, O(P·top_m) pooled
    candidates per round, bit-identical to ``"host"`` at P=1."""
    from ..core.refinement import check_refine_mode
    check_refine_mode(refine)
    part = np.asarray(part, dtype=np.int64)
    l_max_vec = np.asarray(l_max_vec, dtype=np.int64)
    if shards is None:
        with spans.span("dist.distribute", n=g.n, m=g.m, P=P):
            shards = distribute_graph(g, P)
    if refine == "unconstrained":
        part = dist_ulp_refine(shards, part, l_max_vec,
                               num_iterations=num_iterations,
                               num_chunks=num_chunks, seed=seed,
                               use_grid=use_grid, mesh=mesh,
                               weights=weights)
    else:
        part = dist_lp_refine(shards, part, l_max_vec,
                              num_iterations=num_iterations,
                              num_chunks=num_chunks, seed=seed,
                              use_grid=use_grid, mesh=mesh,
                              weights=weights)
    if balance == "dist":
        part = dist_rebalance(shards, part, l_max_vec, seed=seed + 1,
                              use_grid=use_grid, mesh=mesh,
                              weights=weights, kernel=kernel,
                              stats=balance_stats)
    else:
        with spans.span("dist.gather", step="balance", n=g.n, m=g.m):
            part = rebalance(g, part, l_max_vec, seed=seed + 1,
                             kernel=kernel, stats=balance_stats)
    return part


def dist_partition_impl(g: Graph,
                        k: int,
                        P: int,
                        cfg: Optional[PartitionerConfig] = None,
                        use_grid: bool = True,
                        mesh=None,
                        trace: Optional[List[Dict]] = None) -> np.ndarray:
    """Distributed deep multilevel k-way partition over P PEs.

    Returns (n,) int64 block ids satisfying the paper's relaxed balance
    constraint. Matches the single-process reference pipeline except that
    fine levels cluster, contract and refine under shard_map. ``mesh``
    lets a serving session reuse one 1D 'pe' mesh across requests;
    ``trace`` collects per-level size/cut/timing records.
    """
    cfg = (cfg or PartitionerConfig()).validate()
    check_k(k, "dist_partition")
    if P < 1:
        raise ValueError(f"dist_partition: P must be >= 1, got {P}")
    if k == 1 or g.n == 0:
        return np.zeros(g.n, dtype=np.int64)
    with spans.recording(trace):
        return _dist_partition(g, k, P, cfg, use_grid, mesh, trace)


def _dist_partition(g: Graph, k: int, P: int, cfg: PartitionerConfig,
                    use_grid: bool, mesh, trace: Optional[List[Dict]]
                    ) -> np.ndarray:
    total_c = g.total_vweight
    l_final = metrics.l_max(total_c, k, cfg.epsilon,
                            int(g.vweights.max()) if g.n else 1)
    C, K = cfg.contraction_limit, cfg.initial_k

    # ---- distributed deep coarsening -----------------------------------
    # hierarchy rows carry the level's shards so uncoarsening reuses them
    # instead of re-distributing the same graph
    hierarchy: List[Tuple[Graph, np.ndarray, GraphShards]] = []
    G = g
    shards: Optional[GraphShards] = None
    level = 0
    while G.n > C * min(k, K) and G.n >= 2 * P and level < cfg.max_levels:
        kprime = max(1, min(k, G.n // max(1, C)))
        W = max(1, int(cfg.epsilon * total_c / kprime))
        with spans.span("dist.coarsen_level", level=level, n=G.n, m=G.m,
                        P=P) as sp:
            t0 = time.perf_counter()
            if shards is None:  # sharded contraction hands us the next level
                with spans.span("dist.distribute", n=G.n, m=G.m, P=P):
                    shards = distribute_graph(G, P)
            labels = dist_cluster(shards, W,
                                  num_iterations=cfg.cluster_iterations,
                                  num_chunks=cfg.num_chunks,
                                  seed=cfg.seed + level, use_grid=use_grid,
                                  mesh=mesh, weights=cfg.weights,
                                  kernel=cfg.kernel)
            if cfg.balance == "dist":
                # coarsening-side balancing stays sharded: the exact
                # eject-to-singleton sweep runs owner-side instead of
                # round-tripping the labels through host numpy
                labels = dist_enforce_cluster_weights(
                    shards, labels, W, use_grid=use_grid, mesh=mesh)
            else:
                with spans.span("dist.gather", step="enforce", n=G.n):
                    labels = enforce_cluster_weights(
                        labels, np.asarray(G.vweights), W)
            if cfg.contraction == "sharded":
                res = dist_contract(shards, labels, use_grid=use_grid,
                                    mesh=mesh, kernel=cfg.kernel)
                Gc, mapping, next_shards = res.graph, res.mapping, res.shards
                cstats = res.stats
            else:
                with spans.span("dist.gather", step="contract", n=G.n,
                                m=G.m):
                    Gc, mapping = contract(G, labels, kernel=cfg.kernel)
                next_shards, cstats = None, None
            dt = time.perf_counter() - t0
            sp.set(coarse_n=Gc.n)
        if Gc.n >= G.n * cfg.min_shrink:
            # converged — coarsest distributed level reached; record the
            # discarded level so benchmark traces explain the early exit
            trace_event(trace, phase="dist-coarsen-converged", level=level,
                        n=G.n, m=G.m, coarse_n=Gc.n, W=W, P=P,
                        time_s=round(dt, 6))
            break
        rec = dict(phase="dist-coarsen", level=level, n=G.n, m=G.m,
                   coarse_n=Gc.n, W=W, P=P, contraction=cfg.contraction,
                   weights=cfg.weights, time_s=round(dt, 6))
        if cstats is not None:
            rec.update(exchange_s=cstats["exchange_s"],
                       payload_bytes=cstats["payload_bytes"])
        trace_event(trace, **rec)
        hierarchy.append((G, mapping, shards))
        G, shards = Gc, next_shards
        level += 1

    # ---- base case: single-process deep MGP on the coarse graph --------
    part = sp_partition(G, k, cfg, trace=trace)

    # ---- uncoarsening: project + distributed refine/balance ------------
    lvec = np.full(k, l_final, dtype=np.int64)
    for lvl, (Gf, mapping, fshards) in enumerate(reversed(hierarchy)):
        lvl_seed = uncoarsen_seed(cfg.seed, lvl, stream=1)
        bal_stats: Dict = {}
        with spans.span("dist.uncoarsen_level", level=lvl, n=Gf.n, m=Gf.m,
                        P=P):
            t0 = time.perf_counter()
            part = part[mapping]
            part = dist_refine_and_balance(
                Gf, part, lvec, P, num_iterations=cfg.refine_iterations,
                num_chunks=cfg.num_chunks,
                seed=lvl_seed, use_grid=use_grid, mesh=mesh,
                shards=fshards, weights=cfg.weights, balance=cfg.balance,
                kernel=cfg.kernel, refine=cfg.refine,
                balance_stats=bal_stats)
            dt = time.perf_counter() - t0
        if trace is not None:
            rec = dict(phase="dist-uncoarsen", level=lvl, n=Gf.n,
                       m=Gf.m, blocks=k, P=P, seed=lvl_seed,
                       balance=cfg.balance,
                       balance_rounds=bal_stats.get("rounds"),
                       cut=trace_cut(Gf, part), time_s=round(dt, 6))
            if cfg.refine != "lp":
                # unconstrained tier: the balancer doubles as the
                # feasibility afterburner, so balance_rounds IS the
                # repair-round count (docs/REFINEMENT.md)
                from ..core.unconstrained import penalty_schedule
                rec.update(refine=cfg.refine,
                           penalty=penalty_schedule(cfg.refine_iterations),
                           repair_rounds=bal_stats.get("rounds"))
            trace_event(trace, **rec)
    return part


# The deprecated ``dist_partition`` shim was removed after its release
# of grace: route through ``repro.api`` (backends "dist" / "dist-grid"),
# which calls ``dist_partition_impl`` — see docs/API.md.
