"""Multi-device selftest — run in a subprocess with a forced device count.

Usage:  python -m repro.launch.selftest --devices 8 --test all

A CPU tool: the devices are host devices forced into existence, never
chips (``chip_smoke.py --four-chips`` is the check on a TPU host).

Forces the device count through ``repro.api.runtime`` *before* any jax
init (the count locks at first backend creation; the helper raises
instead of silently misconfiguring), then validates the distributed
implementation against the single-process reference: collectives
round-trip, distributed clustering validity (replicated and
owner-sharded weight tables), sharded contraction invariants
(``--test contract``), distributed partition feasibility + quality
under both memory models, both refinement tiers (``--test refine``:
size-constrained LP plus the Jet-style unconstrained pass, which must
end feasible after afterburner repair and be bit-identical across
weight-table layouts), the distributed balancer (``--test balance``:
P=1 bit-identity with the host balancer, adversarial-start feasibility,
sharded cluster-weight enforcement, and the no-host-gather trace
assertion for ``balance="dist"``), grid vs direct all-to-all
equivalence, the ``repro.api`` facade (driver equality, batched
sessions), and the ``repro.serve`` multi-mesh tier (``--test serve``:
a 2-mesh server drains concurrent mixed-size requests bit-identically
to solo runs, a killed worker's request completes via retry on the
other mesh, and deadline expiry surfaces a structured error), and the
shape-bucketed batched dispatch (``--test batch``: a duplicate-heavy
hot mix is served in batches bit-identically to solo runs with
coalescing observed in the metrics, and the stacked level-0 clustering
path — forced on even on CPU hosts — reproduces solo results bit for
bit), and the fused Pallas hot-loop kernels (``--test kernels``, *not* part
of ``all`` — off-TPU they run interpret mode, so the step carries its
own reduced instance: the ``kernel="fused"`` pipeline must reproduce
``"composed"`` labels and cut bit for bit on the host path and under
both distributed memory models), and the cross-process fabric
(``--test fabric``, *not* part of
``all`` because it spawns real worker subprocesses: a front door plus
two worker processes serve bit-identically to solo runs, a SIGKILLed
worker's admitted requests fail over to the survivor, and a SIGTERM
drain finishes in-flight work and answers queued tickets with
structured errors — nothing hangs). Prints one JSON line per test;
exit code 0 iff all pass.
"""
import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--test", default="all",
                    choices=["all", "collectives", "halo", "cluster",
                             "contract", "partition", "refine", "balance",
                             "smoke", "api", "serve", "batch", "fabric",
                             "kernels", "analysis"])
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--family", default="rgg2d")
    args = ap.parse_args()

    from repro.api import runtime
    runtime.force_host_devices(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as PS

    from repro.core import PartitionerConfig, metrics
    from repro.core.deep_mgp import partition
    from repro.dist.collectives import (direct_all_to_all, grid_all_to_all,
                                        halo_exchange)
    from repro.dist.compat import shard_map
    from repro.dist.dist_lp import dist_cluster, make_mesh_1d
    from repro.dist.dist_partitioner import (dist_partition_impl,
                                             dist_refine_and_balance)
    from repro.graphs import generators
    from repro.graphs.distribute import distribute_graph

    P = args.devices
    assert len(jax.devices()) >= P, jax.devices()
    ok = True

    def report(name, passed, **kw):
        nonlocal ok
        ok &= bool(passed)
        print(json.dumps({"test": name, "pass": bool(passed), **kw}),
              flush=True)

    cfg = PartitionerConfig(contraction_limit=128, ip_repetitions=2,
                            num_chunks=4)
    g = generators.make(args.family, args.n, 8.0, seed=5)

    if args.test in ("all", "collectives", "smoke"):
        mesh = make_mesh_1d(P)
        rng = np.random.default_rng(0)
        slab = rng.integers(0, 1000, size=(P, P, 3)).astype(np.int32)

        def run(fn):
            f = shard_map(lambda s: fn(s[0])[None], mesh=mesh,
                          in_specs=PS("pe"), out_specs=PS("pe"),
                          check=True)
            return np.asarray(jax.jit(f)(jnp.asarray(slab)))

        out_direct = run(lambda s: direct_all_to_all(s, "pe"))
        out_grid = run(lambda s: grid_all_to_all(s, "pe", P))
        # ground truth: out[p, q] == in[q, p]
        want = np.swapaxes(slab, 0, 1)
        report("collectives.direct", np.array_equal(out_direct, want))
        report("collectives.grid", np.array_equal(out_grid, want))

    if args.test in ("all", "halo", "smoke"):
        mesh = make_mesh_1d(P)
        shards = distribute_graph(g, P)
        n, n_loc, n_ghost = g.n, shards.n_loc, shards.n_ghost
        # per-vertex payload: an injective hash of the global id, so a
        # wrong routing cannot collide into a false pass
        f_gid = lambda x: ((x.astype(np.int64) * 40503 + 7) % 65521) \
            .astype(np.int32)
        vals = np.where(shards.local_gid < n, f_gid(shards.local_gid), 0)

        def run_halo(use_grid):
            fn = shard_map(
                lambda v, si, rs: halo_exchange(
                    v[0], si[0], rs[0], n_ghost, "pe", P,
                    use_grid=use_grid)[None],
                mesh=mesh, in_specs=(PS("pe"),) * 3, out_specs=PS("pe"),
                check=True)
            return np.asarray(jax.jit(fn)(
                jnp.asarray(vals), jnp.asarray(shards.send_idx),
                jnp.asarray(shards.recv_slot)))

        got_d = run_halo(False)
        got_g = run_halo(True)
        valid = shards.ghost_gid < n
        want_ghost = f_gid(np.where(valid, shards.ghost_gid, 0))
        ok_d = np.array_equal(got_d[valid], want_ghost[valid])
        ok_g = np.array_equal(got_g[valid], want_ghost[valid])
        report("halo.direct", ok_d, ghosts=int(valid.sum()),
               payload_bytes=shards.comm_bytes_per_halo())
        report("halo.grid_vs_direct", ok_g and
               np.array_equal(got_d, got_g))

    if args.test in ("all", "cluster"):
        from repro.core.coarsening import enforce_cluster_weights
        shards = distribute_graph(g, P)
        W = max(1, int(0.03 * g.total_vweight / args.k))
        labels = dist_cluster(shards, W, num_iterations=3, num_chunks=4,
                              seed=1, use_grid=True)
        raw = labels.copy()
        # driver behaviour: distributed revert is approximate (paper §4 —
        # races bounce weight back); exact enforcement happens before
        # contraction
        labels = enforce_cluster_weights(labels, np.asarray(g.vweights), W)
        cw = np.zeros(g.n + 1, dtype=np.int64)
        np.add.at(cw, labels, g.vweights)
        members = np.bincount(labels, minlength=g.n + 1)
        shrunk = np.unique(labels).size < 0.7 * g.n
        multi_ok = np.all(cw[members > 1] <= W)
        report("cluster.dist", shrunk and multi_ok,
               clusters=int(np.unique(labels).size), n=g.n, W=W,
               max_multi_cw=int(cw[members > 1].max() if
                                (members > 1).any() else 0))
        labels2 = dist_cluster(shards, W, num_iterations=3, num_chunks=4,
                               seed=1, use_grid=False)
        report("cluster.grid_vs_direct",
               np.array_equal(raw, labels2))
        # owner-sharded weight tables apply the same integer arithmetic in
        # the same order as the replicated psum path -> identical labels
        labels3 = dist_cluster(shards, W, num_iterations=3, num_chunks=4,
                               seed=1, use_grid=True, weights="owner")
        report("cluster.owner_vs_replicated",
               np.array_equal(raw, labels3))

    if args.test in ("all", "contract"):
        from repro.core.coarsening import enforce_cluster_weights
        from repro.core.contraction import contract
        from repro.dist.dist_contraction import dist_contract
        shards = distribute_graph(g, P)
        W = max(1, int(0.03 * g.total_vweight / args.k))
        labels = enforce_cluster_weights(
            dist_cluster(shards, W, num_iterations=3, num_chunks=4,
                         seed=1, use_grid=True),
            np.asarray(g.vweights), W)
        res = dist_contract(shards, labels, use_grid=True)
        gc_h, map_h = contract(g, labels)
        gc_d, map_d = res.graph, res.mapping
        # invariants: weight conservation, no self loops, symmetry
        src = gc_d.arc_tails()
        inv_ok = (gc_d.total_vweight == g.total_vweight
                  and bool(np.all(src != gc_d.adjncy)))
        try:
            gc_d.validate()
        except AssertionError:
            inv_ok = False
        # host and sharded contraction agree up to a coarse-id bijection
        pairs = np.unique(np.stack([map_h, map_d], 1), axis=0)
        iso_ok = (gc_d.n == gc_h.n and gc_d.m == gc_h.m
                  and pairs.shape[0] == gc_h.n
                  and np.unique(pairs[:, 0]).size == gc_h.n
                  and np.unique(pairs[:, 1]).size == gc_h.n)
        # cut of any coarse partition == cut of its fine projection
        rng = np.random.default_rng(4)
        pc = rng.integers(0, args.k, size=gc_d.n)
        cut_ok = metrics.edge_cut(gc_d, pc) == \
            metrics.edge_cut(g, pc[map_d])
        report("contract.sharded", inv_ok and iso_ok and cut_ok,
               coarse_m=gc_d.m, **res.stats)
        # grid and direct routing ship identical coarse graphs
        res2 = dist_contract(shards, labels, use_grid=False)
        report("contract.grid_vs_direct",
               np.array_equal(res2.mapping, res.mapping) and
               np.array_equal(res2.graph.indptr, res.graph.indptr) and
               np.array_equal(res2.graph.adjncy, res.graph.adjncy) and
               np.array_equal(res2.graph.eweights, res.graph.eweights))

    if args.test in ("all", "refine"):
        rng = np.random.default_rng(2)
        part0 = rng.integers(0, args.k, size=g.n)
        lmax = np.full(args.k, metrics.l_max(
            g.total_vweight, args.k, 0.03, int(g.vweights.max())),
            dtype=np.int64)
        cut0 = metrics.edge_cut(g, part0)
        part1 = dist_refine_and_balance(g, part0, lmax, P, num_iterations=3,
                                        num_chunks=4, seed=3)
        cut1 = metrics.edge_cut(g, part1)
        feas = metrics.is_feasible(g, part1, args.k, 0.03)
        report("refine.dist", feas and cut1 < cut0, cut_before=cut0,
               cut_after=cut1, feasible=feas)

        # unconstrained tier: penalty-weighted moves + afterburner repair
        # must end feasible and improve the same random start
        part_u = dist_refine_and_balance(g, part0, lmax, P,
                                         num_iterations=3, num_chunks=4,
                                         seed=3, refine="unconstrained")
        cut_u = metrics.edge_cut(g, part_u)
        feas_u = metrics.is_feasible(g, part_u, args.k, 0.03)
        report("refine.unconstrained", feas_u and cut_u < cut0,
               cut_before=cut0, cut_after=cut_u, cut_lp=cut1,
               feasible=feas_u)

        # owner-sharded and replicated weight tables are bit-identical
        # for the unconstrained pass (same dense table at every chunk top)
        from repro.dist.dist_lp import dist_ulp_refine
        shards_r = distribute_graph(g, P)
        u_rep = dist_ulp_refine(shards_r, part0, lmax, num_iterations=3,
                                num_chunks=4, seed=3,
                                weights="replicated")
        u_own = dist_ulp_refine(shards_r, part0, lmax, num_iterations=3,
                                num_chunks=4, seed=3, weights="owner")
        report("refine.unconstrained.owner_vs_replicated",
               np.array_equal(u_rep, u_own))

    if args.test in ("all", "balance"):
        import dataclasses
        from repro.core.balance import rebalance
        from repro.core.coarsening import (ejection_candidates,
                                           enforce_cluster_weights)
        from repro.dist import dist_partitioner as dp
        from repro.dist.dist_balance import (dist_enforce_cluster_weights,
                                             dist_rebalance)

        lmax = np.full(args.k, metrics.l_max(
            g.total_vweight, args.k, 0.03, int(g.vweights.max())),
            dtype=np.int64)
        part0 = np.zeros(g.n, dtype=np.int64)   # adversarial: one block

        # distributed balancer == host balancer, bit for bit, at P=1
        sh1 = distribute_graph(g, 1)
        want = rebalance(g, part0.copy(), lmax, seed=11)
        got = dist_rebalance(sh1, part0.copy(), lmax, seed=11,
                             use_grid=False)
        report("balance.p1_bit_identical", np.array_equal(want, got))

        # P devices: feasibility from the adversarial start, identical
        # labels across routing and weight-table layouts
        shP = distribute_graph(g, P)
        bstats = {}
        fixed = dist_rebalance(shP, part0.copy(), lmax, seed=11,
                               use_grid=True, stats=bstats)
        bw = np.zeros(args.k, dtype=np.int64)
        np.add.at(bw, fixed, g.vweights)
        report("balance.dist_adversarial", bool(np.all(bw <= lmax)),
               rounds=bstats["rounds"], pool_bytes=bstats["pool_bytes"])
        fixed_d = dist_rebalance(shP, part0.copy(), lmax, seed=11,
                                 use_grid=False)
        fixed_o = dist_rebalance(shP, part0.copy(), lmax, seed=11,
                                 use_grid=True, weights="owner")
        report("balance.grid_owner_equal",
               np.array_equal(fixed, fixed_d) and
               np.array_equal(fixed, fixed_o))

        # heterogeneous per-block budgets stay exactly enforced
        lvec = lmax * (1 + (np.arange(args.k) % 2))
        fixed_h = dist_rebalance(shP, part0.copy(), lvec, seed=13,
                                 use_grid=True)
        bwh = np.zeros(args.k, dtype=np.int64)
        np.add.at(bwh, fixed_h, g.vweights)
        report("balance.heterogeneous_lmax", bool(np.all(bwh <= lvec)))

        # sharded cluster-weight enforcement ejects the same vertex set
        # as the host sweep and yields the same clustering up to a
        # relabeling of the fresh singletons
        rng = np.random.default_rng(7)
        labels = rng.integers(0, max(2, args.k), g.n).astype(np.int64)
        W = max(1, int(g.total_vweight / (4 * args.k)))
        lab_d = dist_enforce_cluster_weights(shP, labels, W, use_grid=True)
        ej = ejection_candidates(labels, np.asarray(g.vweights), W)
        same_set = np.array_equal(np.sort(np.flatnonzero(lab_d != labels)),
                                  np.sort(ej))

        def canon(lab):
            _, inv = np.unique(lab, return_inverse=True)
            first = np.full(int(inv.max()) + 1, g.n, dtype=np.int64)
            np.minimum.at(first, inv, np.arange(g.n))
            return first[inv]

        lab_h = enforce_cluster_weights(labels.copy(),
                                        np.asarray(g.vweights), W)
        report("balance.enforce_sharded", same_set and
               np.array_equal(canon(lab_d), canon(lab_h)),
               ejected=int(ej.size))

        # full uncoarsening path with balance="dist": *no* host-side
        # rebalance gather (trace assertion via an instrumented counter),
        # feasible, and within the 1.5x quality bound — both weight-table
        # layouts
        ref_cut = metrics.edge_cut(g, partition(g, args.k, cfg))
        calls = {"n": 0}
        orig_rebalance = dp.rebalance

        def counting_rebalance(*a, **kw):
            calls["n"] += 1
            return orig_rebalance(*a, **kw)

        dp.rebalance = counting_rebalance
        try:
            for wmode in ("replicated", "owner"):
                calls["n"] = 0
                cfg_b = dataclasses.replace(
                    cfg, balance="dist", weights=wmode,
                    contraction="sharded" if wmode == "owner" else "host")
                tr = []
                part_b = dp.dist_partition_impl(g, args.k, P, cfg=cfg_b,
                                                trace=tr)
                s_b = metrics.summarize(g, part_b, args.k, 0.03)
                seeds = [t["seed"] for t in tr
                         if t.get("phase") == "dist-uncoarsen"]
                levels = len(seeds)
                report(f"balance.no_host_gather_{wmode}",
                       s_b["feasible"] and calls["n"] == 0 and
                       levels >= 1 and len(set(seeds)) == levels and
                       s_b["cut"] <= max(1.5 * ref_cut, ref_cut + 50),
                       cut=s_b["cut"], ref_cut=ref_cut, levels=levels,
                       host_rebalance_calls=calls["n"])
            # instrumentation sanity: the host mode *does* hit the counter
            calls["n"] = 0
            dp.dist_partition_impl(g, args.k, P, cfg=cfg)
            report("balance.host_gather_counter_sane", calls["n"] >= 1,
                   host_rebalance_calls=calls["n"])
        finally:
            dp.rebalance = orig_rebalance

    if args.test in ("all", "partition"):
        import dataclasses
        part = dist_partition_impl(g, args.k, P, cfg=cfg)
        s = metrics.summarize(g, part, args.k, 0.03)
        ref = partition(g, args.k, cfg)
        cut_ref = metrics.edge_cut(g, ref)
        # distributed quality within 1.5x of the single-process reference
        report("partition.dist", s["feasible"] and
               s["cut"] <= max(1.5 * cut_ref, cut_ref + 50),
               dist=s, ref_cut=cut_ref)
        # fully sharded memory model: in-place contraction + owner-sharded
        # weight tables must stay feasible within the same quality bound
        cfg_sh = dataclasses.replace(cfg, contraction="sharded",
                                     weights="owner")
        part_sh = dist_partition_impl(g, args.k, P, cfg=cfg_sh)
        s_sh = metrics.summarize(g, part_sh, args.k, 0.03)
        report("partition.dist_sharded_owner", s_sh["feasible"] and
               s_sh["cut"] <= max(1.5 * cut_ref, cut_ref + 50),
               dist=s_sh, ref_cut=cut_ref)

    if args.test in ("all", "api"):
        from repro.api import (PartitionRequest, Partitioner,
                               PartitionSession)
        from repro.core.deep_mgp import level_records
        engine = Partitioner()

        # facade(dist-grid) must reproduce the direct driver bit-exactly
        req = PartitionRequest(graph=g, k=args.k, config=cfg,
                               backend="dist-grid", devices=P)
        res = engine.run(req)
        want = dist_partition_impl(g, args.k, P, cfg=cfg, use_grid=True)
        report("api.dist_matches_driver",
               res.feasible and np.array_equal(res.assignment, want),
               cut=res.cut, levels=len(level_records(res.trace)))

        # feasibility flag must agree with the metrics module
        report("api.feasible_flag",
               res.feasible == metrics.is_feasible(g, res.assignment,
                                                   args.k, 0.03))

        # auto policy routes this (large-enough) graph to a dist backend
        auto = engine.run(PartitionRequest(graph=g, k=args.k, config=cfg,
                                           backend="auto", devices=P))
        report("api.auto_backend", auto.backend in ("dist", "dist-grid"),
               backend=auto.backend)

        # batched session == per-request results, mesh reused across both
        reqs = [PartitionRequest(graph=g, k=kk, config=cfg, backend="dist",
                                 devices=P)
                for kk in (args.k, max(1, args.k // 2))]
        with PartitionSession(devices=P, max_workers=2) as sess:
            batch = sess.run_batch(reqs)
            served = sess.stats()["served"]
        solo = [engine.run(r) for r in reqs]
        same = all(np.array_equal(b.assignment, s.assignment)
                   for b, s in zip(batch, solo))
        report("api.session_batch", same and served == len(reqs),
               served=served,
               cuts=[b.cut for b in batch])

    if args.test in ("all", "serve"):
        import time
        from repro.api import (GraphSpec, PartitionRequest, Partitioner)
        from repro.serve import PartitionServer

        dpm = max(1, P // 2)
        engine = Partitioner()
        # >= 8 concurrent mixed-size requests: three sizes, two k
        # values, and (on multi-device hosts) distributed requests that
        # exercise the second mesh's device slice
        mixed = []
        for i in range(8):
            nn = max(600, args.n // 4) * (1 + i % 3)
            kk = max(2, args.k // 2) * (1 + i % 2)
            dev = dpm if (i % 4 == 3 and dpm > 1) else 1
            mixed.append(PartitionRequest(
                graph=GraphSpec(args.family, nn, 8.0, seed=23 + i % 3),
                k=kk, config=cfg, devices=dev))
        solo = [engine.run(r) for r in mixed]

        # 2-mesh server over disjoint device slices drains the batch
        # bit-identically to solo runs, using both meshes
        with PartitionServer(meshes=2, devices_per_mesh=dpm) as srv:
            results = srv.serve(mixed)
            st = srv.stats()
        same = all(r.ok and np.array_equal(r.result.assignment,
                                           s.assignment)
                   for r, s in zip(results, solo))
        report("serve.bit_identical_mixed",
               same and st["completed"] == len(mixed),
               served=st["per_worker_served"],
               queue_depth_max=st["queue_depth_max"])
        report("serve.both_meshes_used",
               all(c > 0 for c in st["per_worker_served"]),
               served=st["per_worker_served"])

        # a killed worker's requests complete via retry on the other
        # mesh — hold worker 1 at its gate so it provably owns work
        with PartitionServer(meshes=2, devices_per_mesh=dpm) as srv:
            srv.workers[1].hold()
            futs = [srv.submit(r) for r in mixed[:4]]
            t_end = time.monotonic() + 30
            while time.monotonic() < t_end and \
                    srv.workers[1].inflight == 0:
                time.sleep(0.01)
            had_work = srv.workers[1].inflight > 0
            srv.kill_worker(1)
            rs = [f.result(timeout=600) for f in futs]
            st = srv.stats()
        same_k = all(r.ok and np.array_equal(r.result.assignment,
                                             s.assignment)
                     for r, s in zip(rs, solo[:4]))
        report("serve.killed_worker_retry",
               had_work and same_k and st["retried"] >= 1 and
               st["per_worker_served"][1] == 0,
               retried=st["retried"], served=st["per_worker_served"])

        # deadline expiry surfaces a structured error, not a hang
        with PartitionServer(meshes=2, devices_per_mesh=1) as srv:
            for w in srv.workers:
                w.hold()
            fut = srv.submit(mixed[0], deadline_s=0.05)
            time.sleep(0.2)
            for w in srv.workers:
                w.release()
            r = fut.result(timeout=60)
            st = srv.stats()
        report("serve.deadline_error",
               (not r.ok) and r.error == "deadline_exceeded" and
               st["expired"] == 1, error=r.error)

    if args.test in ("all", "batch"):
        import time
        from repro.api import (GraphSpec, PartitionRequest, Partitioner,
                               PartitionSession)
        from repro.serve import PartitionServer, run_coalesced

        engine = Partitioner()
        nn = max(400, args.n // 4)
        distinct = [PartitionRequest(
            graph=GraphSpec(args.family, nn, 8.0, seed=31 + i),
            k=max(2, args.k // 2), config=cfg, backend="single")
            for i in range(4)]
        solo = [engine.run(r) for r in distinct]

        # a duplicate-heavy hot mix piles up behind a held worker, then
        # drains as batches: bit-identical results, coalescing observed
        mix = [distinct[i % 4] for i in range(12)]
        with PartitionServer(meshes=1, batch_max=8,
                             batch_window_ms=50.0) as srv:
            srv.workers[0].hold()
            futs = [srv.submit(r) for r in mix]
            t_end = time.monotonic() + 30
            while time.monotonic() < t_end and \
                    srv.workers[0].inflight == 0:
                time.sleep(0.01)
            srv.workers[0].release()
            rs = [f.result(timeout=600) for f in futs]
            st = srv.stats()
        same = all(r.ok and np.array_equal(r.result.assignment,
                                           solo[i % 4].assignment)
                   for i, r in enumerate(rs))
        report("batch.coalesced_bit_identical",
               same and st["completed"] == len(mix) and
               st["batches"] >= 1 and st["coalesced"] >= 1,
               batches=st["batches"], coalesced=st["coalesced"],
               batch_size_max=st["batch_size_max"])

        # the stacked level-0 kernel path, forced on (the CPU auto-gate
        # would skip it), reproduces solo results bit for bit
        with PartitionSession(devices=1, stack="on") as sess:
            out = run_coalesced(sess, distinct, stack="on")
        report("batch.stacked_bit_identical",
               all(np.array_equal(o.assignment, s.assignment) and
                   o.cut == s.cut for o, s in zip(out, solo)),
               cuts=[o.cut for o in out])

    if args.test == "kernels":
        # fused Pallas hot loops vs the composed XLA pipeline: one knob
        # (PartitionerConfig.kernel), every kernel (lp_move, seg_merge,
        # bal_round), labels AND cut bit-identical — host path and both
        # distributed memory models. Not part of "all": off-TPU the
        # fused path runs Pallas interpret mode, so it gets its own CI
        # step with a reduced instance (docs/KERNELS.md).
        import dataclasses
        nn = max(400, args.n // 4)
        gk = generators.make(args.family, nn, 8.0, seed=13)
        kk = max(2, args.k // 2)
        cfg_k = PartitionerConfig(contraction_limit=80, ip_repetitions=1,
                                  num_chunks=4, seed=3)
        parts = {}
        for mode in ("composed", "fused"):
            parts[mode] = partition(
                gk, kk, dataclasses.replace(cfg_k, kernel=mode))
        cut_f = metrics.edge_cut(gk, parts["fused"])
        report("kernels.host_bit_identical",
               np.array_equal(parts["fused"], parts["composed"]) and
               cut_f == metrics.edge_cut(gk, parts["composed"]),
               cut=cut_f, n=gk.n)
        for name, contraction, weights, balance in (
                ("host_replicated", "host", "replicated", "host"),
                ("sharded_owner", "sharded", "owner", "dist")):
            got = {}
            for mode in ("composed", "fused"):
                cfg_d = dataclasses.replace(
                    cfg_k, contraction=contraction, weights=weights,
                    balance=balance, kernel=mode)
                got[mode] = dist_partition_impl(gk, kk, P, cfg=cfg_d)
            feas = metrics.is_feasible(gk, got["fused"], kk, 0.03)
            report(f"kernels.dist_bit_identical_{name}",
                   np.array_equal(got["fused"], got["composed"]) and feas,
                   cut=metrics.edge_cut(gk, got["fused"]), P=P,
                   feasible=feas)

    if args.test == "analysis":
        # not part of "all": each direction re-imports jax in a fresh
        # subprocess (the verifier forces its own host device count).
        # The static verifier must pass on the repo as committed AND
        # fail on every seeded-violation fixture — both directions, or
        # the CI gate is vacuous (docs/ANALYSIS.md).
        import os
        import subprocess

        import repro

        src_dir = os.path.dirname(os.path.dirname(repro.__file__))
        env = dict(os.environ)
        env["PYTHONPATH"] = src_dir + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.pop("XLA_FLAGS", None)  # verifier forces its own devices

        def run_analysis(*extra):
            return subprocess.run(
                [sys.executable, "-m", "repro.analysis", *extra],
                capture_output=True, text=True, env=env)

        proc = run_analysis()
        report("analysis.repo_clean", proc.returncode == 0,
               tail=proc.stdout.strip().splitlines()[-1:])
        for fx in ("collective", "overflow", "lint", "vmem"):
            proc = run_analysis("--fixture", fx)
            report(f"analysis.fixture_{fx}_fires",
                   proc.returncode != 0,
                   tail=proc.stdout.strip().splitlines()[-1:])

    if args.test == "fabric":
        # not part of "all": spawns real worker subprocesses (each
        # imports jax), so it runs as its own CI step
        import os
        import signal as _signal
        import subprocess
        import time

        import repro
        from repro.api import GraphSpec, PartitionRequest, Partitioner
        from repro.fabric import FabricClient, status_of

        src_dir = os.path.dirname(os.path.dirname(repro.__file__))
        env = dict(os.environ)
        env["PYTHONPATH"] = src_dir + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.pop("XLA_FLAGS", None)  # workers pick their own device count

        def spawn(role, *extra):
            proc = subprocess.Popen(
                [sys.executable, "-m", "repro.launch.fabric", role,
                 *extra],
                stdout=subprocess.PIPE, env=env, text=True)
            ready = json.loads(proc.stdout.readline())
            return proc, ready

        fd_proc, fd_ready = spawn("frontdoor", "--lease-ttl-s", "3.0")
        host, port = fd_ready["host"], fd_ready["port"]
        w_procs = {}
        for i in range(2):
            proc, _ = spawn("worker", "--frontdoor", f"{host}:{port}",
                            "--server-id", f"selftest-w{i}",
                            "--heartbeat-s", "0.3")
            w_procs[f"selftest-w{i}"] = proc
        t_end = time.monotonic() + 60
        while time.monotonic() < t_end and \
                len(status_of(host, port)["servers"]) < 2:
            time.sleep(0.1)
        regs = [s["server_id"] for s in status_of(host, port)["servers"]]
        report("fabric.registered", sorted(regs) ==
               ["selftest-w0", "selftest-w1"], servers=regs)

        engine = Partitioner()
        nn = max(600, args.n // 4)
        mixed = [PartitionRequest(
            graph=GraphSpec(args.family, nn * (1 + i % 2), 8.0,
                            seed=41 + i % 3),
            k=max(2, args.k // 2) * (1 + i % 2), config=cfg)
            for i in range(6)]
        solo = [engine.run(r) for r in mixed]
        try:
            with FabricClient(host, port) as client:
                rs = client.serve(mixed)
                same = all(r.ok and np.array_equal(r.assignment,
                                                   s.assignment)
                           for r, s in zip(rs, solo))
                report("fabric.bit_identical_2proc",
                       same and {r.server for r in rs} ==
                       set(w_procs), servers=sorted(
                           {str(r.server) for r in rs}))

                # SIGKILL one worker while it provably owns a request:
                # every admitted ticket must still resolve ok via
                # failover to the survivor — none may hang
                slow = [PartitionRequest(
                    graph=GraphSpec(args.family, max(2000, args.n // 2),
                                    8.0, seed=51 + i % 2),
                    k=args.k, config=cfg) for i in range(6)]
                slow_solo = [engine.run(r) for r in slow]
                futs = [client.submit(r) for r in slow]
                victim = None
                t_end = time.monotonic() + 60
                while victim is None and time.monotonic() < t_end:
                    for s in status_of(host, port)["servers"]:
                        if s.get("inflight", 0) > 0:
                            victim = s["server_id"]
                            break
                    time.sleep(0.02)
                report("fabric.victim_had_work", victim is not None,
                       victim=victim)
                w_procs[victim].send_signal(_signal.SIGKILL)
                rs = [f.result(timeout=600) for f in futs]
                survivor = next(s for s in w_procs if s != victim)
                same = all(r.ok and np.array_equal(r.assignment,
                                                   s.assignment)
                           for r, s in zip(rs, slow_solo))
                retried = sum(1 for r in rs if r.attempts > 1)
                report("fabric.sigkill_failover",
                       same and retried >= 1 and
                       all(r.server == survivor for r in rs),
                       retried=retried,
                       attempts=[r.attempts for r in rs])

                # SIGTERM drain of the survivor: the in-flight request
                # finishes ok, queued ones resolve with a structured
                # error (deadline at the latest) — nothing hangs
                # let the survivor heartbeat an idle window first:
                # worker_inflight below must come from *our* submissions,
                # not a stale renewal from the failover phase
                time.sleep(0.8)
                futs = [client.submit(r, deadline_s=20.0)
                        for r in slow[:4]]
                # wait for the attempt to be running on the worker's
                # own mesh (heartbeated back), not merely dispatched —
                # a merely-queued ticket legitimately drains to a
                # server_closed error instead of finishing
                t_end = time.monotonic() + 60
                while time.monotonic() < t_end and not any(
                        s.get("worker_inflight", 0) > 0
                        for s in status_of(host, port)["servers"]):
                    time.sleep(0.02)
                w_procs[survivor].send_signal(_signal.SIGTERM)
                rs = [f.result(timeout=600) for f in futs]
                w_procs[survivor].wait(timeout=120)
                n_ok = sum(1 for r in rs if r.ok)
                structured = all(
                    r.ok or r.error in ("server_closed", "worker_failed",
                                        "no_worker", "deadline_exceeded")
                    for r in rs)
                report("fabric.sigterm_drain",
                       n_ok >= 1 and structured,
                       ok=n_ok, errors=[r.error for r in rs if not r.ok])
        finally:
            for proc in w_procs.values():
                if proc.poll() is None:
                    proc.kill()
            fd_proc.send_signal(_signal.SIGTERM)
            try:
                fd_proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                fd_proc.kill()

    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
