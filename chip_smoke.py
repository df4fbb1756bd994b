"""Smoke run of the partitioner on a TPU, through its public entry points.

  python chip_smoke.py                # one chip: kernels, partition, serve
  python chip_smoke.py --four-chips   # four chips: the dist backend only

One process, no child processes. Each phase prints one JSON line; the
last line is ``{"ok": true, "device": {...}}``. The script exits non-zero
and prints no such line when JAX finds no TPU, when the repository's
``src/`` is not next to this file, when a phase raises, or when a check
fails.

Phases on one chip:

* ``kernels``: the fused Pallas kernels compiled by Mosaic. Two small
  rgg2d graphs small enough that every lp_move, bal_round and seg_merge
  call passes the VMEM gate; fused and composed runs must give the same
  assignment, the fused run no ``kernel-fallback`` record, and each
  kernel must run at least once.
* ``partition``: ``Partitioner().run`` (single backend, ``kernel="auto"``)
  on rgg2d with n = 2^20, avg degree 8, k = 16, eps = 0.03: feasible,
  with a cold and a warm time and the device's peak memory.
* ``serve``: eight requests through ``PartitionServer(meshes=1)`` with
  repeats, so batching, coalescing and the stacked level-0 program run;
  every answer must equal a solo ``Partitioner().run``.

``--four-chips`` runs the dist backend on n = 2^16 in both memory models
of docs/DIST.md, compares each with the single backend on the same
graph, checks owner against replicated weight tables for one level of
``dist_cluster``, and prints every device's memory.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
N_ONE_CHIP = 2**20
# 2^14 vertices per chip: a cold four-chip run is mostly compilation of
# the dist programs, and at this size the single-backend programs it is
# compared with are the ones the one-chip serve phase already compiled
N_FOUR_CHIPS = 2**16


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}), flush=True)


def fallbacks(result):
    return [t for t in result.trace if t.get("event") == "kernel-fallback"]


def memory(dev):
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def spy_calls(counts, targets):
    """Count calls of module-level functions (the fused drivers are
    looked up on their modules at call time)."""
    for mod, name, key in targets:
        fn = getattr(mod, name)

        def wrapped(*a, _fn=fn, _key=key, **kw):
            counts[_key] += 1
            return _fn(*a, **kw)

        setattr(mod, name, wrapped)


def phase_kernels():
    import numpy as np

    from repro.api import GraphSpec, PartitionRequest, Partitioner
    from repro.core import PartitionerConfig
    from repro.kernels import dispatch
    from repro.kernels.bal_round import ops as bal_ops
    from repro.kernels.lp_move import ops as move_ops
    from repro.kernels.seg_merge import ops as seg_ops

    check(dispatch.resolve_kernel_mode("auto") == "fused",
          "kernel='auto' does not resolve to 'fused'")
    check(dispatch.kernel_interpret() is False,
          "fused kernels would run in interpret mode")
    calls = collections.Counter()
    spy_calls(calls, [
        (move_ops, "cluster_iteration_fused", "lp_move"),
        (bal_ops, "balance_round_fused", "bal_round"),
        (seg_ops, "dedup_arcs_fused", "seg_merge"),
    ])
    # n = 1024 is the largest size at which bal_round's gate admits the
    # finest level (its rows span the n_pad + 1 label table); the two
    # (contraction limit, seed) pairs between them rebalance and coarsen
    # on every level with all three kernels
    runs = []
    for C, seed in ((128, 1), (96, 2)):
        cfg = PartitionerConfig(contraction_limit=C, seed=seed)
        req = PartitionRequest(
            graph=GraphSpec("rgg2d", 1024, 8.0, seed=seed), k=16,
            epsilon=0.03, config=cfg, seed=seed)
        before = dict(calls)
        t0 = time.perf_counter()
        fused = Partitioner().run(dataclasses.replace(req, kernel="fused"))
        t_fused = time.perf_counter() - t0
        ran = {k: calls[k] - before.get(k, 0) for k in calls}
        composed = Partitioner().run(
            dataclasses.replace(req, kernel="composed"))
        same = bool(np.array_equal(fused.assignment, composed.assignment))
        runs.append({"n": 1024, "contraction_limit": C, "seed": seed,
                     "identical": same, "fallbacks": len(fallbacks(fused)),
                     "kernel_calls": ran, "cut": fused.cut,
                     "feasible": fused.feasible,
                     "fused_s": round(t_fused, 3)})
        check(same, f"fused != composed (C={C}, seed={seed})")
        check(not fallbacks(fused), f"fallbacks in fused run: "
              f"{fallbacks(fused)}")
        check(fused.feasible, "fused run infeasible")
    emit("kernels", runs=runs, kernel_calls=dict(calls))
    for key in ("lp_move", "bal_round", "seg_merge"):
        check(calls[key] > 0, f"{key} never ran")


def phase_partition(n: int):
    import jax

    from repro.api import GraphSpec, PartitionRequest, Partitioner

    t0 = time.perf_counter()
    g = GraphSpec("rgg2d", n, 8.0, seed=0).materialize()
    gen_s = time.perf_counter() - t0
    req = PartitionRequest(graph=g, k=16, epsilon=0.03, seed=0)
    engine = Partitioner()
    t0 = time.perf_counter()
    cold = engine.run(req)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = engine.run(req)
    warm_s = time.perf_counter() - t0
    fb = collections.Counter(t["kernel"] for t in fallbacks(cold))
    emit("partition", n=g.n, m=g.m, k=16, epsilon=0.03,
         backend=cold.backend, feasible=cold.feasible, cut=cold.cut,
         imbalance=cold.metrics["imbalance"],
         levels=sum(1 for t in cold.trace if t.get("phase") == "coarsen"),
         kernel_fallbacks=dict(fb), graph_gen_s=round(gen_s, 3),
         cold_s=round(cold_s, 3), warm_s=round(warm_s, 3),
         warm_same=bool((cold.assignment == warm.assignment).all()),
         memory=memory(jax.devices()[0]))
    check(cold.feasible, "main partition infeasible")
    check((cold.assignment == warm.assignment).all(),
          "warm run differs from cold run")


def phase_serve():
    import numpy as np

    from repro.api import GraphSpec, PartitionRequest, Partitioner
    from repro.serve import PartitionServer
    from repro.serve import batching

    stacked = collections.Counter()
    spy_calls(stacked, [(batching, "stacked_level0_labels", "programs")])
    # (n, k, graph seed): same-bucket pairs with distinct seeds share a
    # stacked level-0 program, exact repeats coalesce
    mix = [(2**14, 8, 0), (2**14, 8, 1), (2**14, 8, 0), (2**15, 16, 0),
           (2**15, 16, 1), (2**15, 16, 1), (2**16, 8, 0), (2**16, 16, 0)]
    reqs = [PartitionRequest(graph=GraphSpec("rgg2d", n, 8.0, seed=s),
                             k=k, epsilon=0.03, collect_trace=False)
            for n, k, s in mix]
    t0 = time.perf_counter()
    with PartitionServer(meshes=1, devices_per_mesh=1) as srv:
        results = srv.serve(reqs)
        stats = srv.stats()
    serve_s = time.perf_counter() - t0
    engine = Partitioner()
    identical = [bool(r.ok and np.array_equal(
        r.result.assignment, engine.run(q).assignment))
        for r, q in zip(results, reqs)]
    emit("serve", requests=len(reqs), ok=[r.ok for r in results],
         identical=identical, feasible=[bool(r.ok and r.result.feasible)
                                        for r in results],
         batches=stats.get("batches"), coalesced=stats.get("coalesced"),
         stacked_programs=stacked["programs"], serve_s=round(serve_s, 3))
    check(all(r.ok for r in results), "a served request failed")
    check(all(identical), "served != solo")
    check(stats.get("coalesced", 0) > 0, "no request was coalesced")
    check(stacked["programs"] > 0, "the stacked level-0 path never ran")


def phase_four_chips(n: int):
    import jax
    import numpy as np

    from repro.api import PartitionRequest, Partitioner
    from repro.dist.dist_lp import dist_cluster
    from repro.graphs import generators
    from repro.graphs.distribute import distribute_graph

    P = 4
    check(len(jax.devices()) >= P, f"need {P} devices, "
          f"have {len(jax.devices())}")
    t0 = time.perf_counter()
    g = generators.make("rgg2d", n, 8.0, seed=0)
    gen_s = time.perf_counter() - t0
    engine = Partitioner()
    base = dict(graph=g, k=16, epsilon=0.03, seed=0)
    t0 = time.perf_counter()
    single = engine.run(PartitionRequest(**base, backend="single"))
    single_s = time.perf_counter() - t0
    emit("four_chips.single", n=g.n, m=g.m, feasible=single.feasible,
         cut=single.cut, time_s=round(single_s, 3),
         graph_gen_s=round(gen_s, 3))
    check(single.feasible, "single backend infeasible")
    models = {
        "default": {},
        "scaling": dict(contraction="sharded", weights="owner",
                        balance="dist"),
    }
    for name, kw in models.items():
        t0 = time.perf_counter()
        res = engine.run(PartitionRequest(**base, backend="dist",
                                          devices=P, **kw))
        dt = time.perf_counter() - t0
        emit(f"four_chips.dist.{name}", feasible=res.feasible, cut=res.cut,
             cut_ratio_to_single=round(res.cut / max(1, single.cut), 4),
             imbalance=res.metrics["imbalance"], time_s=round(dt, 3),
             kernel_fallbacks=len(fallbacks(res)), **kw)
        check(res.feasible, f"dist ({name}) infeasible")
    shards = distribute_graph(g, P)
    W = max(1, int(0.03 * g.total_vweight / 16))
    lab = {w: dist_cluster(shards, W, num_iterations=3, seed=1, weights=w)
           for w in ("replicated", "owner")}
    same = bool(np.array_equal(lab["replicated"], lab["owner"]))
    emit("four_chips.cluster_owner_vs_replicated", identical=same,
         clusters=int(np.unique(lab["owner"]).size), W=W)
    check(same, "owner and replicated cluster labels differ")
    mems = [memory(d) for d in jax.devices()[:P]]
    emit("four_chips.memory", devices=mems)
    check(all((m["peak_bytes_in_use"] or 0) > 0 for m in mems),
          "a device shows no memory in use")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the dist backend on four chips, nothing else")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository sources at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.api import runtime

    cache_dir = runtime.enable_compile_cache()
    import jax
    from jax import monitoring

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 3
    cache = collections.Counter()
    monitoring.register_event_listener(
        lambda event, **kw: cache.update(
            [event.rsplit("/", 1)[-1]]
            if event.startswith("/jax/compilation_cache/") else []))
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    t_start = time.perf_counter()
    if args.four_chips:
        phase_four_chips(N_FOUR_CHIPS)
    else:
        phase_kernels()
        phase_partition(N_ONE_CHIP)
        phase_serve()
    emit("compile_cache", **{k: cache[k] for k in
                             ("cache_hits", "cache_misses")},
         total_s=round(time.perf_counter() - t_start, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
