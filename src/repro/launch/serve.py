"""Serving-tier CLI — drive a multi-mesh ``PartitionServer``.

  python -m repro.launch.serve --requests 16 --verify   # one mesh/device
  python -m repro.launch.serve --meshes 2 --devices-per-mesh 2 \
      --requests 12 --n 4000 --k 8
  python -m repro.launch.serve ... --offered-rate 8   # paced admission

Without ``--meshes`` the server carves every device the host has into
meshes of ``--devices-per-mesh``. An explicit ``--meshes`` with
``--devices-per-mesh`` above 1 forces that many host (CPU) devices, a
tool for running the multi-mesh tier on a machine without chips.

Generates a mixed request set (sizes, k, single + distributed), serves
it through the admission queue, prints one JSON summary line per
result and a final stats line. ``--verify`` re-runs every request solo
through ``repro.api.Partitioner`` and asserts bit-identical
assignments. Exit 0 iff every request succeeded (and verified).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def build_requests(args):
    """A deterministic mixed workload: three sizes, two k values,
    single-device and (when the server has multi-device meshes)
    distributed requests."""
    from repro.api import GraphSpec, PartitionRequest
    from repro.core import PartitionerConfig

    cfg = PartitionerConfig(
        contraction_limit=128, ip_repetitions=2, num_chunks=4)
    reqs = []
    for i in range(args.requests):
        n = args.n // 2 * (1 + i % 3)           # n/2, n, 3n/2
        k = args.k * (1 + i % 2)                # k, 2k
        devices = args.devices_per_mesh if i % 4 == 3 else 1
        reqs.append(PartitionRequest(
            graph=GraphSpec(args.family, n, 8.0, seed=11 + i % 5),
            k=k, config=cfg, devices=devices, collect_trace=False))
    return reqs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--meshes", type=int, default=None,
                    help="worker meshes (default: as many as the host's "
                         "devices fill)")
    ap.add_argument("--devices-per-mesh", type=int, default=1)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--family", default="rgg2d")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--offered-rate", type=float, default=0.0,
                    help="requests/s admission pacing (0 = burst)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request completion deadline")
    ap.add_argument("--verify", action="store_true",
                    help="assert bit-identity against solo runs")
    args = ap.parse_args()

    # compile cache and device forcing first, before any jax init
    # (forcing errors cleanly if an earlier import initialized a backend)
    from repro.api import runtime
    runtime.enable_compile_cache()
    if args.meshes is None:
        args.meshes = max(
            1, runtime.device_count() // args.devices_per_mesh)
    elif args.devices_per_mesh > 1:
        runtime.force_host_devices(args.meshes * args.devices_per_mesh)

    from repro.serve import PartitionServer

    reqs = build_requests(args)
    t0 = time.perf_counter()
    with PartitionServer(meshes=args.meshes,
                         devices_per_mesh=args.devices_per_mesh) as srv:
        futures = []
        for i, r in enumerate(reqs):
            futures.append(srv.submit(r, priority=i % 2,
                                      deadline_s=args.deadline_s))
            if args.offered_rate > 0:
                time.sleep(1.0 / args.offered_rate)
        results = [f.result() for f in futures]
        stats = srv.stats()
    wall = time.perf_counter() - t0

    ok = all(r.ok for r in results)
    for r in results:
        print(json.dumps(r.summary()), flush=True)

    if args.verify:
        import numpy as np
        from repro.api import Partitioner
        engine = Partitioner()
        for r, req in zip(results, reqs):
            if not r.ok:
                continue
            solo = engine.run(req)
            if not np.array_equal(r.result.assignment, solo.assignment):
                print(json.dumps({"verify": "MISMATCH",
                                  "k": req.k, "n": req.graph.n}))
                ok = False
        print(json.dumps({"verify": "bit-identical" if ok else "failed"}))

    stats["wall_s"] = round(wall, 3)
    stats["throughput_rps"] = round(len(results) / max(wall, 1e-9), 3)
    print(json.dumps({"stats": stats}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
