"""Partitioner CLI — the paper's tool as a command, on the `repro.api`
facade.

  python -m repro.launch.partition --family rgg2d --n 20000 --k 16
  python -m repro.launch.partition --family rhg --n 10000 --k 64 \
      --preset strong --compare
  python -m repro.launch.partition ... --devices 8      # distributed
  python -m repro.launch.partition ... --backend dist-grid

Prints one JSON summary line per backend run; exit 0 iff the primary
run is feasible.
"""
from __future__ import annotations

import argparse
import json
import sys

COMPARE_BACKENDS = ["plain_mgp", "single_level_lp"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="rgg2d")
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--epsilon", type=float, default=0.03)
    ap.add_argument("--preset", default="fast", choices=["fast", "strong"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="auto",
                    help="registry name (single | dist | dist-grid | "
                         "plain_mgp | single_level_lp) or 'auto'")
    ap.add_argument("--compare", action="store_true",
                    help="also run plain-MGP and single-level baselines "
                         "as backends of the same request")
    ap.add_argument("--devices", type=int, default=0,
                    help=">0: force that many host devices (must happen "
                         "before jax initializes)")
    ap.add_argument("--contraction", default=None,
                    choices=["host", "sharded"],
                    help="dist-backend memory model: gather each level "
                         "(host) or contract in place (sharded) — "
                         "docs/DIST.md")
    ap.add_argument("--weights", default=None,
                    choices=["replicated", "owner"],
                    help="dist-backend weight tables: psum-replicated or "
                         "owner-sharded (O(n/P + k) per PE)")
    ap.add_argument("--balance", default=None,
                    choices=["host", "dist"],
                    help="dist-backend balancer: gather each uncoarsening "
                         "level to the host (host) or run the pooled "
                         "greedy balancer over the level's shards (dist) "
                         "— docs/DIST.md")
    ap.add_argument("--kernel", default=None,
                    choices=["auto", "fused", "composed"],
                    help="hot-loop implementation on any backend: fused "
                         "Pallas kernels or the composed XLA pipeline "
                         "(bit-identical results) — docs/KERNELS.md")
    ap.add_argument("--refine", default=None,
                    choices=["lp", "unconstrained"],
                    help="refinement algorithm on any backend: "
                         "size-constrained LP (default) or the Jet-style "
                         "unconstrained search with afterburner repair "
                         "(better cuts, always feasible) — "
                         "docs/REFINEMENT.md")
    ap.add_argument("--quality", default=None,
                    choices=["fast", "best"],
                    help="serving-facing spelling of --refine (fast=lp, "
                         "best=unconstrained); an explicit --refine wins "
                         "— docs/SERVING.md")
    ap.add_argument("--trace", action="store_true",
                    help="also print the trace records (per-level "
                         "records, kernel-fallback events and spans)")
    args = ap.parse_args()

    # compile cache and device forcing first — repro.api.runtime errors
    # cleanly if some earlier import already initialized jax, instead of
    # silently serving a stale device count.
    from repro.api import runtime
    runtime.enable_compile_cache()
    if args.devices:
        runtime.force_host_devices(args.devices)

    from repro.api import GraphSpec, PartitionRequest, Partitioner

    req = PartitionRequest(
        graph=GraphSpec(args.family, args.n, args.avg_deg, seed=args.seed),
        k=args.k, epsilon=args.epsilon, preset=args.preset,
        seed=args.seed, backend=args.backend,
        devices=args.devices or 1,
        contraction=args.contraction, weights=args.weights,
        balance=args.balance, kernel=args.kernel, refine=args.refine,
        quality=args.quality)
    engine = Partitioner()
    res = engine.run(req)
    print(json.dumps(res.summary()))
    if args.trace:
        for rec in res.trace:
            print(json.dumps(rec))
    if args.compare:
        for r in engine.compare(req, COMPARE_BACKENDS):
            print(json.dumps(r.summary()))
    return 0 if res.feasible else 1


if __name__ == "__main__":
    sys.exit(main())
