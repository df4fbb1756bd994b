"""Benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

Sections:
  api            — repro.api facade: every backend on one request,
                   emits BENCH_api.json (cut/feasibility/time per backend)
  dist           — distributed memory models: host/replicated vs
                   sharded/owner on forced devices, emits BENCH_dist.json
                   (per-level coarsen/exchange timings, peak replicated
                   bytes per PE)
  balance        — host vs distributed balancer: rounds to feasibility,
                   per-round time, bytes exchanged (gather vs pooled
                   candidates), emits BENCH_balance.json
  serve          — multi-mesh serving tier: throughput, p50/p99 latency,
                   queue depth vs offered load at 1 vs 2 meshes, emits
                   BENCH_serve.json
  quality        — Fig 2a/b: deep vs plain vs single-level LP edge cuts
  large_k        — Table 2: feasibility at large k
  balancer       — §4 Balancing: repair of adversarial imbalance
  scaling        — Fig 4-6: weak/strong scaling over simulated PEs
  kernels        — fused vs composed hot-loop kernels (bit-identity,
                   steady-state times, VMEM + roofline accounting),
                   emits BENCH_kernels.json; plus the legacy
                   micro-kernel CSV rows
  roofline       — §Roofline table (needs artifacts/dryrun from
                   ``python -m repro.launch.dryrun --all --out ...``)

``python -m benchmarks.run [--fast] [--sections a,b,c]``

Each section runs in a child process of its own, one after another, and
this parent never imports jax: a process that has started a jax backend
holds the host's chip, and a later child could not get it.
"""
import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# section -> the code its child runs ({fast} is the --fast flag)
SECTIONS = {
    "api": "from benchmarks import api_bench; api_bench.run(fast={fast})",
    "dist": "from benchmarks import dist_bench; dist_bench.run(fast={fast})",
    "balance": "from benchmarks import balance_bench; "
               "balance_bench.run(fast={fast})",
    "serve": "from benchmarks import serve_bench; "
             "serve_bench.run(fast={fast})",
    "quality": "from benchmarks import quality; quality.run(scale='small', "
               "ks=(2, 8, 32), seeds=(0,) if {fast} else (0, 1))",
    "large_k": "from benchmarks import large_k; "
               "large_k.run(ks=(64, 256) if {fast} else (64, 256, 1024))",
    "balancer": "from benchmarks import balancer_stats; "
                "balancer_stats.run()",
    "kernels": "from benchmarks import kernels_bench; "
               "kernels_bench.run(fast={fast})",
    "scaling": "from benchmarks import scaling; "
               "scaling.run(pes=(1, 2, 4) if {fast} else (1, 2, 4, 8))",
    "roofline": "import os; from benchmarks import roofline; "
                "roofline.run('artifacts/dryrun') "
                "if os.path.isdir('artifacts/dryrun') else "
                "print('roofline,0,skipped (run repro.launch.dryrun --all "
                "--out artifacts/dryrun first)')",
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smallest instances (CI mode)")
    ap.add_argument("--sections", default="api,dist,balance,serve,quality,"
                    "large_k,balancer,kernels,scaling")
    args = ap.parse_args()
    sections = args.sections.split(",")
    unknown = [s for s in sections if s not in SECTIONS]
    if unknown:
        ap.error(f"unknown sections {unknown}; expected {list(SECTIONS)}")
    print("name,us_per_call,derived", flush=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    failed = []
    for name in sections:
        code = SECTIONS[name].format(fast=args.fast)
        if subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=env).returncode:
            failed.append(name)
    if failed:
        print(f"failed sections: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
