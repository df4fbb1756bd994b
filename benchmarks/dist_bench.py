"""Distributed memory-model benchmark: one artifact tracking both paths.

Runs the same instance through the ``dist-grid`` backend under the two
memory models (``contraction="host"``/``weights="replicated"`` vs
``"sharded"``/``"owner"``) in a forced-multi-device subprocess and writes
``BENCH_dist.json``: per-level coarsen/uncoarsen wall times, the sharded
path's exchange timings and payload bytes, and the peak *persistent*
replicated bytes per PE each model carries (the replicated table is
O(n); the owner shard is O(n/P + k) — the scaling argument of ROADMAP's
larger-n scenarios, measured run-over-run).

Each mode runs twice and keeps the second trace: the discarded warmup
absorbs jit/Pallas compilation so the committed per-level numbers are
steady state. ``kernel`` selects the hot-loop implementation
(docs/KERNELS.md); the default commits the fused-kernel numbers.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
P = int(sys.argv[1]); n = int(sys.argv[2]); k = int(sys.argv[3])
kernel = sys.argv[4]
from repro.api import runtime
runtime.force_host_devices(P)
from repro.api import PartitionRequest, Partitioner
from repro.core import PartitionerConfig
from repro.graphs import generators

g = generators.make("rgg2d", n, 8.0, seed=29)
out = {"P": P, "n": g.n, "m": g.m, "k": k, "kernel": kernel, "modes": {}}
engine = Partitioner()
for name, contraction, weights in (
        ("host_replicated", "host", "replicated"),
        ("sharded_owner", "sharded", "owner")):
    cfg = PartitionerConfig(contraction_limit=128, ip_repetitions=1,
                            num_chunks=4, contraction=contraction,
                            weights=weights, kernel=kernel)
    req = PartitionRequest(graph=g, k=k, config=cfg, backend="dist-grid",
                           devices=P)
    engine.run(req)       # discarded warmup: absorbs jit/Pallas compiles
    res = engine.run(req)  # steady state (same shapes, warm caches)
    levels = [t for t in res.trace
              if t.get("phase", "").startswith("dist-coarsen")]
    unc = [t for t in res.trace if t.get("phase") == "dist-uncoarsen"]
    # peak persistent replicated state per PE: the cluster weight table
    # of the largest level plus the block weight table (4-byte entries)
    def table_bytes(nl):
        if weights == "owner":
            return 4 * (-(-(nl + 1) // P) + -(-(k + 1) // P))
        return 4 * ((nl + 1) + (k + 1))
    out["modes"][name] = {
        "time_s": round(float(res.time_s), 4),
        "cut": res.cut, "feasible": res.feasible,
        "levels": levels, "uncoarsen": unc,
        "coarsen_s_total": round(sum(t["time_s"] for t in levels), 4),
        "exchange_s_total": round(
            sum(t.get("exchange_s", 0.0) for t in levels), 4),
        "exchange_payload_bytes": int(
            sum(t.get("payload_bytes", 0) for t in levels)),
        "peak_replicated_bytes_per_pe": max(
            (table_bytes(t["n"]) for t in levels), default=table_bytes(0)),
    }
print(json.dumps(out))
"""


def run(fast: bool = True, P: int = 4, out_json: str = "BENCH_dist.json",
        kernel: str = "fused") -> Dict:
    from .common import emit

    n = 3000 if fast else 20000
    k = 8
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(P), str(n), str(k), kernel],
        capture_output=True, text=True, env=env, timeout=820)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    for name, rec in result["modes"].items():
        emit(f"dist/{name}", rec["time_s"],
             f"cut={rec['cut']};feas={rec['feasible']};"
             f"repl_bytes_per_pe={rec['peak_replicated_bytes_per_pe']};"
             f"exchange_s={rec['exchange_s_total']}")
    host = result["modes"]["host_replicated"]
    shard = result["modes"]["sharded_owner"]
    emit("dist/replicated_bytes_ratio", 0.0,
         f"{host['peak_replicated_bytes_per_pe']}/"
         f"{shard['peak_replicated_bytes_per_pe']}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(result, f, indent=1)
        emit("dist/artifact", 0.0, out_json)
    return result


if __name__ == "__main__":
    run(fast=True)
