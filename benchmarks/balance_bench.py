"""Host vs distributed balancer benchmark: one artifact tracking both.

Runs the same adversarially imbalanced instance through
``core.balance.rebalance`` (host: one O(m) single-chunk gather, then
greedy rounds) and ``dist.dist_balance.dist_rebalance`` (no gather;
O(P·top_m) pooled candidate records per round, replicated and
owner-sharded block tables) in a forced-multi-device subprocess, and
writes ``BENCH_balance.json``: rounds to feasibility, per-round wall
time, and bytes exchanged per mode — the host's up-front gather volume
against the distributed pool + halo traffic. A full ``dist-grid``
pipeline pass per ``balance`` mode records the per-level balancer
rounds from the driver trace.
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
import numpy as np
P = int(sys.argv[1]); n = int(sys.argv[2]); k = int(sys.argv[3])
from repro.api import runtime
runtime.force_host_devices(P)
from repro.api import PartitionRequest, Partitioner
from repro.core import PartitionerConfig, metrics
from repro.core.balance import rebalance
from repro.dist.dist_balance import dist_rebalance
from repro.graphs import generators
from repro.graphs.distribute import distribute_graph

g = generators.make("rgg2d", n, 8.0, seed=31)
rng = np.random.default_rng(5)
part = rng.integers(0, k, g.n)
part[rng.random(g.n) < 0.6] = 0           # adversarial: 60% in block 0
lmax = np.full(k, metrics.l_max(g.total_vweight, k, 0.03,
                                int(g.vweights.max())), dtype=np.int64)
before = metrics.summarize(g, part, k, 0.03)
shards = distribute_graph(g, P)
out = {"P": P, "n": g.n, "m": g.m, "k": k, "imbalance_before":
       before["imbalance"], "modes": {}}

host_stats = {}
fixed_h = rebalance(g, part.copy(), lmax, seed=7, stats=host_stats)
out["modes"]["host"] = {
    "rounds": host_stats["rounds"],
    "time_s": round(host_stats["time_s"], 4),
    "s_per_round": round(host_stats["time_s"] /
                         max(1, host_stats["rounds"]), 5),
    "bytes_exchanged": host_stats["gather_bytes"],
    "feasible": bool(metrics.is_feasible(g, fixed_h, k, 0.03)),
    "cut": metrics.edge_cut(g, fixed_h),
}
for wmode in ("replicated", "owner"):
    st = {}
    fixed_d = dist_rebalance(shards, part.copy(), lmax, seed=7,
                             use_grid=True, weights=wmode, stats=st)
    out["modes"][f"dist_{wmode}"] = {
        "rounds": st["rounds"],
        "time_s": round(st["time_s"], 4),
        "s_per_round": round(st["time_s"] / max(1, st["rounds"]), 5),
        "bytes_exchanged": st["pool_bytes"] + st["halo_bytes"],
        "feasible": bool(metrics.is_feasible(g, fixed_d, k, 0.03)),
        "cut": metrics.edge_cut(g, fixed_d),
    }

# full-pipeline pass per balance mode: per-level balancer rounds
cfgs = {"host": PartitionerConfig(contraction_limit=128, ip_repetitions=1,
                                  num_chunks=4),
        "dist": PartitionerConfig(contraction_limit=128, ip_repetitions=1,
                                  num_chunks=4, balance="dist")}
out["pipeline"] = {}
for name, cfg in cfgs.items():
    res = Partitioner().run(PartitionRequest(
        graph=g, k=k, config=cfg, backend="dist-grid", devices=P))
    unc = [t for t in res.trace if t.get("phase") == "dist-uncoarsen"]
    out["pipeline"][name] = {
        "time_s": round(float(res.time_s), 4),
        "cut": res.cut, "feasible": res.feasible,
        "levels": [{"n": t["n"], "balance_rounds": t.get("balance_rounds"),
                    "time_s": t["time_s"]} for t in unc],
    }
print(json.dumps(out))
"""


def run(fast: bool = True, P: int = 4, out_json: str = "BENCH_balance.json"
        ) -> Dict:
    from .common import emit

    n = 3000 if fast else 20000
    k = 16
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(P), str(n), str(k)],
        capture_output=True, text=True, env=env, timeout=820)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    for name, rec in result["modes"].items():
        emit(f"balance/{name}", rec["time_s"],
             f"rounds={rec['rounds']};feas={rec['feasible']};"
             f"bytes={rec['bytes_exchanged']};cut={rec['cut']}")
    host_b = result["modes"]["host"]["bytes_exchanged"]
    dist_b = result["modes"]["dist_replicated"]["bytes_exchanged"]
    emit("balance/bytes_ratio_host_over_dist", 0.0,
         f"{host_b}/{dist_b}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(result, f, indent=1)
        emit("balance/artifact", 0.0, out_json)
    return result


if __name__ == "__main__":
    run(fast=True)
