"""level_host_s: seconds per partition of host work in the per-level
programs (core.coarsening, contraction, refinement, balance): the self
time of the program's `level.*` span records (their duration less what
their child spans cover, so less their `wait` children) less the compile
seconds counted on them. None where the trace holds no span records."""

PREFIX = "level."


def _host_s(trace):
    spans = [r for r in trace if "span" in r]
    covered = {}
    for r in spans:
        if r["parent"] is not None:
            key = (r["request"], r["parent"])
            covered[key] = covered.get(key, 0) + r["end_ns"] - r["start_ns"]
    return sum((r["end_ns"] - r["start_ns"]
                - covered.get((r["request"], r["id"]), 0)) / 1e9
               - r["counters"].get("compile_s", 0.0)
               for r in spans if r["span"].startswith(PREFIX))


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    if not any("span" in r for t in traces for r in t):
        return None
    return sum(_host_s(t) for t in traces) / len(traces)
