"""bipartition_s: seconds per partition in block extension's serial host
loop of bipartitions (deep_mgp.extend_partition calling
initial_partition.bipartition once per splittable block), the program's
`extend.bipartition` span records. None where the trace holds no span
records."""

SPAN = "extend.bipartition"


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    if not any("span" in r for t in traces for r in t):
        return None
    return sum((r["end_ns"] - r["start_ns"]) / 1e9
               for t in traces for r in t
               if r.get("span") == SPAN) / len(traces)
