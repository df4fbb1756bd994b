"""bipartition_vertices: vertices per partition handed to
initial_partition.bipartition by block extension, the `vertices`
counters of the program's `extend.bipartition` span records: how much
graph the serial host loop splits. None where no record carries the
counter (a program that does not count it)."""

SPAN = "extend.bipartition"
COUNTER = "vertices"


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    records = [r for t in traces for r in t if r.get("span") == SPAN]
    if not any(COUNTER in r["counters"] for r in records):
        return None
    return sum(r["counters"].get(COUNTER, 0)
               for r in records) / len(traces)
