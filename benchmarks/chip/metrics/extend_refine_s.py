"""extend_refine_s: seconds per partition in the sibling-restricted
balancing and LP refinement that follows each round of block extension
(deep_mgp.extend_partition calling balance_and_refine with parent=),
the program's `extend.refine` span records, compiles included. None
where the trace holds no span records."""

SPAN = "extend.refine"


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    if not any("span" in r for t in traces for r in t):
        return None
    return sum((r["end_ns"] - r["start_ns"]) / 1e9
               for t in traces for r in t
               if r.get("span") == SPAN) / len(traces)
