"""coarsen_s: seconds per partition in the multilevel scheme's coarsening phase,
the sum of its `coarsen` records (repro.core.deep_mgp)."""


def read(obs):
    if not obs.partitions:
        return None
    return sum(sum(r["time_s"] for r in p["trace"]
                   if r.get("phase") == "coarsen")
               for p in obs.partitions) / len(obs.partitions)
