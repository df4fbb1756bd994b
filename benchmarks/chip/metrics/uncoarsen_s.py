"""uncoarsen_s: seconds per partition from the initial partition to the
end, the sum of the `initial`, `uncoarsen` and `final` records
(repro.core.deep_mgp). Each of these also times one O(m) cut pass."""

PHASES = ("initial", "uncoarsen", "final")


def read(obs):
    if not obs.partitions:
        return None
    return sum(sum(r["time_s"] for r in p["trace"]
                   if r.get("phase") in PHASES)
               for p in obs.partitions) / len(obs.partitions)
