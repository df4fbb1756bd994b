"""extend_s: seconds per partition inside deep_mgp.extend_partition
(block extension by recursive bipartitioning), by the benchmark's span
around that call."""


def read(obs):
    if not obs.partitions:
        return None
    return sum(p["spans"].get("extend_partition", 0.0)
               for p in obs.partitions) / len(obs.partitions)
