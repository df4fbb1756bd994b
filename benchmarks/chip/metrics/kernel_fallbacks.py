"""kernel_fallbacks: kernel-fallback records per partition: calls of a
fused kernel that fell back to the composed path at its VMEM gate."""


def read(obs):
    if not obs.partitions:
        return None
    return sum(sum(1 for r in p["trace"]
                   if r.get("event") == "kernel-fallback")
               for p in obs.partitions) / len(obs.partitions)
