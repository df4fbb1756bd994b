"""fused_kernel_share: device time of the fused Pallas kernels (lp_move,
seg_merge, bal_round) over device busy time, from the profiler trace."""


def read(obs):
    d = obs.device
    if not d or d["busy_s"] <= 0:
        return None
    return d["kernel_s"] / d["busy_s"]
