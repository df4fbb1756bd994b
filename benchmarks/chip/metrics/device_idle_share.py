"""device_idle_share: 1 - (union of device op intervals) / (traced
window), from the profiler trace."""


def read(obs):
    d = obs.device
    if not d or d["window_s"] <= 0:
        return None
    return 1.0 - d["busy_s"] / d["window_s"]
