"""device_wait_s: seconds per partition the host spent blocked in the
program's `wait` spans, reading back a value the device computes. None
where the trace holds no span records."""


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    if not any("span" in r for t in traces for r in t):
        return None
    return sum((r["end_ns"] - r["start_ns"]) / 1e9
               for t in traces for r in t
               if r.get("span") == "wait") / len(traces)
