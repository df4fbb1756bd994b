"""h2d_bytes: bytes per partition the program put on the device from
the host, the `h2d_bytes` counters of its span records. None where the
trace holds no span records."""


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    if not any("span" in r for t in traces for r in t):
        return None
    return sum(r["counters"].get("h2d_bytes", 0)
               for t in traces for r in t if "span" in r) / len(traces)
