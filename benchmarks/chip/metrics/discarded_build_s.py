"""discarded_build_s: seconds per partition spent building the ELL
inputs of a fused kernel (lp_move, bal_round) that its VMEM gate then
refused: the program's `level.ell_build` span records whose attribute
`used` is false. 0 where every build was used or none was made; None
where the trace holds no span records."""


def read(obs):
    traces = [p["trace"] for p in obs.partitions]
    if not any("span" in r for t in traces for r in t):
        return None
    return sum((r["end_ns"] - r["start_ns"]) / 1e9
               for t in traces for r in t
               if r.get("span") == "level.ell_build"
               and not r["attrs"].get("used", True)) / len(traces)
