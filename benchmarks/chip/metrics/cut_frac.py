"""cut_frac: cut weight over undirected edge weight, each summed over
the window's completed partitions, by the benchmark's own arithmetic."""


def read(obs):
    return obs.cut_weight / obs.edge_weight if obs.edge_weight else None
