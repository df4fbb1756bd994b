"""rgg3d: random geometric graph in the unit cube (KaGen semantics,
arXiv 2303.01417 §6): n uniform points, an edge between every pair
closer than r, with r chosen so that the expected degree is avg_deg.

A copy of the program's generator, so that the inputs stay fixed.
Returns the points and each edge once.
"""
import numpy as np
from scipy.spatial import cKDTree


def generate(n: int, avg_deg: float, seed: int):
    pts = np.random.default_rng(seed).random((n, 3))
    # E[deg] = n * (4/3) pi r^3
    r = (avg_deg / ((4.0 / 3.0) * np.pi * n)) ** (1.0 / 3.0)
    pairs = cKDTree(pts).query_pairs(r, output_type="ndarray")
    return pts, pairs[:, 0], pairs[:, 1]
