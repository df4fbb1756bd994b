"""Readings that set the limits of ``correct``, at a cell's own size.

For each seed, in one process (set-up is long, so the program warms up
once), on that seed's input 1, each kind asked for:

* ``program``: the program's numbers;
* ``control``: the reference put in the program's place with one
  guarantee broken. The configuration states no precision, so the broken
  guarantee is balance: the configuration's quality reference whose
  first k/2 blocks get ``CONTROL_SKEW`` * eps more than their share;
* ``altered``: the program's answer altered where it is produced: the
  labels of a ``ALTER_SHARE`` of the vertices, drawn from the seed,
  shuffled among themselves. Block weights stay as they were; the
  answer's reported cut is that of the altered labels, as the facade
  computes it from what the backend returns;
* ``unrefined``: the program with LP refinement handing back the
  partition it was given (``repro.core.refinement.lp_refine``), while
  balancing still runs: a step that returns its state unchanged.

Benchmark runs never run this; ``python3 benchmarks/chip/probe.py``
does, on the chip.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, Iterable, List

import numpy as np

from . import cell, reference, registry

CONTROL_SKEW = 4.0
ALTER_SHARE = 0.1
KINDS = ("program", "control", "altered", "unrefined")


def control_assignment(cfg: Dict, pts, base, perm) -> np.ndarray:
    """The control's answer for the input that renames v to perm[v]."""
    k, eps = int(cfg["k"]), float(cfg["epsilon"])
    ref = registry.quality_reference(cfg["quality_reference"])
    part = np.empty_like(perm)
    part[perm] = ref.partition(pts, base, k, skew=CONTROL_SKEW * eps)
    return part


def alter(part: np.ndarray, seed) -> np.ndarray:
    """Shuffle the labels of ALTER_SHARE of the vertices."""
    rng = np.random.default_rng(seed)
    out = np.array(part, copy=True)
    idx = rng.choice(out.shape[0], int(ALTER_SHARE * out.shape[0]),
                     replace=False)
    out[idx] = out[rng.permutation(idx)]
    return out


@contextlib.contextmanager
def unrefined():
    """LP refinement returns its input; balancing still runs."""
    from repro.core import refinement
    saved = refinement.lp_refine
    refinement.lp_refine = lambda g, part, *a, **kw: part
    try:
        yield
    finally:
        refinement.lp_refine = saved


def truthful(csr, part, k: int, eps: float, ref_cut: int) -> Dict:
    """Numbers of an answer whose reported cut and feasibility are its
    own, as the facade reports them."""
    cut = reference.edge_cut(csr, part)
    feas = bool(np.bincount(part, minlength=k).max()
                <= reference.l_max(part.shape[0], k, eps))
    return reference.check_partition(csr, part, k, eps, cut, feas, ref_cut)


def readings(cfg: Dict, seeds: Iterable[int], kinds=KINDS) -> List[Dict]:
    from repro.api import Partitioner

    k, eps = int(cfg["k"]), float(cfg["epsilon"])
    engine = Partitioner()
    warm = False
    out = []
    pts, base = cell.generate(cfg)
    ref_cut = cell.reference_cut(cfg, pts, base)
    n = base[0].shape[0] - 1
    for seed in seeds:
        perm = np.random.default_rng(cell.seed_sequence(seed, 1)) \
            .permutation(n)
        csr = reference.relabel(base, perm)
        g = cell.program_graph(csr)
        rows = {"seed": seed, "ref_cut": ref_cut}
        if "control" in kinds:
            rows["control"] = truthful(
                csr, control_assignment(cfg, pts, base, perm), k, eps,
                ref_cut)
        if not warm and {"program", "altered", "unrefined"} & set(kinds):
            engine.run(cell.request(cfg, cell.program_graph(
                cell.make_input(base, seed, 0)), False))
            warm = True
        if {"program", "altered"} & set(kinds):
            t0 = time.perf_counter()
            res = engine.run(cell.request(cfg, g, False))
            rows["partition_s"] = time.perf_counter() - t0
            rows["program"] = reference.check_partition(
                csr, res.assignment, k, eps, res.cut, res.feasible, ref_cut)
            if "altered" in kinds:
                rows["altered"] = truthful(
                    csr, alter(res.assignment, cell.seed_sequence(seed, 2)),
                    k, eps, ref_cut)
        if "unrefined" in kinds:
            with unrefined():
                res = engine.run(cell.request(cfg, g, False))
            rows["unrefined"] = reference.check_partition(
                csr, res.assignment, k, eps, res.cut, res.feasible, ref_cut)
        out.append(rows)
        print(json.dumps(rows), flush=True)
    return out
