"""The large-k cell on the CPU at a small size: a sound run is correct and
its traced run reports the block-extension metrics; each fault planted
under the timed path, the control in the program's place, extension
returning its input and LP refinement skipped are not correct."""
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import cell, probe  # noqa: E402
from test_chipbench_cell import _altered, _raises  # noqa: E402

CELL = "rgg3d-k1024.batch"
SEED = 2**33 + 5
N, K = 2**13, 128


def small(cfg):
    return dict(cfg, graph=dict(cfg["graph"], n=N), k=K)


def run(configure=small, **kw):
    return cell.run(CELL, SEED, 1.5, False, t_process=time.perf_counter(),
                    require_tpu=False, compile_cache=False,
                    configure=configure, **kw)


def test_sound_large_k_run_is_correct():
    out = run()
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "partition_s", "cut_frac"}


def test_traced_large_k_run_reports_extension_metrics():
    out = cell.run(CELL, SEED + 1, 1.0, True, t_process=time.perf_counter(),
                   require_tpu=False, compile_cache=False, configure=small)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"bipartition_s", "extend_refine_s",
                                   "bipartition_vertices"}
    assert out["metrics"]["bipartition_s"]["value"] > 0
    assert out["metrics"]["extend_refine_s"]["value"] > 0
    # every round splits every block of its level graph, N at the finest
    assert out["metrics"]["bipartition_vertices"]["value"] > N


def _control(fn, ctx):
    """The reference with balance broken, put in the program's place."""
    pts, base = cell.generate(ctx.cfg)

    def partition(g, k, pcfg, *a, **kw):
        i = next(i for i, (_, pg) in enumerate(ctx.inputs)
                 if np.array_equal(pg.adjncy, g.adjncy))
        perm = np.random.default_rng(
            cell.seed_sequence(SEED, i)).permutation(N)
        return probe.control_assignment(ctx.cfg, pts, base, perm)
    return partition


@pytest.mark.parametrize("hook,number", [
    (_altered, "cut_over_ref"),
    (_raises, "unanswered"),
    (_control, "slack_used"),
])
def test_planted_large_k_fault_is_not_correct(hook, number):
    out = run(partition_hook=hook)
    assert out["correct"] is False
    c = out["checks"][number]
    assert c["value"] is None or c["value"] > c["limit"]


def test_large_k_extension_returning_its_input_is_not_correct(monkeypatch):
    """Block extension hands back the blocks it was given; the final
    balancing pass then fills the empty blocks with whatever vertices it
    moves: feasible, at a cut the reference beats."""
    from repro.core import deep_mgp
    monkeypatch.setattr(deep_mgp, "extend_partition",
                        lambda g, part, block_k, *a, **kw: (part, block_k))
    out = run()
    assert out["correct"] is False
    c = out["checks"]["cut_over_ref"]
    assert c["value"] > c["limit"]


def _unrefined(fn, ctx):
    def partition(*a, **kw):
        with probe.unrefined():
            return fn(*a, **kw)
    return partition


def test_skipped_refinement_at_large_k_is_not_correct():
    """LP refinement handing back its input (`probe.unrefined`) while
    balancing still runs: a feasible answer whose cut the large-k limit
    on `cut_over_ref` catches. Refinement gains more as blocks grow, so
    this runs at 128 vertices a block (k = 256), where the program reads
    about -0.34 and the fault about -0.30, as at the configuration's
    size (-0.36 and -0.31)."""
    def medium(cfg):
        return dict(cfg, graph=dict(cfg["graph"], n=2**15), k=256)

    assert run(medium)["correct"] is True
    out = run(medium, partition_hook=_unrefined)
    assert out["correct"] is False
    c = out["checks"]["cut_over_ref"]
    assert c["value"] > c["limit"]
    assert out["checks"]["slack_used"]["value"] <= 1.0
