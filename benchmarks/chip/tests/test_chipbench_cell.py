"""A whole run of a cell on the CPU at a small size, past the harness's
look for a chip: a sound run is correct, and each fault planted under
the timed path, or the control in the program's place, is not. And
``run.py`` itself refuses to run without a TPU."""
import contextlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import cell, probe, registry  # noqa: E402

CELL = "rgg2d-n20-k16.batch"
SEED = 2**33 + 5
N, K = 3000, 16


def small(cfg):
    return dict(cfg, graph=dict(cfg["graph"], n=N), k=K)


def run(**kw):
    return cell.run(CELL, SEED, 1.5, False, t_process=time.perf_counter(),
                    require_tpu=False, compile_cache=False,
                    configure=small, **kw)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "partition_s", "cut_frac"}
    assert 0 < out["metrics"]["cut_frac"]["value"] < 1
    assert list(out)[-1] == "checks"
    assert out["checks"]["cut_gap"] == {"value": 0, "limit": 0}
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics():
    out = cell.run(CELL, SEED + 1, 1.0, True, t_process=time.perf_counter(),
                   require_tpu=False, compile_cache=False, configure=small)
    assert out["correct"] is True
    # the CPU trace has no device plane: the device metrics are left out
    assert {"coarsen_s", "uncoarsen_s", "extend_s", "kernel_fallbacks",
            "compiles_in_window"} <= set(out["metrics"])
    assert out["metrics"]["extend_s"]["value"] > 0
    assert "fused_kernel_share" not in out["metrics"]


def _altered(fn, ctx):
    def partition(g, k, cfg, *a, **kw):
        return probe.alter(fn(g, k, cfg, *a, **kw), 0)
    return partition


def _raises(fn, ctx):
    """The warm-up gets its answers; no partition of the window does."""
    def partition(*a, **kw):
        if ctx.in_window:
            raise RuntimeError("planted fault")
        return fn(*a, **kw)
    return partition


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
def test_planted_fault_is_not_correct(hook, number):
    out = run(partition_hook=hook)
    assert out["correct"] is False
    c = out["checks"][number]
    assert c["value"] is None or c["value"] > c["limit"]


def test_extension_returning_its_input_is_not_correct(monkeypatch):
    """A step that returns its state unchanged: block extension hands
    back the blocks it was given, so fewer than k are ever split off."""
    from repro.core import deep_mgp
    monkeypatch.setattr(deep_mgp, "extend_partition",
                        lambda g, part, block_k, *a, **kw: (part, block_k))
    out = run()
    assert out["correct"] is False


class _FakeContext:
    """Just what the closed loop drives; each partition costs 0.01 s
    and compiles as many programs as ``compiles`` says for its turn."""

    def __init__(self, compiles, seconds=0.05):
        self.seconds, self.inputs, self.calls = seconds, [], []
        self.compiles = type("Count", (), {"count": 0})()
        self._compiles = list(compiles)
        self.attempted = 0
        self.opened = None

    def new_input(self):
        self.inputs.append(None)
        return len(self.inputs) - 1

    def warm(self, i):
        time.sleep(0.01)
        self.compiles.count += self._compiles.pop(0) if self._compiles \
            else 0

    def window(self):
        self.opened = len(self.inputs)
        t0 = time.perf_counter()
        return contextlib.nullcontext(lambda: time.perf_counter() - t0)

    def call(self, i):
        time.sleep(0.01)
        self.attempted += 1
        self.calls.append(i)


@pytest.mark.parametrize("compiles,warmups", [
    ([40, 0], 2), ([40, 3, 1, 0], 4), ([40, 3, 1, 1, 1, 1, 1], 5)])
def test_closed_loop_warms_up_until_nothing_compiles(compiles, warmups):
    loop = registry.loop("closed")
    ctx = _FakeContext(compiles)
    loop.run(ctx)
    assert loop.MAX_WARMUPS == 5
    # the window's inputs are built before it opens, and are new
    assert ctx.calls[0] == warmups and ctx.opened > warmups
    assert ctx.calls == list(range(warmups, warmups + len(ctx.calls)))
    assert max(ctx.calls) < ctx.opened


def test_probe_unrefined_skips_refinement():
    cfg = small(registry.config("rgg2d-n20-k16"))
    from repro.core import refinement
    saved = refinement.lp_refine
    rows = probe.readings(cfg, [SEED], ("program", "unrefined"))
    assert refinement.lp_refine is saved
    assert rows[0]["program"]["cut"] != rows[0]["unrefined"]["cut"]
    assert rows[0]["unrefined"]["slack_used"] <= 1.0


def test_probe_readings_separate():
    cfg = small(registry.config("rgg2d-n20-k16"))
    rows = probe.readings(cfg, [SEED], ("program", "control", "altered"))
    lim = cfg["limits"]
    assert rows[0]["program"]["slack_used"] <= lim["slack_used"]
    assert rows[0]["control"]["slack_used"] > lim["slack_used"]
    assert rows[0]["altered"]["cut_over_ref"] > lim["cut_over_ref"]
    assert rows[0]["program"]["cut_over_ref"] < lim["cut_over_ref"]


def test_run_py_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(registry.BENCH_DIR / "run.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=registry.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_run_py_refuses_without_program(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


def test_compile_cache_is_fixed_and_unbounded(tmp_path):
    """The checkout's cache, whatever directory and size the environment
    names: a bounded cache stops caching once an entry is evicted."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "from chipbench import cell; cell.use_compile_cache(); "
            "import jax; print(jax.config.jax_compilation_cache_dir); "
            "print(jax.config.jax_compilation_cache_max_size)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_COMPILATION_CACHE_MAX_SIZE="201326592",
               PYTHONPATH=str(registry.ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code, str(registry.BENCH_DIR)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(cell.CACHE_DIR), "-1"]


def test_unknown_workload_exits_without_result():
    p = subprocess.run(
        [sys.executable, str(registry.BENCH_DIR / "run.py"), "--workload",
         "nope.batch", "--seed", "1", "--seconds", "1"],
        cwd=registry.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and not _result_lines(p.stdout)
