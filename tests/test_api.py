"""repro.api facade: request/result contract, backend registry, old-vs-new
equivalence, batched sessions, runtime helpers.

Multi-device facade coverage lives in test_distributed.py (subprocess
selftest ``--test api``); here the dist backends run at P=1 in-process.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import (GraphSpec, PartitionRequest, Partitioner,
                       PartitionSession, available_backends,
                       partition as api_partition, register_backend,
                       resolve_backend, runtime)
from repro.core import PartitionerConfig, metrics
from repro.core.deep_mgp import partition as driver_partition
from repro.graphs import generators

CFG = PartitionerConfig(contraction_limit=128, ip_repetitions=2,
                        num_chunks=4)


@pytest.fixture(scope="module")
def g():
    return generators.make("rgg2d", 2000, 8.0, seed=3)


@pytest.fixture(scope="module")
def single_result(g):
    return Partitioner().run(
        PartitionRequest(graph=g, k=8, config=CFG, backend="single"))


# ---------------------------------------------------------------------------
# registry + auto policy
# ---------------------------------------------------------------------------

def test_builtin_backends_registered():
    assert {"single", "dist", "dist-grid", "plain_mgp",
            "single_level_lp"} <= set(available_backends())


def test_auto_policy_is_pure():
    import dataclasses
    req = PartitionRequest(graph=GraphSpec("rgg2d", 50000), k=16)
    assert resolve_backend(req, 50000) == "single"          # 1 device
    assert resolve_backend(
        dataclasses.replace(req, devices=4), 50000) == "dist"
    assert resolve_backend(
        dataclasses.replace(req, devices=16), 50000) == "dist-grid"
    # too small to shard -> stays single even with devices
    assert resolve_backend(
        dataclasses.replace(req, devices=8), 100) == "single"
    # explicit hint always wins
    assert resolve_backend(
        dataclasses.replace(req, backend="plain_mgp", devices=8),
        50000) == "plain_mgp"


def test_register_backend_roundtrip(g):
    @register_backend("toy-zeros")
    def _toy(graph, req, ctx):
        return np.zeros(graph.n, dtype=np.int64)
    try:
        res = Partitioner().run(
            PartitionRequest(graph=g, k=4, config=CFG,
                             backend="toy-zeros"))
        assert res.backend == "toy-zeros"
        assert not res.assignment.any()
        assert not res.feasible        # everything in one block
    finally:
        from repro.api import backends as _b
        _b._REGISTRY.pop("toy-zeros")


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(k=0), dict(k=-3), dict(epsilon=0.0), dict(epsilon=-1.0),
    dict(devices=0), dict(preset="turbo"), dict(backend="nope"),
    dict(contraction="gather"), dict(weights="dense"),
    dict(balance="gathered"),
])
def test_request_validation_rejects(kw, g):
    base = dict(graph=g, k=8)
    base.update(kw)
    with pytest.raises(ValueError):
        PartitionRequest(**base).validate()


def test_request_memory_model_overrides(g):
    """contraction/weights/balance ride into the resolved config; None
    defers."""
    req = PartitionRequest(graph=g, k=8, contraction="sharded",
                           weights="owner", balance="dist").validate()
    cfg = req.resolve_config()
    assert cfg.contraction == "sharded" and cfg.weights == "owner"
    assert cfg.balance == "dist"
    base = PartitionRequest(graph=g, k=8).resolve_config()
    assert base.contraction == "host" and base.weights == "replicated"
    assert base.balance == "host"
    # an explicit config is still overridden by request-level knobs
    cfg2 = PartitionRequest(graph=g, k=8, config=CFG,
                            weights="owner").resolve_config()
    assert cfg2.weights == "owner" and cfg2.contraction == "host"


def test_request_validation_unknown_family():
    with pytest.raises(ValueError):
        PartitionRequest(graph=GraphSpec("nosuch", 100), k=2).validate()


@pytest.mark.parametrize("kw", [
    dict(epsilon=-0.5), dict(num_chunks=0),
    dict(contraction_limit=1, initial_k=2), dict(cluster_iterations=0),
    dict(contraction="gather"), dict(weights="dense"),
    dict(balance="gathered"),
])
def test_config_validate_rejects(kw):
    with pytest.raises(ValueError):
        PartitionerConfig(**kw).validate()


def test_driver_rejects_bad_k(g):
    with pytest.raises(ValueError):
        driver_partition(g, 0, CFG)
    from repro.dist.dist_partitioner import dist_partition_impl
    with pytest.raises(ValueError):
        dist_partition_impl(g, 0, 1, cfg=CFG)
    with pytest.raises(ValueError):
        dist_partition_impl(g, 4, 0, cfg=CFG)


# ---------------------------------------------------------------------------
# facade-vs-driver equivalence + shim removal
# ---------------------------------------------------------------------------

def test_single_matches_driver(g, single_result):
    want = driver_partition(g, 8, CFG)
    assert np.array_equal(single_result.assignment, want)


def test_dist_p1_matches_driver(g):
    from repro.dist.dist_partitioner import dist_partition_impl
    want = dist_partition_impl(g, 4, 1, cfg=CFG, use_grid=True)
    res = Partitioner().run(
        PartitionRequest(graph=g, k=4, config=CFG, backend="dist-grid",
                         devices=1))
    assert np.array_equal(res.assignment, want)
    assert res.feasible


def test_deprecated_shims_are_gone():
    """The PR 2 deprecation shims had one release of grace (docs/API.md)
    and must no longer exist — the facade is the only entrypoint."""
    from repro.core import partitioner as core_partitioner
    from repro.dist import dist_partitioner
    assert not hasattr(core_partitioner, "partition")
    assert not hasattr(dist_partitioner, "dist_partition")
    import repro.core
    assert not hasattr(repro.core, "partition")


def test_dist_p1_sharded_owner_memory_model(g):
    """The fully sharded memory model through the unchanged facade:
    feasible, and its coarsen trace records the sharded exchange."""
    res = Partitioner().run(
        PartitionRequest(graph=g, k=4, config=CFG, backend="dist",
                         devices=1, contraction="sharded",
                         weights="owner"))
    assert res.feasible
    coarsen = [t for t in res.trace if t.get("phase") == "dist-coarsen"]
    assert coarsen and all(t["contraction"] == "sharded"
                           and "exchange_s" in t for t in coarsen)


# ---------------------------------------------------------------------------
# result contract
# ---------------------------------------------------------------------------

def test_feasible_flag_agrees_with_metrics(g, single_result):
    res = single_result
    assert res.feasible == metrics.is_feasible(g, res.assignment, 8, 0.03)
    assert res.feasible == res.metrics["feasible"]


def test_result_summary_and_trace(g, single_result):
    res = single_result
    s = res.summary()
    import json
    json.dumps(s)                       # JSON-serializable
    assert s["backend"] == "single" and s["n"] == g.n and s["m"] == g.m
    assert res.trace, "per-level trace must be populated"
    records = [t for t in res.trace if "phase" in t]
    phases = [t["phase"] for t in records]
    assert phases[0] == "coarsen" and phases[-1] == "final"
    assert all("time_s" in t for t in records)
    assert s["levels"] == len(records)
    # the final trace record's cut is the result's cut
    assert records[-1]["cut"] == res.cut == metrics.edge_cut(
        g, res.assignment)


def test_convenience_partition_wrapper(g):
    res = api_partition(g, 4, config=CFG)
    assert res.backend == "single"
    assert res.assignment.shape == (g.n,)
    assert res.feasible


# ---------------------------------------------------------------------------
# batched sessions
# ---------------------------------------------------------------------------

def test_session_batch_equals_per_request():
    spec = GraphSpec("rgg2d", 1200, 8.0, seed=7)
    reqs = [PartitionRequest(graph=spec, k=k, config=CFG,
                             backend="single") for k in (2, 4, 8)]
    with PartitionSession(devices=1, max_workers=3) as sess:
        batch = sess.run_batch(reqs)
        stats = sess.stats()
        assert len(sess._graph_cache) == 1   # one spec -> one materialize
    solo = Partitioner().run_batch(reqs)
    for b, s in zip(batch, solo):
        assert np.array_equal(b.assignment, s.assignment)
        assert b.cut == s.cut
    assert stats["served"] == len(reqs)


def test_session_rejects_after_close():
    sess = PartitionSession(devices=1)
    sess.close()
    with pytest.raises(RuntimeError):
        sess.submit(PartitionRequest(graph=GraphSpec("rgg2d", 100), k=2))


def test_session_submit_close_race_raises_session_closed():
    """Hammer submit against close: every losing submit must raise the
    documented session-closed RuntimeError — never the raw executor
    shutdown error (the old race: closed-check outside the lock)."""
    import threading

    req = PartitionRequest(graph=GraphSpec("rgg2d", 120), k=2,
                           config=CFG, backend="single")
    for _ in range(10):
        sess = PartitionSession(devices=1, max_workers=2)
        errors, futs = [], []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    futs.append(sess.submit(req))
                except RuntimeError as e:
                    errors.append(str(e))
                    return

        t = threading.Thread(target=hammer)
        t.start()
        sess.close(wait=False)
        stop.set()
        t.join(timeout=30)
        assert all(e == "session is closed" for e in errors), errors
        for f in futs:
            if not f.cancelled():
                try:
                    f.result(timeout=60)
                except Exception:
                    pass


def test_run_batch_mid_loop_failure_cleans_up_futures():
    """A submit raise mid-batch must not leak already-submitted work:
    run_batch cancels/awaits the captured futures before re-raising."""
    sess = PartitionSession(devices=1, max_workers=2)
    captured = []
    orig_submit = sess.submit

    def flaky_submit(req):
        if captured:
            raise RuntimeError("injected submit failure")
        fut = orig_submit(req)
        captured.append(fut)
        return fut

    sess.submit = flaky_submit
    reqs = [PartitionRequest(graph=GraphSpec("rgg2d", 150, seed=i), k=2,
                             config=CFG, backend="single")
            for i in range(3)]
    try:
        with pytest.raises(RuntimeError, match="injected"):
            sess.run_batch(reqs)
        assert len(captured) == 1
        # the survivor was awaited (or cancelled) before the re-raise
        assert captured[0].done() or captured[0].cancelled()
    finally:
        sess.close()


# ---------------------------------------------------------------------------
# runtime helper
# ---------------------------------------------------------------------------

def test_force_host_devices_after_init():
    import jax
    jax.devices()                       # ensure the backend exists
    assert runtime.jax_backend_initialized()
    runtime.force_host_devices(0)       # no-op
    runtime.force_host_devices(1)       # enough devices -> no-op
    with pytest.raises(RuntimeError, match="already initialized"):
        runtime.force_host_devices(4096)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache_probe(env_dir, code):
    """First line a fresh process prints after ``enable_compile_cache``."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.api import runtime\n"
         "print(runtime.enable_compile_cache())\n" + code],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[0]


def test_compile_cache_written_to_env_dir(tmp_path):
    got = _cache_probe(tmp_path, "import jax, jax.numpy as jnp\n"
                       "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))"
                       ".block_until_ready()")
    assert got == str(tmp_path)
    assert os.listdir(tmp_path), "nothing cached in JAX_COMPILATION_CACHE_DIR"


def test_compile_cache_defaults_to_checkout():
    assert _cache_probe(None, "") == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
