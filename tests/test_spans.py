"""Spans and counters inside the partitioner (``repro.spans``): how a
span is recorded, that recording changes no result, where counters land,
and that concurrent requests keep their records apart."""
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.api import (GraphSpec, PartitionRequest, PartitionSession,
                       Partitioner)
from repro.core import coarsening, deep_mgp, lp
from repro.core.deep_mgp import PartitionerConfig
from repro.graphs import generators

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = PartitionerConfig(contraction_limit=128, ip_repetitions=1,
                        num_chunks=4)
# the names the chip benchmark wraps around deep_mgp's calls
BENCH_SPANS = {"cluster", "contract", "extend_partition",
               "partition_into_counts", "balance_and_refine"}


def span_records(trace):
    return [r for r in trace if "span" in r]


def fallback_events(trace):
    return [r for r in trace if r.get("event") == "kernel-fallback"]


@pytest.fixture(scope="module")
def g():
    return generators.make("rgg2d", 3000, 8.0, seed=4)


@pytest.fixture
def all_fused_fall_back(monkeypatch):
    """Every fused kernel over its gate: each call builds its inputs, is
    refused and falls back to the composed path (no interpret-mode
    Pallas runs)."""
    from repro.kernels import dispatch
    from repro.kernels.bal_round import ops as bal_ops
    from repro.kernels.lp_move import ops as move_ops
    from repro.kernels.seg_merge import ops as seg_ops
    for mod in (move_ops, bal_ops, seg_ops):
        monkeypatch.setattr(mod, "VMEM_BUDGET_BYTES", 0)
    dispatch.reset_fallback_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        yield


def test_span_nesting_parent_ids_and_self_time():
    trace = []
    with spans.recording(trace):
        with spans.span("level.cluster", n=5) as outer:
            time.sleep(0.02)
            with spans.span(spans.WAIT):
                time.sleep(0.03)
            outer.set(n_pad=np.int64(8))
            outer.add("h2d_bytes", 12)
        with spans.span("level.refine"):
            pass
    inner, outer_rec, sibling = trace
    assert [r["span"] for r in trace] == ["wait", "level.cluster",
                                          "level.refine"]
    assert outer_rec["parent"] is None and sibling["parent"] is None
    assert inner["parent"] == outer_rec["id"] != sibling["id"]
    assert len({r["request"] for r in trace}) == 1
    assert outer_rec["attrs"] == {"n": 5, "n_pad": 8}
    assert type(outer_rec["attrs"]["n_pad"]) is int
    assert outer_rec["counters"] == {"h2d_bytes": 12}
    assert not any("phase" in r or "event" in r for r in trace)
    dur = {r["span"]: r["end_ns"] - r["start_ns"] for r in trace}
    self_ns = dur["level.cluster"] - dur["wait"]
    assert dur["wait"] >= 30e6 and self_ns >= 20e6
    assert self_ns < dur["level.cluster"]
    assert outer_rec["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= outer_rec["end_ns"] <= sibling["start_ns"]
    json.dumps(trace)


def test_recording_into_another_list_starts_a_new_request():
    outer_trace, inner_trace = [], []
    with spans.recording(outer_trace), spans.span("api.run"):
        with spans.recording(outer_trace), spans.span("api.backend"):
            pass
        with spans.recording(inner_trace), spans.span("mgp.initial"):
            pass
    (backend, run), (initial,) = outer_trace, inner_trace
    assert backend["parent"] == run["id"]
    assert initial["parent"] is None
    assert initial["request"] != run["request"] == backend["request"]


def test_no_records_when_recording_is_off(g):
    with spans.span("level.cluster") as s:
        s.add("h2d_bytes", 1)
    spans.append({"event": "kernel-fallback"})
    res = Partitioner().run(PartitionRequest(graph=g, k=4, config=CFG,
                                             collect_trace=False))
    assert res.trace == ()
    assert deep_mgp.partition(g, 4, CFG) is not None


def test_partitions_bit_identical_with_recording_on_and_off(g):
    for kw in ({}, {"refine": "unconstrained"}):
        cfg = PartitionerConfig(**{**CFG.__dict__, **kw})
        on = Partitioner().run(PartitionRequest(graph=g, k=8, config=cfg))
        off = Partitioner().run(PartitionRequest(graph=g, k=8, config=cfg,
                                                 collect_trace=False))
        assert span_records(on.trace) and off.trace == ()
        assert np.array_equal(on.assignment, off.assignment)
        assert on.cut == off.cut


def test_every_recorded_name_is_declared(g, all_fused_fall_back):
    assert not BENCH_SPANS & set(spans.SPAN_NAMES)
    assert len(set(spans.SPAN_NAMES)) == len(spans.SPAN_NAMES)
    names = set()
    for backend, kw in (("single", {"kernel": "fused"}),
                        ("single", {"refine": "unconstrained"}),
                        ("dist", {})):
        cfg = PartitionerConfig(**{**CFG.__dict__, **kw})
        res = Partitioner().run(PartitionRequest(graph=g, k=8, config=cfg,
                                                 backend=backend))
        names |= {r["span"] for r in span_records(res.trace)}
    assert names <= set(spans.SPAN_NAMES)
    assert {"api.run", "mgp.coarsen_level", "extend.bipartition",
            "level.cluster", "level.ell_build", "level.h2d", "wait",
            "dist.coarsen_level", "dist.gather"} <= names


def test_phase_records_and_levels_count(g):
    res = Partitioner().run(PartitionRequest(graph=g, k=8, config=CFG))
    records = span_records(res.trace)
    phases = [r for r in res.trace if "phase" in r]
    assert res.summary()["levels"] == len(phases) < len(res.trace)
    (root,) = [r for r in records if r["parent"] is None]
    assert root["span"] == "api.run"
    assert root["attrs"]["backend"] == "single"
    ids = {r["id"] for r in records}
    assert all(r["parent"] in ids for r in records if r is not root)
    levels = [r for r in records if r["span"] == "mgp.coarsen_level"]
    assert len(levels) >= sum(p["phase"] == "coarsen" for p in phases)
    assert all({"level", "n", "m"} <= set(r["attrs"]) for r in levels)
    # each per-level record's cut pass runs after its phase's span closed
    phase_spans = [r for r in records
                   if r["span"] in ("mgp.initial", "mgp.uncoarsen_level",
                                    "mgp.final")]
    cuts = [r for r in records if r["span"] == "mgp.trace_cut"]
    assert len(cuts) == len(phase_spans) >= 2
    for ph, cut in zip(phase_spans, cuts):
        assert cut["start_ns"] >= ph["end_ns"]
    timed = [p for p in phases if p["phase"] != "coarsen"]
    for p, ph in zip(timed, phase_spans):
        assert p["time_s"] <= (ph["end_ns"] - ph["start_ns"]) / 1e9 + 1e-6


def test_cluster_h2d_bytes_match_slab_shapes(g):
    iterations, chunks_n = 3, 4
    trace = []
    with spans.recording(trace):
        coarsening.cluster(g, 40, num_iterations=iterations,
                           num_chunks=chunks_n, seed=5, kernel="composed")
    _, g2 = lp.reorder(g, 5)
    chunks = lp.build_chunks(g2, chunks_n)
    slab_bytes = chunks.src.nbytes + chunks.dst.nbytes + chunks.w.nbytes
    want = 4 * (chunks.n_pad + 1) + iterations * slab_bytes
    assert sum(r["counters"].get("h2d_bytes", 0) for r in trace) == want
    (top,) = [r for r in trace if r["span"] == "level.cluster"]
    assert top["attrs"]["n_pad"] == chunks.n_pad
    assert top["attrs"]["m_pad"] == chunks.src.shape[1]
    assert [r["span"] for r in trace if r["parent"] == top["id"]] == [
        "level.reorder", "level.slab_build", "level.h2d", "level.iterate",
        "wait", "level.enforce_weights"]


def test_compiles_counted_on_the_innermost_span():
    shape = (3, 7, 11)           # a shape no other test compiles
    trace = []
    with spans.recording(trace), spans.span("level.iterate"):
        with spans.span("level.refine"):
            jax.jit(lambda x: x * 2 + 1)(jnp.zeros(shape)).block_until_ready()
    inner, outer = trace
    assert inner["counters"]["compiles"] >= 1
    assert inner["counters"]["compile_s"] > 0
    assert "compiles" not in outer["counters"]


def test_concurrent_session_requests_keep_their_fallback_records(
        all_fused_fall_back, monkeypatch):
    """Two requests in flight at once, each falling back at every gate:
    each result holds the records of its own partition only."""
    specs = [GraphSpec("rgg2d", 1500, 8.0, seed=1),
             GraphSpec("rgg2d", 2600, 8.0, seed=2)]
    cfg = PartitionerConfig(**{**CFG.__dict__, "kernel": "fused"})
    reqs = [PartitionRequest(graph=s, k=4, config=cfg, backend="single")
            for s in specs]
    solo = [Partitioner().run(r) for r in reqs]
    barrier = threading.Barrier(2, timeout=60)
    cluster = deep_mgp.cluster
    met = threading.local()

    def cluster_after_both_started(*a, **kw):
        if not getattr(met, "done", False):
            met.done = True
            barrier.wait()
        return cluster(*a, **kw)

    monkeypatch.setattr(deep_mgp, "cluster", cluster_after_both_started)
    with PartitionSession(devices=1, max_workers=2) as sess:
        both = sess.run_batch(reqs)
    for s, b in zip(solo, both):
        assert fallback_events(s.trace)
        assert fallback_events(b.trace) == fallback_events(s.trace)
        assert np.array_equal(s.assignment, b.assignment)
        assert len({r["request"] for r in span_records(b.trace)}) == 1
    assert span_records(both[0].trace)[0]["request"] != \
        span_records(both[1].trace)[0]["request"]


DIST_SCRIPT = """
import json
from repro.api import runtime
runtime.force_host_devices(4)
from repro.api import GraphSpec, PartitionRequest, Partitioner
from repro.core.deep_mgp import PartitionerConfig
cfg = PartitionerConfig(contraction_limit=64, ip_repetitions=1,
                        num_chunks=2)
res = Partitioner().run(PartitionRequest(
    graph=GraphSpec("rgg2d", 2000, 8.0, seed=1), k=4, config=cfg,
    backend="dist", devices=4))
print(json.dumps({"feasible": res.feasible, "trace": res.trace}))
"""


def test_dist_driver_records_spans_on_four_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", DIST_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["feasible"]
    trace = out["trace"]
    records = span_records(trace)
    names = {r["span"] for r in records}
    assert {"dist.coarsen_level", "dist.uncoarsen_level",
            "dist.distribute", "dist.gather", "mgp.trace_cut"} <= names
    assert names <= set(spans.SPAN_NAMES)
    levels = [r for r in records if r["span"] == "dist.coarsen_level"]
    assert levels and all(r["attrs"]["P"] == 4 for r in levels)
    coarsen = [r for r in trace if r.get("phase") == "dist-coarsen"]
    assert coarsen and len(levels) >= len(coarsen)
    assert any(r["counters"].get("h2d_bytes") for r in records)
