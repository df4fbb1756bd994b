"""The readers of the program's span records (level_host_s,
device_wait_s, h2d_bytes, discarded_build_s) on synthetic observations,
and a traced run of the cell on the CPU that reports all four."""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import cell, registry  # noqa: E402

SPAN_METRICS = ("level_host_s", "device_wait_s", "h2d_bytes",
                "discarded_build_s")


def rec(name, id_, parent, start_s, end_s, request=1, attrs=None,
        **counters):
    return {"span": name, "id": id_, "parent": parent, "request": request,
            "start_ns": int(start_s * 1e9), "end_ns": int(end_s * 1e9),
            "attrs": attrs or {}, "counters": counters}


def partition(trace):
    return {"trace": trace, "spans": {}}


# one partition: a 10 s cluster call holding a 1 s reorder, a 2 s ELL
# build the gate refused, a 0.5 s upload of 800 B, an iteration that
# compiled for 1.5 s and a 4 s wait; a phase record and a fallback event
# beside them
PART_A = [
    {"phase": "coarsen", "level": 0, "time_s": 10.0},
    {"event": "kernel-fallback", "kernel": "lp_move"},
    rec("mgp.coarsen_level", 1, None, 0, 11),
    rec("level.cluster", 2, 1, 0, 10),
    rec("level.reorder", 3, 2, 0, 1),
    rec("level.ell_build", 4, 2, 1, 3, attrs={"used": False}),
    rec("level.h2d", 5, 2, 3, 3.5, h2d_bytes=800),
    rec("level.iterate", 6, 2, 3.5, 5.5, compiles=2, compile_s=1.5),
    rec("wait", 7, 2, 5.5, 9.5),
]
# another partition: a balance call with a used ELL build, 200 B up, and
# a 1 s wait; ids repeat under another request
PART_B = [
    rec("level.balance", 1, None, 0, 3, request=2),
    rec("level.ell_build", 2, 1, 0, 0.5, request=2, attrs={"used": True}),
    rec("level.h2d", 3, 1, 0.5, 1, request=2, h2d_bytes=200),
    rec("wait", 4, 1, 1, 2, request=2),
]


def observe(*traces):
    return cell.Observation(setup_s=1.0, window_s=10.0,
                            completed=len(traces),
                            partitions=[partition(t) for t in traces])


@pytest.mark.parametrize("name,one,two", [
    # cluster self 10-1-2-0.5-2-4 = 0.5, reorder 1, build 2, h2d 0.5,
    # iterate 2-1.5 = 0.5: 4.5; balance self 3-0.5-0.5-1 = 1, build 0.5,
    # h2d 0.5: 2
    ("level_host_s", 4.5, (4.5 + 2.0) / 2),
    ("device_wait_s", 4.0, (4.0 + 1.0) / 2),
    ("h2d_bytes", 800, (800 + 200) / 2),
    ("discarded_build_s", 2.0, 2.0 / 2),
])
def test_span_reader_on_synthetic_traces(name, one, two):
    read = registry.metric_reader(name)
    assert read(observe(PART_A)) == pytest.approx(one)
    assert read(observe(PART_A, PART_B)) == pytest.approx(two)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_reader_leaves_out_a_program_without_spans(name):
    read = registry.metric_reader(name)
    assert read(observe()) is None
    assert read(observe([{"phase": "coarsen", "time_s": 1.0},
                         {"event": "kernel-fallback"}])) is None


def test_discarded_build_s_is_zero_when_every_build_is_used():
    read = registry.metric_reader("discarded_build_s")
    assert read(observe(PART_B)) == 0


def small(cfg):
    return dict(cfg, graph=dict(cfg["graph"], n=3000), k=16)


def test_traced_cpu_run_reports_the_span_metrics():
    out = cell.run("rgg2d-n20-k16.batch", 2**33 + 11, 1.0, True,
                   t_process=time.perf_counter(), require_tpu=False,
                   compile_cache=False, configure=small)
    assert out["correct"] is True
    got = out["metrics"]
    assert set(SPAN_METRICS) <= set(got)
    assert got["level_host_s"]["value"] > 0
    assert got["device_wait_s"]["value"] > 0
    assert got["h2d_bytes"] == {"value": got["h2d_bytes"]["value"],
                                "unit": "B"}
    assert got["h2d_bytes"]["value"] > 0
    # kernel="auto" is composed on the CPU: no ELL input is built
    assert got["discarded_build_s"]["value"] == 0
    # the per-level phase records read as before beside the spans (at
    # n = 3000 below C * 2 nothing coarsens)
    assert got["coarsen_s"]["value"] == 0
    assert got["uncoarsen_s"]["value"] > 0
