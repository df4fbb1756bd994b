"""The trace reduction on small synthetic traces: busy and idle share,
kernel device time, and idle gaps labelled by the open host span."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import xplane  # noqa: E402
from chipbench.xplane import Event, Line, Plane  # noqa: E402

MS = 1_000_000


def host(*spans):
    return Plane("/host:CPU", [Line("python", [
        Event(name, a * MS, (b - a) * MS) for name, a, b in spans])])


def device(name, ops, line="XLA Ops"):
    return Plane(name, [Line("XLA Modules", [Event("jit_x", 0, 10**12)]),
                        Line(line, [Event(n, a * MS, (b - a) * MS, s)
                                    for n, a, b, s in ops])])


def test_busy_idle_and_kernel_share():
    planes = [
        host((xplane.WINDOW, 0, 100), ("cluster", 0, 40),
             ("contract", 40, 100), ("balance_and_refine", 50, 70)),
        device("/device:TPU:0", [
            ("fusion.1", 0, 10, {}),
            ("fusion.2", 5, 20, {}),        # overlaps: counted once
            ("custom-call.3", 30, 35,
             {"long_name": "custom-call.3 = tpu_custom_call(...)"}),
            ("fusion.1", 80, 90, {}),
            ("fusion.4", 95, 120, {}),      # clipped at the window's end
            ("fusion.5", 200, 210, {}),     # outside the window
        ]),
    ]
    red = xplane.reduce_trace(planes, ["cluster", "contract",
                                       "balance_and_refine"])
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.040)   # 20 + 5 + 10 + 5 ms
    assert red["kernel_s"] == pytest.approx(0.005)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert "fusion.5" not in ops
    gaps = dict(red["idle_gaps"])
    # idle 20-30 and 35-40 under cluster; 40-50 and 70-80 under contract;
    # 50-70 under balance_and_refine (innermost); 90-95 under contract
    assert gaps["cluster"] == pytest.approx(0.015)
    assert gaps["contract"] == pytest.approx(0.025)
    assert gaps["balance_and_refine"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.040)


def test_busy_averaged_over_devices_and_short_gaps_lumped():
    ops0 = [("a", i, i + 0.5, {}) for i in range(10)]   # 0.5 ms gaps
    ops1 = [("a", 0, 10, {})]
    planes = [host((xplane.WINDOW, 0, 10)),
              device("/device:TPU:0", ops0), device("/device:TPU:1", ops1)]
    red = xplane.reduce_trace(planes)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.005 + 0.010) / 2)
    assert dict(red["idle_gaps"]) == {
        "between ops, under 1 ms": pytest.approx(0.005)}


def test_no_device_op_reads_nothing():
    assert xplane.reduce_trace([host((xplane.WINDOW, 0, 10))]) is None
    planes = [host((xplane.WINDOW, 0, 10)),
              device("/device:TPU:0", [("a", 20, 30, {})])]
    assert xplane.reduce_trace(planes) is None


def test_kernel_events_by_name_or_stats():
    assert xplane.is_kernel("custom-call.7",
                            {"hlo": "tpu_custom_call"})
    assert not xplane.is_kernel("fusion.7", {"hlo_category": "fusion"})
    assert not xplane.is_kernel("fusion.7", {"n": 3})


def test_union_of_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8], [10, 11]], float)
    np.testing.assert_array_equal(xplane._union(iv),
                                  [[0, 3], [5, 8], [10, 11]])
