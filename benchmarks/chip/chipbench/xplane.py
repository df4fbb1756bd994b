"""Reduce a JAX profiler trace to the benchmark's device numbers.

The trace is read with ``jax.profiler.ProfileData`` into plain
``Plane``/``Line``/``Event`` records, so that the reduction itself runs
on recorded or synthetic traces in the tests.

* busy: the union of the intervals of the device's op events, clipped
  to the window, averaged over the devices;
* kernel time: the summed duration of the fused Pallas kernels' events;
* idle gaps: the stretches of the window in which no op ran, cut at the
  host spans' edges, each piece labelled with the innermost benchmark
  span open on the host.

The window is the host span named ``WINDOW``, which the benchmark opens
around its measured partitions; host and device events share the
profiler's clock.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
# op lines of a device plane, in order of preference
OP_LINES = ("XLA Ops",)
# how a Pallas kernel shows in the device trace: the Mosaic custom call,
# and the names of the kernel bodies of lp_move, seg_merge and bal_round
KERNEL_MARKERS = ("tpu_custom_call", "_scores_kernel", "_pick_kernel")
KERNEL_NAMES = ("_kernel",)
# device op names are HLO text; the breakdown keeps their head
OP_NAME_CHARS = 120
# idle gaps shorter than this are counted together, not labelled
GAP_LABEL_MIN_NS = 1_000_000


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def find_trace(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> List[Plane]:
    """Planes of an ``.xplane.pb``. Stats are read only for the first
    event of each name on a device plane, which is all ``is_kernel``
    needs, since reading them all is slow."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        device = p.name.startswith("/device:")
        lines = []
        for ln in p.lines:
            seen = set()
            events = []
            for e in ln.events:
                stats = {}
                if device and e.name not in seen:
                    seen.add(e.name)
                    stats = {str(k): v for k, v in e.stats}
                events.append(Event(e.name, e.start_ns, e.duration_ns,
                                    stats))
            lines.append(Line(ln.name, events))
        planes.append(Plane(p.name, lines))
    return planes


def is_kernel(name: str, stats: Dict[str, object]) -> bool:
    if name in KERNEL_NAMES:
        return True
    texts = [name] + [v for v in stats.values() if isinstance(v, str)]
    return any(m in t for t in texts for m in KERNEL_MARKERS)


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted cover of a set of [start, end) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.append(new[1:], True))
    return np.stack([starts, ends[last]], axis=1)


def _device_events(plane: Plane) -> List[Event]:
    for want in OP_LINES:
        for ln in plane.lines:
            if ln.name == want:
                return ln.events
    return [e for ln in plane.lines for e in ln.events]


def _spans(planes: Sequence[Plane], names: Iterable[str]
           ) -> List[Tuple[float, float, str]]:
    names = set(names) | {WINDOW}
    out = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name in names:
                    out.append((e.start_ns, e.start_ns + e.dur_ns, e.name))
    out.sort()
    return out


def _label(spans: List[Tuple[float, float, str]], starts: np.ndarray,
           t: float) -> str:
    """Innermost span open at t: the latest-started one that covers it."""
    i = int(np.searchsorted(starts, t, side="right")) - 1
    while i >= 0:
        s, e, name = spans[i]
        if s <= t <= e and name != WINDOW:
            return name
        i -= 1
    return "outside spans"


def reduce_trace(planes: Sequence[Plane], span_names: Iterable[str] = (),
                 top: int = 10) -> Optional[Dict]:
    """Device numbers of the traced window, or None when the trace holds
    no device op inside it."""
    spans = _spans(planes, span_names)
    starts = np.array([s for s, _, _ in spans], dtype=float)
    edges_all = np.unique([t for s, e, _ in spans for t in (s, e)])
    windows = [(s, e) for s, e, name in spans if name == WINDOW]
    devices = [p for p in planes if p.name.startswith("/device:")
               and _device_events(p)]
    if not devices:
        return None
    if windows:
        w0, w1 = windows[0]
    else:
        evs = [e for p in devices for e in _device_events(p)]
        w0 = min(e.start_ns for e in evs)
        w1 = max(e.start_ns + e.dur_ns for e in evs)
    busy, kernel = [], []
    ops: Dict[str, float] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    for d, plane in enumerate(devices):
        evs = _device_events(plane)
        kinds: Dict[str, bool] = {}
        iv = np.empty((len(evs), 2))
        k_ns = 0.0
        for i, e in enumerate(evs):
            a = max(e.start_ns, w0)
            b = min(e.start_ns + e.dur_ns, w1)
            iv[i] = (a, max(a, b))
            if b > a:
                ops[e.name[:OP_NAME_CHARS]] += (b - a) / len(devices)
                if e.name not in kinds:
                    kinds[e.name] = is_kernel(e.name, e.stats)
                if kinds[e.name]:
                    k_ns += b - a
        cover = _union(iv[iv[:, 1] > iv[:, 0]])
        busy.append(float((cover[:, 1] - cover[:, 0]).sum()))
        kernel.append(k_ns)
        if d == 0:
            edges = np.concatenate([[w0], cover.ravel(), [w1]])
            for a, b in edges.reshape(-1, 2):
                if b <= a:
                    continue
                if b - a < GAP_LABEL_MIN_NS:
                    gaps["between ops, under 1 ms"] += (b - a)
                    continue
                lo, hi = np.searchsorted(edges_all, [a, b], side="right")
                cuts = np.concatenate([[a], edges_all[lo:hi], [b]])
                for x, y in zip(cuts[:-1], cuts[1:]):
                    if y > x:
                        gaps[_label(spans, starts, (x + y) / 2)] += y - x
    busy_ns = float(np.mean(busy))
    if busy_ns <= 0:
        return None
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernel_s": float(np.mean(kernel)) / 1e9,
        "devices": len(devices),
        "device_ops": [[n, s / 1e9] for n, s in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, s / 1e9] for n, s in
                      sorted(gaps.items(), key=lambda x: -x[1])[:top]],
    }
