"""Spans and counters inside the partitioner.

``span(name, **attrs)`` marks a stretch of host work. It always opens a
``jax.profiler.TraceAnnotation`` of that name, so a profiled run shows
the span on the same clock as the device ops. When a ``Recorder`` is
active in the current context, closing the span also appends one
JSON-serialisable record to the request's trace (the list behind
``PartitionResult.trace``)::

    {"span": "level.cluster", "id": 12, "parent": 3, "request": 1,
     "start_ns": ..., "end_ns": ..., "attrs": {"n": ..., "n_pad": ...},
     "counters": {"h2d_bytes": ..., "compiles": ..., "compile_s": ...}}

Times are ``time.perf_counter_ns``. A span record has a ``span`` key and
no ``phase`` or ``event`` key, so readers of the per-level phase records
and of the ``kernel-fallback`` events see what they saw before.

The recorder lives in a ``ContextVar``: each thread, and so each request
a ``PartitionSession`` runs, records into its own trace. With no
recorder a span costs one ContextVar read plus the annotation, and
nothing here adds a device synchronisation: ``fetch`` wraps reads the
program makes anyway.

Counters land on the innermost open span: ``upload`` counts
``h2d_bytes``; a ``jax.monitoring`` listener, registered with the first
recorder, counts ``compiles`` (programs compiled or loaded from the
persistent cache), ``cache_loads`` and ``compile_s`` (tracing, lowering
and compiling or loading).
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

# Every span name the program records, by layer (PERF.md §3). None equals
# a name the benchmark wraps around deep_mgp's calls.
SPAN_NAMES = (
    # entry: api.Partitioner.run
    "api.run", "api.resolve_graph", "api.backend", "api.summarize",
    # multilevel phases: core.deep_mgp.partition (and the dist driver's
    # trace-only cut pass)
    "mgp.coarsen_level", "mgp.initial", "mgp.uncoarsen_level", "mgp.final",
    "mgp.trace_cut",
    # block extension: deep_mgp.extend_partition
    "extend.subgraphs", "extend.bipartition", "extend.refine",
    # per-level programs: core.coarsening, contraction, refinement,
    # unconstrained, balance
    "level.cluster", "level.refine", "level.balance", "level.contract",
    "level.reorder", "level.slab_build", "level.ell_build", "level.h2d",
    "level.iterate", "level.feasibility", "level.enforce_weights",
    "level.dedup",
    # device: a blocking read of a device value
    "wait",
    # distributed driver: dist.dist_partitioner
    "dist.coarsen_level", "dist.uncoarsen_level", "dist.distribute",
    "dist.gather",
)
WAIT = "wait"

_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_PARTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Recorder:
    """Where one request's span records and events go."""

    _requests = itertools.count(1)

    def __init__(self, trace: List[Dict[str, Any]]):
        self.trace = trace
        self.request = next(Recorder._requests)
        self.ids = itertools.count(1)


_RECORDER: contextvars.ContextVar[Optional[Recorder]] = \
    contextvars.ContextVar("repro_spans_recorder", default=None)
_OPEN: contextvars.ContextVar[Optional["span"]] = \
    contextvars.ContextVar("repro_spans_open", default=None)
_listening = False
_listen_lock = threading.Lock()


def _plain(v: Any) -> Any:
    return v.item() if isinstance(v, np.generic) else v


class span:
    """``with span(name, **attrs) as s:`` — see the module docstring.
    ``s.set(**attrs)`` adds attributes, ``s.add(counter, x)`` a count."""

    __slots__ = ("name", "attrs", "counters", "id", "parent", "start_ns",
                 "_rec", "_token", "_ann")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        rec = self._rec = _RECORDER.get()
        if rec is not None:
            outer = _OPEN.get()
            self.id = next(rec.ids)
            self.parent = outer.id if outer is not None else None
            self.counters: Dict[str, float] = {}
            self._token = _OPEN.set(self)
            self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        if rec is not None:
            end_ns = time.perf_counter_ns()
            _OPEN.reset(self._token)
            rec.trace.append({
                "span": self.name, "id": self.id, "parent": self.parent,
                "request": rec.request, "start_ns": self.start_ns,
                "end_ns": end_ns,
                "attrs": {k: _plain(v) for k, v in self.attrs.items()},
                "counters": dict(self.counters)})
        self._ann.__exit__(*exc)

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def add(self, counter: str, value: float) -> None:
        if self._rec is not None:
            self.counters[counter] = self.counters.get(counter, 0) + value


def _count(counter: str, value: float) -> None:
    """Add to a counter of the innermost open span, if one records."""
    s = _OPEN.get()
    if s is not None:
        s.add(counter, value)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _count("cache_loads", 1)


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE:
        _count("compiles", 1)
        _count("compile_s", duration)
    elif event in _COMPILE_PARTS:
        _count("compile_s", duration)


def _listen_compiles() -> None:
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


@contextlib.contextmanager
def recording(trace: Optional[List[Dict[str, Any]]]) -> Iterator[None]:
    """Record this context's spans into ``trace``. A no-op where ``trace``
    is None or a recorder already writes to that very list; a different
    list gets a recorder, and a request id, of its own."""
    active = _RECORDER.get()
    if trace is None or (active is not None and active.trace is trace):
        yield
        return
    _listen_compiles()
    rec_token = _RECORDER.set(Recorder(trace))
    open_token = _OPEN.set(None)
    try:
        yield
    finally:
        _OPEN.reset(open_token)
        _RECORDER.reset(rec_token)


def append(record: Dict[str, Any]) -> None:
    """Append an event record to the active request's trace, if any."""
    rec = _RECORDER.get()
    if rec is not None:
        rec.trace.append(record)


def upload(x):
    """``jnp.asarray`` of a host array in a ``level.h2d`` span that counts
    the bytes put on the device as ``h2d_bytes``."""
    import jax.numpy as jnp
    with span("level.h2d") as s:
        out = jnp.asarray(x)
        s.add("h2d_bytes", out.nbytes)
    return out


def fetch(x) -> np.ndarray:
    """``np.asarray`` of a device value in a ``wait`` span: the host blocks
    there until the device has computed it and copied it back."""
    with span(WAIT):
        return np.asarray(x)
