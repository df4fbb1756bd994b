"""The ``Partitioner`` facade — one entrypoint from 1 to 8192 PEs."""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, List, Optional, Sequence, Union

import numpy as np

from .. import spans
from ..core import metrics
from ..graphs.format import Graph
from .backends import BackendContext, get_backend, resolve_backend
from .request import GraphSpec, PartitionRequest
from .result import PartitionResult


class Partitioner:
    """Runs ``PartitionRequest``s through the backend registry.

    ``backend`` replaces the ``"auto"`` hint of incoming requests (an
    explicit per-request backend always wins); ``None`` keeps the auto
    policy. Stateless apart from that — ``PartitionSession`` adds mesh
    reuse and batching on top.
    """

    def __init__(self, backend: Optional[str] = None):
        self.backend = backend

    def run(self, request: PartitionRequest, *,
            _ctx: Optional[BackendContext] = None) -> PartitionResult:
        """Run one request. With ``collect_trace`` its trace gets the
        per-level records and the spans of ``repro.spans`` under one
        ``api.run`` root."""
        req = request
        if self.backend is not None and req.backend == "auto":
            req = dataclasses.replace(req, backend=self.backend)
        req.validate()
        ctx = _ctx or BackendContext(devices=req.devices)
        if ctx.trace is None and req.collect_trace:
            ctx.trace = []
        with spans.recording(ctx.trace), \
                spans.span("api.run", k=req.k) as root:
            with spans.span("api.resolve_graph"):
                g = req.resolve_graph()
            name = resolve_backend(req, g.n)
            root.set(backend=name, n=g.n, m=g.m)
            fn = get_backend(name)
            with spans.span("api.backend", backend=name):
                t0 = time.perf_counter()
                assignment = np.asarray(fn(g, req, ctx), dtype=np.int64)
                dt = time.perf_counter() - t0
            with spans.span("api.summarize"):
                s = metrics.summarize(g, assignment, req.k, req.epsilon)
        s.update({"n": g.n, "m": g.m})
        return PartitionResult(assignment=assignment,
                               feasible=bool(s["feasible"]),
                               metrics=s, backend=name, time_s=dt,
                               trace=tuple(ctx.trace or ()), request=req)

    def run_batch(self, requests: Iterable[PartitionRequest]
                  ) -> List[PartitionResult]:
        """Sequential batch; ``PartitionSession`` runs these concurrently."""
        return [self.run(r) for r in requests]

    def compare(self, request: PartitionRequest,
                backends: Sequence[str]) -> List[PartitionResult]:
        """Run the *same* request against several backends — the
        ``--compare`` flag is exactly this. A GraphSpec is materialized
        once, not once per backend."""
        request = dataclasses.replace(request,
                                      graph=request.resolve_graph())
        return [self.run(dataclasses.replace(request, backend=b))
                for b in backends]


def partition(graph: Union[Graph, GraphSpec], k: int,
              **request_kw) -> PartitionResult:
    """One-shot convenience: build a request, run the default facade.

    ``repro.api.partition(g, k=16, epsilon=0.03).assignment`` replaces
    the removed ``repro.core.partitioner.partition(g, 16)``.
    """
    return Partitioner().run(PartitionRequest(graph=graph, k=k,
                                              **request_kw))
