"""Shape-bucketed batched dispatch for the serving tier.

The saxml servable-model idiom (padded batch shapes + ``remove_padding``)
adapted to graph partitioning: requests land in **shape buckets** keyed
by ``(padded_n, padded_m, k, backend)`` on geometric padding ladders, the
dispatcher pops up to ``batch_max`` same-bucket tickets (lingering up to
``batch_window_ms`` for stragglers), and a worker serves the whole batch
as ONE unit of work. Two mechanisms amortize cost inside a batch, both
bit-identical to solo ``Partitioner.run``:

1. **Coalescing** — a ``PartitionRequest`` is a pure function of its
   fields (graph spec, k, config, *seed* — seeds are per-request, never
   derived from the batch), so identical requests in a batch share one
   partition run. This is exact by construction and is the dominant
   saving on hot traffic mixes.

2. **Stacked level-0 clustering** — distinct requests whose padded chunk
   slabs share a jit shape run their (dominant) level-0 LP clustering as
   one vmapped program (``lp.cluster_iteration_stacked``), the result
   re-entering each request's solo driver via ``level0_labels``. Rows
   are padded to a common ``(n_pad, m_pad)``; padding is provably inert:

     * padded vertices are weight-0 singletons with no arcs — they can
       never move (their best connection is 0, and moves require a
       strictly positive gain), and no real vertex can adopt them as a
       target (sentinel arcs carry weight 0, so their label groups
       score 0);
     * per-request slab construction (seeded degree-bucket reorder,
       chunk boundaries) stays on the host exactly as in a solo run —
       only the already-shape-padded jit operands are stacked;
     * the kernels are integer-only, and vmap of integer ops is exactly
       semantics-preserving — no float reassociation exists to break
       bit-identity.

   Stacking is gated by ``stack``: ``"auto"`` enables it only off-CPU
   (the XLA CPU per-row sort is compute-bound, so vmap amortizes
   nothing there), ``"on"``/``"off"`` force it.

``pad_graph`` / ``remove_padding`` are the graph-level analogues of the
saxml helpers — padded vertices are weight-0 and isolated, so any
assignment's cut and block weights are untouched. They canonicalize
graphs onto the bucket ladder for cache keys and tests; the execution
path pads at the chunk-slab level instead, because whole-graph padding
would shift the host-side reorder RNG and break solo bit-identity.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..api.backends import is_batchable, resolve_backend
from ..api.request import GraphSpec, PartitionRequest
from ..graphs.format import Graph

# ladder floors: tiny requests share one bucket instead of fragmenting
# the cache across near-identical shapes
_MIN_PAD_N = 256
_MIN_PAD_M = 1024


def pad_dim(x: int, floor: int = 1) -> int:
    """Geometric (power-of-two) padding ladder, mirroring the rung the
    jit shape-bucket cache uses (``lp.build_chunks`` pads to powers of
    two): the smallest power of two >= max(x, floor)."""
    x = max(int(x), int(floor), 1)
    return 1 << (x - 1).bit_length()


class BucketKey(NamedTuple):
    """Dispatch bucket of a batchable request. Requests in one bucket
    pad to the same rung of the shape ladder, so batching them trades
    no extra padding and their stacked slabs share one jit program."""

    padded_n: int
    padded_m: int
    k: int
    backend: str


def _graph_dims(graph) -> Tuple[int, int]:
    if isinstance(graph, GraphSpec):
        # directed arc count of the materialized graph is ~n * avg_deg;
        # the ladder only needs the rung, not the exact count
        return graph.n, int(graph.n * graph.avg_deg)
    return graph.n, graph.m


def bucket_of(req: PartitionRequest) -> Optional[BucketKey]:
    """The request's dispatch bucket, or None when it must stay on the
    solo serve path (non-batchable backend, or a multi-device ask)."""
    n, m = _graph_dims(req.graph)
    backend = resolve_backend(req, n)
    if not is_batchable(backend) or req.devices != 1:
        return None
    return BucketKey(
        padded_n=pad_dim(n, _MIN_PAD_N),
        padded_m=pad_dim(m, _MIN_PAD_M),
        k=req.k,
        backend=backend,
    )


def request_fingerprint(req: PartitionRequest) -> tuple:
    """Hashable identity of a request's *result*: equal fingerprints are
    guaranteed equal results (requests are pure functions of their
    fields). Raw ``Graph`` payloads key by object identity — a
    conservative stand-in for content equality."""
    key = []
    for f in dataclasses.fields(req):
        v = getattr(req, f.name)
        if f.name == "graph" and not isinstance(v, GraphSpec):
            v = ("graph-id", id(v))
        key.append((f.name, v))
    return tuple(key)


# ---------------------------------------------------------------------------
# Graph-level padding (saxml remove_padding idiom)
# ---------------------------------------------------------------------------


def pad_graph(g: Graph, n_pad: int) -> Graph:
    """Pad ``g`` to ``n_pad`` vertices with weight-0 isolated vertices.

    The padding is inert for partitioning metrics: isolated vertices
    contribute no arcs (cut unchanged) and zero weight (block weights
    unchanged) whatever block an assignment puts them in. The padded
    graph intentionally fails ``validate()`` (which requires vweights
    >= 1) — it is a batching artifact, not a model input."""
    if n_pad < g.n:
        raise ValueError(f"n_pad ({n_pad}) < graph n ({g.n})")
    if n_pad == g.n:
        return g
    extra = n_pad - g.n
    pad_ptr = np.full(extra, g.indptr[-1], dtype=g.indptr.dtype)
    pad_w = np.zeros(extra, dtype=g.vweights.dtype)
    return Graph(
        indptr=np.concatenate([g.indptr, pad_ptr]),
        adjncy=g.adjncy,
        eweights=g.eweights,
        vweights=np.concatenate([g.vweights, pad_w]),
    )


def remove_padding(assignment: np.ndarray, n: int) -> np.ndarray:
    """Slice a padded-graph assignment back to the real vertices."""
    return np.asarray(assignment)[:n]


# ---------------------------------------------------------------------------
# Stacked level-0 clustering
# ---------------------------------------------------------------------------


def stack_enabled(stack: str) -> bool:
    """Resolve the ``stack`` knob. ``"auto"`` is on only off-CPU: the
    measured CPU reality is that the per-row sort dominates and a
    vmapped batch costs as much as the rows run back to back."""
    if stack == "on":
        return True
    if stack == "off":
        return False
    import jax

    return jax.default_backend() != "cpu"


def stacked_level0_labels(
    graphs: Sequence[Graph], plans: Sequence[Dict]
) -> List[np.ndarray]:
    """Level-0 clustering labels for several (graph, plan) pairs via one
    stacked jitted program per shared slab shape, bit-identical to
    ``coarsening.cluster(g, plan["W"], ...)`` per entry.

    ``plans`` entries come from ``deep_mgp.level0_cluster_plan``. Host
    preparation (seeded reorder, chunking) runs per request; only the
    padded jit operands stack. Entries whose chunk slabs do not share a
    (num_chunks, iterations) signature fall into separate stacks."""
    import jax.numpy as jnp

    from .. import spans
    from ..core import lp
    from ..core.coarsening import cluster_finish, cluster_prepare
    from ..core.coarsening import cluster_seed

    prepped = []
    for g, plan in zip(graphs, plans):
        nc = plan["num_chunks"]
        perm, g2, chunks = cluster_prepare(g, nc, plan["seed"])
        prepped.append((g, plan, perm, g2, chunks))

    groups: Dict[tuple, List[int]] = {}
    for i, (_, plan, _, _, chunks) in enumerate(prepped):
        sig = (chunks.num_chunks, plan["num_iterations"])
        groups.setdefault(sig, []).append(i)

    out: List[Optional[np.ndarray]] = [None] * len(prepped)
    for (num_chunks, num_iterations), idxs in groups.items():
        n_pad = max(prepped[i][4].n_pad for i in idxs)
        m_pad = max(prepped[i][4].w.shape[1] for i in idxs)
        src_rows: List[np.ndarray] = []
        dst_rows: List[np.ndarray] = []
        w_rows: List[np.ndarray] = []
        vw_rows: List[np.ndarray] = []
        w_bound: List[int] = []
        seeds: List[int] = []
        for i in idxs:
            _, plan, _, g2, chunks = prepped[i]
            src = np.full((num_chunks, m_pad), n_pad, dtype=np.int32)
            dst = np.full((num_chunks, m_pad), n_pad, dtype=np.int32)
            w = np.zeros((num_chunks, m_pad), dtype=np.int32)
            mp = chunks.w.shape[1]
            # a row's own sentinel id (its n_pad) becomes a *real* slot
            # under the stack's larger n_pad — remap it (real vertex
            # ids are < n <= row n_pad, so only sentinels match)
            src_sentinel = chunks.src == chunks.n_pad
            dst_sentinel = chunks.dst == chunks.n_pad
            src[:, :mp] = np.where(src_sentinel, n_pad, chunks.src)
            dst[:, :mp] = np.where(dst_sentinel, n_pad, chunks.dst)
            w[:, :mp] = chunks.w
            vw = np.zeros(n_pad + 1, dtype=np.int32)
            vw[: g2.n] = g2.vweights
            src_rows.append(src)
            dst_rows.append(dst)
            w_rows.append(w)
            vw_rows.append(vw)
            w_bound.append(max(1, plan["W"]))
            seeds.append(plan["seed"])
        R = len(idxs)
        labels = jnp.broadcast_to(
            jnp.arange(n_pad + 1, dtype=jnp.int32),
            (R, n_pad + 1),
        )
        vw = spans.upload(np.stack(vw_rows))
        cluster_w = vw
        src = spans.upload(np.stack(src_rows))
        dst = spans.upload(np.stack(dst_rows))
        w = spans.upload(np.stack(w_rows))
        W = jnp.asarray(np.asarray(w_bound, dtype=np.int32))
        for it in range(num_iterations):
            salts = [cluster_seed(s, it) for s in seeds]
            it_seeds = jnp.asarray(np.asarray(salts, dtype=np.uint32))
            labels, cluster_w = lp.cluster_iteration_stacked(
                labels, cluster_w, src, dst, w, vw, W, it_seeds, n=n_pad
            )
        labels_np = spans.fetch(labels)
        for row, i in enumerate(idxs):
            _, plan, perm, g2, _ = prepped[i]
            out[i] = cluster_finish(
                labels_np[row], g2, perm, max(1, plan["W"])
            )
    return out  # type: ignore[return-value]


def _level0_hints(
    session, requests: Sequence[PartitionRequest], stack: str
) -> List[Optional[np.ndarray]]:
    """Precomputed level-0 labels for the stack-eligible requests of a
    deduplicated batch (None entries keep the solo path)."""
    hints: List[Optional[np.ndarray]] = [None] * len(requests)
    if len(requests) < 2 or not stack_enabled(stack):
        return hints
    from ..core.deep_mgp import level0_cluster_plan

    eligible: List[int] = []
    graphs: List[Graph] = []
    plans: List[Dict] = []
    for i, req in enumerate(requests):
        eff = session._resolve_graph(req)
        override = session._engine.backend
        if override is not None and eff.backend == "auto":
            eff = dataclasses.replace(eff, backend=override)
        # only the "single" driver consumes the hint
        if resolve_backend(eff, eff.graph.n) != "single":
            continue
        plan = level0_cluster_plan(eff.graph, eff.k, eff.resolve_config())
        if plan is None:
            continue
        eligible.append(i)
        graphs.append(eff.graph)
        plans.append(plan)
    if len(eligible) < 2:
        return hints
    labels = stacked_level0_labels(graphs, plans)
    for i, lab in zip(eligible, labels):
        hints[i] = lab
    return hints


# ---------------------------------------------------------------------------
# Batch execution
# ---------------------------------------------------------------------------


def run_coalesced(
    session, requests: Sequence[PartitionRequest], stack: str = "auto"
) -> List[object]:
    """Serve a same-bucket batch through ``session``, returning
    ``PartitionResult``s in request order, each bit-identical to a solo
    ``Partitioner.run`` of its request.

    Identical requests (by :func:`request_fingerprint`) share one run;
    distinct stack-eligible requests share one stacked level-0
    clustering program. Runs on the session's executor thread — callers
    go through ``PartitionSession.submit_many``."""
    groups: Dict[tuple, List[int]] = {}
    order: List[tuple] = []
    for i, req in enumerate(requests):
        fp = request_fingerprint(req)
        if fp not in groups:
            groups[fp] = []
            order.append(fp)
        groups[fp].append(i)
    distinct = [requests[groups[fp][0]] for fp in order]
    hints = _level0_hints(session, distinct, stack)
    out: List[object] = [None] * len(requests)
    for fp, req, hint in zip(order, distinct, hints):
        res = session._run_one(req, level0_labels=hint)
        for i in groups[fp]:
            out[i] = res
    return out


def distinct_count(requests: Sequence[PartitionRequest]) -> int:
    """Number of distinct results a batch needs (metrics accounting)."""
    return len({request_fingerprint(r) for r in requests})
