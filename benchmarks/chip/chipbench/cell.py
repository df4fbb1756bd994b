"""One run of one cell: build the inputs, warm up, measure, check, print.

The general part is here: the configuration's graph and its inputs, the
program's request, spans, the profiler, the check of every answer
against the reference arithmetic and the result line. The schedule of
partitions, warm-up and window belongs to the traffic mix: its file
``traffic/<traffic>.json`` names a loop ``loops/<loop>.py`` whose
``run(ctx)`` drives a ``Context``. Each input is the configuration's
graph under a permutation drawn from ``--seed`` and the input's index,
so that no two partitions of a run get the same input.

Exit codes: 0 with a result line; 2 for a name, a file or the program's
sources missing; 3 when JAX finds no accelerator, too few chips, or a
device kind that ``peaks.json`` lacks. No result line unless 0.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import reference, registry, xplane

# the module-level calls of repro.core.deep_mgp that get a span each
SPANS = ("cluster", "contract", "extend_partition",
         "partition_into_counts", "balance_and_refine")
PARTITION_SPAN = "bench.partition"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")
# numbers compared against the configuration's limits, in print order
CHECKS = ("unanswered", "bad_labels", "cut_gap", "flag_gap",
          "slack_used", "cut_over_ref")
CACHE_DIR = registry.ROOT / ".jax_cache"


class SetupError(Exception):
    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


def seed_sequence(seed: int, *extra: int) -> np.random.SeedSequence:
    """Entropy for any whole number, negative and above 64 bits too."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0), *extra])


@dataclasses.dataclass
class Observation:
    """What the metric readers read (``metrics/<name>.py``)."""
    setup_s: float
    window_s: float
    completed: int
    cut_weight: int = 0             # summed over completed partitions
    edge_weight: int = 0            # undirected, summed likewise
    partitions: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)       # per completed partition: trace, spans
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    device: Optional[Dict[str, Any]] = None     # xplane.reduce_trace
    compiles_in_window: int = 0


class Spans:
    """Host-clock spans, and ``TraceAnnotation``s when tracing, around
    the calls that ``repro.core.deep_mgp`` makes by module-level name."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.totals: Dict[str, float] = collections.Counter()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        ctx = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            try:
                yield
            finally:
                self.totals[name] += time.perf_counter() - t0

    @contextlib.contextmanager
    def installed(self):
        from repro.core import deep_mgp
        saved = {n: getattr(deep_mgp, n) for n in SPANS}

        def wrap(name, fn):
            def wrapped(*a, **kw):
                with self.span(name):
                    return fn(*a, **kw)
            return wrapped

        for n, fn in saved.items():
            setattr(deep_mgp, n, wrap(n, fn))
        try:
            yield self
        finally:
            for n, fn in saved.items():
                setattr(deep_mgp, n, fn)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.count += 1


def use_compile_cache() -> None:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, a fixed
    path taken over any directory the environment names, so that the two
    sides of a check share none. Unbounded: JAX's eviction keeps an
    access-time file beside every entry, and one missing fails every
    later write, so that nothing more is cached."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    from repro.api import runtime
    runtime.enable_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)


def use_program() -> None:
    src = registry.ROOT / "src"
    if not (src / "repro").is_dir():
        raise SetupError(2, f"no program sources at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def chip_devices(chips: int) -> list:
    """The cell's devices. ``SetupError(3)`` where JAX finds no TPU,
    fewer chips than asked, or a device kind that ``peaks.json`` lacks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(3, f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise SetupError(3, f"the cell asks for {chips} chips, JAX "
                            f"found {len(devs)}")
    try:
        registry.peaks(devs[0].device_kind)
    except registry.UnknownName as e:
        raise SetupError(3, str(e))
    return devs[:chips]


def load_cell(workload: str):
    try:
        bench = registry.benchmark()
        w = registry.workload(bench, workload)
        return bench, w, registry.config(w["config"]), \
            registry.traffic(w["traffic"])
    except (registry.UnknownName, KeyError, OSError, ValueError) as e:
        raise SetupError(2, f"cannot load workload {workload!r}: {e}")


def cell_metrics(bench: Dict, workload: str, kind: str) -> List[Dict]:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def generate(cfg: Dict):
    """(points or None, base CSR) of the configuration's graph: its
    family's ``generate`` gets the graph's other keys by name. The
    graph's own seed is the configuration's, so that every run partitions
    the same sizes: only the relabellings come from ``--seed``."""
    g = dict(cfg["graph"])
    family, seed = g.pop("family"), g.pop("seed")
    pts, src, dst = registry.graph_family(family).generate(
        **g, seed=seed_sequence(int(seed)))
    return pts, reference.csr_from_pairs(int(g["n"]), src, dst)


def reference_cut(cfg: Dict, pts, base) -> int:
    """Cut of the configuration's quality reference on the base graph."""
    ref = registry.quality_reference(cfg["quality_reference"])
    return reference.edge_cut(base, ref.partition(pts, base, int(cfg["k"])))


def make_input(csr, seed: int, i: int):
    """Input i: the base graph under a permutation drawn from (seed, i)."""
    n = csr[0].shape[0] - 1
    perm = np.random.default_rng(seed_sequence(seed, i)).permutation(n)
    return reference.relabel(csr, perm)


def program_graph(csr):
    from repro.graphs.format import Graph
    indptr, adjncy, eweights = csr
    return Graph(indptr=indptr, adjncy=adjncy, eweights=eweights,
                 vweights=np.ones(indptr.shape[0] - 1, dtype=np.int64))


def request(cfg: Dict, graph, collect_trace: bool):
    from repro.api import PartitionRequest
    return PartitionRequest(
        graph=graph, k=int(cfg["k"]), epsilon=float(cfg["epsilon"]),
        preset=cfg["preset"], seed=int(cfg["partition_seed"]),
        backend=cfg["backend"], kernel=cfg["kernel"],
        collect_trace=collect_trace)


def check_settings(cfg: Dict, req) -> None:
    """The program must run as the configuration states."""
    resolved = dataclasses.asdict(req.resolve_config())
    off = {k: (v, resolved.get(k)) for k, v in cfg["settings"].items()
           if resolved.get(k) != v}
    if off:
        raise SetupError(2, f"the program's {cfg['preset']!r} preset "
                            f"departs from the configuration: {off}")


class Context:
    """What a traffic loop drives (``loops/<loop>.py``: ``run(ctx)``).

    ``new_input()`` builds the next input and returns its index;
    ``warm(i)`` partitions it outside the window; ``window()`` opens the
    measured window (set-up ends there) and yields its clock; ``call(i)``
    partitions input i in the window and keeps the answer for the check;
    a loop that submits in its own way (``request(i)``, ``engine``)
    counts ``attempted`` and reports through ``answered`` and
    ``unanswered``. ``compiles.count`` counts the programs compiled or
    loaded so far."""

    def __init__(self, cfg, traffic, seed, seconds, trace, base, engine,
                 compiles, spans, t_process):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds, self.trace, self.base = seconds, trace, base
        self.engine, self.compiles, self.spans = engine, compiles, spans
        self.t_process = t_process
        self.inputs: List[tuple] = []       # (csr, program graph)
        self.answers: List[Dict[str, Any]] = []
        self.per_part: List[Dict[str, Any]] = []
        self.attempted = self.raised = 0
        self.in_window = False
        self.setup_s = self.window_s = None
        self.compiles_in_window = 0
        self.device = None

    def new_input(self) -> int:
        csr = make_input(self.base, self.seed, len(self.inputs))
        self.inputs.append((csr, program_graph(csr)))
        return len(self.inputs) - 1

    def request(self, i: int):
        # per-level records only in the traced run
        return request(self.cfg, self.inputs[i][1], self.trace)

    def warm(self, i: int) -> None:
        self.engine.run(self.request(i))

    @contextlib.contextmanager
    def window(self):
        trace_dir = None
        if self.trace:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=jax_profile_options())
        c0 = self.compiles.count
        span = (self.spans.span(xplane.WINDOW) if self.trace
                else contextlib.nullcontext())
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_process
        self.in_window = True
        try:
            with span:
                yield lambda: time.perf_counter() - t0
        finally:
            self.window_s = time.perf_counter() - t0
            self.in_window = False
            self.compiles_in_window = self.compiles.count - c0
            if self.trace:
                self.device = stop_trace(trace_dir)

    def call(self, i: int) -> None:
        self.attempted += 1
        self.spans.totals.clear()
        t0 = time.perf_counter()
        try:
            with (self.spans.span(PARTITION_SPAN) if self.trace
                  else contextlib.nullcontext()):
                res = self.engine.run(self.request(i))
        except Exception as e:
            self.unanswered(i, e)
            return
        self.answered(i, res, time.perf_counter() - t0)

    def answered(self, i: int, res, latency_s: float) -> None:
        """Keep the answer to input i (a ``PartitionResult``)."""
        self.answers.append({"input": i, "cut": res.cut,
                             "assignment": res.assignment,
                             "feasible": res.feasible,
                             "latency_s": latency_s})
        self.per_part.append({"trace": res.trace,
                              "spans": dict(self.spans.totals)})

    def unanswered(self, i: int, err: BaseException) -> None:
        """Input i got no answer: it raised, or never came."""
        self.raised += 1
        print(f"partition of input {i} raised: {err!r}", file=sys.stderr)


def stop_trace(trace_dir: str) -> Optional[Dict[str, Any]]:
    import jax
    jax.profiler.stop_trace()
    try:
        path = xplane.find_trace(trace_dir)
        return xplane.reduce_trace(xplane.load(path),
                                   SPANS + (PARTITION_SPAN,)) \
            if path else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def check_answers(cfg: Dict, ref_cut: int, inputs, answers,
                  failed_raise: int):
    """Compare every answer of the window with the reference arithmetic.
    Returns (numbers, cut weight summed over answers)."""
    k, eps = int(cfg["k"]), float(cfg["epsilon"])
    worst: Dict[str, float] = {c: 0 for c in CHECKS}
    worst["unanswered"] = failed_raise if answers else max(1, failed_raise)
    worst["cut_over_ref"] = -math.inf
    total = 0
    for ans in answers:
        nums = reference.check_partition(
            inputs[ans["input"]][0], ans["assignment"], k, eps, ans["cut"],
            ans["feasible"], ref_cut)
        total += nums.get("cut", 0)
        ans["feasible_ref"] = nums.get("feasible", False)
        for c in CHECKS[1:]:
            worst[c] = max(worst[c], nums[c])
    return worst, total


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True,
        compile_cache: bool = True,
        configure: Optional[Callable[[Dict], Dict]] = None,
        partition_hook: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of a cell; returns the result line as a dict. Raises
    ``SetupError`` where the run must exit without a result.

    For the tests, which run on the CPU at a small size and leave the
    process's compile cache alone (``compile_cache=False``):
    ``configure(cfg) -> cfg`` rewrites the loaded configuration, and
    ``partition_hook(fn, ctx) -> fn`` wraps the single backend's
    partition function for the whole run, to plant a fault."""
    bench, wl, cfg, traffic = load_cell(workload)
    if configure is not None:
        cfg = configure(cfg)
    try:
        loop = registry.loop(traffic["loop"])
        registry.quality_reference(cfg["quality_reference"])
    except (registry.UnknownName, KeyError) as e:
        raise SetupError(2, f"cannot load workload {workload!r}: {e}")
    use_program()
    if compile_cache:
        use_compile_cache()
    import jax

    chips = int(wl["chips"])
    devs = chip_devices(chips) if require_tpu else jax.devices()[:chips]
    from repro.api import Partitioner
    from repro.api import backends

    pts, base = generate(cfg)
    check_settings(cfg, request(cfg, program_graph(base), False))
    spans = Spans(annotate=trace)
    ctx = Context(cfg, traffic, seed, seconds, trace, base, Partitioner(),
                  CompileCounter(), spans, t_process)
    saved_fn = backends._single_partition
    if partition_hook is not None:
        backends._single_partition = partition_hook(saved_fn, ctx)
    try:
        with spans.installed() if trace else contextlib.nullcontext():
            loop.run(ctx)
    finally:
        backends._single_partition = saved_fn
    if ctx.window_s is None:
        raise SetupError(2, f"loop {traffic['loop']!r} opened no window")
    return _result(bench, wl, cfg, ctx, pts, devs)


def _result(bench, wl, cfg, ctx: Context, pts, devs) -> Dict[str, Any]:
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    # the reference runs on the host, after the peak has been read
    numbers, cut_total = check_answers(
        cfg, reference_cut(cfg, pts, ctx.base), ctx.inputs, ctx.answers,
        ctx.raised)
    limits = cfg["limits"]
    checks = {c: {"value": _finite(numbers[c]), "limit": limits[c]}
              for c in CHECKS}
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in checks.values())
    failed = ctx.raised + sum(1 for a in ctx.answers
                              if not a["feasible_ref"])
    n_edges = int(ctx.base[2].sum()) // 2
    obs = Observation(setup_s=ctx.setup_s, window_s=ctx.window_s,
                      completed=len(ctx.answers),
                      cut_weight=cut_total,
                      edge_weight=n_edges * len(ctx.answers),
                      partitions=ctx.per_part,
                      latencies_s=[a["latency_s"] for a in ctx.answers],
                      device=ctx.device,
                      compiles_in_window=ctx.compiles_in_window)
    if obs.compiles_in_window:
        print(f"{obs.compiles_in_window} programs compiled or loaded in "
              f"the window", file=sys.stderr)
    kind = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, wl["name"], kind):
        value = registry.metric_reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": ctx.attempted, "failed": failed,
                           "metrics": metrics, "device": device}
    if ctx.trace and ctx.device is not None:
        device["busy_s"] = ctx.device["busy_s"]
        device["window_s"] = ctx.device["window_s"]
        out["breakdown"] = {"device_ops": ctx.device["device_ops"],
                            "idle_gaps": ctx.device["idle_gaps"]}
    out["checks"] = checks
    return out


def jax_profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # Python calls would swamp the trace
    opts.host_tracer_level = 2
    return opts


def main(argv: Optional[List[str]] = None, t_process: float = 0.0) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result "
                    "line (the last line of standard output).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_process=t_process)
    except SetupError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
