"""Result objects for the unified partitioning facade."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import numpy as np

from ..core.deep_mgp import level_records
from .request import PartitionRequest


@dataclasses.dataclass(frozen=True, eq=False)
class PartitionResult:
    """Outcome of one ``PartitionRequest``.

    ``metrics`` is ``repro.core.metrics.summarize`` output plus the graph
    sizes ``n``/``m``; ``feasible`` mirrors its feasibility flag.
    ``trace`` holds one record per driver phase/level (sizes, cuts, wall
    times) in execution order, with the span records and
    ``kernel-fallback`` events of ``repro.spans`` among them.
    """
    assignment: np.ndarray          # (n,) int64 block ids
    feasible: bool
    metrics: Dict[str, Any]
    backend: str                    # resolved backend name (never "auto")
    time_s: float
    trace: Tuple[Dict[str, Any], ...]
    request: PartitionRequest

    @property
    def cut(self) -> int:
        return int(self.metrics["cut"])

    @property
    def k(self) -> int:
        return int(self.metrics["k"])

    def summary(self) -> Dict[str, Any]:
        """JSON-serializable one-line summary (no assignment array)."""
        out = dict(self.metrics)
        out.update({
            "backend": self.backend,
            "algo": f"dkaminpar-{self.request.preset}"
            if self.backend in ("single", "dist", "dist-grid")
            else self.backend,
            "time_s": round(float(self.time_s), 3),
            "devices": int(self.request.devices),
            "levels": len(level_records(self.trace)),
        })
        return out
