"""Kernel-mode resolution shared by the fused Pallas paths.

The ``kernel`` knob on ``PartitionerConfig`` selects the implementation
of the three fused hot loops (docs/KERNELS.md):

  * ``"composed"`` — the original XLA-composed pipelines (sort +
    segment ops). Always available; the reference the fused kernels are
    bit-identical to.
  * ``"fused"``    — single-pass Pallas kernels (lp_move, seg_merge,
    balance_round). On TPU they compile to Mosaic; off-TPU they run in
    ``interpret=True`` mode, which is correct but slow — useful only to
    exercise the fused code path in tests/CI.
  * ``"auto"``     — per-backend default: "fused" on TPU, "composed"
    anywhere else.

Fused wrappers also fall back to the composed path per call site when a
shape exceeds the kernel's VMEM budget (see ``tiled_bytes``); the fallback
is safe because both paths are bit-identical by construction, and it is
*observable*, not silent: every decision is recorded via
``report_fallback`` (a one-shot warning per kernel plus a
``kernel-fallback`` record in the trace of the request that made it,
through ``repro.spans``).
"""
from __future__ import annotations

import functools
import warnings

from .. import spans

KERNEL_MODES = ("auto", "fused", "composed")

# single-core VMEM working-set budget the fused wrappers plan against
# (v5e has ~16 MiB more than half of which we leave to Mosaic)
VMEM_BUDGET_BYTES = 8 * 2**20


def check_kernel_mode(kernel: str) -> str:
    if kernel not in KERNEL_MODES:
        raise ValueError(f"unknown kernel mode {kernel!r}; expected one "
                         f"of {KERNEL_MODES}")
    return kernel


@functools.lru_cache(maxsize=1)
def _default_backend_is_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def resolve_kernel_mode(kernel: str) -> str:
    """Map the config knob to a concrete mode ("fused" | "composed")."""
    check_kernel_mode(kernel)
    if kernel == "auto":
        return "fused" if _default_backend_is_tpu() else "composed"
    return kernel


def kernel_interpret() -> bool:
    """Whether fused kernels must run in Pallas interpret mode (no TPU)."""
    return not _default_backend_is_tpu()


def tiled_bytes(*shape: int) -> int:
    """VMEM bytes of a 32-bit array in Mosaic's ``T(8, 128)`` layout.

    The last two dims pad to 8 sublanes and 128 lanes, so an ``(R, 1)``
    column costs 512 B per row and a ``(1, L)`` laneset 32 B per lane.
    The planning formulas count every resident array this way: counted
    at 4 B per element, they admitted shapes the compiler refuses.
    """
    *lead, rows, cols = (1, *shape) if len(shape) == 1 else shape
    n = 4 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128)
    for d in lead:
        n *= d
    return n


# --- fallback observability -------------------------------------------
# A fused wrapper that falls back to the composed path is *correct* but
# silently loses the kernel speedup; callers used to find out only by
# profiling. Decision sites call ``report_fallback``, which writes a
# ``kernel-fallback`` record into the active request's trace, and the
# first fallback per kernel raises a one-shot ``UserWarning``.

_fallback_warned: set = set()


def report_fallback(kernel: str, estimated_bytes: int,
                    budget: int = VMEM_BUDGET_BYTES,
                    detail: str = "") -> None:
    """Record one fused->composed fallback decision."""
    spans.append({
        "event": "kernel-fallback",
        "kernel": kernel,
        "estimated_bytes": int(estimated_bytes),
        "budget_bytes": int(budget),
        "detail": detail,
    })
    if kernel not in _fallback_warned:
        _fallback_warned.add(kernel)
        warnings.warn(
            f"fused kernel {kernel!r} fell back to the composed path "
            f"({detail or 'no detail'}): the shape is outside the "
            f"kernel's gate (estimated working set "
            f"{int(estimated_bytes)} B, VMEM budget {int(budget)} B)"
            "; results are identical but the kernel speedup is lost "
            "(warning once per kernel)",
            UserWarning, stacklevel=3)


def reset_fallback_warnings() -> None:
    """Re-arm the one-shot warnings."""
    _fallback_warned.clear()
