"""Static VMEM estimator for the fused Pallas kernels.

For each kernel the repo dispatches (``lp_move``, ``seg_merge``,
``bal_round``) this module enumerates the tensors the kernel actually
keeps resident — operands, outputs, scratch, and the transient
row-tile workspaces — as ``(name, shape, dtype)`` entries derived from
the kernel signatures in ``repro.kernels``. Summing the inventory
gives a worst-case VMEM byte count as a pure function of
``(row_tile, bucket, dtype)``, each tensor at its size in Mosaic's
``T(8, 128)`` tiled layout (last two dims padded to 8 sublanes and 128
lanes: an ``(R, 1)`` column costs 512 B per row); the pass cross-checks it against the
runtime planning formulas (``lp_move_vmem_bytes`` & co) that gate the
fused->composed fallback (reported via ``dispatch.report_fallback``),
so the fallback boundary is unit-testable without a TPU.

Rules: ``VMEM001`` — static inventory and runtime formula diverge by
more than 5% at some grid point; ``VMEM002`` — they classify a grid
point differently against ``kernels.dispatch.VMEM_BUDGET_BYTES``
(one says the kernel fits, the other says fall back); ``VMEM003`` —
an ops module froze a stale copy of the budget constant.

Scalar operands (the ``[[W, v0]]`` / salt cells) are excluded: they
are O(1) cells, not VMEM-resident slabs, and the runtime formulas
exclude them too.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

from .findings import Finding, Report

ITEM = 4  # every kernel tensor is an int32/float32 laneset
SUBLANES, LANES = 8, 128  # the 32-bit VMEM tile

Tensor = Tuple[str, Tuple[int, ...]]


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def lp_move_inventory(
    R: int, D: int, row_tile: int, fit_sum: bool
) -> List[Tensor]:
    """Resident tensors of ``kernels.lp_move.lp_move_chunk``."""
    tensors: List[Tensor] = [
        ("nlab", (R, D)),  # ELL neighbor labels
        ("nw", (R, D)),  # ELL arc weights
        ("ncw", (R, D)),  # gathered cluster weights
        ("own", (R, 1)),  # own-cluster connectivity column
        ("vw", (R, 1)),  # vertex weights column
        ("moved", (R, 1)),  # output: move flags
        ("tgt", (R, 1)),  # output: move targets
        ("scratch_pmove", (R, 1)),  # pre-revert move flags
        ("scratch_light", (R, 1)),  # cw[target] at chunk start
        ("scratch_cand", (R, 1)),  # revert candidates
        ("scratch_newcw", (R, 1)),  # updated target weights
        ("eq_cube", (row_tile, D, D)),  # phase-A label equality cube
        ("pair_mask_a", (row_tile, R)),  # phase-B pairwise masks
        ("pair_mask_b", (row_tile, R)),
        ("pair_mask_c", (row_tile, R)),
        ("pair_mask_d", (row_tile, R)),
    ]
    if not fit_sum:
        tensors.insert(3, ("nbud", (R, D)))  # per-target budget slab
    return tensors


def bal_round_inventory(
    R: int, D: int, row_tile: int, restricted: bool
) -> List[Tensor]:
    """Resident tensors of ``kernels.bal_round.bal_scores``."""
    tensors: List[Tensor] = [
        ("nlab", (R, D)),  # ELL neighbor labels
        ("nw", (R, D)),  # ELL arc weights
        ("nbw", (R, D)),  # gathered block weights
        ("nlm", (R, D)),  # gathered block budgets
        ("own", (R, 1)),  # own-block connectivity
        ("vw", (R, 1)),  # vertex weights
        ("ovr", (R, 1)),  # overloaded-block flags
        ("vld", (R, 1)),  # valid-row flags
        ("fb_t", (R, 1)),  # fallback targets
        ("fb_ok", (R, 1)),  # fallback admissibility
        ("rel", (R, 1)),  # output: relative gains
        ("tgt", (R, 1)),  # output: targets
        ("eq_cube", (row_tile, D, D)),  # row-tile equality cube
    ]
    if restricted:
        tensors.insert(4, ("npar", (R, D)))  # gathered parent ids
        tensors.insert(5, ("opar", (R, 1)))  # own parent column
    return tensors


def seg_merge_inventory(L: int) -> List[Tensor]:
    """Resident lanesets of ``kernels.seg_merge.seg_merge``."""
    Lp = max(LANES, _next_pow2(L))
    names = [
        "src",  # input keys
        "dst",
        "w",  # input payload
        "osrc",  # output: sorted keys
        "odst",
        "tot",  # output: per-run totals
        "first",  # output: run-start flags
        "iota",  # lane ids for the bitonic network
        "partner",  # exchange partner values
        "flags",  # compare/segment flags
    ]
    return [(name, (1, Lp)) for name in names]


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def inventory_bytes(tensors: List[Tensor]) -> int:
    total = 0
    for _, shape in tensors:
        *lead, rows, cols = shape
        size = ITEM * _round_up(rows, SUBLANES) * _round_up(cols, LANES)
        for dim in lead:
            size *= dim
        total += size
    return total


def _grids() -> Dict[str, List[dict]]:
    """The (row_tile, bucket) grid each kernel is checked over."""
    lp: List[dict] = []
    bal: List[dict] = []
    for row_tile in (8, 16):
        for R in (128, 512, 1024, 2048, 8192):
            for D in (128, 256, 384):
                for flag in (False, True):
                    lp.append(
                        dict(R=R, D=D, row_tile=row_tile, fit_sum=flag)
                    )
                    bal.append(
                        dict(R=R, D=D, row_tile=row_tile, restricted=flag)
                    )
    seg = [dict(L=L) for L in (2, 100, 1024, 4095, 65536, 1 << 20)]
    return {"lp_move": lp, "bal_round": bal, "seg_merge": seg}


def _static_bytes(kernel: str, point: dict) -> int:
    builders: Dict[str, Callable[..., List[Tensor]]] = {
        "lp_move": lp_move_inventory,
        "bal_round": bal_round_inventory,
        "seg_merge": seg_merge_inventory,
    }
    return inventory_bytes(builders[kernel](**point))


def _runtime_bytes(kernel: str, point: dict) -> int:
    if kernel == "lp_move":
        from repro.kernels.lp_move.lp_move import lp_move_vmem_bytes

        return lp_move_vmem_bytes(
            point["R"],
            point["D"],
            row_tile=point["row_tile"],
            fit_sum=point["fit_sum"],
        )
    if kernel == "bal_round":
        from repro.kernels.bal_round.bal_round import bal_scores_vmem_bytes

        return bal_scores_vmem_bytes(
            point["R"],
            point["D"],
            row_tile=point["row_tile"],
            restricted=point["restricted"],
        )
    from repro.kernels.seg_merge.seg_merge import seg_merge_vmem_bytes

    return seg_merge_vmem_bytes(point["L"])


def run(
    report: Report,
    static_fn: Callable[[str, dict], int] = _static_bytes,
    tolerance: float = 0.05,
) -> int:
    """Cross-check static inventories against the runtime gate."""
    from repro.kernels import dispatch

    budget = dispatch.VMEM_BUDGET_BYTES
    checked = 0
    for kernel, grid in _grids().items():
        for point in grid:
            checked += 1
            static = static_fn(kernel, point)
            runtime = _runtime_bytes(kernel, point)
            gap = abs(static - runtime) / max(1, runtime)
            if gap > tolerance:
                report.add(
                    Finding(
                        rule="VMEM001",
                        pass_name="vmem",
                        message=(
                            f"{kernel}{point}: static inventory "
                            f"{static}B vs runtime gate {runtime}B "
                            f"({gap:.1%} > {tolerance:.0%})"
                        ),
                        function=kernel,
                    )
                )
            elif (static <= budget) != (runtime <= budget):
                report.add(
                    Finding(
                        rule="VMEM002",
                        pass_name="vmem",
                        message=(
                            f"{kernel}{point}: fallback boundary "
                            f"disagrees (static {static}B, runtime "
                            f"{runtime}B, budget {budget}B)"
                        ),
                        function=kernel,
                    )
                )

    # ops modules freeze the budget at import; detect drift
    from repro.kernels.bal_round import ops as bal_ops
    from repro.kernels.lp_move import ops as move_ops
    from repro.kernels.seg_merge import ops as seg_ops

    for mod in (move_ops, bal_ops, seg_ops):
        frozen = getattr(mod, "VMEM_BUDGET_BYTES", budget)
        if frozen != budget:
            report.add(
                Finding(
                    rule="VMEM003",
                    pass_name="vmem",
                    message=(
                        f"{mod.__name__} froze VMEM_BUDGET_BYTES="
                        f"{frozen} but kernels.dispatch says {budget}"
                    ),
                    function=mod.__name__,
                )
            )
    return checked
