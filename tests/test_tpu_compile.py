"""Compile the fused Pallas kernels for a described TPU v5e, no chip needed.

Interpret mode (every other kernel test) cannot see what only Mosaic
checks: VMEM capacity, tiled layouts, the ops it can lower. These tests
compile each kernel with ``interpret=False`` for a ``v5e:2x2`` topology
that is described, not attached, at the largest shape its VMEM gate
admits, so a gate that admits more than the compiler accepts fails here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bal_round.bal_round import bal_scores, greedy_pick
from repro.kernels.bal_round.ops import balance_ell_fits
from repro.kernels.dispatch import VMEM_BUDGET_BYTES
from repro.kernels.lp_move.lp_move import lp_move_chunk, lp_move_vmem_bytes
from repro.kernels.lp_move.ops import LANE, ROW_TILE
from repro.kernels.seg_merge.seg_merge import seg_merge, seg_merge_vmem_bytes

D = LANE  # one vreg of neighbour lanes: the ELL width of a degree <= 128 graph


def _largest_pow2(fits, lo: int = ROW_TILE) -> int:
    x = lo
    while fits(2 * x):
        x *= 2
    assert fits(x), f"the gate admits not even {lo}"
    return x


def _lp_fits(R: int, fit_sum: bool = True) -> bool:
    return lp_move_vmem_bytes(R, D, ROW_TILE, fit_sum) <= VMEM_BUDGET_BYTES


def _seg_fits(L: int) -> bool:
    return seg_merge_vmem_bytes(L) <= VMEM_BUDGET_BYTES


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


I32, U32, F32 = jnp.int32, jnp.uint32, jnp.float32


@pytest.mark.parametrize("fit_sum", [True, False])
def test_lp_move_compiles_at_largest_admitted_rows(one_chip, fit_sum):
    R = _largest_pow2(lambda r: _lp_fits(r, fit_sum))
    assert R == 1024
    shapes = [((R, D), I32)] * 3 + [((R, 1), I32)] * 2 + \
        [((1, 2), I32), ((1, 1), U32)]
    if not fit_sum:
        shapes.append(((R, D), I32))
    _compile(one_chip,
             lambda *a: lp_move_chunk(*a, fit_sum=fit_sum, interpret=False),
             *shapes)


@pytest.mark.parametrize("restricted", [False, True])
def test_bal_scores_compiles_at_lp_move_rows(one_chip, restricted):
    R = _largest_pow2(_lp_fits)
    assert balance_ell_fits(R, D, restricted)
    shapes = [((R, D), I32)] * 4 + [((R, 1), I32)] * 6 + [((1, 1), U32)]
    if restricted:
        shapes += [((R, D), I32), ((R, 1), I32)]
    _compile(one_chip,
             lambda *a: bal_scores(*a, restricted=restricted,
                                   interpret=False),
             *shapes)


def test_greedy_pick_compiles(one_chip):
    M, K = 1024, 128
    _compile(one_chip, lambda *a: greedy_pick(*a, interpret=False),
             ((M,), F32), ((M,), I32), ((M,), I32), ((M,), I32),
             ((K,), I32), ((K,), I32))


def test_seg_merge_compiles_at_largest_admitted_lanes(one_chip):
    L = _largest_pow2(_seg_fits, lo=128)
    assert L == 16384
    _compile(one_chip, lambda *a: seg_merge(*a, interpret=False),
             ((L,), I32), ((L,), I32), ((L,), I32))


def test_gate_rejects_lp_move_rows_the_compiler_refuses():
    # Mosaic runs out of VMEM at R = 2048, D = 128: the (R, 1) columns
    # take 512 B per row in the T(8, 128) layout, not 4 B
    assert not _lp_fits(2048)
    assert not _lp_fits(2048, fit_sum=False)
