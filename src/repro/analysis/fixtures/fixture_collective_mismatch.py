"""Seeded SPMD violation: a ``lax.cond`` inside a ``shard_map`` body
whose branches issue different collective sequences (one psums, the
other computes locally). If PEs diverge on the predicate, the psum
deadlocks — the collectives pass must flag this (SPMD002), and the
``check=False`` staging is deliberately *not* allowlisted
(SPMD003).
"""

from __future__ import annotations

from typing import Any, List, Tuple


def captured(P: int = 2) -> List[Tuple[str, Any]]:
    """Stage the defective program; returns ``[(name, jaxpr)]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as PS

    from repro.dist.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:P]), ("pe",))

    def body(x):
        pred = x[0, 0] > 0

        def with_psum(v):
            return jax.lax.psum(v, "pe")

        def without(v):
            return v * 2

        return jax.lax.cond(pred, with_psum, without, x)

    fn = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=PS("pe"),
            out_specs=PS("pe"),
            check=False,
        )
    )
    x = jnp.zeros((P, 4), jnp.int32)
    return [("fixture_collective_mismatch", jax.make_jaxpr(fn)(x))]
