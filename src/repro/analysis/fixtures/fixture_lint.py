"""Seeded lint violations (AST-scanned only, never imported by the
pipeline): a jit-staged function calling host numpy and the Python
RNG (LNT001), a ``shard_map`` call without ``check=`` (LNT002),
and a ``.item()`` device sync treated as serve-hot-path code
(LNT003).
"""

import functools
import random

import jax
import numpy as np


@functools.partial(jax.jit, static_argnames=())
def staged_bad(x):
    noise = np.random.rand(*x.shape)  # LNT001: host RNG under jit
    pick = random.random()  # LNT001: Python RNG under jit
    return x + noise + pick


def build(mesh, spec, shard_map):
    return shard_map(  # LNT002: no explicit check=
        lambda v: v,
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
    )


def hot_path(result):
    return result.assignment.item()  # LNT003: device sync per request
