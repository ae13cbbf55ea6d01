"""Pallas TPU kernel: two-lane 32-bit tuple hashing (Alg. 2's hash step).

TPU adaptation of the paper's xxhash-based composite hashing: the VPU
has native 32-bit integer lanes (no 64-bit vector ops), so a 64-bit
tuple hash is computed as two independent 32-bit murmur-finalizer lanes
with different seeds.  Used by the distributed engine to hash-partition
rows for all_to_all repartitioning (group-by/join shuffles).

Block layout: rows are tiled (BN, k) into VMEM; each grid step mixes k
columns into both lanes entirely in registers.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

_BN = 1024


def _kernel(cols_ref, out_ref, *, k: int):
    cols = cols_ref[...].astype(jnp.uint32)  # (BN, k)
    n = cols.shape[0]
    lanes = []
    for seed in ref._SEEDS:
        h = jnp.full((n,), seed, dtype=jnp.uint32)
        for j in range(k):
            h = ref.fmix32(h ^ ref.fmix32(cols[:, j] + np.uint32(j + 1)))
        lanes.append(h)
    out_ref[...] = jnp.stack(lanes, axis=1)


def hash32x2_pallas(cols: jax.Array, *, block_rows: int = _BN, interpret: bool) -> jax.Array:
    n, k = cols.shape
    pad = (-n) % block_rows
    if pad:
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
    grid = (cols.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((cols.shape[0], 2), jnp.uint32),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 2), lambda i: (i, 0)),
        interpret=interpret,
    )(cols)
    return out[:n]
