"""Pallas TPU kernel: per-row first-occurrence substring search on a
packed (n, L) uint8 string tensor.

This is the compute core of the paper's headline TPC-H Q13 UDF
(``not_string_exists_before``): stateless string matching, evaluated as
sliding-window byte comparisons over VMEM tiles — one row block per
grid step, the m pattern bytes unrolled statically so the VPU sees pure
vector compares/ands.

Layout: the per-row vectors (lengths, start offsets, the result) travel
as (n, 1) columns blocked (BN, 1), which fit the TPU's (8, 128) tiling;
1-D blocks do not.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BN = 512


def _make_kernel(pattern_bytes: tuple, L: int, use_start: bool):
    m = len(pattern_bytes)

    def kernel(packed_ref, lens_ref, start_ref, out_ref):
        b = packed_ref[...].astype(jnp.int32)  # (BN, L)
        lens = lens_ref[...].astype(jnp.int32)  # (BN, 1)
        npos = L - m + 1
        match = jnp.ones((b.shape[0], npos), dtype=jnp.bool_)
        for j in range(m):
            match = match & (b[:, j : j + npos] == jnp.int32(pattern_bytes[j]))
        pos = jax.lax.broadcasted_iota(jnp.int32, (b.shape[0], npos), 1)
        ok = match & (pos + m <= lens)
        if use_start:
            ok = ok & (pos >= start_ref[...].astype(jnp.int32))
        scores = jnp.where(ok, pos, jnp.int32(npos + 1))
        first = scores.min(axis=1, keepdims=True)
        out_ref[...] = jnp.where(first <= npos, first, jnp.int32(-1))

    return kernel


def substr_find_pallas(
    packed: jax.Array,
    lens: jax.Array,
    pattern: jax.Array,
    start: Optional[jax.Array] = None,
    *,
    block_rows: int = _BN,
    interpret: bool,
) -> jax.Array:
    n, L = packed.shape
    m = int(pattern.shape[0])
    if m == 0:
        return jnp.zeros((n,), dtype=jnp.int32)
    if m > L:
        return jnp.full((n,), -1, dtype=jnp.int32)
    pat = tuple(int(x) for x in np.asarray(pattern))
    use_start = start is not None
    if start is None:
        start = jnp.zeros((n,), dtype=jnp.int32)
    pad = (-n) % block_rows
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0)))
        lens = jnp.pad(lens, (0, pad))
        start = jnp.pad(start, (0, pad))
    total = packed.shape[0]
    column = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _make_kernel(pat, L, use_start),
        out_shape=jax.ShapeDtypeStruct((total, 1), jnp.int32),
        grid=(total // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, L), lambda i: (i, 0)), column, column],
        out_specs=column,
        interpret=interpret,
    )(packed, lens.reshape(total, 1), start.reshape(total, 1))
    return out[:n, 0]


def exists_before_pallas(packed, lens, pat_a, pat_b, **kw) -> jax.Array:
    fa = substr_find_pallas(packed, lens, pat_a, **kw)
    start = jnp.where(fa >= 0, fa + int(pat_a.shape[0]), 0).astype(jnp.int32)
    fb = substr_find_pallas(packed, lens, pat_b, start=start, **kw)
    return (fa >= 0) & (fb >= 0)
