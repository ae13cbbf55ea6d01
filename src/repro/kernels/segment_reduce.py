"""Pallas TPU kernel: segmented sum over *sorted* segment ids.

The group-by aggregation hot spot (Alg. 2 step 4).  TPUs have no fast
vector scatter, so the per-block reduction is reformulated for the
vector and matrix units: within a block of BN rows the (sorted) ids are
*ranked* by run boundaries (rank = number of id changes so far, always
< BN regardless of id gaps), computed as one MXU product of the boundary
flags with a triangular (BN, BN) matrix; a (BN slots, BN rows) one-hot
over ranks then reduces the block to per-run partials and each run's
segment id with lane reductions.  The (tiny) cross-block combine is a
scatter-add done by the XLA wrapper.

Layout: the inputs travel as one (1, n) row, blocked (1, BN) — lane
dense, and legal under the (8, 128) tiling because the row dimension is
the whole array; the per-slot outputs come back as (n, 1) columns
blocked (BN, 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BN = 512


def _kernel(vals_ref, ids_ref, partial_ref, slotid_ref):
    vals = vals_ref[...].astype(jnp.float32)  # (1, BN)
    ids = ids_ref[...]  # (1, BN) int32, sorted ascending
    bn = vals.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    prev = pltpu.roll(ids, 1, 1)  # prev[i] = ids[i - 1] (wraps at 0)
    boundary = ((ids != prev) & (lane > 0)).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    upper = (row <= col).astype(jnp.float32)
    # rank[i] = sum_{j <= i} boundary[j]; 0/1 terms, so exact in f32
    rank = jnp.dot(boundary, upper, preferred_element_type=jnp.float32)
    onehot = rank.astype(jnp.int32) == row  # (BN slots, BN rows)
    partial_ref[...] = jnp.sum(jnp.where(onehot, vals, 0.0), axis=1, keepdims=True)
    # segment id owning each slot (ids are >= 0; empty slots get 0 and
    # carry a zero partial)
    slotid_ref[...] = jnp.max(jnp.where(onehot, ids, 0), axis=1, keepdims=True)


def segment_sum_sorted_pallas(
    values: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    *,
    block_rows: int = _BN,
    interpret: bool,
) -> jax.Array:
    """values (n,), seg_ids (n,) sorted ascending, ids >= 0.

    ``block_rows`` is a multiple of 128 (one lane-dense block)."""
    n = values.shape[0]
    if n == 0:
        return jnp.zeros((num_segments,), dtype=jnp.float32)
    pad = (-n) % block_rows
    if pad:
        values = jnp.pad(values, (0, pad))
        # pad with the last id so padding lands in an existing bucket
        # with zero value contribution
        seg_ids = jnp.pad(seg_ids, (0, pad), mode="edge")
    total = values.shape[0]
    partials, slotids = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((total, 1), jnp.float32),
            jax.ShapeDtypeStruct((total, 1), jnp.int32),
        ),
        grid=(total // block_rows,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(
        values.astype(jnp.float32).reshape(1, total),
        seg_ids.astype(jnp.int32).reshape(1, total),
    )
    out = jnp.zeros((num_segments,), dtype=jnp.float32)
    return out.at[slotids.reshape(-1)].add(partials.reshape(-1))

