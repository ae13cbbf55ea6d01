"""Aggregation functions: whole-frame and per-segment (group-by) forms.

Per-group reductions are XLA segment ops — the TPU-native replacement
for Mojo's dictionary accumulation (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Dict, List, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from .frame import INT, TensorFrame, float_dtype
from .prims import to_host

AggSpec = Tuple[str, str, str]  # (out_name, fn, column) — column '' for size

AGG_FNS = ("sum", "mean", "min", "max", "count", "size", "nunique", "first")


def normalize_specs(specs) -> List[AggSpec]:
    out: List[AggSpec] = []
    if isinstance(specs, dict):
        for out_name, v in specs.items():
            fn, colname = v if isinstance(v, (tuple, list)) else (v, "")
            out.append((out_name, fn, colname))
    else:
        for item in specs:
            out.append(tuple(item))  # type: ignore[arg-type]
    for name, fn, _ in out:
        if fn not in AGG_FNS:
            raise ValueError(f"unknown aggregation {fn!r} for {name!r}")
    return out


def _num_values(frame: TensorFrame, name: str) -> jax.Array:
    m = frame.meta(name)
    if m.kind in ("float", "int", "bool", "date"):
        # view-aware: a lazy frame gathers only this column
        return frame.col_values(name)
    raise TypeError(f"aggregation over non-numeric column {name!r}")


def _count_weights(frame: TensorFrame, name: str) -> jax.Array:
    """1 where the value is non-null else 0 (SQL COUNT(col))."""
    valid = frame.valid_array(name)
    if valid is None:
        return jnp.ones((frame.nrows,), dtype=INT)
    return valid.astype(INT)


def _segment_sum(vals: jax.Array, gids: jax.Array, m: int) -> jax.Array:
    """Segment sum with an optional sharded route.

    The single-device path is the plain XLA segment op; when the
    distributed route is enabled (CONFIG.distributed / device count, see
    repro.dist.dframe.dist_enabled) the reduction runs as shard-local
    dense sums + psum over a data mesh spanning all visible devices.
    """
    from repro.core.config import CONFIG

    if CONFIG.distributed != "off":
        from repro.dist import dframe

        if dframe.dist_enabled(int(vals.shape[0])):
            return dframe.dist_groupby_sum(dframe.data_mesh(), gids, vals, m)
    return jax.ops.segment_sum(vals, gids, m)


# ----------------------------------------------------------------------
# segment (grouped) aggregation
# ----------------------------------------------------------------------
def segment_agg(
    frame: TensorFrame,
    gids: jax.Array,
    m: int,
    fn: str,
    colname: str,
):
    if fn == "size":
        return _segment_sum(jnp.ones((frame.nrows,), dtype=INT), gids, m)
    if fn == "count":
        return _segment_sum(_count_weights(frame, colname), gids, m)
    if fn == "nunique":
        return _segment_nunique(frame, gids, m, colname)
    if fn == "first":
        rep = jax.ops.segment_min(
            jnp.arange(frame.nrows, dtype=INT), gids, m
        )
        return frame.col_values(colname)[rep]
    vals = _num_values(frame, colname)
    valid = frame.valid_array(colname)
    if fn == "sum":
        if valid is not None:
            vals = jnp.where(valid, vals, jnp.zeros((), dtype=vals.dtype))
        return _segment_sum(vals, gids, m)
    if fn == "mean":
        if valid is not None:
            vals = jnp.where(valid, vals, jnp.zeros((), dtype=vals.dtype))
        s = _segment_sum(vals.astype(float_dtype()), gids, m)
        c = _segment_sum(_count_weights(frame, colname), gids, m)
        return s / jnp.maximum(c, 1).astype(float_dtype())
    if fn == "min":
        if valid is not None:
            big = jnp.asarray(np.iinfo(np.int64).max if not jnp.issubdtype(vals.dtype, jnp.floating) else np.inf, dtype=vals.dtype)
            vals = jnp.where(valid, vals, big)
        return jax.ops.segment_min(vals, gids, m)
    if fn == "max":
        if valid is not None:
            small = jnp.asarray(np.iinfo(np.int64).min if not jnp.issubdtype(vals.dtype, jnp.floating) else -np.inf, dtype=vals.dtype)
            vals = jnp.where(valid, vals, small)
        return jax.ops.segment_max(vals, gids, m)
    raise ValueError(fn)


def _segment_nunique(frame: TensorFrame, gids: jax.Array, m: int, colname: str) -> jax.Array:
    """COUNT(DISTINCT col) per group: distinct (gid, code) pairs, then a
    per-gid count — pure tensor ops, no dictionaries."""
    from . import hashing

    codes, card = hashing.key_codes(frame, colname) if frame.meta(colname).kind != "float" else (None, 0)
    if codes is None:
        raise TypeError("nunique over float column")
    valid = frame.valid_array(colname)
    card64 = np.int64(max(1, card))
    pair = gids * card64 + codes.astype(INT)
    if valid is not None:
        # shunt nulls into a per-group sentinel bucket that we exclude
        pair = jnp.where(valid, pair, np.int64(-1))
    uniq, _, mu = hashing.distinct(pair)
    pair_gid = jnp.where(uniq >= 0, uniq // card64, np.int64(m))
    ones = (uniq >= 0).astype(INT)
    return jax.ops.segment_sum(ones, pair_gid, m + 1)[:m]


# ----------------------------------------------------------------------
# whole-frame aggregation
# ----------------------------------------------------------------------
def frame_agg(frame: TensorFrame, specs) -> Dict[str, Union[float, int]]:
    out: Dict[str, Union[float, int]] = {}
    gids = jnp.zeros((frame.nrows,), dtype=INT)
    for out_name, fn, colname in normalize_specs(specs):
        if frame.nrows == 0:
            # Pandas semantics (the paper's comparison target): empty
            # SUM is 0; empty mean/min/max are NaN
            if fn in ("count", "size", "nunique"):
                out[out_name] = 0
            elif fn == "sum":
                out[out_name] = 0.0
            else:
                out[out_name] = float("nan")
            continue
        v = segment_agg(frame, gids, 1, fn, colname)[0]
        v = to_host(v, "agg.frame_agg")[()]
        out[out_name] = v.item() if hasattr(v, "item") else v
    return out
