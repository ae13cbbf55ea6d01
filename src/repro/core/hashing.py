"""Composite-key construction (paper §IV-B).

The paper's insight — build the composite key **once per row** in
row-major (transposed) layout as an immutable tuple, instead of
incrementally per column with mutable keys — maps to TPU as:

- *exact packing*: factorized codes have known cardinalities, so a
  k-tuple packs into one int64 by Horner's rule when the domain product
  fits 63 bits.  The packed scalar IS the immutable tuple.
- *hash fallback*: when the domain overflows, a splitmix64-style mix
  combines the columns (collision odds ~ n^2 / 2^64; documented).

Finding distinct keys then becomes sort + run-boundary detection, and
the per-group reduction a segment op — the TPU replacement for Mojo's
Dict insert.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import prims
from .frame import INT, TensorFrame

_SPLIT_K1 = np.uint64(0x9E3779B97F4A7C15)
_SPLIT_K2 = np.uint64(0xBF58476D1CE4E5B9)
_SPLIT_K3 = np.uint64(0x94D049BB133111EB)

MAX_PACK = (1 << 62)


def splitmix64(x: jax.Array) -> jax.Array:
    z = x.astype(jnp.uint64) + _SPLIT_K1
    z = (z ^ (z >> np.uint64(30))) * _SPLIT_K2
    z = (z ^ (z >> np.uint64(27))) * _SPLIT_K3
    return z ^ (z >> np.uint64(31))


def hash_combine(cols: Sequence[jax.Array]) -> jax.Array:
    """64-bit tuple hash of k integer columns (the Alg.2 hash step)."""
    h = jnp.zeros(cols[0].shape, dtype=jnp.uint64)
    for c in cols:
        h = splitmix64(h ^ splitmix64(c.astype(jnp.uint64)))
    # shift into non-negative int64 for sorting/searchsorted
    return (h >> np.uint64(1)).astype(INT)


def key_codes(frame: TensorFrame, name: str) -> Tuple[jax.Array, int]:
    """Dense (codes, cardinality) for a grouping/join key column.

    dict/obj columns already have dense codes; integer-like columns are
    range-compressed (val - min) when the range is sane, else densified
    via a device unique (host-syncs the count — eager engine).
    """
    m = frame.meta(name)
    if m.kind == "dict":
        return frame.col_values(name), int(m.dictionary.shape[0])
    if m.kind == "obj":
        codes, dictionary = frame.offloaded[name].codes()
        return codes, int(dictionary.shape[0])
    if m.kind == "float":
        # group-by on measures (e.g. TPC-H Q10's c_acctbal): a value's
        # rank in the sorted column is its code
        f = frame.col_values(name)
        if f.shape[0] == 0:
            return f.astype(INT), 1
        return prims.float_rank(f), int(f.shape[0]) + 1
    arr = frame.col_values(name)
    if arr.shape[0] == 0:
        return arr, 1
    lo, hi = frame.int_bounds(name)  # cached; one fused fetch on miss
    span = hi - lo + 1
    if span <= max(4 * arr.shape[0], 1 << 20):
        return arr - lo, span
    # sparse domain: densify
    _, codes, m = distinct(arr, lohi=(lo, hi))
    return codes, m


def composite_key(
    frame: TensorFrame, keys: Sequence[str]
) -> Tuple[jax.Array, bool, Optional[int]]:
    """Single int64 composite key per row.

    Returns (key_array, exact, dense_domain) — exact=True when keys
    pack losslessly; dense_domain is the packed domain size when every
    packed value is additionally guaranteed non-negative (no dict/obj
    null sentinels), which lets ``distinct`` take its sort-free dense
    path.  This is the transposed single-pass construction of Alg. 2:
    all key columns are gathered first ("transpose"), then combined
    row-wise.
    """
    cols: List[Tuple[jax.Array, int]] = [key_codes(frame, k) for k in keys]
    prod = 1
    for _, card in cols:
        prod *= max(1, card)
        if prod >= MAX_PACK:
            break
    if prod < MAX_PACK:
        packed = jnp.zeros((frame.nrows,), dtype=INT)
        for codes, card in cols:
            packed = packed * np.int64(max(1, card)) + codes.astype(INT)
        dense_ok = all(
            not (
                frame.meta(k).kind in ("dict", "obj") and frame.has_nulls(k)
            )
            for k in keys
        )
        return packed, True, (prod if dense_ok else None)
    return hash_combine([c for c, _ in cols]), False, None


#: Fuse the dense-distinct phases with jit at this size (shapes repeat
#: in real workloads; small test shapes stay op-by-op, no compiles).
_JIT_MIN_ROWS = 1 << 17


def _dense_rank(packed: jax.Array, domain: int):
    present = jnp.zeros((domain,), dtype=INT).at[packed].set(1)
    ranks = prims.prefix_sum(present) - 1  # value -> its sorted-unique id
    return present, ranks


def _dense_compact(packed, present, ranks, m: int):
    gids = ranks[packed]
    dest = jnp.where(present == 1, ranks, np.int64(m))
    uniques = (
        jnp.zeros((m + 1,), dtype=packed.dtype)
        .at[dest]
        .set(jnp.arange(present.shape[0], dtype=packed.dtype))[:m]
    )
    return uniques, gids.astype(INT)


_dense_rank_jit = jax.jit(_dense_rank, static_argnums=(1,))
_dense_compact_jit = jax.jit(_dense_compact, static_argnums=(3,))


def distinct(
    packed: jax.Array,
    domain: Optional[int] = None,
    lohi: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, int]:
    """(sorted_uniques, group_ids, n_groups). Host-syncs n_groups.

    Dense path: when the caller knows the packed values are dense
    non-negative codes over a small ``domain`` (exact composite
    packing), distinct-finding needs NO sort at all — scatter presence
    bits, prefix-sum them into ranks, gather.  O(n + domain), the
    classic dense group-by.

    Sort path: one argsort + run boundaries + two scatters: group ids
    are the run ranks scattered back through the sort permutation, and
    the uniques compact by rank — no second sort (``jnp.unique``) and
    no binary search.  ``lohi`` bounds the keys when the caller knows
    them; otherwise the argsort fetches them.
    """
    n = int(packed.shape[0])
    if n == 0:
        return packed, packed, 0
    if domain is not None and 0 < domain <= 4 * n:  # O(n+domain) stays O(n)
        jit = n >= _JIT_MIN_ROWS
        present, ranks = (_dense_rank_jit if jit else _dense_rank)(
            packed, domain
        )
        m = int(prims.to_host(ranks[-1], "hashing.distinct")) + 1
        uniques, gids = (_dense_compact_jit if jit else _dense_compact)(
            packed, present, ranks, m
        )
        return uniques, gids, m
    order = prims.argsort(packed, lohi)
    sp = packed[order]
    boundary = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sp[1:] != sp[:-1]]
    )
    ranks = prims.prefix_sum(boundary.astype(INT)) - 1  # group id, sorted order
    m = int(prims.to_host(ranks[-1], "hashing.distinct")) + 1
    uniques = jnp.zeros((m,), dtype=packed.dtype).at[ranks].set(sp)
    gids = jnp.zeros((n,), dtype=INT).at[order].set(ranks)
    return uniques, gids, m
