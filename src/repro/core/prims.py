"""Scans, compaction and integer sorts that compile quickly for the TPU.

The TPU compiler lowers a 1-D cumulative sum or maximum as one
reduce-window over the whole array, and a sort's compile time grows
with its key width and its number of operands.  Compiled for a v5e at
2^20 elements: a 1-D int64 ``cumsum`` takes about 60 s, a ``cummax``
90 s, ``jnp.nonzero`` two minutes, a stable int64 ``argsort`` 50 s,
while a one-operand int64 sort takes 18 s and a one-operand int32
sort 4 s.  The op-by-op engine compiles again for every new length,
so the engine uses these forms instead:

- ``prefix_sum`` / ``running_max``: log-step (Hillis-Steele) scans,
  elementwise shifts that compile in about a second at any length.
- ``compact``: the positions of a mask's set entries
  (``jnp.nonzero(mask, size=count)``) as a prefix sum and one scatter.
- ``sort`` / ``argsort`` of integer keys: the key minus its lower
  bound and, for ``argsort``, the row number pack into ONE operand of
  the narrowest integer type that holds both.  A stable argsort is then
  a one-operand sort, and the operand is padded to a power of two so
  one compiled sort serves every length up to its bucket.

Inside a traced program (no host sync for bounds) use
``lax.sort(x, is_stable=False)`` for one-operand sorts: stability means
nothing there, and the stable form compiles three times slower.

Every device->host fetch of the engine goes through ``to_host``, which
counts it (``obs.metrics`` group ``device``: ``host_syncs`` and
``by_site``) and, with tracing on, times it as a ``device.sync`` span.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro import obs as _obs

INT = jnp.int64

#: Device->host fetches since the last reset, per call site.  A plain
#: dict increment, counted with tracing off too, like the engine's other
#: STATS dicts.
SYNC_SITES: Dict[str, int] = {}


def _syncs_snapshot() -> Dict:
    sites = dict(SYNC_SITES)
    return {"host_syncs": sum(sites.values()), "by_site": sites}


_obs.metrics.register_group("device", _syncs_snapshot, SYNC_SITES.clear)


def to_host(x: jax.Array, site: str) -> np.ndarray:
    """``np.asarray(x)``: wait for the device, copy ``x`` to the host.

    The engine's one device->host fetch: ``site`` names the caller in
    the per-site counts and on the ``device.sync`` span, whose time is
    the host blocked on the device.  It is also the one place that lets
    a transfer through ``jax.transfer_guard_device_to_host``."""
    SYNC_SITES[site] = SYNC_SITES.get(site, 0) + 1
    with _obs.span("device.sync", site=site), jax.transfer_guard_device_to_host(
        "allow"
    ):
        return np.asarray(x)


def _log_step_scan(x: jax.Array, combine, identity) -> jax.Array:
    n = x.shape[0]
    k = 1
    while k < n:
        shifted = jnp.concatenate([jnp.full((k,), identity, x.dtype), x[:-k]])
        x = combine(x, shifted)
        k *= 2
    return x


@jax.jit
def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D array (``jnp.cumsum``)."""
    return _log_step_scan(x, jnp.add, 0)


@jax.jit
def running_max(x: jax.Array) -> jax.Array:
    """Inclusive running maximum of a 1-D integer array (``lax.cummax``)."""
    return _log_step_scan(x, jnp.maximum, jnp.iinfo(x.dtype).min)


@jax.jit
def float_rank(x: jax.Array) -> jax.Array:
    """Each value's first position in the sorted 1-D float array, NaN
    last: equal values share a rank and ranks keep the values' order.
    (The TPU keeps float64 as two float32s and cannot bitcast it to an
    integer key.)"""
    rank = jnp.searchsorted(lax.sort(x, is_stable=False), x)
    return jnp.where(jnp.isnan(x), x.shape[0], rank).astype(INT)


@partial(jax.jit, static_argnums=1)
def compact(mask: jax.Array, count: int) -> jax.Array:
    """Positions of the ``count`` set entries of a 1-D bool ``mask``, in
    order: ``jnp.nonzero(mask, size=count)[0]`` as int64."""
    n = mask.shape[0]
    pos = prefix_sum(mask.astype(jnp.int32 if n < 2**31 else INT)) - 1
    dest = jnp.where(mask, pos, count)
    out = jnp.zeros((count + 1,), INT).at[dest].set(jnp.arange(n, dtype=INT))
    return out[:count]


# ----------------------------------------------------------------------
# integer sorts
# ----------------------------------------------------------------------
@jax.jit
def _minmax(x: jax.Array) -> jax.Array:
    return jnp.stack([x.min(), x.max()]).astype(INT)


def bounds(x: jax.Array) -> Tuple[int, int]:
    """``(min, max)`` of a non-empty integer array, in one fetch."""
    lo, hi = to_host(_minmax(x), "prims.bounds").tolist()
    return int(lo), int(hi)


def _bucket(n: int) -> int:
    return max(1024, 1 << (n - 1).bit_length())


@partial(jax.jit, static_argnames=("shift", "dtype", "size"))
def _pack(keys, lo, *, shift: int, dtype, size: int):
    v = (keys - lo).astype(dtype)
    if shift:
        v = (v << shift) | jnp.arange(keys.shape[0], dtype=dtype)
    pad = jnp.full((size - keys.shape[0],), jnp.iinfo(dtype).max, dtype)
    return jnp.concatenate([v, pad])


@jax.jit
def _sort_one(x: jax.Array) -> jax.Array:
    return lax.sort(x, is_stable=False)


@partial(jax.jit, static_argnames=("n",))
def _unpack_values(s, lo, *, n: int):
    return s[:n].astype(INT) + lo


@partial(jax.jit, static_argnames=("n", "shift"))
def _unpack_rows(s, *, n: int, shift: int):
    return (s[:n] & ((1 << shift) - 1)).astype(INT)


@jax.jit
def _argsort_wide(keys: jax.Array) -> jax.Array:
    rows = jnp.arange(keys.shape[0], dtype=INT)
    return lax.sort((keys, rows), num_keys=2, is_stable=False)[1]


def sort(keys: jax.Array, lohi: Optional[Tuple[int, int]] = None) -> jax.Array:
    """Ascending sort of 1-D integer keys (int64 result).  ``lohi``
    bounds the keys when the caller knows them; otherwise they are
    fetched."""
    n = int(keys.shape[0])
    if n <= 1:
        return keys.astype(INT)
    lo, hi = bounds(keys) if lohi is None else lohi
    dtype = jnp.int32 if hi - lo < 2**31 - 1 else INT
    s = _sort_one(_pack(keys, np.int64(lo), shift=0, dtype=dtype, size=_bucket(n)))
    return _unpack_values(s, np.int64(lo), n=n)


def argsort(keys: jax.Array, lohi: Optional[Tuple[int, int]] = None) -> jax.Array:
    """Stable argsort of 1-D integer keys (int64 row numbers): equal keys
    keep their input order.  ``lohi`` as in ``sort``."""
    n = int(keys.shape[0])
    if n <= 1:
        return jnp.zeros((n,), INT)
    lo, hi = bounds(keys) if lohi is None else lohi
    shift = (n - 1).bit_length()
    width = (hi - lo).bit_length() + shift
    if width > 62:  # key and row do not share one int64
        return _argsort_wide(keys)
    dtype = jnp.int32 if width <= 30 else INT
    s = _sort_one(_pack(keys, np.int64(lo), shift=shift, dtype=dtype, size=_bucket(n)))
    return _unpack_rows(s, n=n, shift=shift)
