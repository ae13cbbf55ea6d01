"""Sharded dataframe ops over a 1-D device mesh.

The paper's single-device kernels (factorize-then-join, dense group-by)
scale out the way partitioned dataframe engines do:

- ``dist_groupby_sum`` — every shard reduces its rows into a dense
  ``(domain,)`` accumulator (the same segment-reduction the single-device
  engine uses; optionally through the Pallas sorted-segment kernel), then
  one ``psum`` over the mesh axis combines the shard partials.  The
  all-reduce moves ``O(domain)`` floats per device instead of
  ``O(n)`` rows — the classic dense-aggregate shuffle avoidance.
- ``dist_semi_join_mask`` — the build side is broadcast (replicated) to
  every shard; each shard probes its local rows with the engine's
  sorted-membership kernel.  Right-sized for TPC-H-style selective
  semi/anti joins where the build side is small.
- ``dist_repartition_by_key`` — a hash-partition all-to-all: rows are
  routed to ``splitmix64(key) % ndev`` so each key's rows land on
  exactly one shard (the precondition for shard-local joins or
  group-bys over huge key domains).  Fixed-capacity send buckets give
  static shapes; rows beyond a bucket's capacity are counted in
  ``dropped`` (capacity >= n guarantees a lossless shuffle).

Every op takes *global* arrays plus a mesh and works unchanged on a
1-device mesh (single-device fallback).  Inputs whose length does not
divide the mesh size are padded with null keys (negative), which every
op already ignores.  ``data_mesh`` builds its axis as ``Auto``: the
outputs carry no mesh axis in their type, so the engine's single-device
code (``mask_rows`` compacting a semi-join mask) consumes them as is.

``dist_groupby_sum`` and ``dist_semi_join_mask`` count their calls in
the ``repro.obs`` counters ``dist.groupby_sum`` and ``dist.semi_join``.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import hashing, prims
from repro.core.config import CONFIG
from repro.core.join import member_sorted

AXIS = "data"


# ----------------------------------------------------------------------
# mesh + routing helpers
# ----------------------------------------------------------------------
def data_mesh(ndev: int | None = None):
    """A 1-D mesh over (the first ``ndev``) visible devices."""
    n = jax.device_count() if ndev is None else ndev
    return jax.make_mesh((n,), (AXIS,), axis_types=(AxisType.Auto,))


def dist_enabled(nrows: int) -> bool:
    """Should the engine take the sharded route for an ``nrows`` input?

    ``CONFIG.distributed``: 'off' never; 'force' always (tests use this
    with a 1-device mesh); 'auto' only when more than one device is
    visible and the input is large enough to amortize dispatch.
    """
    mode = CONFIG.distributed
    if mode == "off":
        return False
    if mode == "force":
        return True
    return jax.device_count() > 1 and nrows >= CONFIG.dist_min_rows


def _from_chunks(arr, dtype) -> jax.Array:
    """Accept a chunk sequence (repro.store column chunks, or any list
    of host arrays) as one device array — a chunk is the store's
    natural shard unit, so chunked columns feed the all-to-all without
    a host-side copy round-trip through a monolithic array."""
    if isinstance(arr, (list, tuple)):
        parts = [jnp.asarray(np.asarray(c), dtype=dtype) for c in arr]
        if not parts:
            return jnp.zeros((0,), dtype=dtype)
        return jnp.concatenate(parts)
    return jnp.asarray(arr)


def _pad_to(mesh, axis: str, keys: jax.Array, vals: jax.Array | None):
    """Pad to a multiple of the mesh size with null keys / zero values."""
    ndev = mesh.shape[axis]
    n = int(keys.shape[0])
    pad = (-n) % ndev
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), -1, keys.dtype)])
        if vals is not None:
            vals = jnp.concatenate([vals, jnp.zeros((pad,), vals.dtype)])
    return keys, vals, n


# ----------------------------------------------------------------------
# shard-local dense segment sum (reused by the group-by route)
# ----------------------------------------------------------------------
def local_dense_sum(
    keys: jax.Array, vals: jax.Array, domain: int, *, use_pallas: bool = False
) -> jax.Array:
    """Dense ``(domain,)`` sums of ``vals`` bucketed by ``keys``.

    Negative keys (nulls / padding) contribute nothing.  With
    ``use_pallas`` the reduction runs through the sorted-segment Pallas
    kernel (``kernels/segment_reduce``) after a shard-local sort —
    the TPU MXU formulation; the default is the XLA scatter-add, which
    preserves the input dtype (float64 analytics on CPU hosts).
    """
    ok = keys >= 0
    safe = jnp.where(ok, keys, domain).astype(jnp.int32)
    vz = jnp.where(ok, vals, jnp.zeros((), vals.dtype))
    if use_pallas:
        from repro.kernels import ops

        safe, vz = jax.lax.sort((safe, vz), num_keys=1, is_stable=False)
        return ops.segment_sum_sorted(vz, safe, domain + 1)[:domain]
    out = jnp.zeros((domain + 1,), vz.dtype).at[safe].add(vz)
    return out[:domain]


# ----------------------------------------------------------------------
# sharded group-by sum
# ----------------------------------------------------------------------
def dist_groupby_sum(
    mesh,
    keys: jax.Array,
    vals: jax.Array,
    domain: int,
    axis: str = AXIS,
    *,
    use_pallas: bool = False,
) -> jax.Array:
    """Global dense group-by sum: shard-local segment sums + ``psum``.

    ``keys`` are dense group ids in ``[0, domain)`` (negative = null);
    returns the replicated ``(domain,)`` per-group sums.
    """
    obs.metrics.counter("dist.groupby_sum").inc()
    keys, vals, _ = _pad_to(mesh, axis, keys, vals)

    def shard(k, v):
        return jax.lax.psum(local_dense_sum(k, v, domain, use_pallas=use_pallas), axis)

    fn = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(keys, vals)


# ----------------------------------------------------------------------
# broadcast-build semi join
# ----------------------------------------------------------------------
def dist_semi_join_mask(
    mesh, probe: jax.Array, build: jax.Array, axis: str = AXIS
) -> jax.Array:
    """``exists(probe[i] in build)`` with the probe side sharded.

    The build side is sorted once, broadcast to every shard (replicated
    in-spec) and probed with the engine's sorted-membership kernel;
    negative probe keys (nulls) never match, matching SQL semi/anti-join
    semantics.
    """
    obs.metrics.counter("dist.semi_join").inc()
    if int(build.shape[0]) == 0:
        return jnp.zeros(probe.shape, dtype=bool)
    probe, _, n = _pad_to(mesh, axis, probe, None)
    fn = jax.shard_map(
        member_sorted,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(probe, prims.sort(build))[:n]


# ----------------------------------------------------------------------
# hash-partition all-to-all
# ----------------------------------------------------------------------
def dist_repartition_by_key(
    mesh,
    keys: jax.Array,
    vals: jax.Array,
    capacity: int,
    axis: str = AXIS,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Shuffle rows so each key's rows land on exactly one shard.

    Rows are routed to shard ``splitmix64(key) % ndev``.  Each source
    shard owns a fixed send bucket of ``ceil(capacity / ndev)`` slots
    per destination (static shapes for the all-to-all); a source's rows
    beyond its bucket are counted in ``dropped``.  A source shard holds
    at most ``ceil(len(keys) / ndev)`` rows, so ``capacity >= len(keys)``
    guarantees no bucket overflows — the shuffle is lossless.

    Returns ``(keys2, vals2, valid, dropped)``: global slot arrays of
    length ``ndev * ndev * ceil(capacity / ndev)``, a boolean mask of
    the occupied slots, and the replicated global overflow count.

    ``keys``/``vals`` may each be a *sequence of chunks* (e.g. the
    per-chunk physical arrays of a ``repro.store`` column) instead of
    one array — the chunk grid is the store's natural shard unit, and
    the row order is the chunks' concatenation order.
    """
    ndev = mesh.shape[axis]
    keys = _from_chunks(keys, jnp.int64)
    vals = _from_chunks(vals, None)
    # ceil: a source shard holds ceil(n/ndev) rows, so capacity >= n
    # really does guarantee every row fits its bucket (lossless)
    bucket = max(1, -(-capacity // ndev))
    keys, vals, _ = _pad_to(mesh, axis, keys, vals)

    def shard(k, v):
        nl = k.shape[0]
        ok = k >= 0
        dest = (hashing.splitmix64(k.astype(jnp.int64)) % np.uint64(ndev)).astype(
            jnp.int32
        )
        dest = jnp.where(ok, dest, ndev)  # nulls/padding -> trash bucket
        order = jnp.argsort(dest)  # stable: ties keep row order
        ks, vs, ds, oks = k[order], v[order], dest[order], ok[order]
        counts = jnp.zeros((ndev + 1,), jnp.int32).at[ds].add(1)
        offs = jnp.cumsum(counts) - counts
        within = jnp.arange(nl, dtype=jnp.int32) - offs[ds]
        fits = oks & (within < bucket)
        dropped_local = jnp.sum(oks & ~fits)
        slot = jnp.where(fits, ds * bucket + within, ndev * bucket)
        buf_k = (
            jnp.full((ndev * bucket + 1,), -1, ks.dtype)
            .at[slot]
            .set(jnp.where(fits, ks, -1))[: ndev * bucket]
            .reshape(ndev, bucket)
        )
        buf_v = (
            jnp.zeros((ndev * bucket + 1,), vs.dtype)
            .at[slot]
            .set(jnp.where(fits, vs, jnp.zeros((), vs.dtype)))[: ndev * bucket]
            .reshape(ndev, bucket)
        )
        rk = jax.lax.all_to_all(buf_k, axis, split_axis=0, concat_axis=0)
        rv = jax.lax.all_to_all(buf_v, axis, split_axis=0, concat_axis=0)
        return (
            rk.reshape(-1),
            rv.reshape(-1),
            (rk >= 0).reshape(-1),
            jax.lax.psum(dropped_local, axis),
        )

    fn = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P()),
        check_vma=False,
    )
    return fn(keys, vals)
