"""GPipe-style microbatch pipeline over a ``pipe`` mesh axis.

Layers are split contiguously across ``S`` pipeline stages (each stage
holds ``n_layers / S`` consecutive layer weights via the sharded
in-spec).  Microbatches stream through the classic ``M + S - 1`` tick
schedule: at tick ``t`` stage 0 ingests microbatch ``t``, every stage
applies its layer slice to the activation it holds, and activations
shift one stage to the right with a ring ``ppermute`` (the wrap-around
into stage 0 is overwritten by the next ingest).  The last stage
finishes microbatch ``t - (S - 1)`` at tick ``t``.

This is the forward-only schedule — exactly what the serving engine
needs for model-parallel layer sharding — and it matches the sequential
reference bit-for-bit per microbatch since stages apply the very same
``block`` function.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

AXIS = "pipe"


def pipeline_forward(
    mesh,
    block: Callable[[jax.Array, jax.Array], jax.Array],
    weights: jax.Array,
    x: jax.Array,
    n_layers: int,
    axis: str = AXIS,
) -> jax.Array:
    """Run ``x`` (microbatches, batch, d) through ``n_layers`` blocks.

    ``weights`` is the stacked ``(n_layers, ...)`` per-layer parameters;
    ``block(w_l, h)`` applies one layer.  ``n_layers`` must divide
    evenly across the mesh's ``axis`` dimension.
    """
    stages = mesh.shape[axis]
    if n_layers % stages:
        raise ValueError(f"{n_layers} layers do not split over {stages} stages")
    n_micro = x.shape[0]

    def apply_stage(w, h):
        out, _ = jax.lax.scan(lambda c, wl: (block(wl, c), None), h, w)
        return out

    def shard(w, xs):
        # w: (n_layers/stages, ...) local slice; xs: (M, B, D) replicated
        sidx = jax.lax.axis_index(axis)
        buf = jnp.zeros(xs.shape[1:], xs.dtype)
        outs = jnp.zeros_like(xs)
        fwd = [(i, (i + 1) % stages) for i in range(stages)]

        def tick(t, carry):
            buf, outs = carry
            inp = xs[jnp.clip(t, 0, n_micro - 1)]
            h = apply_stage(w, jnp.where(sidx == 0, inp, buf))
            out_t = t - (stages - 1)
            write = (sidx == stages - 1) & (out_t >= 0)
            outs = jnp.where(
                write, outs.at[jnp.clip(out_t, 0, n_micro - 1)].set(h), outs
            )
            return jax.lax.ppermute(h, axis, fwd), outs

        _, outs = jax.lax.fori_loop(0, n_micro + stages - 1, tick, (buf, outs))
        # only the last stage holds real outputs; replicate them
        return jax.lax.psum(
            jnp.where(sidx == stages - 1, outs, jnp.zeros((), outs.dtype)), axis
        )

    fn = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(weights, x)
