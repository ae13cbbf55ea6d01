"""Distributed op scaling: 1 device vs every visible device.

Run under forced host devices to see the multi-device lanes::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only dist

On a 1-device host only the ``ndev1`` rows are produced (they are the
single-device fallback path, and double as the bench-compare anchor);
with N devices each op is measured on both mesh sizes so the derived
column reports the speedup (host-device "scaling" on CPU mostly checks
the collectives do not regress; real scaling needs accelerators).
"""
from __future__ import annotations

import numpy as np

from .common import measure, report


def run(quick: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import compression, dframe, pipeline

    n = 1 << 16 if quick else 1 << 20
    domain = 1 << 10
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, domain, n).astype(np.int64))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    build = jnp.asarray(
        rng.choice(np.arange(4 * domain), domain // 2, replace=False).astype(np.int64)
    )

    ndevs = sorted({1, jax.device_count()})
    times = {}
    for ndev in ndevs:
        mesh = dframe.data_mesh(ndev)

        def gsum():
            return jax.block_until_ready(
                dframe.dist_groupby_sum(mesh, keys, vals, domain)
            )

        def semi():
            return jax.block_until_ready(
                dframe.dist_semi_join_mask(mesh, keys, build)
            )

        def repart():
            return jax.block_until_ready(
                dframe.dist_repartition_by_key(mesh, keys, vals, capacity=n)[0]
            )

        g = jnp.asarray(rng.normal(size=(ndev, n // ndev)).astype(np.float32))

        def f(gl):
            mean, resid = compression.compressed_mean(gl[0], "data")
            return mean[None], resid[None]

        cmean_fn = jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"),),
            out_specs=(P("data"), P("data")), check_vma=False,
        )

        def cmean():
            return jax.block_until_ready(cmean_fn(g)[0])

        for name, fn in (
            ("groupby_sum", gsum),
            ("semi_join", semi),
            ("repartition", repart),
            ("compressed_mean", cmean),
        ):
            t = measure(fn, repeats=3, warmup=1)
            times[(name, ndev)] = t
            derived = f"rows_per_s={n / t:.3e}"
            if ndev > 1 and (name, 1) in times:
                derived += f";speedup_vs_1dev={times[(name, 1)] / t:.2f}"
            report(f"dist/{name}/ndev{ndev}", t, derived)

    # pipeline: stages = all devices (only meaningful with >1, but the
    # 1-stage lane anchors the schedule overhead)
    L, D, B, M = 8, 64, 8, 8
    W = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.normal(size=(M, B, D)).astype(np.float32))
    for ndev in ndevs:
        if L % ndev:
            continue
        pmesh = jax.make_mesh((ndev,), ("pipe",))

        def pipe():
            return jax.block_until_ready(
                pipeline.pipeline_forward(pmesh, lambda w, h: jnp.tanh(h @ w), W, x, L)
            )

        report(f"dist/pipeline/ndev{ndev}", measure(pipe, repeats=3, warmup=1))
