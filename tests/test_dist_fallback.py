"""Single-device fallback of every ``repro.dist`` op, plus the CONFIG
routing that sends TensorFrame group-by sums and semi/anti joins through
the sharded path.

These run in-process on the default (1-device CPU) backend — the same
shard_map programs the multi-device tests (tests/test_distributed.py)
run under 8 forced host devices, here on a 1-device mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import TensorFrame
from repro.core.config import CONFIG
from repro.dist import compression, dframe, pipeline


@pytest.fixture
def mesh1():
    return dframe.data_mesh(1)


def test_dist_groupby_sum_single_device(mesh1):
    rng = np.random.default_rng(0)
    n, domain = 1000, 13
    keys = jnp.asarray(rng.integers(0, domain, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = dframe.dist_groupby_sum(mesh1, keys, vals, domain)
    want = np.zeros(domain, np.float32)
    np.add.at(want, np.asarray(keys), np.asarray(vals))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_dist_groupby_sum_null_keys_and_pallas_reuse(mesh1):
    keys = jnp.asarray(np.array([0, -1, 1, 0, -1], dtype=np.int64))
    vals = jnp.asarray(np.array([1.0, 99.0, 2.0, 3.0, 99.0], dtype=np.float32))
    got = dframe.dist_groupby_sum(mesh1, keys, vals, 2)
    np.testing.assert_allclose(np.asarray(got), [4.0, 2.0])
    # shard-local reducer through the sorted-segment Pallas kernel
    got_p = dframe.local_dense_sum(keys, vals, 2, use_pallas=True)
    np.testing.assert_allclose(np.asarray(got_p), [4.0, 2.0], rtol=1e-6)


def test_dist_semi_join_mask_single_device(mesh1):
    rng = np.random.default_rng(1)
    probe = jnp.asarray(rng.integers(0, 50, 777).astype(np.int64))
    build = jnp.asarray(np.array([3, 7, 11, 42], dtype=np.int64))
    mask = dframe.dist_semi_join_mask(mesh1, probe, build)
    np.testing.assert_array_equal(
        np.asarray(mask), np.isin(np.asarray(probe), np.asarray(build))
    )


def test_dist_repartition_single_device_lossless(mesh1):
    rng = np.random.default_rng(2)
    n, domain = 500, 17
    keys = jnp.asarray(rng.integers(0, domain, n).astype(np.int64))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    k2, v2, valid, dropped = dframe.dist_repartition_by_key(mesh1, keys, vals, capacity=n)
    assert int(dropped) == 0
    kept = np.asarray(k2)[np.asarray(valid)]
    assert kept.shape[0] == n
    want = np.zeros(domain, np.float32)
    np.add.at(want, np.asarray(keys), np.asarray(vals))
    got = np.zeros(domain, np.float32)
    np.add.at(got, kept, np.asarray(v2)[np.asarray(valid)])
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_dist_repartition_overflow_accounting(mesh1):
    """capacity below n: the excess is counted in dropped, survivors stay
    consistent."""
    n, cap = 100, 40
    keys = jnp.asarray(np.zeros(n, dtype=np.int64))
    vals = jnp.asarray(np.ones(n, dtype=np.float32))
    k2, v2, valid, dropped = dframe.dist_repartition_by_key(mesh1, keys, vals, capacity=cap)
    assert int(dropped) == n - cap
    assert int(np.asarray(valid).sum()) == cap


def test_quantize_roundtrip_and_error_feedback():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=700).astype(np.float32))
    q, s, r = compression.quantize(x)
    deq = compression.dequantize(q, s, 700)
    # error bound: half a quantization step per block
    step = np.asarray(s).max()
    assert float(jnp.abs(x - deq).max()) <= 0.51 * step
    np.testing.assert_allclose(np.asarray(x - deq), np.asarray(r), atol=1e-6)
    # feeding the residual back recovers the lost mass
    q2, s2, r2 = compression.quantize(jnp.zeros_like(x), resid=r)
    deq2 = compression.dequantize(q2, s2, 700)
    np.testing.assert_allclose(
        np.asarray(deq + deq2), np.asarray(x), atol=2e-2
    )
    # all-zero input: scale falls back to 1, residual exactly zero
    qz, sz, rz = compression.quantize(jnp.zeros(256, jnp.float32))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) == 1.0)
    assert np.all(np.asarray(rz) == 0)


def test_compressed_mean_single_device():
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.normal(size=(1, 512)).astype(np.float32))

    def f(gl):
        mean, resid = compression.compressed_mean(gl[0], "data")
        return mean[None], resid[None]

    fn = jax.shard_map(
        f, mesh=mesh, in_specs=(P("data"),), out_specs=(P("data"), P("data")),
        check_vma=False,
    )
    mean, resid = fn(g)
    # 1-device mean == dequantized self; adding the residual restores x
    np.testing.assert_allclose(
        np.asarray(mean[0] + resid[0]), np.asarray(g[0]), atol=1e-6
    )


def test_pipeline_single_stage_matches_sequential():
    mesh = jax.make_mesh((1,), ("pipe",))
    rng = np.random.default_rng(5)
    L, D = 3, 8
    W = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.normal(size=(4, 2, D)).astype(np.float32))

    def block(w, h):
        return jnp.tanh(h @ w)

    def seq(h):
        for l in range(L):
            h = block(W[l], h)
        return h

    got = pipeline.pipeline_forward(mesh, block, W, x, n_layers=L)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.vmap(seq)(x)), rtol=1e-5, atol=1e-5
    )


# ----------------------------------------------------------------------
# CONFIG routing: the engine takes the sharded route end-to-end
# ----------------------------------------------------------------------
@pytest.fixture
def routed():
    prev = CONFIG.distributed
    CONFIG.distributed = "force"
    try:
        yield
    finally:
        CONFIG.distributed = prev


def _table(n=400, seed=6):
    rng = np.random.default_rng(seed)
    return TensorFrame.from_arrays(
        {
            "k": rng.integers(0, 11, n),
            "g": np.asarray(list("abc"))[rng.integers(0, 3, n)].astype(object),
            "v": rng.normal(size=n),
        }
    )


def test_routed_groupby_matches_local(routed):
    f = _table()
    got = f.groupby(["g", "k"]).agg([("s", "sum", "v"), ("m", "mean", "v"),
                                     ("n", "size", "")])
    CONFIG.distributed = "off"
    want = f.groupby(["g", "k"]).agg([("s", "sum", "v"), ("m", "mean", "v"),
                                      ("n", "size", "")])
    np.testing.assert_allclose(got.column("s"), want.column("s"), rtol=1e-12)
    np.testing.assert_allclose(got.column("m"), want.column("m"), rtol=1e-12)
    np.testing.assert_array_equal(got.column("n"), want.column("n"))


def test_routed_semi_and_anti_join_match_local(routed):
    f = _table(seed=7)
    right = TensorFrame.from_arrays({"k": np.array([1, 2, 3, 5, 8])})
    got_semi = f.join(right, on="k", how="semi")
    got_anti = f.join(right, on="k", how="anti")
    CONFIG.distributed = "off"
    want_semi = f.join(right, on="k", how="semi")
    want_anti = f.join(right, on="k", how="anti")
    np.testing.assert_array_equal(got_semi.column("v"), want_semi.column("v"))
    np.testing.assert_array_equal(got_anti.column("v"), want_anti.column("v"))


def test_auto_route_stays_local_on_one_device():
    assert CONFIG.distributed == "auto"
    # tier-1 runs on a single CPU device: auto must not shard
    assert not dframe.dist_enabled(1 << 30) or jax.device_count() > 1
