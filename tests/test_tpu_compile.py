"""The engine's Pallas kernels compile for a TPU v5e, natively.

No chip is needed: the TPU compiler compiles for a described v5e
topology.  Each test asserts that the kernel reached the compiled
program as a Mosaic custom call (``tpu_custom_call``), i.e. it ran
neither in interpret mode nor as a plain XLA fallback.  The persistent
compilation cache stays off around these compiles: entries written for
a described chip cannot be read back without one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.hash32x2 import hash32x2_pallas
from repro.kernels.segment_reduce import segment_sum_sorted_pallas
from repro.kernels.substr_find import exists_before_pallas, substr_find_pallas

N_ROWS = 1 << 20
N_STRINGS = 1 << 16
WIDTH = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _native(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_hash32x2_compiles_native(one_chip):
    cols = jax.ShapeDtypeStruct((N_ROWS, 2), jnp.int32, sharding=one_chip)
    _native(lambda c: hash32x2_pallas(c, interpret=False), cols)


def test_segment_sum_sorted_compiles_native(one_chip):
    vals = jax.ShapeDtypeStruct((N_ROWS,), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip)
    _native(
        lambda v, i: segment_sum_sorted_pallas(v, i, 4096, interpret=False),
        vals,
        ids,
    )


@pytest.mark.parametrize("kernel", ["substr_find", "exists_before"])
def test_substr_kernels_compile_native(one_chip, kernel):
    packed = jax.ShapeDtypeStruct((N_STRINGS, WIDTH), jnp.uint8, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((N_STRINGS,), jnp.int32, sharding=one_chip)
    special = jnp.asarray(np.frombuffer(b"special", dtype=np.uint8))
    requests = jnp.asarray(np.frombuffer(b"requests", dtype=np.uint8))
    if kernel == "substr_find":
        fn = lambda p, n: substr_find_pallas(p, n, special, interpret=False)  # noqa: E731
    else:
        fn = lambda p, n: exists_before_pallas(  # noqa: E731
            p, n, special, requests, interpret=False
        )
    _native(fn, packed, lens)
