"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref oracle."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hash32x2 import hash32x2_pallas
from repro.kernels.segment_reduce import segment_sum_sorted_pallas
from repro.kernels.substr_find import exists_before_pallas, substr_find_pallas
from repro.kernels.wkv6 import wkv6_pallas


# ----------------------------------------------------------------------
# hash32x2
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 7, 1024, 3000])
@pytest.mark.parametrize("k", [1, 2, 5])
def test_hash32x2_matches_ref(n, k):
    rng = np.random.default_rng(n * 31 + k)
    cols = jnp.asarray(rng.integers(0, 2**31, size=(n, k), dtype=np.int32))
    got = hash32x2_pallas(cols, block_rows=256, interpret=True)
    want = ref.hash32x2(cols)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hash32x2_distributes():
    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.integers(0, 1000, size=(20000, 2), dtype=np.int32))
    h = np.asarray(ref.hash32x2(cols))
    buckets = h[:, 0] % 16
    counts = np.bincount(buckets, minlength=16)
    assert counts.min() > 0.8 * counts.mean()  # roughly uniform


# ----------------------------------------------------------------------
# substr_find
# ----------------------------------------------------------------------
def _pack(strings, L=64):
    n = len(strings)
    buf = np.zeros((n, L), np.uint8)
    lens = np.zeros((n,), np.int32)
    for i, s in enumerate(strings):
        b = s.encode()[:L]
        buf[i, : len(b)] = np.frombuffer(b, np.uint8)
        lens[i] = len(b)
    return jnp.asarray(buf), jnp.asarray(lens)


@pytest.mark.parametrize("L", [16, 64, 128])
@pytest.mark.parametrize("pat", ["ab", "special", "x"])
def test_substr_find_matches_ref(L, pat):
    rng = np.random.default_rng(hash((L, pat)) % 2**31)
    alphabet = list("abspecialx yz")
    strs = ["".join(rng.choice(alphabet, rng.integers(0, L))) for _ in range(733)]
    packed, lens = _pack(strs, L)
    p = jnp.asarray(np.frombuffer(pat.encode(), np.uint8))
    got = substr_find_pallas(packed, lens, p, block_rows=128, interpret=True)
    want = ref.substr_find(packed, lens, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # python ground truth
    truth = np.array([s.find(pat) if len(s) else -1 for s in strs], np.int32)
    np.testing.assert_array_equal(np.asarray(want), truth)


def test_exists_before_matches_python():
    strs = [
        "the special customer filed requests",
        "requests then special",
        "special",
        "",
        "specialrequests",
        "many special words and more requests here",
    ]
    packed, lens = _pack(strs, 64)
    a = jnp.asarray(np.frombuffer(b"special", np.uint8))
    b = jnp.asarray(np.frombuffer(b"requests", np.uint8))
    got = np.asarray(exists_before_pallas(packed, lens, a, b, block_rows=128, interpret=True))
    want = np.asarray(ref.exists_before(packed, lens, a, b))

    def truth(s):
        i = s.find("special")
        return i >= 0 and s.find("requests", i + len("special")) >= 0

    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.array([truth(s) for s in strs]))


# ----------------------------------------------------------------------
# segment_reduce
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n,m", [(1, 1), (100, 5), (5000, 1000), (4096, 4096)])
@pytest.mark.parametrize("gaps", [False, True])
def test_segment_sum_sorted_matches_ref(n, m, gaps):
    rng = np.random.default_rng(n + m)
    ids = np.sort(rng.integers(0, m, n)).astype(np.int32)
    if gaps:  # sparse ids exercise the rank-based path
        ids = np.sort(rng.choice(np.arange(0, 4 * m, 4), n)).astype(np.int32)
        m_eff = 4 * m
    else:
        m_eff = m
    vals = rng.normal(size=n).astype(np.float32)
    got = segment_sum_sorted_pallas(
        jnp.asarray(vals), jnp.asarray(ids), m_eff, block_rows=256, interpret=True
    )
    want = ref.segment_sum_sorted(jnp.asarray(vals), jnp.asarray(ids), m_eff)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [(1, 4, 2, 128, 32), (2, 8, 2, 256, 64), (1, 2, 2, 64, 16)])
def test_flash_attention_matches_ref(dtype, B, Hq, Hkv, S, D):
    rng = np.random.default_rng(B * S + Hq)
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    got = flash_attention_pallas(q, k, v, causal=True, bq=64, bk=64)
    want = ref.mha_reference(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_noncausal():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=False, bq=64, bk=64)
    want = ref.mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# wkv6
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,T,D", [(1, 2, 64, 16), (2, 3, 128, 32)])
def test_wkv6_matches_ref(dtype, B, H, T, D):
    rng = np.random.default_rng(B + T)
    r = jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.5, dtype)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.5, dtype)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.5, dtype)
    w = jnp.asarray(rng.uniform(0.7, 0.999, size=(B, H, T, D)), dtype)
    u = jnp.asarray(rng.normal(size=(H, D)) * 0.1, dtype)
    y_got, s_got = wkv6_pallas(r, k, v, w, u, bt=32)
    y_want, s_want = ref.wkv6_reference(r, k, v, w, u)
    tol = 4e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(y_got, np.float32), np.asarray(y_want, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), rtol=tol, atol=tol)


def test_wkv6_state_chaining():
    """Running two half-sequences with carried state == one full run."""
    rng = np.random.default_rng(9)
    B, H, T, D = 1, 2, 64, 16
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.5, jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.8, 0.99, size=(B, H, T, D)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, D)) * 0.1, jnp.float32)
    y_full, s_full = ref.wkv6_reference(r, k, v, w, u)
    half = T // 2
    y1, s1 = wkv6_pallas(r[:, :, :half], k[:, :, :half], v[:, :, :half], w[:, :, :half], u, bt=32)
    y2, s2 = wkv6_pallas(r[:, :, half:], k[:, :, half:], v[:, :, half:], w[:, :, half:], u, state=s1, bt=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_full[:, :, :half]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, :, half:]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=1e-4, atol=1e-4)
