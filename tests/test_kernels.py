"""Pallas kernels validated in interpret mode against the jnp oracles,
sweeping shapes and dtypes."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.wkv6 import wkv6

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 3e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,hq,hkv,hd,causal", [
    (2, 128, 128, 4, 2, 64, True),
    (1, 200, 200, 8, 8, 128, True),
    (2, 64, 256, 6, 2, 32, False),
    (1, 257, 257, 4, 1, 64, True),
    (2, 96, 96, 2, 2, 16, True),
])
def test_flash_attention(b, sq, sk, hq, hkv, hd, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, hd), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.mha_reference(q, k, v, causal=causal)
    err = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - want.astype(jnp.float32)))
    assert float(err) < _tol(dtype), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,hd,kvb", [
    (2, 256, 8, 2, 64, 64),
    (3, 100, 4, 4, 32, 32),
    (1, 1024, 16, 8, 128, 256),
    (2, 77, 6, 1, 64, 16),
])
def test_decode_attention(b, s, hq, hkv, hd, kvb, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd), dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, hd), dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, hd), dtype)
    lens = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention(q, kc, vc, lens, kv_block=kvb, interpret=True)
    want = ref.decode_attention_reference(q, kc, vc, lens)
    err = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - want.astype(jnp.float32)))
    assert float(err) < _tol(dtype), err


@pytest.mark.parametrize("b,t,h,n,chunk", [
    (2, 64, 2, 32, 16),
    (1, 100, 4, 64, 32),
    (2, 33, 1, 16, 8),
    (1, 16, 2, 64, 64),     # t < chunk
])
def test_wkv6(b, t, h, n, chunk):
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) * 0.5
               for i in range(3))
    w = jax.random.normal(ks[3], (b, t, h, n)) * 0.5
    u = jax.random.normal(ks[4], (h, n)) * 0.5
    s0 = jax.random.normal(ks[5], (b, h, n, n)) * 0.1
    y, sT = wkv6(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    yr, sr = ref.wkv6_reference(r, k, v, w, u, s0)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-4
    assert float(jnp.max(jnp.abs(sT - sr))) < 2e-4


def test_wkv6_chunked_ref_matches_plain():
    ks = jax.random.split(KEY, 5)
    b, t, h, n = 2, 70, 2, 32
    r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) * 0.5
               for i in range(3))
    w = jax.random.normal(ks[3], (b, t, h, n)) * 0.5
    u = jax.random.normal(ks[4], (h, n)) * 0.5
    y1, s1 = ref.wkv6_reference(r, k, v, w, u)
    y2, s2 = ref.wkv6_chunked(r, k, v, w, u, chunk=16)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-5


def test_flash_blocked_matches_naive_long():
    ks = jax.random.split(KEY, 3)
    b, s, hq, hkv, hd = 1, 500, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, hq, hd))
    k = jax.random.normal(ks[1], (b, s, hkv, hd))
    v = jax.random.normal(ks[2], (b, s, hkv, hd))
    out = ref.flash_attention_blocked(q, k, v, causal=True, q_block=128,
                                      kv_block=128)
    want = ref.mha_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


def test_flash_blocked_grad_matches_naive():
    """The checkpointed blocked attention must be differentiable and agree
    with the naive gradient."""
    ks = jax.random.split(KEY, 3)
    b, s, hq, hkv, hd = 1, 96, 2, 1, 16
    q = jax.random.normal(ks[0], (b, s, hq, hd))
    k = jax.random.normal(ks[1], (b, s, hkv, hd))
    v = jax.random.normal(ks[2], (b, s, hkv, hd))

    def f_blocked(q):
        return jnp.sum(ref.flash_attention_blocked(
            q, k, v, causal=True, q_block=32, kv_block=32) ** 2)

    def f_naive(q):
        return jnp.sum(ref.mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(f_blocked)(q)
    g2 = jax.grad(f_naive)(q)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 5e-4


@pytest.mark.parametrize("forced", ["ref", "interpret"])
def test_backend_refuses_non_pallas_on_tpu(forced, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_BACKEND", None)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", forced)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="without its Pallas kernels"):
        ops.backend()
    with pytest.raises(RuntimeError, match="without its Pallas kernels"):
        ops.set_backend(forced)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert ops.backend() == "pallas"
