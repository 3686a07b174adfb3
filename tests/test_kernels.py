"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Sk,H,KH,Dh,bq,bk,causal", [
    (1, 64, 64, 4, 4, 32, 32, 32, True),      # MHA square
    (2, 128, 128, 8, 2, 64, 64, 64, True),    # GQA
    (1, 96, 96, 4, 1, 32, 32, 32, True),      # MQA, ragged blocks
    (2, 64, 128, 4, 2, 16, 64, 64, False),    # cross-attn (non-causal)
    (1, 200, 200, 2, 2, 64, 64, 64, True),    # non-divisible seq (padding)
])
def test_flash_attention_sweep(B, Sq, Sk, H, KH, Dh, bq, bk, causal, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (B, Sq, H, Dh), dtype)
    k = _rand(k2, (B, Sk, KH, Dh), dtype)
    v = _rand(k3, (B, Sk, KH, Dh), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,Dh,bs", [
    (2, 128, 8, 2, 64, 64),
    (1, 300, 4, 1, 32, 128),                  # MQA + padding
    (3, 64, 4, 4, 16, 32),                    # MHA
])
def test_decode_attention_sweep(B, S, H, KH, Dh, bs, dtype):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = _rand(k1, (B, H, Dh), dtype)
    kc = _rand(k2, (B, S, KH, Dh), dtype)
    vc = _rand(k3, (B, S, KH, Dh), dtype)
    lens = jax.random.randint(k4, (B,), 1, S + 1)
    out = ops.decode_attention(q, kc, vc, lens, block_s=bs)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,S,H,KH,Dh,bs", [
    (3, 64, 8, 2, 64, 16),                    # GQA, four blocks
    (2, 32, 4, 1, 32, 32),                    # MQA, one block
])
def test_decode_attention_stacked_reads_each_layer(L, S, H, KH, Dh, bs,
                                                   dtype):
    """The stacked-cache kernel, read at each layer in place, against the
    reference over that layer alone; lengths 1, a block edge, one past it
    and the whole cache."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    lens = jnp.asarray([1, bs, min(bs + 1, S), S], jnp.int32)
    B = lens.shape[0]
    q = _rand(k1, (B, H, Dh), dtype)
    kc = _rand(k2, (L, B, S, KH, Dh), dtype)
    vc = _rand(k3, (L, B, S, KH, Dh), dtype)
    assert ops.stacked_fits(kc.shape, bs)
    for layer in range(L):
        out = ops.decode_attention_stacked(q, kc, vc, lens, jnp.int32(layer),
                                           block_s=bs)
        want = ref.decode_attention_ref(q, kc[layer], vc[layer], lens)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,H,P,N,chunk,bh", [
    (1, 64, 8, 16, 16, 16, 4),
    (2, 100, 16, 32, 64, 32, 8),              # padding tail
    (1, 48, 4, 64, 128, 16, 4),               # big state
])
def test_ssd_scan_sweep(B, L, H, P, N, chunk, bh, dtype):
    ks = jax.random.split(KEY, 5)
    x = _rand(ks[0], (B, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, 1, N))
    Cm = jax.random.normal(ks[4], (B, L, 1, N))
    y, fs = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, block_h=bh)
    yr, fsr = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(fs, fsr, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,br", [((4, 32, 128), 16), ((100, 96), 32),
                                      ((3, 5, 7, 64), 8)])
def test_rmsnorm_sweep(shape, br, dtype):
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, shape, dtype)
    w = _rand(k2, shape[-1:], dtype)
    out = ops.rmsnorm(x, w, block_rows=br)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_matches_model_chunked_attention():
    """Kernel agrees with the model's lax.scan flash implementation too."""
    from repro.models import layers as L
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 96, 4, 32))
    k = jax.random.normal(k2, (2, 96, 2, 32))
    v = jax.random.normal(k3, (2, 96, 2, 32))
    a = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    b = L.chunked_attention(q, k, v, causal=True, chunk=32)
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-4)
