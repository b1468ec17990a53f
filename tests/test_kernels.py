"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.ssm import ssd_reference_sequential


def rand(key, shape, dtype=jnp.float32):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("s,h,kh,d", [
    (128, 4, 4, 64),     # MHA
    (256, 4, 2, 64),     # GQA 2:1
    (256, 8, 1, 32),     # MQA
    (512, 2, 2, 128),    # long-seq, MXU-width head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, h, kh, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (2, s, h, d), dtype)
    k = rand(ks[1], (2, s, kh, d), dtype)
    v = rand(ks[2], (2, s, kh, d), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 256, 4, 32))
    k = rand(ks[1], (1, 256, 2, 32))
    v = rand(ks[2], (1, 256, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n_layers", [None, 3])
@pytest.mark.parametrize("L,kh,d", [(256, 2, 64), (512, 4, 32), (128, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(L, kh, d, dtype, n_layers):
    """One layer's K/V, or (``n_layers``) the stacked cache read at each
    layer index: bitwise the call on that layer's own buffers."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, H = 3, 4
    stack = () if n_layers is None else (n_layers,)
    q = rand(ks[0], (B, 1, H, d), dtype)
    k = rand(ks[1], stack + (B, L, kh, d), dtype)
    v = rand(ks[2], stack + (B, L, kh, d), dtype)
    kv_len = jnp.array([1, L // 2, L], jnp.int32)  # heterogeneous depths
    q_off = kv_len - 1
    if n_layers is None:
        k, v = k[None], v[None]
    for layer in range(k.shape[0]):
        out = ops.flash_decode(q, k[layer], v[layer], kv_len=kv_len,
                               q_offset=q_off)
        if n_layers is not None:
            stacked = ops.flash_decode(q, k, v, kv_len=kv_len,
                                       q_offset=q_off, layer=layer)
            assert np.array_equal(np.asarray(stacked), np.asarray(out))
        want = ref.reference_decode_attention(q, k[layer], v[layer],
                                              kv_len=kv_len, q_offset=q_off)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype],
        )


def test_flash_decode_window():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, H, L, d = 2, 2, 256, 32
    q = rand(ks[0], (B, 1, H, d))
    k = rand(ks[1], (B, L, H, d))
    v = rand(ks[2], (B, L, H, d))
    kv_len = jnp.array([200, 256])
    out = ops.flash_decode(q, k, v, kv_len=kv_len, q_offset=kv_len - 1,
                           window=64)
    want = ref.reference_decode_attention(q, k, v, kv_len=kv_len,
                                          q_offset=kv_len - 1, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("s,h,p,n,chunk", [
    (128, 2, 16, 8, 32),
    (256, 3, 32, 16, 64),
    (64, 1, 64, 128, 64),   # mamba2-130m-like head
])
def test_ssd_scan_sweep(s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    B = 2
    x = rand(ks[0], (B, s, h, p))
    a = -jnp.abs(rand(ks[1], (B, s, h))) * 0.1
    Bm = rand(ks[2], (B, s, h, n))
    Cm = rand(ks[3], (B, s, h, n))
    y, fs = ops.ssd_scan(x, a, Bm, Cm, chunk=chunk)
    y_ref, fs_ref = ssd_reference_sequential(x, a, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fs_ref), atol=1e-4)


def test_ssd_scan_chunk_invariance():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    B, s, h, p, n = 1, 128, 2, 8, 4
    x = rand(ks[0], (B, s, h, p))
    a = -jnp.abs(rand(ks[1], (B, s, h))) * 0.05
    Bm = rand(ks[2], (B, s, h, n))
    Cm = rand(ks[3], (B, s, h, n))
    y32, f32_ = ops.ssd_scan(x, a, Bm, Cm, chunk=32)
    y64, f64_ = ops.ssd_scan(x, a, Bm, Cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y64), atol=1e-4)
    np.testing.assert_allclose(np.asarray(f32_), np.asarray(f64_), atol=1e-4)
