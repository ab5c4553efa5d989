"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,Sq,Skv,H,KV,hd", [
    (2, 128, 128, 4, 2, 64), (1, 256, 256, 4, 4, 64),
    (2, 100, 100, 2, 1, 32), (1, 64, 192, 4, 2, 128),
    (1, 96, 96, 8, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32),
                                           (False, 0)])
def test_flash_attention(B, Sq, Skv, H, KV, hd, dtype, causal, window):
    if not causal and Sq != Skv:
        q = jax.random.normal(KEY, (B, Sq, H, hd), dtype)
    q = jax.random.normal(KEY, (B, Sq, H, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Skv, KV, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Skv, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="pallas_interpret")
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol(dtype),
                               rtol=tol(dtype))


@pytest.mark.parametrize("B,H,KV,hd,S", [
    (2, 8, 2, 64, 512), (1, 4, 4, 128, 300), (3, 5, 1, 32, 64),
    (2, 16, 8, 64, 1024),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, KV, hd, S, dtype):
    q = jax.random.normal(KEY, (B, H, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd), dtype)
    kl = jax.random.randint(jax.random.PRNGKey(3), (B,), 1, S + 1)
    out = ops.decode_attention(q, k, v, kl, impl="pallas_interpret")
    want = ref.decode_attention_ref(q, k, v, kl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol(dtype),
                               rtol=tol(dtype))


@pytest.mark.parametrize("B,S,H,K,V", [
    (2, 64, 2, 16, 16), (1, 48, 4, 32, 64), (2, 16, 1, 8, 8),
    (1, 128, 2, 64, 64),
])
def test_rwkv6(B, S, H, K, V):
    r = jax.random.normal(KEY, (B, S, H, K))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, K)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, V))
    lw = -jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (B, S, H, K)))
    u = jax.random.normal(jax.random.PRNGKey(4), (H, K)) * 0.1
    y1, s1 = ops.rwkv6(r, k, v, lw, u, impl="pallas_interpret")
    y2, s2 = ref.rwkv6_ref(r, k, v, jnp.clip(lw, -4.0, 0.0), u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4,
                               rtol=1e-4)


def test_rwkv6_chunked_matches_sequential_model_path():
    """models/linear_scan (the XLA path) == kernel == ref on one input."""
    from repro.models.linear_scan import chunked_linear_attention
    B, S, H, K, V = 2, 64, 2, 16, 16
    r = jax.random.normal(KEY, (B, S, H, K))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, K)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, V))
    lw = -jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (B, S, H, K)))
    u = jax.random.normal(jax.random.PRNGKey(4), (H, K)) * 0.1
    y_model, s_model = chunked_linear_attention(r, k, v, lw, u=u, chunk=16)
    y_ref, s_ref = ref.rwkv6_ref(r, k, v, jnp.clip(lw, -4.0, 0.0), u)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_model), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-4)
