"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Deliberately the simplest correct implementations: full score-matrix softmax
for attention, a sequential lax.scan over time for RWKV6 - no chunking, no
blocking, no numerical tricks beyond fp32 softmax.

The placement kernels in ``kernels.fitscore`` have their jnp twin in
``core.jaxsim._select_slot`` instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd); returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    m = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        m &= kpos <= qpos
    if window > 0:
        m &= qpos - kpos < window
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention_ref(q, k, v, kv_len, *, scale=None):
    """q: (B,H,hd) one token; k,v: (B,S,KV,hd); kv_len: (B,) valid lengths."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, :] < kv_len[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def rwkv6_ref(r, k, v, logw, u, *, initial_state=None):
    """Sequential RWKV6: r,k,logw (B,S,H,K); v (B,S,H,V); u (H,K).
    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k v."""
    B, S, H, K = k.shape
    V = v.shape[-1]
    f32 = lambda x: x.astype(jnp.float32)
    r, k, v, logw = f32(r), f32(k), f32(v), f32(logw)
    state = jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None \
        else f32(initial_state)

    def step(s, xs):
        rt, kt, vt, lw = xs
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt, s) \
            + jnp.einsum("bhk,hk->bh", rt * kt, f32(u))[..., None] * vt
        s = jnp.exp(lw)[..., None] * s + kv
        return s, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, logw))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state
