"""Capacity-based top-k Mixture-of-Experts with locality-preserving dispatch.

Routing/dispatch runs inside ``shard_map`` so the token sort/gather/scatter
stays *local to each data shard* (no global argsort collectives).  Expert
weights shard over the "model" axis on the expert dim when divisible (EP),
else on the hidden dim (expert-TP).  In both layouts every model shard sees
all local tokens (replicated over "model"), computes its expert slice, and a
single psum over "model" combines - no all-to-all in the baseline schedule.

A ``dense`` reference mode (all experts for all tokens, gate-weighted) backs
the unit tests: with ample capacity the dropping path must match it exactly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from .config import ModelConfig


def _act(cfg: ModelConfig, gate, up):
    if cfg.mlp_act == "silu_glu":
        return jax.nn.silu(gate) * up
    if cfg.mlp_act == "gelu_glu":
        return jax.nn.gelu(gate) * up
    if cfg.mlp_act == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up)


def router_probs(x, router_w, dtype=jnp.float32):
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1), logits


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    c = int(T * k / E * cf) + 1
    return max(8, -(-c // 8) * 8)   # round up to a multiple of 8


def _dispatch_local(x, gates, k: int, C: int, norm_topk: bool):
    """x: (T,d); gates: (T,E) fp32.  Returns (xe (E,C,d), table (E,C) token
    ids with OOB sentinel T, wtable (E,C) combine weights)."""
    T, d = x.shape
    E = gates.shape[1]
    w, ids = jax.lax.top_k(gates, k)                      # (T,k)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-9)
    e_flat = ids.reshape(-1)                              # (T*k,)
    onehot = (e_flat[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot             # rank within expert
    p_flat = jnp.sum(pos * onehot, axis=1)
    t_flat = jnp.arange(T * k, dtype=jnp.int32) // k
    table = jnp.full((E, C), T, jnp.int32)
    table = table.at[e_flat, p_flat].set(t_flat, mode="drop")
    wtable = jnp.zeros((E, C), w.dtype)
    wtable = wtable.at[e_flat, p_flat].set(w.reshape(-1), mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = x_pad[table]                                     # (E,C,d)
    return xe, table, wtable


def _expert_ffn(cfg: ModelConfig, blk, xe):
    """xe: (E_loc, C, d) -> (E_loc, C, d) through each expert's MLP slice."""
    up = jnp.einsum("ecd,edf->ecf", xe, blk["we_in"].astype(xe.dtype))
    if "we_gate" in blk:
        gate = jnp.einsum("ecd,edf->ecf", xe, blk["we_gate"].astype(xe.dtype))
    else:
        gate = None
    h = _act(cfg, gate, up)
    return jnp.einsum("ecf,efd->ecd", h, blk["we_out"].astype(xe.dtype))


def _combine_local(ye, table, wtable, T: int, d: int):
    out = jnp.zeros((T, d), ye.dtype)
    contrib = ye * wtable[..., None].astype(ye.dtype)
    return out.at[table.reshape(-1)].add(contrib.reshape(-1, d), mode="drop")


def aux_losses(gates, ids, E: int):
    """Load-balance loss (Switch) + router z-loss ingredients."""
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)    # (T,k,E)
    frac_tokens = onehot.sum((0, 1)) / (ids.shape[0] * ids.shape[1])
    frac_prob = gates.mean(0)
    return E * jnp.sum(frac_tokens * frac_prob)


def moe_block(blk, x, cfg: ModelConfig, mesh: Optional[Mesh] = None,
              data_axes: Tuple[str, ...] = ("data",), norm_topk: bool = True,
              impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    if impl == "dense" or mesh is None:
        gates, logits = router_probs(x.reshape(-1, d), blk["router"])
        w, ids = jax.lax.top_k(gates, k)
        if norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-9)
        full = jnp.zeros_like(gates).at[
            jnp.arange(gates.shape[0])[:, None], ids].set(w)
        xe = jnp.einsum("td,edf->tef", x.reshape(-1, d),
                        blk["we_in"].astype(x.dtype))
        ge = jnp.einsum("td,edf->tef", x.reshape(-1, d),
                        blk["we_gate"].astype(x.dtype)) if "we_gate" in blk else None
        h = _act(cfg, ge, xe)
        ye = jnp.einsum("tef,efd->ted", h, blk["we_out"].astype(x.dtype))
        out = jnp.einsum("ted,te->td", ye, full.astype(x.dtype))
        aux = aux_losses(gates, ids, E)
        out = out.reshape(B, S, d)
    else:
        model_n = mesh.shape["model"]
        ep = E % model_n == 0
        wspec = {n: P("model", None, None) if ep else P(None, None, "model")
                 for n in ("we_in", "we_gate", "we_out")}
        if not ep:
            wspec["we_out"] = P(None, "model", None)
        specs = {"router": P(None, None)}
        specs.update({n: wspec[n] for n in blk if n in wspec})
        xspec = P(data_axes, None, None)

        def local(x_l, *ws):
            wb = dict(zip(sorted(specs), ws))
            T = x_l.shape[0] * x_l.shape[1]
            xf = x_l.reshape(T, d)
            gates, logits = router_probs(xf, wb["router"])
            C = _capacity(T, k, E, cfg.capacity_factor)
            if ep:
                # each model shard owns E/model experts: slice dispatch tables
                E_loc = E // model_n
                xe, table, wtable = _dispatch_local(xf, gates, k, C, norm_topk)
                mi = jax.lax.axis_index("model")
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, mi * E_loc, E_loc, 0)
                ye = _expert_ffn(cfg, wb, sl(xe))
                part = _combine_local(ye, sl(table), sl(wtable), T, d)
            else:
                xe, table, wtable = _dispatch_local(xf, gates, k, C, norm_topk)
                ye = _expert_ffn(cfg, wb, xe)   # hidden dim is model-sharded
                part = _combine_local(ye, table, wtable, T, d)
            out = jax.lax.psum(part, "model")
            w_top, ids = jax.lax.top_k(gates, k)
            aux = aux_losses(gates, ids, E)
            return out.reshape(x_l.shape), aux

        names = sorted(specs)
        out, aux = shard_map(
            local, mesh=mesh,
            in_specs=(xspec,) + tuple(specs[n] for n in names),
            out_specs=(xspec, P()),
            check_vma=False)(x, *[blk[n] for n in names])
        aux = aux / 1.0   # already averaged per shard; identical across shards

    if cfg.n_shared_experts:
        up = x @ blk["shared_w_in"].astype(x.dtype)
        gate = x @ blk["shared_w_gate"].astype(x.dtype) \
            if "shared_w_gate" in blk else None
        out = out + _act(cfg, gate, up) @ blk["shared_w_out"].astype(x.dtype)
    return out, aux
