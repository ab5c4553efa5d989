"""Parameter templates: one source of truth for shapes, logical axes, init.

``template(cfg)`` returns a pytree of ``ParamMeta`` describing every weight.
``init_params`` materializes it; ``abstract_params`` gives ShapeDtypeStructs;
``sharding.tree_pspecs`` maps the logical axes to mesh PartitionSpecs.  Layer blocks are stacked along a leading "layers" axis so the
stacks are consumed by ``lax.scan`` (constant compile time in depth).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Axes = Tuple[Optional[str], ...]


@dataclasses.dataclass(frozen=True)
class ParamMeta:
    shape: Tuple[int, ...]
    axes: Axes                 # logical axis names, len == len(shape)
    init: str = "normal"       # normal | zeros | ones
    scale: float = 1.0         # stddev multiplier for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes)


def _norm(d: int) -> ParamMeta:
    return ParamMeta((d,), (None,), "ones")


def _dense(fan_in: int, fan_out: int, axes: Axes, scale: float = 1.0) -> ParamMeta:
    return ParamMeta((fan_in, fan_out), axes, "normal", scale / np.sqrt(fan_in))


def _attention_block(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamMeta]:
    d, hd = cfg.d_model, cfg.head_dim
    q_dim, kv_dim = cfg.q_dim, cfg.kv_dim
    if cfg.mla and not cross:
        lora, r = cfg.kv_lora_rank, cfg.rope_head_dim
        blk = {
            "wq": _dense(d, cfg.n_heads * (hd + r), ("embed", "heads")),
            "w_dkv": _dense(d, lora + r, ("embed", None)),
            "kv_norm": _norm(lora),
            "w_uk": _dense(lora, cfg.n_heads * hd, ("kv_lora", "heads")),
            "w_uv": _dense(lora, cfg.n_heads * hd, ("kv_lora", "heads")),
            "wo": _dense(q_dim, d, ("heads", "embed")),
        }
        return blk
    blk = {
        "wq": _dense(d, q_dim, ("embed", "heads")),
        # kv projections get their own logical axis: replicated when
        # n_kv_heads doesn't divide the model axis (a ragged shard would
        # force GSPMD partial-sum all-reduces over sub-head groups - §Perf)
        "wk": _dense(d, kv_dim, ("embed", "kv_heads")),
        "wv": _dense(d, kv_dim, ("embed", "kv_heads")),
        "wo": _dense(q_dim, d, ("heads", "embed")),
    }
    if cfg.qkv_bias:
        blk["bq"] = ParamMeta((q_dim,), ("heads",), "zeros")
        blk["bk"] = ParamMeta((kv_dim,), ("kv_heads",), "zeros")
        blk["bv"] = ParamMeta((kv_dim,), ("kv_heads",), "zeros")
    return blk


def _mlp_block(cfg: ModelConfig, d_ff: int) -> Dict[str, ParamMeta]:
    d = cfg.d_model
    blk = {"w_in": _dense(d, d_ff, ("embed", "mlp")),
           "w_out": _dense(d_ff, d, ("mlp", "embed"))}
    if cfg.mlp_act.endswith("_glu"):
        blk["w_gate"] = _dense(d, d_ff, ("embed", "mlp"))
    return blk


def _moe_block(cfg: ModelConfig) -> Dict[str, ParamMeta]:
    d, E, fe = cfg.d_model, cfg.n_experts, cfg.d_expert
    glu = cfg.mlp_act.endswith("_glu")
    s = 1.0 / np.sqrt(d)
    blk = {
        "router": _dense(d, E, ("embed", None)),
        "we_in": ParamMeta((E, d, fe), ("expert", "embed", "mlp"), "normal", s),
        "we_out": ParamMeta((E, fe, d), ("expert", "mlp", "embed"), "normal",
                            1.0 / np.sqrt(fe)),
    }
    if glu:
        blk["we_gate"] = ParamMeta((E, d, fe), ("expert", "embed", "mlp"),
                                   "normal", s)
    if cfg.n_shared_experts:
        blk.update({f"shared_{k}": v for k, v in
                    _mlp_block(cfg, cfg.n_shared_experts * fe).items()})
    return blk


def _rwkv_block(cfg: ModelConfig) -> Dict[str, ParamMeta]:
    d, a = cfg.d_model, cfg.q_dim
    blk = {
        "ln1": _norm(d),
        # static token-shift mixing coefficients (RWKV6 uses LoRA-modulated
        # mixing; we keep the decay LoRA data-dependent, mixing static).
        **{f"mix_{n}": ParamMeta((d,), (None,), "zeros") for n in "rkvgw"},
        "w_r": _dense(d, a, ("embed", "heads")),
        "w_k": _dense(d, a, ("embed", "heads")),
        "w_v": _dense(d, a, ("embed", "heads")),
        "w_g": _dense(d, a, ("embed", "heads")),
        "decay_a": _dense(d, 64, ("embed", None)),
        "decay_b": _dense(64, a, (None, "heads")),
        "decay_base": ParamMeta((a,), ("heads",), "zeros"),
        "bonus_u": ParamMeta((a,), ("heads",), "zeros"),
        "gn_scale": ParamMeta((a,), ("heads",), "ones"),
        "wo": _dense(a, d, ("heads", "embed")),
        "ln2": _norm(d),
        "mix_f": ParamMeta((d,), (None,), "zeros"),
        **_mlp_block(cfg, cfg.d_ff),
    }
    return blk


def _ssm_block(cfg: ModelConfig) -> Dict[str, ParamMeta]:
    """SSD-style selective SSM branch (hymba's mamba heads, state N)."""
    d, H, N = cfg.d_model, cfg.n_heads, cfg.ssm_state
    P = cfg.head_dim
    return {
        "ws_in": _dense(d, H * P, ("embed", "heads")),
        "ws_dt": _dense(d, H, ("embed", "heads")),
        "dt_bias": ParamMeta((H,), ("heads",), "zeros"),
        "ws_B": _dense(d, H * N, ("embed", "heads")),
        "ws_C": _dense(d, H * N, ("embed", "heads")),
        "A_log": ParamMeta((H,), ("heads",), "zeros"),
        "ssm_D": ParamMeta((H,), ("heads",), "ones"),
        "ssm_norm": _norm(H * P),
        "ws_out": _dense(H * P, d, ("heads", "embed")),
    }


def _decoder_layer(cfg: ModelConfig, moe: bool) -> Dict[str, ParamMeta]:
    if cfg.rwkv:
        return _rwkv_block(cfg)
    blk = {"ln1": _norm(cfg.d_model), **_attention_block(cfg),
           "ln2": _norm(cfg.d_model)}
    if moe:
        blk.update(_moe_block(cfg))
    else:
        blk.update(_mlp_block(cfg, cfg.dense_d_ff if cfg.first_k_dense and not moe
                              and cfg.n_experts else cfg.d_ff))
    if cfg.ssm:
        blk.update(_ssm_block(cfg))
    if cfg.arch_kind == "encdec":
        blk.update({"ln_x": _norm(cfg.d_model)})
        blk.update({f"x_{k}": v for k, v in
                    _attention_block(cfg, cross=True).items()})
    return blk


def _encoder_layer(cfg: ModelConfig) -> Dict[str, ParamMeta]:
    return {"ln1": _norm(cfg.d_model), **_attention_block(cfg, cross=True),
            "ln2": _norm(cfg.d_model), **_mlp_block(cfg, cfg.d_ff)}


def template(cfg: ModelConfig) -> Dict:
    """Full parameter template.  Layer dicts are *unstacked*; `stacked_axes`
    marks which top-level entries carry a leading layer axis."""
    n_moe_layers = cfg.n_layers - cfg.first_k_dense if cfg.n_experts else 0
    tpl = {
        "embed": ParamMeta((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                           "normal", 1.0),
        "final_norm": _norm(cfg.d_model),
        "layers": _decoder_layer(cfg, moe=bool(cfg.n_experts)),
    }
    if not cfg.tie_embeddings:
        tpl["lm_head"] = _dense(cfg.d_model, cfg.vocab, ("embed", "vocab"))
    if cfg.first_k_dense:
        tpl["dense_layers"] = _decoder_layer(cfg, moe=False)
    if cfg.arch_kind == "encdec":
        tpl["enc_layers"] = _encoder_layer(cfg)
        tpl["enc_norm"] = _norm(cfg.d_model)
    return tpl


def stack_counts(cfg: ModelConfig) -> Dict[str, int]:
    out = {"layers": cfg.n_layers - cfg.first_k_dense}
    if cfg.first_k_dense:
        out["dense_layers"] = cfg.first_k_dense
    if cfg.arch_kind == "encdec":
        out["enc_layers"] = cfg.n_enc_layers
    return out


def _finalize(cfg: ModelConfig, leaf_fn) -> Dict:
    """Apply leaf_fn(meta, stacked_n) over the template with layer stacking."""
    tpl = template(cfg)
    stacks = stack_counts(cfg)
    out = {}
    for key, sub in tpl.items():
        n = stacks.get(key)
        if isinstance(sub, dict):
            out[key] = {k: leaf_fn(m, n) for k, m in sub.items()}
        else:
            out[key] = leaf_fn(sub, None)
    return out


def abstract_params(cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)

    def leaf(meta: ParamMeta, n):
        shape = ((n,) + meta.shape) if n else meta.shape
        return jax.ShapeDtypeStruct(shape, dtype)
    return _finalize(cfg, leaf)


def logical_axes(cfg: ModelConfig) -> Dict:
    def leaf(meta: ParamMeta, n):
        return (("layers",) + meta.axes) if n else meta.axes
    return _finalize(cfg, leaf)


def init_params(key: jax.Array, cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    metas, treedef = jax.tree.flatten(
        _finalize(cfg, lambda m, n: (m, n)),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], ParamMeta))
    keys = jax.random.split(key, len(metas))

    def materialize(k, meta_n):
        meta, n = meta_n
        shape = ((n,) + meta.shape) if n else meta.shape
        if meta.init == "zeros":
            return jnp.zeros(shape, dtype)
        if meta.init == "ones":
            return jnp.ones(shape, dtype)
        return (jax.random.normal(k, shape, jnp.float32) * meta.scale).astype(dtype)

    leaves = [materialize(k, m) for k, m in zip(keys, metas)]
    return jax.tree.unflatten(treedef, leaves)
