"""Parameter specs: one source of truth for shapes, logical axes and init.

``param_specs(cfg)`` returns a nested dict of :class:`ParamSpec`; from it
we derive real params (init), ShapeDtypeStructs (dry-run) and logical-axis
trees (sharding) without writing the structure three times.
Per-layer specs get a leading ("layers", L) axis for scan-over-layers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    logical: tuple
    init: str = "normal"        # normal | zeros | ones | fanin
    dtype: Optional[str] = None


def _attn_specs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, h, hd), ("embed", "q_heads", "head_dim"), "fanin"),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), "fanin"),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), "fanin"),
        "wo": ParamSpec((h, hd, d), ("q_heads", "head_dim", "embed"), "fanin"),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), ("head_dim",), "ones")
        s["k_norm"] = ParamSpec((hd,), ("head_dim",), "ones")
    return s


def _mlp_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    s = {
        "w_in": ParamSpec((d, f), ("embed", "ffn"), "fanin"),
        "w_out": ParamSpec((f, d), ("ffn", "embed"), "fanin"),
    }
    if cfg.mlp_gated:
        s["w_gate"] = ParamSpec((d, f), ("embed", "ffn"), "fanin")
    return s


def _moe_specs(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    s = {
        "router": ParamSpec((d, e), ("embed", None), "fanin"),
        "w_in": ParamSpec((e, d, f), ("experts", "embed", "expert_ffn"), "fanin"),
        "w_out": ParamSpec((e, f, d), ("experts", "expert_ffn", "embed"), "fanin"),
    }
    if cfg.mlp_gated:
        s["w_gate"] = ParamSpec((e, d, f),
                                ("experts", "embed", "expert_ffn"), "fanin")
    return s


def _ssm_specs(cfg: ModelConfig) -> dict:
    d, din, h = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state         # B and C: G groups of N
    w = cfg.conv_width
    s = {
        "w_z": ParamSpec((d, din), ("embed", "ssm_inner"), "fanin"),
        "w_x": ParamSpec((d, din), ("embed", "ssm_inner"), "fanin"),
        "w_B": ParamSpec((d, gn), ("embed", None), "fanin"),
        "w_C": ParamSpec((d, gn), ("embed", None), "fanin"),
        "w_dt": ParamSpec((d, h), ("embed", "ssm_heads"), "fanin"),
        "conv_x": ParamSpec((w, din), (None, "ssm_inner"), "fanin"),
        "conv_B": ParamSpec((w, gn), (None, None), "fanin"),
        "conv_C": ParamSpec((w, gn), (None, None), "fanin"),
        "A_log": ParamSpec((h,), ("ssm_heads",), "ones"),
        "D": ParamSpec((h,), ("ssm_heads",), "ones"),
        "dt_bias": ParamSpec((h,), ("ssm_heads",), "zeros"),
        "norm": ParamSpec((din,), ("ssm_inner",), "ones"),
        "out_proj": ParamSpec((din, d), ("ssm_inner", "embed"), "fanin"),
    }
    if cfg.conv_bias:
        s["conv_x_bias"] = ParamSpec((din,), ("ssm_inner",), "zeros")
        s["conv_B_bias"] = ParamSpec((gn,), (None,), "zeros")
        s["conv_C_bias"] = ParamSpec((gn,), (None,), "zeros")
    return s


def layer_specs(cfg: ModelConfig) -> dict:
    s: dict = {"norm1": ParamSpec((cfg.d_model,), ("embed_nofsdp",), "ones")}
    if cfg.has_attention:
        s["attn"] = _attn_specs(cfg)
    if cfg.has_ssm:
        s["ssm"] = _ssm_specs(cfg)
    if cfg.d_ff or cfg.n_experts:
        s["norm2"] = ParamSpec((cfg.d_model,), ("embed_nofsdp",), "ones")
    if cfg.d_ff:
        s["mlp"] = _mlp_specs(cfg)
    if cfg.n_experts:
        s["moe"] = _moe_specs(cfg)
    return s


def param_specs(cfg: ModelConfig) -> dict:
    def stack(spec: ParamSpec) -> ParamSpec:
        return ParamSpec((cfg.n_layers,) + spec.shape,
                         ("layers",) + spec.logical, spec.init, spec.dtype)

    per_layer = jax.tree.map(stack, layer_specs(cfg),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    emb_ax = (("vocab_tbl", "embed_tbl") if cfg.embed_gather_local
              else ("vocab", "embed"))
    specs = {
        "embed": ParamSpec((cfg.vocab_padded, cfg.d_model),
                           emb_ax, "normal"),
        "layers": per_layer,
        "final_norm": ParamSpec((cfg.d_model,), ("embed_nofsdp",), "ones"),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_padded),
                                     ("embed", "vocab"), "fanin")
    return specs


_IS_SPEC = lambda x: isinstance(x, ParamSpec)  # noqa: E731


def abstract_params(cfg: ModelConfig):
    dt = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or dt)),
        param_specs(cfg), is_leaf=_IS_SPEC)


def logical_axes(cfg: ModelConfig):
    return jax.tree.map(lambda s: s.logical, param_specs(cfg),
                        is_leaf=_IS_SPEC)


def init_params(cfg: ModelConfig, rng: jax.Array):
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_IS_SPEC)
    keys = jax.random.split(rng, len(leaves))
    dt = jnp.dtype(cfg.param_dtype)

    def mk(spec: ParamSpec, key):
        dtype = jnp.dtype(spec.dtype or dt)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        if spec.init == "fanin":
            fan = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            return (jax.random.normal(key, spec.shape, jnp.float32)
                    * (fan ** -0.5)).astype(dtype)
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * 0.02).astype(dtype)

    return treedef.unflatten([mk(s, k) for s, k in zip(leaves, keys)])
