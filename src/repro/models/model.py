"""The LM: blocks, scan-over-layers stack, loss, prefill and decode.

Pure-functional: params/caches are pytrees, every entry point is
jit/pjit-able. Layer params are stacked on a leading (L,) axis and the
stack is a lax.scan (compact HLO for 80-layer models — essential for the
512-device dry-run compiles).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (cross_entropy, embed_tokens, mlp, rmsnorm, scaled,
                     unembed)
from .params import abstract_params, init_params, logical_axes  # noqa: F401


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _mixer(cfg: ModelConfig, p: dict, h: jax.Array, attn_fn, ssm_fn):
    """The block's token mixer on the normed input ``h``: attention, the
    SSD, or (hybrid) both in parallel on the same ``h``, summed, each
    branch with its μP output multiplier and the SSD with its input one
    (Falcon-H1's block; its attention input multiplier is 1).
    ``attn_fn(p, h)`` and ``ssm_fn(p, h)`` run one branch as forward,
    prefill or decode does and return (out, cache entries).
    Returns (out, cache entries)."""
    outs, cache = [], {}
    if cfg.has_attention:
        with jax.named_scope("attn"):
            a, c = attn_fn(p["attn"], h)
            outs.append(scaled(a, cfg.attention_out_multiplier))
        cache.update(c)
    if cfg.has_ssm:
        with jax.named_scope("ssm"):
            s, c = ssm_fn(p["ssm"], scaled(h, cfg.ssm_in_multiplier))
            outs.append(scaled(s, cfg.ssm_out_multiplier))
        cache.update(c)
    return sum(outs[1:], outs[0]), cache


def _block(cfg: ModelConfig, p: dict, x: jax.Array, attn_fn, ssm_fn):
    """Pre-norm residual block: x + mixer, then x + MLP (or experts).
    Returns (x, moe aux loss, cache entries)."""
    y, cache = _mixer(cfg, p, rmsnorm(x, p["norm1"]), attn_fn, ssm_fn)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if cfg.d_ff or cfg.n_experts:
        h2 = rmsnorm(x, p["norm2"])
        with jax.named_scope("mlp"):
            if cfg.n_experts:
                y, aux = moe_mod.moe_layer(cfg, p["moe"], h2)
            else:
                y = mlp(p["mlp"], h2, cfg.mlp_gated, cfg.mlp_multipliers)
        x = x + y
    return x, aux, cache


def _constrain_residual(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return constrain(x, ("batch", "seq_sp" if cfg.sp else None, "act_embed"))


def block(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """One transformer/ssm/hybrid block. Returns (x, aux)."""
    x, aux, _ = _block(
        cfg, p, x,
        lambda pa, h: (attn.attention(cfg, pa, h, positions), {}),
        lambda ps, h: (ssm_mod.ssd_forward(cfg, ps, h), {}))
    return _constrain_residual(cfg, x), aux


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


def stack(cfg: ModelConfig, layer_params, x: jax.Array,
          positions: jax.Array, train: bool):
    fn = functools.partial(block, cfg)
    if train:
        fn = _remat(cfg, fn)

    def body(carry, lp):
        h, aux = carry
        h, a = fn(lp, h, positions)
        return (h, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               layer_params, unroll=cfg.scan_unroll)
    return x, aux / cfg.n_layers


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    """Token embeddings times the μP ``embedding_multiplier``."""
    x = embed_tokens(params["embed"], tokens).astype(jnp.dtype(cfg.act_dtype))
    return scaled(x, cfg.embedding_multiplier)


def _inputs(cfg: ModelConfig, params, batch: dict) -> jax.Array:
    if "embeddings" in batch:            # stubbed VLM/audio frontend
        return batch["embeddings"].astype(jnp.dtype(cfg.act_dtype))
    return _embed(cfg, params, batch["tokens"])


def forward(cfg: ModelConfig, params, batch: dict, train: bool):
    x = constrain(_inputs(cfg, params, batch), ("batch", None, "act_embed"))
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)   # uniform across batch
    x, aux = stack(cfg, params["layers"], x, positions, train)
    return rmsnorm(x, params["final_norm"]), aux


def _unembed_w(cfg: ModelConfig, params):
    return (params["embed"].T if cfg.tie_embeddings else params["unembed"])


def logits(cfg: ModelConfig, w: jax.Array, x: jax.Array) -> jax.Array:
    """f32 logits over the true vocabulary from final-normed hidden states
    and the unembedding ``w`` (``_unembed_w``), times the μP
    ``lm_head_multiplier``."""
    return scaled(unembed(w, x, cfg.vocab), cfg.lm_head_multiplier)


def loss_fn(cfg: ModelConfig, params, batch: dict,
            aux_weight: float = 0.01):
    x, aux = forward(cfg, params, batch, train=True)
    w = _unembed_w(cfg, params)
    if cfg.ce_chunk and x.shape[1] % cfg.ce_chunk == 0:
        # chunk unembed+CE over seq: never materialise (B,S,V) logits
        nc = x.shape[1] // cfg.ce_chunk
        xc = x.reshape(x.shape[0], nc, cfg.ce_chunk, x.shape[2])
        tc = batch["targets"].reshape(x.shape[0], nc, cfg.ce_chunk)

        def chunk(carry, inp):
            xx, tt = inp
            l, _ = cross_entropy(logits(cfg, w, xx), tt)
            return carry + l, None

        tot, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                              (jnp.moveaxis(xc, 1, 0),
                               jnp.moveaxis(tc, 1, 0)),
                              unroll=nc if cfg.scan_unroll > 1 else 1)
        loss = tot / nc
        metrics = {"ce": loss, "z_loss": jnp.zeros((), jnp.float32)}
    else:
        loss, metrics = cross_entropy(logits(cfg, w, x), batch["targets"])
    loss = loss + aux_weight * aux
    metrics.update(loss=loss, moe_aux=aux)
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def _abstract_layer_cache(cfg: ModelConfig, batch: int, seq_len: int):
    dt = jnp.dtype(cfg.act_dtype)
    c = {}
    if cfg.has_attention:
        t = attn.cache_len(cfg, seq_len)
        kv = (batch, t, cfg.n_kv_heads, cfg.head_dim)
        c["k"] = jax.ShapeDtypeStruct(kv, dt)
        c["v"] = jax.ShapeDtypeStruct(kv, dt)
    if cfg.has_ssm:
        w, gn = cfg.conv_width - 1, cfg.ssm_groups * cfg.ssm_state
        c["conv"] = {
            "x": jax.ShapeDtypeStruct((batch, w, cfg.d_inner), dt),
            "B": jax.ShapeDtypeStruct((batch, w, gn), dt),
            "C": jax.ShapeDtypeStruct((batch, w, gn), dt),
        }
        c["state"] = jax.ShapeDtypeStruct(
            (batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state),
            jnp.float32)
    return c


def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int):
    """Stacked (L, ...) cache ShapeDtypeStructs (dry-run input specs)."""
    layer = _abstract_layer_cache(cfg, batch, seq_len)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.n_layers,) + s.shape, s.dtype),
        layer)


def cache_logical_axes(cfg: ModelConfig):
    axes = {}
    if cfg.has_attention:
        kvax = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
        axes["k"] = kvax
        axes["v"] = kvax
    if cfg.has_ssm:
        axes["conv"] = {
            "x": ("layers", "batch", None, "ssm_inner"),
            "B": ("layers", "batch", None, None),
            "C": ("layers", "batch", None, None),
        }
        axes["state"] = ("layers", "batch", "ssm_heads", None, None)
    return axes


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_cache(cfg, batch, seq_len))


def grow_cache(cfg: ModelConfig, cache: dict, prefill_len: int,
               capacity: int) -> dict:
    """Make a prefill cache decodable up to `capacity` positions.

    Non-SWA: zero-pad the seq dim. SWA: the rolling cache is already at
    window size; rotate entries so absolute position p sits at slot
    p % window (the decode-side invariant)."""
    if not cfg.has_attention:
        return cache
    new = dict(cache)
    for key in ("k", "v"):
        c = cache[key]
        if cfg.swa_window:
            w = c.shape[-3]
            if prefill_len > w:
                c = jnp.roll(c, shift=prefill_len % w, axis=-3)
        else:
            pad = capacity - c.shape[-3]
            if pad > 0:
                widths = [(0, 0)] * c.ndim
                widths[-3] = (0, pad)
                c = jnp.pad(c, widths)
        new[key] = c
    return new


def _block_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                  pos: jax.Array):
    def attn_fn(pa, h):
        a, k, v = attn.attention_decode(cfg, pa, h, cache["k"], cache["v"],
                                        pos)
        return a, {"k": k, "v": v}

    def ssm_fn(ps, h):
        s, conv, state = ssm_mod.ssd_decode(cfg, ps, h, cache["conv"],
                                            cache["state"])
        return s, {"conv": conv, "state": state}

    x, _, new_cache = _block(cfg, p, x, attn_fn, ssm_fn)
    return x, new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
                pos: jax.Array):
    """One serve step: tokens (B, 1) int32, pos scalar int32.

    Returns (logits (B, vocab), new_cache)."""
    x = _embed(cfg, params, tokens)

    def body(h, inp):
        lp, lc = inp
        h, nc = _block_decode(cfg, lp, h, lc, pos)
        return h, nc

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                unroll=cfg.scan_unroll)
    x = rmsnorm(x, params["final_norm"])
    return logits(cfg, _unembed_w(cfg, params), x[:, 0]), new_cache


def _block_prefill(cfg: ModelConfig, p: dict, x: jax.Array,
                   positions: jax.Array):
    """block() that also emits the decode cache (no double compute)."""
    def attn_fn(pa, h):
        a, (k, v) = attn.attention(cfg, pa, h, positions, return_cache=True)
        return a, {"k": k, "v": v}

    def ssm_fn(ps, h):
        s, (state, conv) = ssm_mod.ssd_forward(cfg, ps, h, return_state=True)
        return s, {"state": state, "conv": conv}

    x, _, cache = _block(cfg, p, x, attn_fn, ssm_fn)
    return _constrain_residual(cfg, x), cache


def prefill(cfg: ModelConfig, params, batch: dict):
    """Full-sequence pass building the decode cache.

    Returns (last-position logits (B, vocab), cache)."""
    x = _inputs(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)   # uniform across batch

    def body(h, lp):
        return _block_prefill(cfg, lp, h, positions)

    x, cache = jax.lax.scan(body, x, params["layers"],
                            unroll=cfg.scan_unroll)
    x = rmsnorm(x, params["final_norm"])
    return logits(cfg, _unembed_w(cfg, params), x[:, -1]), cache
