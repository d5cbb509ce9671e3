"""Attention: GQA/MQA/MHA with RoPE, qk_norm, SWA; three implementations.

impl='full'     — paper-baseline ("base ISA"): materialised logits.
impl='chunked'  — XLA online-softmax over q chunks: the flash-attention
                  recurrence expressed in stock jnp (what the c6 kernel
                  fuses); bounds activation memory at long seq.
impl='kernel'   — c6_flashattn Pallas kernel (TPU target; 'interpret' in
                  kernel tests).

Decode: one new token against a KV cache held as (B, KV, T, hd), the
layout the decode dots contract in. Its KV-head dim takes the `model`
mesh axis where the head count divides it, else its *sequence* dim does
(DESIGN.md §6 — kv-head counts never divide a 16-way TP axis, seq does);
the softmax/weighted-sum reductions over a sharded seq dim compile to the
partial-reduce + small all-reduce pattern (flash-decode). The step's own
key and value are one more softmax term beside the cache, which the step
reads and does not write: the caller stores the new rows.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops as kops

from .layers import apply_rope, rmsnorm, scaled

NEG_INF = -1e30


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array,
                 positions: jax.Array):
    """x: (B, S, D) → q (B,S,H,hd), k/v (B,S,KV,hd), RoPE'd + qk-normed;
    k times the μP ``key_multiplier``."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = scaled(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), cfg.key_multiplier)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask(q_pos, k_pos, window: int):
    """Additive mask from 1D position vectors — (len(q), len(k)) only.
    (Per-batch masks would materialise a (B,KV,G,S,T) pred that SPMD
    reshards catastrophically; positions are uniform across the batch.)"""
    m = q_pos[:, None] >= k_pos[None, :]
    if window:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)


def _full_attn(cfg: ModelConfig, q, k, v, q_pos, k_pos):
    """Materialised-logits GQA attention. q:(B,S,H,hd) k/v:(B,T,KV,hd)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    logits *= hd ** -0.5
    logits += _mask(q_pos, k_pos, cfg.swa_window)[None, None, None]
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", w.astype(q.dtype), v)
    return o.reshape(b, s, h, hd)


def _chunked_attn(cfg: ModelConfig, q, k, v, q_pos, k_pos):
    """Online-softmax over q chunks (XLA flash): O(chunk·T) live logits."""
    b, s, h, hd = q.shape
    if cfg.attn_flat_heads:
        # GQA grouped einsums make the partitioner shard over (kv, g)
        # subgroups and all-reduce fp32 activations; flat heads keep one
        # clean q_heads@model sharding (KV repeat is cheap bf16).
        k = constrain(jnp.repeat(k, h // k.shape[2], axis=2),
                      ("batch", None, "q_heads", "head_dim"))
        v = constrain(jnp.repeat(v, h // v.shape[2], axis=2),
                      ("batch", None, "q_heads", "head_dim"))
    kvh = k.shape[2]
    g = h // kvh
    c = min(cfg.attn_chunk, s)
    pad = (-s) % c
    if pad:  # pad the q side only (k/v untouched); slice output back
        q = jnp.concatenate(
            [q, jnp.zeros((b, pad) + q.shape[2:], q.dtype)], axis=1)
        q_pos = jnp.concatenate(
            [q_pos, jnp.full((pad,), q_pos[-1], q_pos.dtype)])
    sq = s + pad
    qg = q.reshape(b, sq // c, c, kvh, g, hd)
    qp = q_pos.reshape(sq // c, c)

    def chunk(carry, inp):
        qc, qpc = inp                     # (b, c, kv, g, hd), (c,)
        logits = jnp.einsum("bckgd,btkd->bkgct", qc, k).astype(jnp.float32)
        logits *= hd ** -0.5
        logits += _mask(qpc, k_pos, cfg.swa_window)[None, None, None]
        w = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bkgct,btkd->bckgd", w.astype(qc.dtype), v)
        return carry, o

    # cost probes (scan_unroll>1) unroll so HloCostAnalysis sees all chunks
    unroll = (sq // c) if cfg.scan_unroll > 1 else 1
    _, o = jax.lax.scan(chunk, None, (jnp.moveaxis(qg, 1, 0), qp),
                        unroll=unroll)
    o = jnp.moveaxis(o, 0, 1).reshape(b, sq, h, hd)
    return o[:, :s]


def attention(cfg: ModelConfig, p: dict, x: jax.Array,
              positions: jax.Array, return_cache: bool = False):
    """Training / prefill self-attention. Returns (B, S, D)
    (+ the rolled (k, v) decode cache when return_cache)."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    if cfg.attn_impl == "kernel" and not cfg.swa_window:
        kvh, h = k.shape[2], q.shape[2]
        kk = jnp.repeat(k, h // kvh, axis=2)
        vv = jnp.repeat(v, h // kvh, axis=2)
        o = kops.flash_attention(
            q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
            vv.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
    elif cfg.attn_impl == "chunked" or cfg.attn_impl == "kernel":
        o = _chunked_attn(cfg, q, k, v, positions, positions)
    else:
        o = _full_attn(cfg, q, k, v, positions, positions)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if return_cache:
        t = cache_len(cfg, q.shape[1])
        return out, (jnp.swapaxes(k[:, -t:], 1, 2),
                     jnp.swapaxes(v[:, -t:], 1, 2))
    return out


# ---------------------------------------------------------------------------
# decode (single-token serve step with sharded-seq KV cache)
# ---------------------------------------------------------------------------

def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Rolling window for SWA archs; full seq otherwise."""
    return min(seq_len, cfg.swa_window) if cfg.swa_window else seq_len


def write_slot(cfg: ModelConfig, pos: jax.Array, t: int) -> jax.Array:
    """Cache slot of position ``pos`` in a cache of ``t`` slots: ``pos``,
    or ``pos % t`` in the SWA rolling window."""
    return jnp.mod(pos, t) if cfg.swa_window else pos


def attention_decode(cfg: ModelConfig, p: dict, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array):
    """x: (B, 1, D); caches (B, KV, T, hd) holding the positions before
    ``pos``; pos: scalar current position.

    The step's own key and value join the softmax as one more term, so
    the caches are only read. Old slots count where filled
    (``slot < min(pos, T)``) and not at the write slot, whose position
    the SWA window drops.

    Returns (out (B,1,D), new k row, new v row), rows (B, KV, 1, hd) for
    the caller to store at ``write_slot``.
    """
    b = x.shape[0]
    t = k_cache.shape[2]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)

    h, kvh, hd = q.shape[2], k.shape[2], q.shape[3]
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd)
    k_new, v_new = k.reshape(b, kvh, hd), v.reshape(b, kvh, hd)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache).astype(jnp.float32)
    logits *= hd ** -0.5
    logit_new = jnp.einsum("bkgd,bkd->bkg", qg,
                           k_new).astype(jnp.float32) * hd ** -0.5

    slot_idx = jnp.arange(t)
    valid = ((slot_idx < jnp.minimum(pos, t))
             & (slot_idx != write_slot(cfg, pos, t)))
    logits = jnp.where(valid, logits, NEG_INF)
    m = jnp.maximum(jnp.max(logits, axis=-1), logit_new)   # (B,KV,G)
    e = jnp.exp(logits - m[..., None])
    e_new = jnp.exp(logit_new - m)
    denom = jnp.sum(e, axis=-1) + e_new
    o = jnp.einsum("bkgt,bktd->bkgd", (e / denom[..., None]).astype(x.dtype),
                   v_cache, preferred_element_type=jnp.float32)
    o = o + jnp.einsum("bkg,bkd->bkgd", e_new / denom, v_new,
                       preferred_element_type=jnp.float32)
    o = o.astype(x.dtype).reshape(b, 1, h, hd)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, k_new[:, :, None], v_new[:, :, None]
