"""Shared layers: norms, RoPE, MLPs, embeddings, losses (pure JAX)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def scaled(x: jax.Array, m: float) -> jax.Array:
    """``x · m`` for a constant multiplier; a multiplier of 1 adds no op."""
    return x if m == 1 else x * m


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, d/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., seq, 1, d/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mlp(p: dict, x: jax.Array, gated: bool,
        multipliers: tuple = (1.0, 1.0)) -> jax.Array:
    """``multipliers``: (gate, down), Falcon-H1's μP constants on the gate
    projection (before the SiLU) and on the output."""
    gate_m, down_m = multipliers
    if gated:  # SwiGLU
        g = scaled(jnp.einsum("...d,df->...f", x, p["w_gate"]), gate_m)
        h = jnp.einsum("...d,df->...f", x, p["w_in"])
        a = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    else:      # GPT-style 2-matrix GELU
        h = jnp.einsum("...d,df->...f", x, p["w_in"])
        a = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return scaled(jnp.einsum("...f,fd->...d", a, p["w_out"]), down_m)


def embed_tokens(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(w: jax.Array, x: jax.Array, vocab: int) -> jax.Array:
    """Logits over the true (unpadded) vocab, fp32."""
    logits = jnp.einsum("...d,dv->...v", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    return logits[..., :vocab]


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  z_loss: float = 1e-4):
    """Mean CE over all positions + z-loss; logits fp32 (..., V)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = (lse - ll).mean()
    zl = z_loss * (lse ** 2).mean()
    return ce + zl, {"ce": ce, "z_loss": zl}
