"""Mamba2 / SSD mixer — the paper's carried prefix scan inside a modern LM.

The chunked SSD algorithm (Dao & Gu, 2024) splits the sequence into
chunks: a quadratic intra-chunk term plus an inter-chunk *state
recurrence* ``running[c] = a_chunk[c] · running[c-1] + S_c``. That
recurrence is exactly the paper's c3_prefixsum "add the cumulative sum of
the previous batch" stage, generalised to an affine carry — dispatched
here through the c4_chunkscan ISA instruction (ref on CPU, Pallas kernel
on TPU).

Decode is O(1): a (B, H, P, N) state update per token — why the SSM
archs run the long_500k cell that full attention cannot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_mesh, logical_spec
from repro.kernels import ops as kops

from .layers import rmsnorm


def _causal_conv(x: jax.Array, w: jax.Array, cache: jax.Array | None = None):
    """Depthwise causal conv along seq. x: (B,S,C); w: (W,C).

    With cache (B, W-1, C) (decode), returns (y, new_cache)."""
    width = w.shape[0]
    if cache is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
        xp = jnp.concatenate([pad, x], axis=1)
        new_cache = xp[:, -(width - 1):, :] if width > 1 else None
    else:
        xp = jnp.concatenate([cache, x], axis=1)
        new_cache = xp[:, -(width - 1):, :]
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), new_cache


def _proj(cfg: ModelConfig, p: dict, u: jax.Array):
    """u: (B,S,D) → z,x,(B,S,din), Bc,Cc (B,S,N), dt (B,S,H)."""
    z = jnp.einsum("bsd,de->bse", u, p["w_z"])
    x = jnp.einsum("bsd,de->bse", u, p["w_x"])
    bc = jnp.einsum("bsd,dn->bsn", u, p["w_B"])
    cc = jnp.einsum("bsd,dn->bsn", u, p["w_C"])
    dt = jnp.einsum("bsd,dh->bsh", u, p["w_dt"])
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return z, x, bc, cc, dt


def ssd_forward(cfg: ModelConfig, p: dict, u: jax.Array,
                return_state: bool = False):
    """Training / prefill SSD pass. u: (B, S, D) → (B, S, D)
    (+ (final_state, conv_cache) when return_state, for decode)."""
    b, s_in, _ = u.shape
    h, pd, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    q = min(cfg.ssm_chunk, s_in)
    pad = (-s_in) % q
    if pad:
        if return_state:  # padded decay would corrupt the carried state
            raise ValueError(f"prefill seq {s_in} % ssm_chunk {q} != 0")
        u = jnp.concatenate(
            [u, jnp.zeros((b, pad, u.shape[-1]), u.dtype)], axis=1)
    s = s_in + pad
    nc = s // q

    z, x, bc, cc, dt = _proj(cfg, p, u)
    # SP region ends here: gather seq, shard the SSD internals by heads
    # (otherwise XLA replicates the (B,C,Q,Q,H) intra-chunk tensors).
    z = constrain(z, ("batch", None, "ssm_inner"))
    x = constrain(x, ("batch", None, "ssm_inner"))
    dt = constrain(dt, ("batch", None, "ssm_heads"))
    w = cfg.conv_width - 1
    conv_cache = {"x": x[:, -w:], "B": bc[:, -w:], "C": cc[:, -w:]}
    x, _ = _causal_conv(x, p["conv_x"])
    bc, _ = _causal_conv(bc, p["conv_B"])
    cc, _ = _causal_conv(cc, p["conv_C"])

    a = -jnp.exp(p["A_log"].astype(jnp.float32))           # (H,) negative
    dta = dt * a                                           # (B,S,H) log-decay
    xh = x.reshape(b, s, h, pd)

    # chunk views
    cdt = jnp.bfloat16 if cfg.ssd_bf16 else jnp.float32
    dtac = dta.reshape(b, nc, q, h)
    dtc = dt.reshape(b, nc, q, h).astype(cdt)
    xc = xh.reshape(b, nc, q, h, pd).astype(cdt)
    bcc = bc.reshape(b, nc, q, n).astype(cdt)
    ccc = cc.reshape(b, nc, q, n).astype(cdt)

    cum = jnp.cumsum(dtac, axis=2)                         # (B,C,Q,H)
    # intra-chunk (quadratic within chunk)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (B,C,Q,Q,H) i-j
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    # double-where: upper-triangle seg is large-positive; exp there must
    # never be computed or its cotangent overflows (inf·0 → NaN grads)
    seg = jnp.where(tri, seg, 0.0)
    decay = jnp.where(tri, jnp.exp(seg), 0.0).astype(cdt)
    g = jnp.einsum("bcin,bcjn->bcij", ccc, bcc,
                   preferred_element_type=jnp.float32).astype(cdt)
    # explicit contraction order: the ONLY large intermediate is
    # (B,C,Q,Q,H), head-sharded (constrained) — never a replicated 6D one.
    w_intra = g[..., None] * decay * dtc[:, :, None]       # (B,C,Q,Q,H)
    w_intra = constrain(w_intra,
                        ("batch", None, None, None, "ssm_heads"))
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w_intra, xc,
                         preferred_element_type=jnp.float32)

    # chunk end-states  S_c = Σ_j exp(cum_Q - cum_j) dt_j B_j x_j
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum).astype(cdt)  # (B,C,Q,H)
    xdt = xc * (decay_end * dtc)[..., None]                 # (B,C,Q,H,P)
    states = jnp.einsum("bcjn,bcjhp->bchpn", bcc, xdt,
                        preferred_element_type=jnp.float32)  # (B,C,H,P,N)

    # inter-chunk recurrence — the paper's carried scan (c4_statescan):
    # shared per-(B,C,H) decay, (P,N) state payload, scan along chunks.
    a_chunk = jnp.exp(cum[:, :, -1, :])                    # (B,C,H)
    run = _state_scan(a_chunk, states)                     # (B,C,H,P,N)
    prev = jnp.concatenate(
        [jnp.zeros_like(run[:, :1]), run[:, :-1]], axis=1)  # state before c

    decay_in = jnp.exp(cum).astype(cdt)                    # (B,C,Q,H)
    cprev = jnp.einsum("bcin,bchpn->bcihp", ccc, prev.astype(cdt),
                       preferred_element_type=jnp.float32)
    y_inter = cprev * decay_in[..., None]

    y = (y_intra + y_inter).reshape(b, s, h, pd)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, s, h * pd).astype(u.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])[:, :s_in]
    if return_state:
        return out, (run[:, -1], conv_cache)   # state after last chunk
    return out


def _state_scan(a_chunk: jax.Array, states: jax.Array) -> jax.Array:
    """c4_statescan over chunks, per shard under a mesh: XLA cannot
    partition a Pallas call, so each device scans its own batch × head
    block (the scan runs along chunks, which are never sharded)."""
    mesh = current_mesh()
    if mesh is None:
        return kops.chunk_scan_state(a_chunk, states, axis=1)
    a_spec = logical_spec(("batch", None, "ssm_heads"), a_chunk.shape, mesh)
    s_spec = logical_spec(("batch", None, "ssm_heads", None, None),
                          states.shape, mesh)
    return jax.shard_map(
        lambda a, s: kops.chunk_scan_state(a, s, axis=1), mesh=mesh,
        in_specs=(a_spec, s_spec), out_specs=s_spec,
        check_vma=False)(a_chunk, states)


def ssd_decode(cfg: ModelConfig, p: dict, u: jax.Array,
               conv_cache: dict, ssm_state: jax.Array):
    """One-token step. u: (B,1,D); ssm_state: (B,H,P,N).

    Returns (out (B,1,D), new_conv_cache, new_ssm_state)."""
    b = u.shape[0]
    h, pd, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state

    z, x, bc, cc, dt = _proj(cfg, p, u)
    x, cx = _causal_conv(x, p["conv_x"], conv_cache["x"])
    bc, cb = _causal_conv(bc, p["conv_B"], conv_cache["B"])
    cc_, ccv = _causal_conv(cc, p["conv_C"], conv_cache["C"])

    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt1 = dt[:, 0]                                          # (B,H)
    decay = jnp.exp(dt1 * a)                                # (B,H)
    xh = x[:, 0].reshape(b, h, pd).astype(jnp.float32)
    binc = jnp.einsum("bn,bh,bhp->bhpn", bc[:, 0].astype(jnp.float32),
                      dt1, xh)
    new_state = decay[..., None, None] * ssm_state + binc
    y = jnp.einsum("bn,bhpn->bhp", cc_[:, 0].astype(jnp.float32), new_state)
    y = y + xh * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, 1, h * pd).astype(u.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, {"x": cx, "B": cb, "C": ccv}, new_state
