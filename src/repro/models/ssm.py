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

B and C come in ``ssm_groups`` groups of ``ssm_state``, each shared by
H/G consecutive heads (Mamba-2's ``ngroups``; Falcon-H1 has 2). The
einsums split the heads as (G, H/G); one group (Mamba2) keeps no group
axis (``_groups``). The gated RMSNorm is taken per group.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_mesh, logical_spec
from repro.kernels import ops as kops

from .layers import rmsnorm, scaled


def _causal_conv(x: jax.Array, w: jax.Array, cache: jax.Array | None = None,
                 bias: jax.Array | None = None):
    """Depthwise causal conv along seq, then SiLU. x: (B,S,C); w: (W,C);
    bias (C,) or None.

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
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), new_cache


def _convs(p: dict, x, bc, cc, cache: dict | None = None):
    """The causal convolutions of x, B and C (with their biases where the
    model has them); with ``cache`` also the new convolution cache."""
    cache = cache or {}
    outs = [_causal_conv(v, p[f"conv_{k}"], cache.get(k),
                         p.get(f"conv_{k}_bias"))
            for k, v in (("x", x), ("B", bc), ("C", cc))]
    return [y for y, _ in outs], {k: c for k, (_, c) in zip("xBC", outs)}


def _proj(cfg: ModelConfig, p: dict, u: jax.Array):
    """u: (B,S,D) → z,x (B,S,din), Bc,Cc (B,S,G·N), dt (B,S,H), each
    projection times its μP multiplier (``ssm_multipliers``)."""
    mz, mx, mb, mc, mdt = cfg.ssm_multipliers
    z = scaled(jnp.einsum("bsd,de->bse", u, p["w_z"]), mz)
    x = scaled(jnp.einsum("bsd,de->bse", u, p["w_x"]), mx)
    bc = scaled(jnp.einsum("bsd,dn->bsn", u, p["w_B"]), mb)
    cc = scaled(jnp.einsum("bsd,dn->bsn", u, p["w_C"]), mc)
    dt = scaled(jnp.einsum("bsd,dh->bsh", u, p["w_dt"]), mdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return z, x, bc, cc, dt


def _groups(cfg: ModelConfig):
    """Einsum letters and shapes for B/C in groups: the group axis ``G``
    of B and C, the heads ``H`` split as (group, head in group), and the
    shapes that split them. One group (Mamba2) keeps no group axis, so it
    traces the same ops as before groups existed."""
    g, h = cfg.ssm_groups, cfg.ssm_heads
    if g == 1:
        return "", "h", (), (h,)
    return "g", "gr", (g,), (g, h // g)


def _per_head(t: jax.Array, heads: int, groups: int) -> jax.Array:
    """Per-group values (..., G), or (...) for one group, → per head: head
    i reads group i // (H/G); one group broadcasts over a new last axis."""
    if groups == 1:
        return t[..., None]
    return jnp.repeat(t, heads // groups, axis=-1)


def _gated_norm(cfg: ModelConfig, y: jax.Array, z: jax.Array,
                w: jax.Array) -> jax.Array:
    """RMSNorm(y · silu(z)) taken over each group's d_inner/G channels."""
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    g = cfg.ssm_groups
    if g == 1:
        return rmsnorm(y, w)
    lead, din = y.shape[:-1], y.shape[-1]
    return rmsnorm(y.reshape(*lead, g, din // g),
                   w.reshape(g, din // g)).reshape(*lead, din)


def ssd_forward(cfg: ModelConfig, p: dict, u: jax.Array,
                return_state: bool = False):
    """Training / prefill SSD pass. u: (B, S, D) → (B, S, D)
    (+ (final_state, conv_cache) when return_state, for decode)."""
    b, s_in, _ = u.shape
    h, pd, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    G, H, grp, hsplit = _groups(cfg)
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
    (x, bc, cc), _ = _convs(p, x, bc, cc)

    a = -jnp.exp(p["A_log"].astype(jnp.float32))           # (H,) negative
    dta = dt * a                                           # (B,S,H) log-decay
    xh = x.reshape(b, s, h, pd)

    # chunk views
    cdt = jnp.bfloat16 if cfg.ssd_bf16 else jnp.float32
    dtac = dta.reshape(b, nc, q, h)
    dtc = dt.reshape(b, nc, q, h).astype(cdt)
    xc = xh.reshape(b, nc, q, h, pd).astype(cdt)
    bcc = bc.reshape(b, nc, q, *grp, n).astype(cdt)
    ccc = cc.reshape(b, nc, q, *grp, n).astype(cdt)

    cum = jnp.cumsum(dtac, axis=2)                         # (B,C,Q,H)
    # intra-chunk (quadratic within chunk)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (B,C,Q,Q,H) i-j
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    # double-where: upper-triangle seg is large-positive; exp there must
    # never be computed or its cotangent overflows (inf·0 → NaN grads)
    seg = jnp.where(tri, seg, 0.0)
    decay = jnp.where(tri, jnp.exp(seg), 0.0).astype(cdt)
    cb = jnp.einsum(f"bci{G}n,bcj{G}n->bcij{G}", ccc, bcc,
                    preferred_element_type=jnp.float32).astype(cdt)
    # explicit contraction order: the ONLY large intermediate is
    # (B,C,Q,Q,H), head-sharded (constrained) — never a replicated 6D one.
    w_intra = (_per_head(cb, h, cfg.ssm_groups) * decay
               * dtc[:, :, None])                          # (B,C,Q,Q,H)
    w_intra = constrain(w_intra,
                        ("batch", None, None, None, "ssm_heads"))
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w_intra, xc,
                         preferred_element_type=jnp.float32)

    # chunk end-states  S_c = Σ_j exp(cum_Q - cum_j) dt_j B_j x_j
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum).astype(cdt)  # (B,C,Q,H)
    xdt = xc * (decay_end * dtc)[..., None]                 # (B,C,Q,H,P)
    states = jnp.einsum(f"bcj{G}n,bcj{H}p->bc{H}pn", bcc,
                        xdt.reshape(b, nc, q, *hsplit, pd),
                        preferred_element_type=jnp.float32
                        ).reshape(b, nc, h, pd, n)         # (B,C,H,P,N)

    # inter-chunk recurrence — the paper's carried scan (c4_statescan):
    # shared per-(B,C,H) decay, (P,N) state payload, scan along chunks.
    a_chunk = jnp.exp(cum[:, :, -1, :])                    # (B,C,H)
    run = _state_scan(a_chunk, states)                     # (B,C,H,P,N)
    prev = jnp.concatenate(
        [jnp.zeros_like(run[:, :1]), run[:, :-1]], axis=1)  # state before c

    decay_in = jnp.exp(cum).astype(cdt)                    # (B,C,Q,H)
    cprev = jnp.einsum(f"bci{G}n,bc{H}pn->bci{H}p", ccc,
                       prev.astype(cdt).reshape(b, nc, *hsplit, pd, n),
                       preferred_element_type=jnp.float32
                       ).reshape(b, nc, q, h, pd)
    y_inter = cprev * decay_in[..., None]

    y = (y_intra + y_inter).reshape(b, s, h, pd)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, s, h * pd).astype(u.dtype)
    y = _gated_norm(cfg, y, z, p["norm"])
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
    G, H, grp, hsplit = _groups(cfg)

    z, x, bc, cc, dt = _proj(cfg, p, u)
    (x, bc, cc), new_conv = _convs(p, x, bc, cc, conv_cache)

    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt1 = dt[:, 0]                                          # (B,H)
    decay = jnp.exp(dt1 * a)                                # (B,H)
    xh = x[:, 0].reshape(b, h, pd).astype(jnp.float32)
    binc = jnp.einsum(f"b{G}n,b{H},b{H}p->b{H}pn",
                      bc[:, 0].reshape(b, *grp, n).astype(jnp.float32),
                      dt1.reshape(b, *hsplit), xh.reshape(b, *hsplit, pd))
    new_state = (decay[..., None, None] * ssm_state
                 + binc.reshape(b, h, pd, n))
    y = jnp.einsum(f"b{G}n,b{H}pn->b{H}p",
                   cc[:, 0].reshape(b, *grp, n).astype(jnp.float32),
                   new_state.reshape(b, *hsplit, pd, n)).reshape(b, h, pd)
    y = y + xh * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, 1, h * pd).astype(u.dtype)
    y = _gated_norm(cfg, y, z, p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, new_conv, new_state
