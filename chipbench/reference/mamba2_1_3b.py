"""Plain float32 Mamba2 language model, written from the paper's equations
(Dao & Gu, arXiv:2405.21060: the SSD layer, §6 and Listing 1) and the
published block (pre-norm residual, gated RMSNorm, tied embedding). It
imports nothing of the program under test and takes nothing it made.

It also makes the weights both sides use: :func:`weights` draws them from
the seed, on the device, in one jitted call, with the published
initialisation (``mamba_ssm``: in-projections and convolution Kaiming
uniform, ``out_proj`` divided by sqrt(n_layer), A in [1, 16], dt in
[0.001, 0.1] through the inverse softplus of ``dt_bias``, D = 1, norms 1,
embedding N(0, 0.02)). The program receives them; the reference draws
them again from the same seed.

Departures from the published model, kept because the program has them
and both sides must compute the same function: separate projections and
convolutions for z, x, B, C and dt (the published model packs them, which
is the same map); no convolution bias; RMSNorm epsilon 1e-6 (published
1e-5); the program keeps its residual stream in bfloat16 (published: f32).

:func:`gaps_at` runs the forward pass over token rows and measures how far
the served tokens lie below the reference's best; its ``control`` puts
float8 in place of float32 at every matrix product instead (the control
that the check must fail).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(s: dict) -> dict:
    """Shapes of the parameter tree, keyed as the program keys it."""
    L, d, din = s["n_layers"], s["d_model"], s["d_inner"]
    n, h, w = s["state"], s["heads"], s["conv_width"]
    return {
        "embed": (s["vocab_rows"], d),
        "final_norm": (d,),
        "layers": {
            "norm1": (L, d),
            "ssm": {
                "w_z": (L, d, din), "w_x": (L, d, din), "w_B": (L, d, n),
                "w_C": (L, d, n), "w_dt": (L, d, h),
                "conv_x": (L, w, din), "conv_B": (L, w, n),
                "conv_C": (L, w, n),
                "A_log": (L, h), "D": (L, h), "dt_bias": (L, h),
                "norm": (L, din), "out_proj": (L, din, d),
            },
        },
    }


def _draw(s: dict, key, dtype):
    L = s["n_layers"]
    shapes = weight_shapes(s)
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    flat, tree = jax.tree.flatten(shapes, is_leaf=is_shape)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes,
                                                  is_leaf=is_shape)[0]]
    keys = jax.random.split(key, len(flat))
    out = []
    for name, shape, k in zip(names, flat, keys):
        leaf = name.split("'")[-2]
        if leaf == "embed":
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif leaf in ("final_norm", "norm1", "norm", "D"):
            v = jnp.ones(shape, jnp.float32)
        elif leaf == "A_log":
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            dt = jnp.maximum(dt, 1e-4)
            v = dt + jnp.log(-jnp.expm1(-dt))           # inverse softplus
        elif leaf.startswith("conv_"):
            v = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        else:                                           # linear maps
            bound = 1.0 / math.sqrt(shape[-2])
            v = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            if leaf == "out_proj":
                v = v / math.sqrt(L)
        out.append(v.astype(dtype))
    return jax.tree.unflatten(tree, out)


def weights(s: dict, key, dtype=jnp.bfloat16, out_shardings=None):
    """The whole parameter tree from ``key``, on the device, in one jitted
    call, in the type it is served in."""
    return jax.jit(functools.partial(_draw, s, dtype=dtype),
                   out_shardings=out_shardings)(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _f32(x):
    return x.astype(jnp.float32)


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (the precision below
    bfloat16 that a serving system would switch to)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b))


def rmsnorm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def causal_conv(u, w):
    """Depthwise causal convolution: y_t = Σ_k w_k · u_{t-W+1+k}."""
    width, t = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(w[k] * up[:, k:k + t] for k in range(width))


def segsum(x):
    """out[..., i, j] = x[j+1] + ... + x[i] for i ≥ j, else -inf."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd(X, A, B, C, q):
    """The SSD layer in chunks of ``q`` (Listing 1 of the paper), one group
    of B, C shared by all heads. X (b, T, h, p) inputs times dt;
    A (b, T, h) log-decays A·dt; B, C (b, T, n). Returns (b, T, h, p)."""
    b, t, h, p = X.shape
    n = B.shape[-1]
    c = t // q
    X = X.reshape(b, c, q, h, p)
    A = A.reshape(b, c, q, h).transpose(0, 3, 1, 2)          # b h c l
    B = B.reshape(b, c, q, n)
    C = C.reshape(b, c, q, n)
    acs = jnp.cumsum(A, axis=-1)
    # within chunks
    L = jnp.exp(segsum(A))                                   # b h c l s
    cb = jnp.einsum("bcln,bcsn->bcls", C, B)
    w = cb[:, None] * L                                      # b h c l s
    y_diag = jnp.einsum("bhcls,bcshp->bclhp", w, X)
    # each chunk's end state
    decay_states = jnp.exp(acs[..., -1:] - acs)              # b h c l
    xd = X * decay_states.transpose(0, 2, 3, 1)[..., None]
    states = jnp.einsum("bcln,bclhp->bchpn", B, xd)
    # states entering each chunk, through the chunk recurrence
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(acs[..., -1], ((0, 0), (0, 0),
                                                        (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    # state contribution to each position
    cs = jnp.einsum("bcln,bchpn->bclhp", C, states)
    y_off = cs * jnp.exp(acs).transpose(0, 2, 3, 1)[..., None]
    return (y_diag + y_off).reshape(b, t, h, p)


def mixer(s: dict, lw: dict, u, quant):
    b, t, _ = u.shape
    h, p = s["heads"], s["headdim"]
    z = _mm(u, _f32(lw["w_z"]), quant)
    x = _mm(u, _f32(lw["w_x"]), quant)
    bb = _mm(u, _f32(lw["w_B"]), quant)
    cc = _mm(u, _f32(lw["w_C"]), quant)
    dt = jax.nn.softplus(_mm(u, _f32(lw["w_dt"]), quant) + _f32(lw["dt_bias"]))
    x = jax.nn.silu(causal_conv(x, _f32(lw["conv_x"])))
    bb = jax.nn.silu(causal_conv(bb, _f32(lw["conv_B"])))
    cc = jax.nn.silu(causal_conv(cc, _f32(lw["conv_C"])))
    a = -jnp.exp(_f32(lw["A_log"]))
    xh = x.reshape(b, t, h, p)
    q = s["chunk"]
    pad = (-t) % q          # zero dt past the end: no input, no decay
    y = ssd(jnp.pad(xh * dt[..., None], ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(a * dt, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(bb, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(cc, ((0, 0), (0, pad), (0, 0))), q)[:, :t]
    y = y + xh * _f32(lw["D"])[:, None]
    y = rmsnorm(y.reshape(b, t, h * p) * jax.nn.silu(z), _f32(lw["norm"]))
    return _mm(y, _f32(lw["out_proj"]), quant)


def hidden(s: dict, params, tokens, quant=lambda v: v):
    """Final-normed hidden states (b, T, d) for token rows (b, T)."""
    x = _f32(params["embed"])[tokens]

    def layer(x, lw):
        h = rmsnorm(x, _f32(lw["norm1"]))
        return x + mixer(s, lw["ssm"], h, quant), None

    lw = dict(params["layers"]["ssm"])
    stacked = {"norm1": params["layers"]["norm1"], "ssm": lw}
    x, _ = jax.lax.scan(layer, x, stacked)
    return rmsnorm(x, _f32(params["final_norm"]))


def _rows(x, positions):
    return jnp.take_along_axis(x, positions[..., None], axis=1)   # b k d


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps_at(sizes, params, tokens, positions, chosen, control):
    s = dict(sizes)
    emb = _f32(params["embed"])[: s["vocab"]]
    ref = _rows(hidden(s, params, tokens), positions) @ emb.T
    if control:
        low = _mm(_rows(hidden(s, params, tokens, fp8), positions), emb.T,
                  fp8)
        chosen = jnp.argmax(low, -1)
    got = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    return (jnp.max(ref, -1) - got) / jnp.std(ref, -1)


def gaps_at(s: dict, params, tokens, positions, chosen, control=False):
    """How far each chosen token's reference logit lies below the
    reference's best, in units of the standard deviation of the reference
    logits over the vocabulary at that position: (b, k). ``tokens`` (b, T)
    are the rows, ``positions`` (b, k) where the logits are read, and
    ``chosen`` (b, k) the tokens served there. With ``control`` the tokens
    are instead those that the same forward pass in float8 (e4m3, one
    scale per tensor, at every matrix product) puts first."""
    with jax.default_matmul_precision("highest"):
        return _gaps_at(tuple(sorted(s.items())), params, tokens, positions,
                        chosen, control)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_at(sizes, params, tokens, positions):
    s = dict(sizes)
    return (_rows(hidden(s, params, tokens), positions)
            @ _f32(params["embed"])[: s["vocab"]].T)


def logits_at(s: dict, params, tokens, positions):
    """Reference logits (b, k, vocab) at ``positions`` (b, k)."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(tuple(sorted(s.items())), params, tokens,
                          positions)
