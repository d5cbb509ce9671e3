"""Plain float32 Falcon-H1 language model, written from the published
equations (``transformers`` 4.57.6, ``models/falcon_h1/modeling_falcon_h1.py``:
``FalconH1DecoderLayer``, ``FalconH1Mixer.torch_forward``,
``FalconH1Attention``, ``FalconH1MLP``, ``FalconH1RMSNormGated``). It
imports nothing of the program under test and takes nothing it made: the
shapes come from the configuration's sizes, every constant (the μP
multipliers, RoPE θ) from the published config in
``chipbench/configs/falcon-h1-34b.json``.

Each layer: ``h = RMSNorm(x)``; ``x += ssm_out · Mamba2(ssm_in · h) +
attn_out · Attention(attn_in · h)``; ``x += MLP(RMSNorm(x))``, with
``MLP(h) = (up h ⊙ silu(gate_mult · gate h)) W_down · down_mult``. The
Mamba-2 mixer scales its z, x, B, C and dt projections by
``ssm_multipliers``, convolves x, B and C with a bias, runs the SSD with B
and C in groups (each head reads its group's, as HF's ``repeat_interleave``
does), and takes a gated RMSNorm per group with the gate before the norm.
Attention multiplies the keys by ``key_multiplier`` and applies RoPE, GQA
and a causal softmax; it is computed in blocks of queries so that the
check's 18k-token rows fit the chip. The embedding is multiplied by
``embedding_multiplier``, the logits by ``lm_head_multiplier``.

:func:`weights` draws the weights both sides use, from the seed, on the
device, in one jitted call. Each matrix is N(0, 1/fan_in) divided by the
μP multipliers on its path, and the embedding N(0, 1) divided by its
multiplier, so that every projection's output, multipliers included, has
unit scale as in a trained model, at the published widths and at the small
widths of the CPU tests alike (the multipliers are applied at run time on
both sides, not folded in). Queries lean on the keys, as a trained head's
do: query head j is ``QUERY_SCALE · (QUERY_TIE · k_g + √(1 − QUERY_TIE²) ·
q_j)`` with k_g its group's key projection and q_j its own draw, so that a
token's query meets its own key at ~4.5 standard deviations of its other
scores and the scores' spread is 3. Over a 16k-token context a head then
puts ~0.37 of its weight on the token itself and most of the rest on about
ten of the context's keys (simulated for one head over 16,384 random
tokens), and at the cell's sizes the attention branch's output reads 0.58
of the SSD branch's unit scale at every layer, beside the MLP's 0.60 (a
TPU v5e, one 16,384-token row). Drawn without the tie, the scores are
N(0, 1), attention averages v over thousands of keys, and the branch adds
about 1% to a layer's update: a fault in attention would not show in the
check. The convolutions' weights and biases are U(-0.5, 0.5) (PyTorch's
``Conv1d`` default), A_log = log(1..heads), D 1, norms 1
(``FalconH1Mixer.__init__``), and dt_bias the inverse softplus of a dt
drawn log-uniform in [0.001, 0.1] (the mixer's ``time_step_min`` and
``_max``, by the inverse-dt initialisation its comment names). The
program receives the weights; the reference draws them again from the
same seed.

Departures from the published model, kept because the program has them and
both sides must compute the same function: separate projections for z, x,
B, C and dt and separate convolutions for x, B and C (the published model
packs them, which is the same map); RMSNorm epsilon 1e-6 (published 1e-5).

:func:`gaps_at` runs the forward pass over token rows and measures how far
the served tokens lie below the reference's best; its ``control`` puts
float8 in place of float32 at every product with a weight instead (the
control that the check must fail).
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

EPS = 1e-6
QUERY_SCALE, QUERY_TIE = 3.0, 0.4
PUBLISHED = json.loads(
    (Path(__file__).resolve().parents[1] / "configs" / "falcon-h1-34b.json")
    .read_text())


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(s: dict) -> dict:
    """Shapes of the parameter tree, keyed as the program keys it."""
    L, d, f = s["n_layers"], s["d_model"], s["d_ff"]
    h, kv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    din, hs, w = s["d_inner"], s["ssm_heads"], s["conv_width"]
    gn = s["groups"] * s["state"]
    return {
        "embed": (s["vocab_rows"], d),
        "unembed": (d, s["vocab_rows"]),
        "final_norm": (d,),
        "layers": {
            "norm1": (L, d), "norm2": (L, d),
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd),
                     "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
            "ssm": {
                "w_z": (L, d, din), "w_x": (L, d, din), "w_B": (L, d, gn),
                "w_C": (L, d, gn), "w_dt": (L, d, hs),
                "conv_x": (L, w, din), "conv_B": (L, w, gn),
                "conv_C": (L, w, gn), "conv_x_bias": (L, din),
                "conv_B_bias": (L, gn), "conv_C_bias": (L, gn),
                "A_log": (L, hs), "D": (L, hs), "dt_bias": (L, hs),
                "norm": (L, din), "out_proj": (L, din, d),
            },
            "mlp": {"w_gate": (L, d, f), "w_in": (L, d, f),
                    "w_out": (L, f, d)},
        },
    }


def _multipliers() -> dict:
    """Each weight's μP multipliers on its path, multiplied together."""
    P = PUBLISHED
    ssm_in = P["ssm_in_multiplier"]
    mz, mx, mb, mc, mdt = P["ssm_multipliers"]
    gate, down = P["mlp_multipliers"]
    return {"embed": P["embedding_multiplier"],
            "unembed": P["lm_head_multiplier"],
            "wq": P["attention_in_multiplier"],
            "wk": P["attention_in_multiplier"] * P["key_multiplier"],
            "wv": P["attention_in_multiplier"],
            "wo": P["attention_out_multiplier"],
            "w_z": ssm_in * mz, "w_x": ssm_in * mx, "w_B": ssm_in * mb,
            "w_C": ssm_in * mc, "w_dt": ssm_in * mdt,
            "out_proj": P["ssm_out_multiplier"],
            "w_gate": gate, "w_in": 1.0, "w_out": down}


def _draw(s: dict, key, dtype):
    shapes = weight_shapes(s)
    mult = _multipliers()
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                       is_leaf=is_shape)
    keys = jax.random.split(key, len(paths))
    names = [path[-1].key for path, _ in paths]
    wk_key, wk_shape = keys[names.index("wk")], paths[names.index("wk")][1]
    out = []
    for (path, shape), k in zip(paths, keys):
        leaf = path[-1].key
        if path[0].key == "layers":     # layer by layer: one f32 layer live
            out.append(jax.lax.map(
                lambda kl, leaf=leaf, shape=shape[1:]: _leaf(
                    leaf, shape, kl[0], mult, dtype, (kl[1], wk_shape[1:])),
                (jax.random.split(k, shape[0]),
                 jax.random.split(wk_key, shape[0]))))
        else:
            out.append(_leaf(leaf, shape, k, mult, dtype, None))
    return jax.tree.unflatten(tree, out)


def _unit(k, shape: tuple, fan_in: int):
    return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)


def _leaf(leaf: str, shape: tuple, k, mult: dict, dtype, wk):
    """One weight (one layer's, of a stacked one) from key ``k``; ``wk``
    is the key and shape of the same layer's ``wk``, which ``wq`` leans
    on."""
    if leaf in ("final_norm", "norm1", "norm2", "norm", "D"):
        v = jnp.ones(shape, jnp.float32)
    elif leaf == "dt_bias":         # dt log-uniform in [time_step_min, _max]
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))               # inverse softplus
    elif leaf == "A_log":
        v = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
    elif leaf.startswith("conv_"):
        v = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
    elif leaf == "embed":           # fan-in 1
        v = jax.random.normal(k, shape, jnp.float32) / mult[leaf]
    elif leaf == "wq":              # (d, heads, hd): each head's group's key
        k_wk, wk_shape = wk
        tied = jnp.repeat(_unit(k_wk, wk_shape, shape[0]),
                          shape[1] // wk_shape[1], axis=1)
        v = QUERY_SCALE * (QUERY_TIE * tied + math.sqrt(1 - QUERY_TIE ** 2)
                           * _unit(k, shape, shape[0])) / mult[leaf]
    else:                           # x @ w
        fan_in = shape[0] * shape[1] if leaf == "wo" else shape[0]
        v = _unit(k, shape, fan_in) / mult[leaf]
    return v.astype(dtype)


def weights(s: dict, key, dtype=jnp.bfloat16, out_shardings=None):
    """The whole parameter tree from ``key``, on the device, in one jitted
    call, in the type it is served in (each leaf drawn and cast on its
    own, so no float32 copy of the tree is held)."""
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


def _blocks(fn, x, most):
    """``fn`` over blocks of at most ``most`` positions of x (b, t, ...),
    one after another: the same result with a block's temporaries live."""
    t = x.shape[1]
    blk = t if t <= most else math.gcd(t, most)
    xs = jnp.moveaxis(x.reshape(x.shape[0], t // blk, blk, *x.shape[2:]),
                      1, 0)
    ys = jax.lax.map(fn, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(x.shape[0], t, *ys.shape[3:])


# -- Mamba-2 ---------------------------------------------------------------

def causal_conv(u, w, bias):
    """Depthwise causal convolution with bias:
    y_t = b + Σ_k w_k · u_{t-W+1+k}."""
    width, t = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return bias + sum(w[k] * up[:, k:k + t] for k in range(width))


def segsum(x):
    """out[..., i, j] = x[j+1] + ... + x[i] for i ≥ j, else -inf."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd(X, A, B, C, q):
    """The SSD layer in chunks of ``q`` (Listing 1 of arXiv:2405.21060),
    with B and C in groups, each head reading its group's. X (b, T, h, p)
    inputs times dt; A (b, T, h) log-decays A·dt; B, C (b, T, g, n).
    Returns (b, T, h, p)."""
    b, t, h, p = X.shape
    rep = h // B.shape[2]
    B = jnp.repeat(B, rep, axis=2)                           # b T h n
    C = jnp.repeat(C, rep, axis=2)
    n = B.shape[-1]
    c = t // q
    X = X.reshape(b, c, q, h, p)
    A = A.reshape(b, c, q, h).transpose(0, 3, 1, 2)          # b h c l
    B = B.reshape(b, c, q, h, n)
    C = C.reshape(b, c, q, h, n)
    acs = jnp.cumsum(A, axis=-1)
    # within chunks
    L = jnp.exp(segsum(A))                                   # b h c l s
    cb = jnp.einsum("bclhn,bcshn->bhcls", C, B)
    y_diag = jnp.einsum("bhcls,bcshp->bclhp", cb * L, X)
    # each chunk's end state
    decay_states = jnp.exp(acs[..., -1:] - acs)              # b h c l
    xd = X * decay_states.transpose(0, 2, 3, 1)[..., None]
    states = jnp.einsum("bclhn,bclhp->bchpn", B, xd)
    # states entering each chunk, through the chunk recurrence
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(acs[..., -1], ((0, 0), (0, 0),
                                                        (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    # state contribution to each position
    cs = jnp.einsum("bclhn,bchpn->bclhp", C, states)
    y_off = cs * jnp.exp(acs).transpose(0, 2, 3, 1)[..., None]
    return (y_diag + y_off).reshape(b, t, h, p)


def mamba(s: dict, lw: dict, u, quant):
    b, t, _ = u.shape
    h, p, g, n = s["ssm_heads"], s["headdim"], s["groups"], s["state"]
    mz, mx, mb, mc, mdt = PUBLISHED["ssm_multipliers"]
    z = _mm(u, _f32(lw["w_z"]), quant) * mz
    x = _mm(u, _f32(lw["w_x"]), quant) * mx
    bb = _mm(u, _f32(lw["w_B"]), quant) * mb
    cc = _mm(u, _f32(lw["w_C"]), quant) * mc
    dt = jax.nn.softplus(_mm(u, _f32(lw["w_dt"]), quant) * mdt
                         + _f32(lw["dt_bias"]))
    x, bb, cc = (jax.nn.silu(causal_conv(v, _f32(lw[f"conv_{k}"]),
                                         _f32(lw[f"conv_{k}_bias"])))
                 for k, v in (("x", x), ("B", bb), ("C", cc)))
    a = -jnp.exp(_f32(lw["A_log"]))
    xh = x.reshape(b, t, h, p)
    q = s["chunk"]
    pad = (-t) % q          # zero dt past the end: no input, no decay

    def padded(v):
        return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))

    y = ssd(padded(xh * dt[..., None]), padded(a * dt),
            padded(bb.reshape(b, t, g, n)), padded(cc.reshape(b, t, g, n)),
            q)[:, :t]
    y = (y + xh * _f32(lw["D"])[:, None]).reshape(b, t, h * p)
    # gated RMSNorm, gate first (mamba_norm_before_gate false), per group
    y = (y * jax.nn.silu(z)).reshape(b, t, g, h * p // g)
    y = rmsnorm(y, _f32(lw["norm"]).reshape(g, -1)).reshape(b, t, h * p)
    return _mm(y, _f32(lw["out_proj"]), quant)


# -- attention -------------------------------------------------------------

def rope(x, positions):
    """HF's rotary embedding: x · cos + rotate_half(x) · sin, with
    angles position · θ^(-2i/d) repeated over both halves."""
    d = x.shape[-1]
    inv = 1.0 / (float(PUBLISHED["rope_theta"])
                 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv      # T d/2
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]       # T 1 d
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def attention(s: dict, lw: dict, u, quant):
    b, t, d = u.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    pos = jnp.arange(t)
    q = rope(_mm(u, _f32(lw["wq"]).reshape(d, h * hd), quant)
             .reshape(b, t, h, hd), pos)
    k = rope(_mm(u, _f32(lw["wk"]).reshape(d, kv * hd), quant)
             .reshape(b, t, kv, hd) * PUBLISHED["key_multiplier"], pos)
    v = _mm(u, _f32(lw["wv"]).reshape(d, kv * hd), quant).reshape(b, t, kv,
                                                                   hd)
    k = jnp.repeat(k, h // kv, axis=2)                       # GQA
    v = jnp.repeat(v, h // kv, axis=2)
    qb = min(t, 512)

    def block(args):                    # one block of queries against all
        i, qi = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k) * hd ** -0.5
        causal = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    nb = -(-t // qb)
    qp = jnp.pad(q, ((0, 0), (0, nb * qb - t), (0, 0), (0, 0)))
    o = jax.lax.map(block, (jnp.arange(nb),
                            jnp.moveaxis(qp.reshape(b, nb, qb, h, hd), 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, nb * qb, h * hd)[:, :t]
    return _mm(o, _f32(lw["wo"]).reshape(h * hd, d), quant)


# -- MLP and the stack -----------------------------------------------------

def mlp(lw: dict, u, quant):
    gate_m, down_m = PUBLISHED["mlp_multipliers"]

    def part(ub):
        y = (_mm(ub, _f32(lw["w_in"]), quant)
             * jax.nn.silu(_mm(ub, _f32(lw["w_gate"]), quant) * gate_m))
        return _mm(y, _f32(lw["w_out"]), quant) * down_m

    return _blocks(part, u, 2048)


def hidden(s: dict, params, tokens, quant=lambda v: v):
    """Final-normed hidden states (b, T, d) for token rows (b, T)."""
    x = _f32(params["embed"])[tokens] * PUBLISHED["embedding_multiplier"]

    def layer(x, lw):
        h = rmsnorm(x, _f32(lw["norm1"]))
        x = (x + PUBLISHED["ssm_out_multiplier"]
             * mamba(s, lw["ssm"], h * PUBLISHED["ssm_in_multiplier"], quant)
             + PUBLISHED["attention_out_multiplier"]
             * attention(s, lw["attn"],
                         h * PUBLISHED["attention_in_multiplier"], quant))
        return x + mlp(lw["mlp"], rmsnorm(x, _f32(lw["norm2"])), quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rmsnorm(x, _f32(params["final_norm"]))


def _rows(x, positions):
    return jnp.take_along_axis(x, positions[..., None], axis=1)   # b k d


def _logits(s, params, x, quant=lambda v: v):
    w = _f32(params["unembed"])[:, : s["vocab"]]
    return _mm(x, w, quant) * PUBLISHED["lm_head_multiplier"]


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps_at(sizes, params, tokens, positions, chosen, control):
    s = dict(sizes)
    ref = _logits(s, params, _rows(hidden(s, params, tokens), positions))
    if control:
        low = _logits(s, params, _rows(hidden(s, params, tokens, fp8),
                                       positions), fp8)
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
    scale per tensor, at every product with a weight) puts first."""
    with jax.default_matmul_precision("highest"):
        return _gaps_at(tuple(sorted(s.items())), params, tokens, positions,
                        chosen, control)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_at(sizes, params, tokens, positions):
    s = dict(sizes)
    return _logits(s, params, _rows(hidden(s, params, tokens), positions))


def logits_at(s: dict, params, tokens, positions):
    """Reference logits (b, k, vocab) at ``positions`` (b, k)."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(tuple(sorted(s.items())), params, tokens,
                          positions)
