"""Pure-jnp oracles for every custom SIMD instruction.

These are the "base RV32IM core runs it in software" implementations from
the paper's evaluation (§4.2/§4.3 baselines): semantically identical to
the Pallas kernels, written with stock jnp/lax ops only. Every kernel
test sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# -- c2_sort / c1_merge (sorting networks, §4.3.1) ---------------------------

def sort_chunks(x: jax.Array, width: int = 8, descending: bool = False) -> jax.Array:
    """Sort each contiguous chunk of `width` elements along the last axis."""
    if x.shape[-1] % width:
        raise ValueError(f"last dim {x.shape[-1]} % width {width} != 0")
    shp = x.shape
    xr = x.reshape(*shp[:-1], shp[-1] // width, width)
    s = jnp.sort(xr, axis=-1)
    if descending:
        s = s[..., ::-1]
    return s.reshape(shp)


def max_key(dtype):
    """The key no other key sorts after: padding for merge windows."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dtype).max


def window_keys(x: jax.Array, start: jax.Array, stop: jax.Array,
                width: int) -> jax.Array:
    """(windows, width) keys: row k is ``x.ravel()[start[k]:][:width]``,
    each key at flat index ``stop[k]`` or later read as :func:`max_key`."""
    flat = x.reshape(-1)
    idx = start[:, None] + jnp.arange(width, dtype=start.dtype)
    fill = max_key(flat.dtype)
    keys = flat.at[idx].get(mode="fill", fill_value=fill)
    return jnp.where(idx < stop[:, None], keys, fill)


def merge_sorted(a: jax.Array, b: jax.Array, width: int | None = None,
                 windows=None) -> tuple[jax.Array, jax.Array]:
    """Merge two sorted vectors (paper c1_merge): returns (lower, upper).

    a, b: (..., n), each `width`-chunk sorted ascending (width=None → whole
    row). Per chunk, output the lower/upper halves of the sorted 2w-element
    union (written back to v1/v2 in the paper).

    With ``windows = (a_start, a_stop, b_start, b_stop)`` (int32, one entry
    per window) the chunks are loaded from anywhere in ``a`` and ``b``:
    chunk k is :func:`window_keys` of ``a`` from ``a_start[k]`` up to
    ``a_stop[k]`` (of ``b`` likewise), and both outputs are (windows,
    width).
    """
    if windows is not None:
        a_start, a_stop, b_start, b_stop = windows
        a = window_keys(a, a_start, a_stop, width)
        b = window_keys(b, b_start, b_stop, width)
    n = a.shape[-1]
    w = width or n
    ar = a.reshape(*a.shape[:-1], n // w, w)
    br = b.reshape(*b.shape[:-1], n // w, w)
    s = jnp.sort(jnp.concatenate([ar, br], axis=-1), axis=-1)
    return (s[..., :w].reshape(a.shape), s[..., w:].reshape(a.shape))


def mergesort(x: jax.Array) -> jax.Array:
    """Full sort along the last axis (mergesort app oracle)."""
    return jnp.sort(x, axis=-1)


# -- c3_prefixsum (Hillis–Steele + carry, §4.3.2) ----------------------------

def prefix_sum(x: jax.Array, axis: int = -1) -> jax.Array:
    """Inclusive prefix sum (the arbitrarily-long carried scan's semantics)."""
    return jnp.cumsum(x, axis=axis)


def serial_prefix_sum(x: jax.Array) -> jax.Array:
    """The paper's *serial* baseline: one element per step via lax.scan."""
    def step(c, v):
        c = c + v
        return c, c
    _, out = jax.lax.scan(step, jnp.zeros_like(x[..., 0]),
                          jnp.moveaxis(x, -1, 0))
    return jnp.moveaxis(out, 0, -1)


# -- c4_chunkscan (affine carried scan; SSD inter-chunk recurrence) ----------

def chunk_scan(a: jax.Array, b: jax.Array) -> jax.Array:
    """y[..., i] = a[..., i] * y[..., i-1] + b[..., i]  (y[-1] = 0).

    The generalisation of c3_prefixsum's carry from (+) to an affine map —
    exactly the inter-chunk state recurrence of Mamba2's SSD.
    """
    def comb(p, q):
        pa, pb = p
        qa, qb = q
        return pa * qa, qb + qa * pb
    ya, yb = jax.lax.associative_scan(comb, (a, b), axis=-1)
    del ya
    return yb


def chunk_scan_state(a: jax.Array, b: jax.Array, axis: int = 1) -> jax.Array:
    """Affine carried scan with a SHARED decay per state block:
    a: (..., C, ...) scalars, b: a.shape + (P, N) states; scan along `axis`.
    Broadcast-free (the decay is never materialised at state rank)."""
    extra = b.ndim - a.ndim

    def comb(p, q):
        pa, pb = p
        qa, qb = q
        return pa * qa, qb + qa.reshape(qa.shape + (1,) * extra) * pb

    _, run = jax.lax.associative_scan(comb, (a, b), axis=axis)
    return run


# -- c0_lv / c0_sv (streaming, §4.1) + STREAM kernels ------------------------

def stream_copy(x: jax.Array) -> jax.Array:
    return x + 0  # forces a materialised copy under jit

def stream_scale(x: jax.Array, s) -> jax.Array:
    return x * s

def stream_add(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b

def stream_triad(a: jax.Array, b: jax.Array, s) -> jax.Array:
    return a + s * b


# -- c5_topk (router top-k via sorting network) ------------------------------

def topk(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k along last axis: (values desc, indices)."""
    return jax.lax.top_k(x, k)


# -- c6_flashattn (fused attention "instruction") ----------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: float | None = None) -> jax.Array:
    """Oracle attention. q,k,v: (batch, heads, seq, head_dim); GQA is
    handled by the caller (kv heads repeated before the call)."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    if scale is None:
        scale = d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)
