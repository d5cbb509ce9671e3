"""Compressed cross-pod collectives + error feedback.

At 1000+ nodes the only slow-axis collective in this framework is the
cross-pod gradient all-reduce (DESIGN.md §6). DCN/ICI-spanning links are
~5-20x slower than in-pod ICI, so we ship an int8 block-quantised ring
all-reduce (reduce-scatter + all-gather over ``ppermute``) with
error-feedback state kept by the caller across steps.

Bytes on the slow axis drop 4x (fp32→int8 + one fp32 scale per qblock).
Each hop re-quantises the partial sum; the resulting bias is bounded by
the per-block scale and compensated across steps by ErrorFeedback
(Karimireddy et al.-style), validated numerically in tests/dist.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def quantize_blockwise(x: jax.Array, qblock: int = 256):
    """int8 symmetric quantisation with one fp32 absmax scale per block.

    x: 1D (caller flattens/pads). Returns (q int8 (nb, qblock), scales (nb, 1)).
    """
    if x.ndim != 1 or x.size % qblock:
        raise ValueError(f"need 1D size divisible by qblock={qblock}, "
                         f"got {x.shape}")
    xb = x.reshape(-1, qblock)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xb / safe), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_blockwise(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(-1)


def _pad_to(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    pad = (-x.size) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x, pad


def compressed_ring_allreduce(x: jax.Array, axis_name: str,
                              qblock: int = 256) -> jax.Array:
    """Ring all-reduce (sum) with int8-per-hop payloads.

    Must run inside shard_map/pmap with `axis_name` bound. Semantics match
    lax.psum(x, axis_name) up to quantisation error (tests bound it).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.size
    flat, _ = _pad_to(flat, n * qblock)
    clen = flat.size // n
    chunks = flat.reshape(n, clen)
    me = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def chunk_at(i):
        return lax.dynamic_index_in_dim(chunks, jnp.mod(i, n), 0,
                                        keepdims=False)

    def hop(acc):
        q, s = quantize_blockwise(acc, qblock)
        q = lax.ppermute(q, axis_name, fwd)
        s = lax.ppermute(s, axis_name, fwd)
        return dequantize_blockwise(q, s)

    # -- reduce-scatter: after n-1 hops, device `me` holds the full sum of
    #    chunk (me+1) mod n.
    def rs_body(step, acc):
        recv = hop(acc)
        return recv + chunk_at(me - step - 1)

    acc = lax.fori_loop(0, n - 1, rs_body, chunk_at(me))

    # -- all-gather: circulate completed chunks.
    own = jnp.mod(me + 1, n)
    out0 = jnp.zeros_like(chunks)
    out0 = lax.dynamic_update_index_in_dim(out0, acc, own, 0)

    def ag_body(step, carry):
        out, cur = carry
        recv = hop(cur)
        idx = jnp.mod(me - step, n)
        out = lax.dynamic_update_index_in_dim(out, recv, idx, 0)
        return out, recv

    out, _ = lax.fori_loop(0, n - 1, ag_body, (out0, acc))
    return out.reshape(-1)[:size].reshape(shape).astype(dtype)


class ErrorFeedback:
    """Error-feedback wrapper: residual = what compression dropped last step.

    Usage (per training step, per slow-axis reduction):
        ef = ErrorFeedback.init(grads)
        reduced, ef = ef.apply(grads, lambda g: compressed_ring_allreduce(g, 'pod'))
    State is a pytree shaped like the grads; store it in the train state.
    """

    def __init__(self, residual):
        self.residual = residual

    @staticmethod
    def init(tree):
        return ErrorFeedback(jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), tree))

    def apply(self, grads, reduce_fn: Callable, qblock: int = 256):
        def one(g, r):
            e = g.astype(jnp.float32) + r
            flat, _ = _pad_to(e.reshape(-1), qblock)
            q, s = quantize_blockwise(flat, qblock)
            sent = dequantize_blockwise(q, s)[:e.size].reshape(e.shape)
            new_r = e - sent
            return sent.astype(g.dtype), new_r

        pairs = jax.tree.map(one, grads, self.residual)
        sent = jax.tree.map(lambda p: p[0], pairs,
                            is_leaf=lambda p: isinstance(p, tuple))
        resid = jax.tree.map(lambda p: p[1], pairs,
                             is_leaf=lambda p: isinstance(p, tuple))
        reduced = jax.tree.map(reduce_fn, sent)
        return reduced, ErrorFeedback(resid)
