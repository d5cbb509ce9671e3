"""c5_topk — router top-k as a key/payload sorting network.

This is where the paper's `c2_sort` lands inside a modern LM: MoE expert
routing needs, per token, the k largest of E router logits *with their
indices*. A fixed SIMD ISA spells that as dozens of min/max/shuffle ops
per CAS layer; here it is ONE instruction — a bitonic network whose CAS
units move a (key, payload) pair, exactly the paper's 6-operand-style
"complex instruction" argument (§6) applied to routing.

Payload = lane indices (static iota), so the kernel needs no gather at
the end: after a descending sort the first k lanes are the top-k values
and their original positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stream import LANES

from .sortnet import bitonic_sort_network


def _topk_body(x_ref, vals_ref, idx_ref):
    x = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    keys, payload = bitonic_sort_network(x, payload=lane, descending=True,
                                         roll=pltpu.roll)
    vals_ref[...] = keys
    idx_ref[...] = payload


def _lowest(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def topk_pallas(x: jax.Array, k: int, *, block_rows: int = 8,
                interpret: bool = False):
    """Top-k along the last axis of x: (rows, n). Returns (values (rows, k),
    indices (rows, k)) sorted descending, ties in ascending index order.

    The sort runs over a power-of-two width of at least one lane row
    (128): narrower router widths are padded with the dtype's lowest
    value, which ties lose to every real lane."""
    rows, n = x.shape
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    width = max(LANES, 1 << (n - 1).bit_length())
    if width != n:
        x = jnp.pad(x, ((0, 0), (0, width - n)),
                    constant_values=_lowest(x.dtype))
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        raise ValueError(f"rows={rows} % block_rows={block_rows} != 0")
    spec = pl.BlockSpec((block_rows, width), lambda r: (r, 0))
    vals, idx = pl.pallas_call(
        _topk_body,
        grid=(rows // block_rows,),
        in_specs=[spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, width), x.dtype),
                   jax.ShapeDtypeStruct((rows, width), jnp.int32)),
        interpret=interpret,
    )(x)
    return vals[:, :k], idx[:, :k]
