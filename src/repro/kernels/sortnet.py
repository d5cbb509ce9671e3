"""Sorting-network instructions (paper §2.2 Alg. 1 + §4.3.1) as Pallas kernels.

The paper's `c2_sort` is a bitonic sorting network over one 256-bit vector
register (8 × 32-bit lanes, 6 CAS layers, 3 cycles); `c1_merge` is the
last log2(N) layers of an odd-even/bitonic merger that merges two sorted
registers, writing the lower half to vrd1 and the upper half to vrd2 —
an I'-type instruction using 2 vector sources *and* 2 vector
destinations (the 6-operand encoding is what makes it one instruction).

TPU adaptation (DESIGN.md §2): each CAS layer is a vectorised
compare-and-select between a lane and its XOR-partner lane. Partner
indices are *static* per layer, so each exchange is two lane rotations
and a select — the whole network fuses into ONE kernel (one "instruction"),
versus the ~13-instruction min/max/shuffle sequences of fixed SIMD ISAs
the paper counts in §6. Rows stream through the grid back-to-back, the
pipelining the paper gets from its `c1_cycles` shift registers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stream import LANES


def _check_pow2(w: int, what: str) -> None:
    if w < 2 or (w & (w - 1)):
        raise ValueError(f"{what} must be a power of two ≥ 2, got {w}")


# ---------------------------------------------------------------------------
# The network itself (shared by kernel bodies; built from static lane
# rotations and iota masks so every layer is shuffle + select on the flat
# lane axis — no reversal, no lane-splitting reshape, no data-dependent
# control flow). Chunks of ``width`` lanes sort independently; a chunk's
# local lane index is the low log2(width) bits of the flat lane index.
#
# ``roll`` rotates along an axis with jnp.roll semantics: ``jnp.roll``
# (the default) runs anywhere, kernel bodies pass ``pltpu.roll`` (the
# VPU/XLU lane rotation).
# ---------------------------------------------------------------------------

def _partner(x: jax.Array, j: int, roll=jnp.roll) -> jax.Array:
    """Value at lane XOR j along the last axis: two static rotations and
    a select on lane bit j."""
    w = x.shape[-1]
    ax = x.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
    above = roll(x, w - j, ax)              # x[lane + j]
    below = roll(x, j, ax)                  # x[lane - j]
    return jnp.where((lane & j) == 0, above, below)


def _exchange(keys, kp, payload, pp, lower, keep_lo, descending: bool):
    """Compare-and-select of each lane with its partner value ``kp``:
    ``lower`` marks the lane that holds the lower slot of its pair,
    ``keep_lo`` that the slot keeps the pair's lower-ordered element."""
    lt = keys < kp
    eq = keys == kp
    if payload is None:
        self_is_lo = lt | (eq & lower)      # lane tiebreak (keys only)
        take_self = keep_lo == self_is_lo
        return jnp.where(take_self, keys, kp), None
    # With payload, ties need a lane-independent total order so equal keys
    # emerge in ascending-payload order (= lax.top_k tie semantics for the
    # descending sort used by c5_topk).
    tie = (payload > pp) if descending else (payload < pp)
    self_is_lo = lt | (eq & tie)
    take_self = keep_lo == self_is_lo
    return (jnp.where(take_self, keys, kp),
            jnp.where(take_self, payload, pp))


def _cas_layer(keys: jax.Array, payload: Optional[jax.Array],
               j: int, k: int, descending: bool, width: int, roll):
    """One compare-and-swap layer: partner = lane XOR j, direction from
    the chunk-local lane bit k (k ≥ width → the whole chunk one way)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    lower = (lane & j) == 0                     # partner = lane^j → lower iff bit j unset
    asc = (lane & (k & (width - 1))) == 0       # ascending sub-block?
    keep_lo = (asc != lower) if descending else (asc == lower)
    kp = _partner(keys, j, roll)
    pp = None if payload is None else _partner(payload, j, roll)
    return _exchange(keys, kp, payload, pp, lower, keep_lo, descending)


def _chunk_width(keys: jax.Array, width: Optional[int], what: str) -> int:
    width = width or keys.shape[-1]
    _check_pow2(width, what)
    if keys.shape[-1] % width:
        raise ValueError(f"last dim {keys.shape[-1]} % {what} {width} != 0")
    return width


def bitonic_sort_network(keys: jax.Array, payload: Optional[jax.Array] = None,
                         descending: bool = False,
                         width: Optional[int] = None, roll=jnp.roll):
    """Bitonic sort of every ``width``-chunk of the last axis (width: a
    static power of 2; default the whole axis)."""
    width = _chunk_width(keys, width, "sort width")
    k = 2
    while k <= width:
        j = k // 2
        while j >= 1:
            keys, payload = _cas_layer(keys, payload, j, k, descending,
                                       width, roll)
            j //= 2
        k *= 2
    return (keys, payload) if payload is not None else keys


def bitonic_merge_network(keys: jax.Array, payload: Optional[jax.Array] = None,
                          descending: bool = False,
                          width: Optional[int] = None, roll=jnp.roll):
    """Merge stages only: every ``width``-chunk already bitonic."""
    width = _chunk_width(keys, width, "merge width")
    j = width // 2
    while j >= 1:
        # k = 2·width → every chunk one direction.
        keys, payload = _cas_layer(keys, payload, j, 2 * width, descending,
                                   width, roll)
        j //= 2
    return (keys, payload) if payload is not None else keys


def merge_sorted_network(a: jax.Array, b: jax.Array, width: int,
                         descending: bool = False, roll=jnp.roll):
    """``c1_merge``: per ``width``-chunk, merge sorted a with sorted b into
    the lower (first) and upper (second) halves of their sorted union.

    This is the 2·width bitonic merge of ``a ++ reversed(b)``, computed
    without building either: the reversal is lane XOR (width-1), composed
    from partner exchanges, and the first layer pairs lane i of ``a`` with
    lane i of the reversed ``b``; the remaining layers stay within each
    half."""
    width = _chunk_width(a, width, "merge width")
    rb = b
    j = 1
    while j < width:
        rb = _partner(rb, j, roll)
        j *= 2
    lo, _ = _exchange(a, rb, None, None, True, not descending, descending)
    hi, _ = _exchange(rb, a, None, None, False, descending, descending)
    return (bitonic_merge_network(lo, descending=descending, width=width,
                                  roll=roll),
            bitonic_merge_network(hi, descending=descending, width=width,
                                  roll=roll))


def n_cas_layers(width: int) -> int:
    """Θ(log²N) layers — the paper's pipeline-depth (c2: width 8 → 6)."""
    lg = int(np.log2(width))
    return lg * (lg + 1) // 2


# ---------------------------------------------------------------------------
# c2_sort — sort every contiguous `width`-chunk of each row.
# ---------------------------------------------------------------------------

def _sort_body(width: int, descending: bool, x_ref, o_ref):
    o_ref[...] = bitonic_sort_network(x_ref[...], descending=descending,
                                      width=width, roll=pltpu.roll)


@functools.partial(jax.jit, static_argnames=(
    "width", "descending", "block_rows", "block_cols", "interpret"))
def sort_chunks_pallas(x: jax.Array, *, width: int = 8,
                       descending: bool = False, block_rows: int = 8,
                       block_cols: int = 2 * LANES,
                       interpret: bool = False) -> jax.Array:
    """Pallas c2_sort over a 2D operand (rows stream through the grid)."""
    rows, cols = x.shape
    _check_pow2(width, "width")
    block_cols = max(width, min(block_cols, cols))
    if cols % block_cols or block_cols % width:
        raise ValueError(f"cols={cols} blocks={block_cols} width={width} "
                         f"must nest evenly")
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        raise ValueError(f"rows={rows} % block_rows={block_rows} != 0")
    grid = (rows // block_rows, cols // block_cols)
    return pl.pallas_call(
        functools.partial(_sort_body, width, descending),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda r, c: (r, c))],
        out_specs=pl.BlockSpec((block_rows, block_cols), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# c1_merge — merge two sorted width-chunks: lower→vrd1, upper→vrd2.
# ---------------------------------------------------------------------------

def _merge_body(width: int, descending: bool, a_ref, b_ref, lo_ref, hi_ref):
    lo, hi = merge_sorted_network(a_ref[...], b_ref[...], width,
                                  descending=descending, roll=pltpu.roll)
    lo_ref[...] = lo
    hi_ref[...] = hi


@functools.partial(jax.jit, static_argnames=(
    "width", "descending", "block_rows", "block_cols", "interpret"))
def merge_sorted_pallas(a: jax.Array, b: jax.Array, *, width: Optional[int] = None,
                        descending: bool = False, block_rows: int = 8,
                        block_cols: Optional[int] = None,
                        interpret: bool = False):
    """Pallas c1_merge: per row, merge sorted chunks of a with those of b."""
    if a.shape != b.shape or a.dtype != b.dtype:
        raise ValueError("operands must match")
    rows, cols = a.shape
    width = width or cols
    _check_pow2(width, "width")
    block_cols = block_cols or max(width, min(2 * LANES, cols))
    if cols % block_cols or block_cols % width:
        raise ValueError("cols/block/width must nest evenly")
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        raise ValueError(f"rows={rows} % block_rows={block_rows} != 0")
    grid = (rows // block_rows, cols // block_cols)
    spec = pl.BlockSpec((block_rows, block_cols), lambda r, c: (r, c))
    shp = jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_merge_body, width, descending),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(shp, shp),
        interpret=interpret,
    )(a, b)


# ---------------------------------------------------------------------------
# Batcher odd-even mergesort — the paper's other topology (§2.2 cites both;
# c1_merge is "the last log2(N) layers of odd-even mergesort"). Same
# Θ(log²N) depth as bitonic; all-ascending comparators, partner = lane ± k,
# expressed as static shifts + iota masks (no gathers, no captured arrays).
# ---------------------------------------------------------------------------

def _shift(x: jax.Array, k: int, fill) -> jax.Array:
    """Value at lane+k (k>0) or lane+k (k<0 → lane-|k|), edge-filled."""
    *lead, w = x.shape
    if k > 0:
        pad = jnp.full((*lead, k), fill, x.dtype)
        return jnp.concatenate([x[..., k:], pad], axis=-1)
    pad = jnp.full((*lead, -k), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :k]], axis=-1)


def _oddeven_cas(keys: jax.Array, p: int, k: int) -> jax.Array:
    """One odd-even merge layer: compare (x, x+k) for lanes x with
    x ≡ k mod p (mod 2k) and floor(x/2p) == floor((x+k)/2p)."""
    w = keys.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    x = lane - (k % p)
    is_lo = ((x >= 0) & (jnp.remainder(x, 2 * k) < k)
             & (lane + k < w)
             & ((lane // (2 * p)) == ((lane + k) // (2 * p))))
    up = _shift(keys, k, 0)          # partner above (for lo lanes)
    down = _shift(keys, -k, 0)       # partner below (for hi lanes)
    is_hi_src = _shift(is_lo.astype(jnp.int32), -k, 0) == 1
    new = jnp.where(is_lo, jnp.minimum(keys, up), keys)
    new = jnp.where(is_hi_src, jnp.maximum(new, down), new)
    return new


def oddeven_sort_network(keys: jax.Array) -> jax.Array:
    """Full Batcher odd-even mergesort along the last axis (ascending)."""
    w = keys.shape[-1]
    _check_pow2(w, "sort width")
    p = 1
    while p < w:
        k = p
        while k >= 1:
            keys = _oddeven_cas(keys, p, k)
            k //= 2
        p *= 2
    return keys
