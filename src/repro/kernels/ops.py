"""Public ops: every custom SIMD instruction, registered in the ISA.

This is the "binutils patch": each op below registers one Instruction
with its I'/S'-type operand signature, its pure-jnp oracle (ref.py) and
its Pallas kernel, then exposes a user-facing wrapper that handles
shape normalisation and dispatch-mode plumbing.

Dispatch (repro.core.isa.use):
    'ref'       — base core, no SIMD unit (paper's software baselines)
    'kernel'    — Pallas on TPU
    'interpret' — Pallas simulated on CPU (correctness tests)
    'auto'      — kernel iff running on TPU, ref elsewhere (the default)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import isa
from repro.core.isa import Instruction, OperandSpec
from repro.core.stream import LANES, StreamConfig, flatten_to_blocks
# Shared operand shape normalisation — one entry path for every op (was
# duplicated here and in stream_copy.py; see core/stream.py).
from repro.core.stream import as_rows as _as_rows
from repro.core.stream import pad_rows as _pad_rows
from repro.obs import metrics as _metrics

from . import flashattn as _fa
from . import prefix_scan as _ps
from . import ref
from . import sortnet as _sn
from . import stream_copy as _sc
from . import topk as _tk


# ---------------------------------------------------------------------------
# c2_sort
# ---------------------------------------------------------------------------

def _lane_dense(width: int) -> int:
    """Row width of the flat 2D view the sorting kernels run on: whole
    ``width``-chunks, at least two lane rows wide."""
    return max(width, 2 * LANES)


def _sort_kernel(x, width: int = 8, descending: bool = False, *,
                 interpret: bool = False):
    # chunks never straddle rows, so the flat element order is a sequence
    # of whole chunks: sort it as lane-dense rows whatever the row length.
    if x.shape[-1] % width:
        raise ValueError(f"last dim {x.shape[-1]} % width {width} != 0")
    cols = _lane_dense(width)
    x2d, n = flatten_to_blocks(x, cols)
    out = _sn.sort_chunks_pallas(x2d, width=width, descending=descending,
                                 block_cols=cols, interpret=interpret)
    return out.reshape(-1)[:n].reshape(x.shape)


isa.register(Instruction(
    name="c2_sort",
    spec=OperandSpec(itype="I'", vector_in=1, vector_out=1),
    ref=ref.sort_chunks,
    kernel=_sort_kernel,
    pipeline_depth=_sn.n_cas_layers(8) // 2,    # paper: 6 layers / 3 cycles
    stream=StreamConfig(),
    doc="bitonic sort of each `width`-chunk of a vector register",
))


def sort_chunks(x, width: int = 8, descending: bool = False, mode=None):
    return isa.call("c2_sort", x, width=width, descending=descending, mode=mode)


# ---------------------------------------------------------------------------
# c1_merge  (2 vector in, 2 vector out — the full I'-type operand budget)
# ---------------------------------------------------------------------------

def _merge_kernel(a, b, width=None, windows=None, *, interpret: bool = False):
    if windows is not None:
        if width % LANES == 0 and a.dtype.itemsize == b.dtype.itemsize == 4:
            return _sn.merge_windows_pallas(a, None if b is a else b,
                                            *windows, width=width,
                                            interpret=interpret)
        # narrower windows, or other key widths: gather, then merge rows
        a_start, a_stop, b_start, b_stop = windows
        a = ref.window_keys(a, a_start, a_stop, width)
        b = ref.window_keys(b, b_start, b_stop, width)
    w = width or a.shape[-1]
    if a.shape != b.shape or a.shape[-1] % w:
        raise ValueError(f"operands {a.shape}, {b.shape} must match and "
                         f"hold whole {w}-chunks")
    cols = _lane_dense(w)
    a2, n = flatten_to_blocks(a, cols)
    b2, _ = flatten_to_blocks(b, cols)
    lo, hi = _sn.merge_sorted_pallas(a2, b2, width=w, block_cols=cols,
                                     interpret=interpret)
    return (lo.reshape(-1)[:n].reshape(a.shape),
            hi.reshape(-1)[:n].reshape(a.shape))


isa.register(Instruction(
    name="c1_merge",
    spec=OperandSpec(itype="I'", vector_in=2, vector_out=2),
    ref=ref.merge_sorted,
    kernel=_merge_kernel,
    pipeline_depth=4,
    doc="merge two sorted registers; lower→vrd1, upper→vrd2",
))


def merge_sorted(a, b, width=None, mode=None, windows=None):
    """``windows=(a_start, a_stop, b_start, b_stop)`` loads each register
    pair from its own offsets (``ref.merge_sorted``)."""
    return isa.call("c1_merge", a, b, width=width, windows=windows,
                    mode=mode)


# ---------------------------------------------------------------------------
# c3_prefixsum
# ---------------------------------------------------------------------------

def _scan_blocks(rows: int, cols: int) -> tuple[int, int]:
    """Block of a carried scan over (rows, cols), rows a multiple of 8:
    the widest power-of-two column block up to 512 dividing cols, and as
    many rows as keep one lane-padded f32 block near 512 KiB. A scan over
    few columns (the SSD chunk axis) would otherwise pay a grid step for
    every 8 rows."""
    bc = next(c for c in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
              if cols % c == 0)
    lanes = -(-bc // LANES) * LANES
    return math.gcd(rows, (512 << 10) // (4 * lanes)), bc


def _prefix_kernel(x, *, interpret: bool = False):
    x2d, lead = _as_rows(x, x.shape[-1])
    x2d, r = _pad_rows(x2d)
    br, bc = _scan_blocks(*x2d.shape)
    out = _ps.prefix_sum_pallas(x2d, block_rows=br, block_cols=bc,
                                interpret=interpret)
    return out[:r].reshape(*lead, x.shape[-1])


isa.register(Instruction(
    name="c3_prefixsum",
    spec=OperandSpec(itype="I'", vector_in=1, vector_out=1),
    ref=ref.prefix_sum,
    kernel=_prefix_kernel,
    pipeline_depth=2,
    doc="Hillis–Steele scan with carried batch total (arbitrary length)",
))


def prefix_sum(x, mode=None):
    return isa.call("c3_prefixsum", x, mode=mode)


def exclusive_prefix_sum(x, mode=None):
    inc = prefix_sum(x, mode=mode)
    return inc - x


# ---------------------------------------------------------------------------
# c4_chunkscan (affine carry — SSD inter-chunk recurrence)
# ---------------------------------------------------------------------------

def _chunkscan_kernel(a, b, *, interpret: bool = False):
    a2, lead = _as_rows(a, a.shape[-1])
    b2, _ = _as_rows(b, b.shape[-1])
    a2, r = _pad_rows(a2)
    b2, _ = _pad_rows(b2)
    br, bc = _scan_blocks(*a2.shape)
    out = _ps.chunk_scan_pallas(a2, b2, block_rows=br, block_cols=bc,
                                interpret=interpret)
    return out[:r].reshape(*lead, a.shape[-1])


isa.register(Instruction(
    name="c4_chunkscan",
    spec=OperandSpec(itype="I'", vector_in=2, vector_out=1),
    ref=ref.chunk_scan,
    kernel=_chunkscan_kernel,
    pipeline_depth=2,
    doc="carried affine scan y=a·y'+b (Mamba2 SSD state recurrence)",
))


def chunk_scan(a, b, mode=None):
    return isa.call("c4_chunkscan", a, b, mode=mode)


def _chunkscan_state_kernel(a, b, axis: int = 1, *, interpret: bool = False):
    # kernel path: broadcast decay to state rank, scan along last axis
    # (the model calls it per shard under shard_map, models/ssm.py).
    extra = b.ndim - a.ndim
    ab = jnp.broadcast_to(a.reshape(a.shape + (1,) * extra), b.shape)
    ab = jnp.moveaxis(ab, axis, -1)
    bb = jnp.moveaxis(b, axis, -1)
    out = _chunkscan_kernel(ab.reshape(-1, ab.shape[-1]),
                            bb.reshape(-1, bb.shape[-1]),
                            interpret=interpret)
    return jnp.moveaxis(out.reshape(bb.shape), -1, axis)


isa.register(Instruction(
    name="c4_statescan",
    spec=OperandSpec(itype="I'", vector_in=2, vector_out=1),
    ref=ref.chunk_scan_state,
    kernel=_chunkscan_state_kernel,
    pipeline_depth=2,
    doc="c4_chunkscan with shared per-head decay (SSD chunk states)",
))


def chunk_scan_state(a, b, axis: int = 1, mode=None):
    return isa.call("c4_statescan", a, b, axis=axis, mode=mode)


# ---------------------------------------------------------------------------
# c0 streaming family (S'-type)
# ---------------------------------------------------------------------------

# S'-type: the paper's two scalar sources are the base address + loop index;
# in a dataflow compiler addressing is the BlockSpec index map, so the
# dispatch signature carries only the vector operand.
# Every template-backed op registers its KernelTemplate so Registry.fuse
# can chain its Stage into a single-pallas_call fused program.
isa.register(Instruction(
    name="c0_copy", spec=OperandSpec(itype="S'", scalar_in=0, vector_in=1,
                                     vector_out=1),
    ref=ref.stream_copy, kernel=_sc.stream_copy_pallas, pipeline_depth=1,
    template=_sc.COPY,
    doc="c0_lv + c0_sv: streaming vector move (memcpy building block); "
        "S'-type rs1/rs2 (base+index) become the BlockSpec index map"))

isa.register(Instruction(
    name="c0_scale", spec=OperandSpec(itype="I'", scalar_in=1, vector_in=1,
                                      vector_out=1),
    ref=ref.stream_scale, kernel=_sc.stream_scale_pallas, pipeline_depth=1,
    template=_sc.SCALE, doc="STREAM Scale"))

isa.register(Instruction(
    name="c0_add", spec=OperandSpec(itype="I'", vector_in=2, vector_out=1),
    ref=ref.stream_add, kernel=_sc.stream_add_pallas, pipeline_depth=1,
    template=_sc.ADD, doc="STREAM Add"))

isa.register(Instruction(
    name="c0_triad", spec=OperandSpec(itype="I'", scalar_in=1, vector_in=2,
                                      vector_out=1),
    ref=ref.stream_triad, kernel=_sc.stream_triad_pallas, pipeline_depth=1,
    template=_sc.TRIAD, doc="STREAM Triad"))


def stream_copy(x, mode=None):
    return isa.call("c0_copy", x, mode=mode)

def stream_scale(x, s, mode=None):
    return isa.call("c0_scale", x, s, mode=mode)

def stream_add(a, b, mode=None):
    return isa.call("c0_add", a, b, mode=mode)

def stream_triad(a, b, s, mode=None):
    return isa.call("c0_triad", a, b, s, mode=mode)


# ---------------------------------------------------------------------------
# c5_topk
# ---------------------------------------------------------------------------

def _topk_kernel(x, k: int, *, interpret: bool = False):
    x2d, lead = _as_rows(x, x.shape[-1])
    x2d, r = _pad_rows(x2d)
    vals, idx = _tk.topk_pallas(x2d, k, interpret=interpret)
    return (vals[:r].reshape(*lead, k), idx[:r].reshape(*lead, k))


isa.register(Instruction(
    name="c5_topk",
    spec=OperandSpec(itype="I'", scalar_in=1, vector_in=1, vector_out=2),
    ref=ref.topk,
    kernel=_topk_kernel,
    pipeline_depth=8,
    doc="descending key/payload sort → top-k values + indices (MoE router)",
))


def topk(x, k: int, mode=None):
    return isa.call("c5_topk", x, k, mode=mode)


# ---------------------------------------------------------------------------
# c6_flashattn
# ---------------------------------------------------------------------------

def _flashattn_kernel(q, k, v, causal=True, scale=None, *,
                      interpret: bool = False):
    b, h, s, d = q.shape
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, k.shape[2], d)
    vf = v.reshape(b * h, v.shape[2], d)
    block = 128 if s % 128 == 0 else (64 if s % 64 == 0 else s)
    out = _fa.flash_attention_pallas(qf, kf, vf, causal=causal, scale=scale,
                                     block_q=block, block_k=block,
                                     interpret=interpret)
    return out.reshape(b, h, s, d)


isa.register(Instruction(
    name="c6_flashattn",
    spec=OperandSpec(itype="I'", vector_in=2, vector_out=1),  # (q, kv) fused pair
    ref=ref.flash_attention,
    kernel=_flashattn_kernel,
    pipeline_depth=2,
    doc="fused blockwise attention with carried (m, l) state",
))


def flash_attention(q, k, v, causal=True, scale=None, mode=None):
    # The ISA operand budget counts register *names*; K and V stream from the
    # same base address pair (S'-style), so they count as one vector source —
    # hence manual dispatch here rather than isa.call's 2-operand check.
    mode = isa.resolve_auto(mode or isa.current_mode())
    isa.registry.dispatch_counts[("c6_flashattn", mode)] += 1
    if mode == "ref":
        return ref.flash_attention(q, k, v, causal=causal, scale=scale)
    return isa.kernel_with_oracle_grad(
        functools.partial(_flashattn_kernel, causal=causal, scale=scale,
                          interpret=(mode == "interpret")),
        functools.partial(ref.flash_attention, causal=causal, scale=scale),
        (q, k, v))


# ---------------------------------------------------------------------------
# c0 DAG pipelines — branching/shared-input dataflow graphs over the
# streaming family, the shapes the repro.graph partitioner explores
# (DESIGN.md §11). Linear chains stay on Registry.fuse.
# ---------------------------------------------------------------------------

C0_PIPELINES = ("axpby_residual", "saxpby", "diamond")


def c0_pipeline_graph(kind: str = "axpby_residual"):
    """Build a named DAG-shaped c0 pipeline as a :class:`repro.graph.ir.
    Graph` (branching, shared inputs and fan-out — not just chains).

    axpby_residual: out1 = copy(add(scale(x, s), b)), out2 = triad(x, b, t)
                    — a fusable 3-chain next to a branch sharing both
                    inputs (the bench_graph workload).
    saxpby:         out = add(scale(x, a), scale(y, b)) — two chains
                    joining at an add; only one can absorb the join.
    diamond:        a = scale(x, s); out = add(copy(a), a) — fan-out on a,
                    so a must materialise and cannot be elided.
    """
    from repro.graph.ir import Graph   # deferred: graph imports the ISA
    g = Graph(name=f"c0_{kind}")
    if kind == "axpby_residual":
        x, b = g.input("x"), g.input("b")
        s, t = g.scalar("s"), g.scalar("t")
        u = g.apply("c0_scale", x, s)
        v = g.apply("c0_add", u, b)
        g.output(g.apply("c0_copy", v))
        g.output(g.apply("c0_triad", x, b, t))
    elif kind == "saxpby":
        x, y = g.input("x"), g.input("y")
        a, b = g.scalar("a"), g.scalar("b")
        u = g.apply("c0_scale", x, a)
        v = g.apply("c0_scale", y, b)
        g.output(g.apply("c0_add", u, v))
    elif kind == "diamond":
        x, s = g.input("x"), g.scalar("s")
        a = g.apply("c0_scale", x, s)
        c = g.apply("c0_copy", a)
        g.output(g.apply("c0_add", c, a))
    else:
        raise ValueError(f"unknown c0 pipeline {kind!r}; "
                         f"have {C0_PIPELINES}")
    g.validate()
    return g


# ---------------------------------------------------------------------------
# The mergesort application (paper §4.3.1): sort-in-chunks + pairwise merges.
# ---------------------------------------------------------------------------

_MERGE_PATH_LEVELS = _metrics.REGISTRY.counter(
    "repro_mergesort_mergepath_levels_total",
    help="mergesort levels wider than one kernel block, run as merge-path "
         "partitioned c1_merge launches")


@functools.partial(jax.jit, static_argnames="block")
def _merge_path(x, w, *, block: int):
    """Merge-path partition of one merge level into ``block``-key windows.

    ``x`` holds pairs of sorted runs ``a, b``, each ``w`` keys long, back to
    back (any shape; ``w`` is traced, so every level shares one compiled
    function). Output block ``k`` of the level, at diagonal ``d`` of its
    pair, starts after the first ``i`` keys of ``a`` and ``j = d - i`` of
    ``b``, where the co-rank ``i`` counts the keys of ``a`` among the first
    ``d`` of merge(a, b) (ties taken from ``a`` first): a binary search
    over all blocks at once. Returns the c1_merge ``windows`` of the level:
    ``a`` from ``i`` and ``b`` from ``j``, each cut at its run's end. The
    ``block`` smallest keys of window pair ``k`` are output block ``k``:
    every key of the pair outside it ranks after it, and the padding past
    a run's end or a tie only puts an equal key in its place.
    """
    flat = x.reshape(-1)
    start = jnp.arange(flat.size // block, dtype=jnp.int32) * block
    base = start - start % (2 * w)          # the pair's first key
    d = start - base                        # the block's diagonal in its pair
    lo, hi = jnp.maximum(d - w, 0), jnp.minimum(d, w)

    def search(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        a_mid = flat[base + jnp.minimum(mid, w - 1)]
        b_before = flat[base + w + jnp.maximum(d - mid - 1, 0)]
        after = a_mid <= b_before           # a[mid] is among the first d
        open_ = lo < hi
        return (jnp.where(open_ & after, mid + 1, lo),
                jnp.where(open_ & ~after, mid, hi))

    # hi - lo <= w halves each step: w.bit_length() steps close it
    steps = 32 - jax.lax.clz(jnp.int32(w))
    i, _ = jax.lax.fori_loop(0, steps, search, (lo, hi))
    return base + i, base + w, base + w + d - i, base + 2 * w


def sortnet_mergesort(x: jax.Array, base_width: int = 8,
                      max_kernel_width: int = 4096, mode=None) -> jax.Array:
    """Sort the last axis using c2_sort for chunks then c1_merge levels.

    Levels up to ``max_kernel_width`` keys wide (the VMEM working-set
    bound, the same limit the paper hits when a merge no longer fits one
    register pair) merge whole run pairs in one c1_merge launch. Each wider
    level is merge-path partitioned (:func:`_merge_path`) into windows of
    ``block`` = ``max_kernel_width // 2`` keys, rounded down to a power of
    two, and runs as one c1_merge launch that loads every window pair from
    its offsets and keeps the lower halves; counted by
    ``repro_mergesort_mergepath_levels_total``. The work per level stays
    linear, and every wide level launches the same kernel shape.
    """
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError("length must be a power of two")
    if n <= base_width:
        return sort_chunks(x, width=n, mode=mode)
    x = sort_chunks(x, width=base_width, mode=mode)
    w = base_width
    lead = x.shape[:-1]
    while w < n and 2 * w <= max_kernel_width:
        pairs = x.reshape(*lead, n // (2 * w), 2, w)
        lo, hi = merge_sorted(pairs[..., 0, :].reshape(-1, w),
                              pairs[..., 1, :].reshape(-1, w),
                              width=w, mode=mode)
        merged = jnp.concatenate(
            [lo.reshape(*lead, n // (2 * w), w),
             hi.reshape(*lead, n // (2 * w), w)], axis=-1)
        x = merged.reshape(*lead, n)
        w *= 2
    if w == n:
        return x
    if max_kernel_width < 4:
        raise ValueError(f"max_kernel_width {max_kernel_width} < 4 leaves "
                         f"no merge block of two or more keys")
    block = 1 << (max_kernel_width.bit_length() - 2)
    x = x.reshape(-1, block)        # one shape at every level: one trace
    while w < n:
        x, _ = merge_sorted(x, x, width=block, mode=mode,
                            windows=_merge_path(x, w, block=block))
        _MERGE_PATH_LEVELS.inc()
        w *= 2
    return x.reshape(*lead, n)
