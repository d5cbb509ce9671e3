"""The main path's kernels compile for a TPU v5e at their real widths.

Nothing runs: each test compiles for a described (not attached) chip and
checks that the Pallas kernel is in the executable. That catches what
interpret mode cannot — Mosaic's refusals of unsupported vector ops,
layouts and VMEM footprints — at no chip time. The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels  # noqa: F401 — registers the instruction set
from repro.core import isa
from repro.core.program import _BLOCK_COL_CANDIDATES
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_in(exe) -> bool:
    return "tpu_custom_call" in exe.as_text()


F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16

# (name, fn, operand shapes): the widths chip_smoke.py runs.
CASES = {
    "c2_sort": (lambda x: ops.sort_chunks(x, width=8, mode="kernel"),
                [((1 << 24,), I32)]),
    "c1_merge": (lambda a, b: ops.merge_sorted(a, b, mode="kernel"),
                 [((1 << 12, 2048), I32), ((1 << 12, 2048), I32)]),
    # one merge-path level of the 2^24-key mergesort: 8192 windows
    "c1_merge_windows": (
        lambda x, *win: ops.merge_sorted(x, x, width=2048, windows=win,
                                         mode="kernel"),
        [((1 << 24,), I32)] + [((8192,), I32)] * 4),
    "c1_merge_windows_f32": (
        lambda x, *win: ops.merge_sorted(x, x, width=2048, windows=win,
                                         mode="kernel"),
        [((8192, 2048), F32)] + [((8192,), I32)] * 4),
    "mergesort_merge_path": (
        lambda x: ops.sortnet_mergesort(x, mode="kernel"),
        [((1 << 16,), I32)]),
    "c5_topk_router8": (lambda x: ops.topk(x, 2, mode="kernel"),
                        [((4096, 8), F32)]),
    "c3_prefixsum": (lambda x: ops.prefix_sum(x, mode="kernel"),
                     [((1 << 26,), F32)]),
    "c3_prefixsum_rows": (lambda x: ops.prefix_sum(x, mode="kernel"),
                          [((1024, 4096), F32)]),
    # Mamba2-1.3B prefill, batch 4 × 512 tokens: (B, chunks, heads) decay,
    # (B, chunks, heads, headdim, state) chunk states
    "c4_statescan": (lambda a, s: ops.chunk_scan_state(a, s, axis=1,
                                                       mode="kernel"),
                     [((4, 2, 64), F32), ((4, 2, 64, 64, 128), F32)]),
    # Falcon-H1-34B prefill, one 16,384-token row: 128 chunks of 32 heads,
    # a (128, 256) state payload
    "c4_statescan_falcon_h1": (
        lambda a, s: ops.chunk_scan_state(a, s, axis=1, mode="kernel"),
        [((1, 128, 32), F32), ((1, 128, 32, 128, 256), F32)]),
    "c6_flashattn": (lambda q, k, v: ops.flash_attention(q, k, v,
                                                         mode="kernel"),
                     [((1, 32, 2048, 128), BF16)] * 3),
    "c0_fused_scale_add": (
        lambda s, x, b: isa.fuse("c0_scale", "c0_add")(s, x, b,
                                                       mode="kernel"),
        [((), F32), ((128 << 20,), F32), ((128 << 20,), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert _kernel_in(_compile(fn, *args))


def test_widest_negotiable_block_compiles(one_chip):
    """The widest block negotiation may pick, on a three-stage chain (six
    resident operand blocks), fits the kernel's VMEM limit."""
    prog = isa.fuse("c0_scale", "c0_add", "c0_triad").program
    rows, cols = 8, max(_BLOCK_COL_CANDIDATES)
    vec = jax.ShapeDtypeStruct((64 * rows, 4 * cols), F32, sharding=one_chip)
    sc = jax.ShapeDtypeStruct((1,), F32, sharding=one_chip)
    exe = _compile(lambda s, x, b, t, c: prog.call_blocks(
        s, x, b, t, c, block_rows=rows, block_cols=cols),
        sc, vec, vec, sc, vec)
    assert _kernel_in(exe)
