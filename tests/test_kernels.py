"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs. ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def arr(shape, dtype):
    if np.issubdtype(np.dtype(dtype), np.integer):
        return jnp.asarray(RNG.integers(-10_000, 10_000, shape), dtype)
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# c2_sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("shape,width", [
    ((1, 8), 8), ((5, 64), 8), ((16, 256), 16), ((3, 128), 4),
    ((7, 32), 32), ((2, 1024), 64),
])
def test_sort_chunks(shape, width, dtype):
    x = arr(shape, dtype)
    got = ops.sort_chunks(x, width=width, mode="interpret")
    want = ref.sort_chunks(x, width=width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sort_descending():
    x = arr((4, 64), jnp.float32)
    got = ops.sort_chunks(x, width=8, descending=True, mode="interpret")
    want = ref.sort_chunks(x, width=8, descending=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sort_3d_operand():
    x = arr((2, 3, 32), jnp.float32)
    got = ops.sort_chunks(x, width=8, mode="interpret")
    want = ref.sort_chunks(x, width=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# c1_merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("rows,w", [(1, 8), (4, 16), (9, 64), (16, 128)])
def test_merge_sorted(rows, w, dtype):
    a = jnp.sort(arr((rows, w), dtype), axis=-1)
    b = jnp.sort(arr((rows, w), dtype), axis=-1)
    lo, hi = ops.merge_sorted(a, b, mode="interpret")
    rlo, rhi = ref.merge_sorted(a, b)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


def test_mergesort_app():
    for n in (8, 64, 512, 4096):
        x = arr((3, n), jnp.float32)
        got = ops.sortnet_mergesort(x, mode="interpret")
        np.testing.assert_array_equal(np.asarray(got),
                                      np.sort(np.asarray(x), axis=-1))


def mergesort_keys(keys: str, shape, dtype) -> np.ndarray:
    """Keys for the merge-path mergesort: random, over the dtype's whole
    range with its extremes planted, four distinct values, or (reversed)
    sorted rows."""
    dt = np.dtype(dtype)
    if keys == "ties":
        return RNG.integers(-2, 2, shape).astype(dt)
    if keys == "full_range":
        if dt.kind == "i":
            info = np.iinfo(dt)
            x = RNG.integers(info.min, info.max, shape, dtype=np.int64,
                             endpoint=True).astype(dt)
            ends = [info.min, info.max, info.min, info.max]
        else:
            info = np.finfo(dt)
            x = (RNG.uniform(-1, 1, shape) * info.max).astype(dt)
            ends = [info.min, info.max, -np.inf, np.inf]
        flat = x.reshape(-1)
        flat[RNG.choice(flat.size, len(ends), replace=False)] = ends
        return x
    x = np.asarray(arr(shape, dtype))
    if keys == "sorted":
        return np.sort(x, axis=-1)
    if keys == "reversed":
        return np.sort(x, axis=-1)[..., ::-1].copy()
    return x


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("keys,shape,max_width", [
    ("random", (1, 16384), 1024), ("random", (3, 4096), 1024),
    ("full_range", (1, 4096), 64), ("full_range", (3, 2048), 256),
    ("ties", (1, 4096), 256), ("ties", (3, 2048), 64),
    ("sorted", (1, 2048), 64), ("sorted", (3, 2048), 256),
    ("reversed", (1, 4096), 256), ("reversed", (3, 2048), 64),
    ("random", (1, 4096), 1000),
])
def test_mergesort_merge_path(keys, shape, max_width, dtype):
    # above max_kernel_width the levels run as merge-path windows on c1_merge
    x = mergesort_keys(keys, shape, dtype)
    got = ops.sortnet_mergesort(jnp.asarray(x), max_kernel_width=max_width,
                                mode="interpret")
    assert got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(got), np.sort(x, axis=-1))


def merge_path_runs(order: str, pairs: int, w: int, distinct: int):
    """Sorted run pairs (pairs, 2, w): random keys with ties, or ``b``
    wholly after or before ``a``, or one key of ``a`` first and the rest
    after all of ``b`` (co-rank 1 at diagonal w: the search's longest
    path)."""
    runs = np.sort(RNG.integers(0, distinct, (pairs, 2, w)), axis=-1)
    if order == "b_after":
        runs[:, 1] += distinct
    elif order == "b_before":
        runs[:, 0] += distinct
    elif order == "one_a_first":
        runs[:, 0] = distinct + 1
        runs[:, 0, 0] = -1
        runs[:, 1] = np.sort(RNG.integers(0, distinct + 1, (pairs, w)))
    return runs


@pytest.mark.parametrize("order,w,block,pairs,distinct", [
    ("random", 64, 16, 1, 4), ("random", 256, 32, 3, 1000),
    ("random", 128, 64, 2, 2), ("random", 512, 128, 1, 7),
    ("b_after", 64, 16, 2, 50), ("b_before", 128, 32, 2, 50),
    ("one_a_first", 64, 16, 2, 3), ("one_a_first", 256, 128, 1, 1000),
])
def test_merge_path_windows_blocks(order, w, block, pairs, distinct):
    """Window pair k, merged, holds output block k of each run pair in
    its ``block`` smallest keys."""
    runs = merge_path_runs(order, pairs, w, distinct)
    x = jnp.asarray(runs.reshape(-1), jnp.int32)
    a_start, a_stop, b_start, b_stop = ops._merge_path(x, w, block=block)
    a_win = ref.window_keys(x, a_start, a_stop, block)
    b_win = ref.window_keys(x, b_start, b_stop, block)
    assert a_win.shape == b_win.shape == (pairs * 2 * w // block, block)
    merged = np.sort(np.concatenate([np.asarray(a_win), np.asarray(b_win)],
                                    axis=-1), axis=-1)[:, :block]
    want = np.sort(runs.reshape(pairs, 2 * w), axis=-1)
    np.testing.assert_array_equal(merged.reshape(pairs, 2 * w), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("n_win,width,size", [(16, 128, 4096), (5, 256, 300),
                                              (8, 128, 128)])
def test_merge_windows_kernel(n_win, width, size, dtype):
    """c1_merge over windows (the kernel's lane-tile DMAs, shifts and
    padding) against the oracle, windows anywhere in the keys and cut
    anywhere, the array's end included."""
    keys = jnp.sort(arr((size,), dtype))
    def offsets():
        start = RNG.integers(0, size + 1, n_win)
        stop = np.minimum(start + RNG.integers(0, 2 * width, n_win), size)
        return jnp.asarray(start, jnp.int32), jnp.asarray(stop, jnp.int32)
    windows = (*offsets(), *offsets())
    got = ops.merge_sorted(keys, keys, width=width, windows=windows,
                           mode="interpret")
    want = ref.merge_sorted(keys, keys, width=width, windows=windows)
    for g, r in zip(got, want):
        assert g.shape == (n_win, width)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_mergesort_rejects_kernel_width_below_two_key_blocks():
    with pytest.raises(ValueError, match="max_kernel_width"):
        ops.sortnet_mergesort(arr((1, 64), jnp.int32), max_kernel_width=2,
                              mode="interpret")


def _mergepath_levels() -> int:
    from repro.obs.metrics import REGISTRY
    return REGISTRY.get("repro_mergesort_mergepath_levels_total").value


@pytest.mark.parametrize("n,max_width", [(4096, 64), (2048, 256),
                                         (1024, 1024)])
def test_mergesort_merge_path_launches(n, max_width):
    """Every merge level launches c1_merge once: log2(n / base) launches,
    log2(n / max_kernel_width) of them counted as merge-path levels."""
    from repro.core import isa
    x = arr((2, n), jnp.int32)
    levels0 = _mergepath_levels()
    merges0 = isa.registry.dispatch_counts[("c1_merge", "interpret")]
    ops.sortnet_mergesort(x, base_width=8, max_kernel_width=max_width,
                          mode="interpret")
    assert _mergepath_levels() - levels0 == int(np.log2(n // max_width))
    assert (isa.registry.dispatch_counts[("c1_merge", "interpret")]
            - merges0) == int(np.log2(n // 8))


@pytest.mark.parametrize("mode,has_sort", [("interpret", False),
                                           ("ref", True)])
def test_mergesort_lowering_sort_ops(mode, has_sort):
    """The kernel path lowers to no sort op; the oracle path, which sorts,
    shows that the check can see one."""
    x = arr((1, 1024), jnp.int32)
    text = jax.jit(lambda v: ops.sortnet_mergesort(
        v, max_kernel_width=64, mode=mode)).lower(x).as_text()
    assert ("stablehlo.sort" in text) == has_sort


# ---------------------------------------------------------------------------
# c3_prefixsum / c4_chunkscan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 8), (4, 128), (8, 1024), (3, 4096)])
def test_prefix_sum(shape):
    x = arr(shape, jnp.float32)
    got = ops.prefix_sum(x, mode="interpret")
    np.testing.assert_allclose(np.asarray(got),
                               np.cumsum(np.asarray(x), axis=-1),
                               rtol=2e-5, atol=1e-4)


def test_exclusive_prefix_sum():
    x = arr((4, 64), jnp.float32)
    got = ops.exclusive_prefix_sum(x, mode="interpret")
    want = np.cumsum(np.asarray(x), axis=-1) - np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("shape", [(1, 16), (4, 256), (8, 1024)])
def test_chunk_scan(shape):
    a = jnp.asarray(RNG.uniform(0.2, 1.0, shape), jnp.float32)
    b = arr(shape, jnp.float32)
    got = ops.chunk_scan(a, b, mode="interpret")
    want = ref.chunk_scan(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_chunk_scan_matches_sequential():
    a = jnp.asarray(RNG.uniform(0.2, 1.0, (2, 64)), jnp.float32)
    b = arr((2, 64), jnp.float32)
    got = np.asarray(ops.chunk_scan(a, b, mode="interpret"))
    y = np.zeros(2)
    for i in range(64):
        y = np.asarray(a[:, i]) * y + np.asarray(b[:, i])
        np.testing.assert_allclose(got[:, i], y, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# c0 streaming family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 1000, 5000, 65536])
def test_stream_family(n):
    a = arr((n,), jnp.float32)
    b = arr((n,), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.stream_copy(a, mode="interpret")), np.asarray(a))
    np.testing.assert_allclose(
        np.asarray(ops.stream_scale(a, 2.5, mode="interpret")),
        np.asarray(a) * 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.stream_add(a, b, mode="interpret")),
        np.asarray(a) + np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.stream_triad(a, b, 3.0, mode="interpret")),
        np.asarray(a) + 3.0 * np.asarray(b), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# c5_topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,n,k", [
    (1, 8, 2), (16, 384, 8), (32, 8, 2), (8, 512, 16), (4, 151, 5),
])
def test_topk(rows, n, k):
    x = arr((rows, n), jnp.float32)
    v, i = ops.topk(x, k, mode="interpret")
    rv, ri = ref.topk(x, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_topk_ties_deterministic():
    x = jnp.zeros((4, 16), jnp.float32)
    v, i = ops.topk(x, 4, mode="interpret")
    rv, ri = ref.topk(x, 4)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


# ---------------------------------------------------------------------------
# c6_flashattn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,d", [
    (1, 1, 128, 64), (2, 4, 128, 64), (1, 2, 256, 128), (2, 2, 64, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(b, h, s, d, causal):
    q = arr((b, h, s, d), jnp.float32)
    k = arr((b, h, s, d), jnp.float32)
    v = arr((b, h, s, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, mode="interpret")
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = arr((1, 2, 128, 64), jnp.bfloat16)
    k = arr((1, 2, 128, 64), jnp.bfloat16)
    v = arr((1, 2, 128, 64), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, mode="interpret")
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# odd-even mergesort topology (paper §2.2's other network)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [2, 8, 32, 128, 512])
def test_oddeven_network_sorts(w):
    from repro.kernels.sortnet import oddeven_sort_network
    x = arr((6, w), jnp.float32)
    out = np.asarray(oddeven_sort_network(x))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x), axis=-1))


def test_oddeven_matches_bitonic():
    from repro.kernels.sortnet import (bitonic_sort_network,
                                       oddeven_sort_network)
    x = arr((4, 64), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(oddeven_sort_network(x)),
        np.asarray(bitonic_sort_network(x)))
