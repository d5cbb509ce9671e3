"""Operation and byte counts of each kernel and model step, against
counts made by hand at small shapes; the table of peaks."""
import pytest

from chipbench import harness, peaks


def work(name, **kw):
    return harness.load_module("work", name).work(**kw)


def test_c0_program_counts_external_operands_once():
    # s*x + b over 1024 f32: read x and b, write one: 12 bytes, 2 ops
    assert work("c0_program", n=1024, vec_in=2, vec_out=1,
                flops_per_elem=2) == (2048.0, 12288.0)
    # the axpby_residual DAG: x, b in; two outputs; scale, add, triad
    assert work("c0_program", n=10, vec_in=2, vec_out=2,
                flops_per_elem=4) == (40.0, 160.0)


def test_c1_merge_network():
    # a merge of two sorted 4-chunks: log2(8) = 3 layers of 4
    # compare-exchanges, 2 operations each; 8 keys read and written
    assert work("c1_merge", n=8, width=4) == (24.0, 64.0)


def test_c2_sort_bitonic_layers():
    # width 8: 3 * 4 / 2 = 6 layers of 4 compare-exchanges, 2 ops each
    assert work("c2_sort", n=8, width=8) == (48.0, 64.0)


def test_c3_prefixsum_one_add_per_value():
    assert work("c3_prefixsum", n=1 << 20) == (float(1 << 20), 8.0 * (1 << 20))


def test_c4_statescan_states_read_and_written_once():
    f, b = work("c4_statescan", batch=1, chunks=2, heads=1, headdim=2,
                state=3)
    assert f == 2 * 12                    # a multiply and an add each
    assert b == (2 * 12 + 2) * 4          # states in and out, 2 decays


def test_mamba2_prefill_by_hand_at_a_tiny_shape():
    # d 2, d_inner 4, state 1, 2 heads of 2, chunk 2, conv 2, vocab 3
    f, b = work("mamba2_prefill", batch=1, seq=2, n_layers=1, d_model=2,
                d_inner=4, state=1, heads=2, headdim=2, chunk=2,
                conv_width=2, vocab=3, weight_bytes=100.0)
    proj = 2 * 2 * (8 + 2 + 2) + 2 * 4 * 2           # 48 + 16 = 64
    conv = 2 * 2 * (4 + 2)                           # 24
    chunk = (2 * 4 * 1 + 2 * 4 * 2 * 2 + 4 * 2 * 1 * 2 * 2 + 2 * 2 * 2 * 1)
    assert f == 2 * (proj + conv) + chunk + 2 * 2 * 3
    assert b == 100.0 + 2 * 2 * 2 * 2 + 2 * 2 * 1 * 4


def test_mamba2_decode_bytes_are_weights_state_and_conv_window():
    f, b = work("mamba2_decode", batch=2, n_layers=3, d_model=2, d_inner=4,
                state=1, heads=2, headdim=2, conv_width=2, vocab=3,
                weight_bytes=1000.0)
    assert b == 1000.0 + 3 * 2 * 2 * 2 * 1 * 4 * 2 + 3 * 2 * 1 * 6 * 2 * 2
    proj = 2 * 2 * (8 + 2 + 2) + 2 * 4 * 2
    conv = 2 * 2 * 6
    ssm = 4 * 2 * 2 * 1
    assert f == 2 * (3 * (proj + conv + ssm) + 2 * 2 * 3)


def test_mamba2_1p3b_decode_moves_about_nine_gigabytes_at_batch_32():
    _, b = work("mamba2_decode", batch=32, n_layers=48, d_model=2048,
                d_inner=4096, state=128, heads=64, headdim=64, conv_width=4,
                vocab=50280, weight_bytes=2.69e9)
    assert 9.0e9 < b < 9.3e9


@pytest.mark.parametrize("context", [512.0, 1234.5, 18432.0])
def test_mamba2_decode_count_does_not_depend_on_the_context(context):
    # the state and convolution window have one size at every position
    sizes = dict(batch=32, n_layers=48, d_model=2048, d_inner=4096,
                 state=128, heads=64, headdim=64, conv_width=4, vocab=50280,
                 weight_bytes=2.69e9)
    assert work("mamba2_decode", context=context, **sizes) == \
        work("mamba2_decode", **sizes)


def test_peaks_table_and_least_time():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_s) == (197e12, 819e9)
    assert peaks.least_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert peaks.least_seconds(1.0, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_mergesort_kind_counts_only_its_pallas_levels():
    # 16 keys: the c2_sort of 8-chunks (6 layers) and one c1_merge level of
    # width 8 (4 layers); wider levels, run on XLA's sort, are not counted
    k = harness.load_module("programs", "mergesort")
    got = k.work(16)
    assert got["c2_sort"] == [16.0 * 6, 2 * 16 * 4.0]
    assert got["c1_merge"] == [16.0 * 4, 2 * 16 * 4.0]
    # 2^24 keys: merge widths 8, 16, ..., 2048 (9 levels) on the kernel
    n = 1 << 24
    merge = harness.load_module("work", "c1_merge")
    assert k.work(n)["c1_merge"] == [
        sum(merge.work(n=n, width=8 << i)[0] for i in range(9)),
        9 * 2 * 4.0 * n]


@pytest.mark.parametrize("kind,flops,vectors", [
    ("copy", 0, 2), ("scale", 1, 2), ("add", 1, 3), ("triad", 2, 3)])
def test_stream_kinds_count_stream_bytes(kind, flops, vectors):
    # STREAM's own count: every array read or written once, float32
    got = harness.load_module("programs", kind).work(1 << 20)
    assert got == {"c0_program": [flops * float(1 << 20),
                                  vectors * 4.0 * (1 << 20)]}


def test_mamba2_prefill_kernels_are_one_state_scan_per_layer():
    got = harness.load_module("work", "mamba2_prefill").kernels(
        batch=4, seq=2048, n_layers=48, state=128, heads=64, headdim=64,
        chunk=256, d_model=2048)
    f, b = work("c4_statescan", batch=4, chunks=8, heads=64, headdim=64,
                state=128)
    assert got == {"c4_statescan": [48 * f, 48 * b]}
