"""The reduction from a profiler trace to the benchmark's device numbers,
on hand-made traces whose answers are known (data/two_kernels.pbtxt, and
data/scoped_ops.pbtxt for the ops' op-name paths)."""
import os

import pytest

from chipbench import harness, peaks, trace_reduce
from chipbench.layer_metrics import _common

DATA = os.path.join(os.path.dirname(__file__), "data", "two_kernels.pbtxt")
SCOPED = os.path.join(os.path.dirname(__file__), "data", "scoped_ops.pbtxt")
US = 1e-6


def written(text_path, tmp_path_factory):
    """The hand-made trace as the profiler writes it: a serialized XSpace
    in an ``.xplane.pb`` file under a log directory."""
    from jax.profiler import ProfileData
    with open(text_path) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    log_dir = tmp_path_factory.mktemp("trace")
    path = log_dir / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(xspace)
    return str(log_dir)


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        return trace_reduce.from_profile(ProfileData.from_text_proto(f.read()))


@pytest.fixture(scope="module")
def red(trace):
    return trace_reduce.reduce(trace, devices=[0])


def test_reads_device_ops_and_only_harness_spans(trace):
    assert sorted(trace.device_ops) == [0]
    assert len(trace.device_ops[0]) == 5          # XLA Modules line ignored
    assert [s.name for s in trace.host_spans] == [
        "cb.window", "cb.submit", "cb.drain", "cb.wait"]
    assert trace_reduce.window_of(trace) == (0.0, 100000.0)


def test_busy_and_idle_share(red):
    # [10, 40] + [50, 60] + [95, 100] us of a 100 us window
    assert red.window_s == pytest.approx(100 * US)
    assert red.busy_s == pytest.approx(45 * US)
    assert red.idle_share == pytest.approx(0.55)


def test_op_self_time_excludes_nested_ops_and_clips_to_window(red):
    assert red.op_seconds == pytest.approx({
        "while": 7 * US, "fusion": 8 * US, "tpu_custom_call": 15 * US,
        "prefix_sum_pallas": 10 * US, "copy": 5 * US})


def test_idle_gaps_split_among_the_host_spans_covering_them(red):
    # gaps [0, 10], [40, 50], [60, 95] us; drain runs to 70, wait after
    assert red.gaps_by_host == pytest.approx({
        "cb.submit": 10 * US, "cb.drain": 20 * US, "cb.wait": 25 * US})


def test_breakdown_lists_ops_and_gaps_longest_first(red):
    b = trace_reduce.breakdown(red)
    assert [n for n, _ in b["device_ops"]][:2] == ["tpu_custom_call",
                                                   "prefix_sum_pallas"]
    assert b["idle_gaps"][0] == ["cb.wait", pytest.approx(25 * US)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_busy_within_host_spans(trace):
    busy, total = trace_reduce.busy_within(trace, [(0.0, 12000.0),
                                                   (50000.0, 70000.0)], [0])
    assert total == pytest.approx(32 * US)
    assert busy == pytest.approx((2 + 10) * US)


def test_kernel_seconds_by_trace_name(red):
    pats = harness.kernel_patterns(["c0_program", "c3_prefixsum",
                                    "c4_statescan"])
    assert trace_reduce.kernel_seconds(red, pats) == pytest.approx({
        "c0_program": 15 * US, "c3_prefixsum": 10 * US})


def test_roofline_share_is_least_time_over_device_time(trace, red):
    bw = peaks.PEAKS["TPU v5 lite"].hbm_bytes_s
    data = harness.RunData(
        cell=None, peaks=peaks.PEAKS["TPU v5 lite"], devices=[0],
        records={}, counters={},
        # least times 7.5 us and 2.5 us, both bound by bytes
        work={"c0_program": [1e3, 7.5 * US * bw],
              "c3_prefixsum": [1e3, 2.5 * US * bw]},
        reduction=red, trace=trace,
        kernel_patterns=harness.kernel_patterns(["c0_program",
                                                 "c3_prefixsum"]))
    assert _common.roofline_percent(data, ["c0_program"]) == pytest.approx(50)
    assert _common.roofline_percent(data, ["c3_prefixsum"]) == \
        pytest.approx(25)
    assert _common.roofline_percent(
        data, ["c0_program", "c3_prefixsum"]) == pytest.approx(40)
    # a kernel the trace does not show gives nothing, never 0
    data.work["c4_statescan"] = [1.0, 1.0]
    data.kernel_patterns.update(harness.kernel_patterns(["c4_statescan"]))
    assert _common.roofline_percent(data, ["c4_statescan"]) is None
    assert _common.idle_percent(data) == pytest.approx(55)


def test_op_name_strips_the_hlo_text():
    assert trace_reduce.op_name(
        "%merge_sorted_pallas.1 = (s32[8]) custom-call(s32[8] %a)") == \
        "merge_sorted_pallas"
    assert trace_reduce.op_name("%while = (s32[]) while(...)") == "while"


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return trace_reduce.load(written(SCOPED, tmp_path_factory))


def test_device_ops_carry_their_op_name_paths(scoped):
    assert [e.scope for e in scoped.device_ops[0]] == [
        "", "jit(step)/while/body/attn/dot_general:",
        "jit(step)/while/body/ssm/mul:",
        "jit(step)/while/body/mlp/pallas_call:", "jit(step)/copy:",
        "",             # two metadata entries of this name, two paths
        "jit(step)/while/body/attn/dot_general:"]
    assert [e.scope for e in scoped.device_ops[1]] == [
        "jit(step)/while/body/attn/dot_general:"]
    assert all(e.scope == "" for e in scoped.host_spans)


@pytest.mark.parametrize("component,seconds", [
    # the loop's own 17 us carry no path; the last attention op is
    # clipped to the window's 5 us
    ("while", 38 * US), ("body", 38 * US), ("attn", 13 * US),
    ("ssm", 15 * US), ("mlp", 10 * US), ("copy", 10 * US),
    ("dot_general", 13 * US), ("jit(step)", 48 * US)])
def test_scope_seconds_by_path_component(scoped, component, seconds):
    assert trace_reduce.scope_seconds(scoped, [0], component) == \
        pytest.approx(seconds)


def test_scope_seconds_is_the_mean_over_devices(scoped):
    assert trace_reduce.scope_seconds(scoped, [0, 1], "attn") == \
        pytest.approx((13 + 10) / 2 * US)


@pytest.mark.parametrize("component", ["nothing", "jit(sample)", "tf_op",
                                       "at"])
def test_scope_seconds_finds_nothing_where_no_op_has_the_part(scoped,
                                                              component):
    assert trace_reduce.scope_seconds(scoped, [0], component) is None


def test_scope_seconds_is_none_where_the_ops_carry_no_paths(trace):
    assert all(e.scope == "" for e in trace.device_ops[0])
    assert trace_reduce.scope_seconds(trace, [0], "while") is None


def test_op_times_of_a_scoped_trace_are_by_op_name(scoped):
    assert trace_reduce.reduce(scoped, devices=[0]).op_seconds == \
        pytest.approx({"while": 17 * US, "fusion": 28 * US,
                       "mlp_pallas": 10 * US, "copy": 15 * US})


def test_loading_the_written_trace_leaves_the_reduction_as_it_was(
        red, tmp_path_factory):
    loaded = trace_reduce.reduce(
        trace_reduce.load(written(DATA, tmp_path_factory)), devices=[0])
    assert loaded == red
    assert trace_reduce.breakdown(loaded) == trace_reduce.breakdown(red)
    pats = harness.kernel_patterns(["c0_program", "c3_prefixsum",
                                    "c4_statescan"])
    assert trace_reduce.kernel_seconds(loaded, pats) == \
        trace_reduce.kernel_seconds(red, pats)
