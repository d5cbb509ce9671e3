"""Device idle put down to the program's own spans (``repro.*`` host
events, written by ``repro.obs.trace`` while the profiler collects): the
three readers on a hand-made trace whose answers are known
(data/program_spans.pbtxt), the interval arithmetic under them, the
trace reduction left as it was by those spans, and traced tiny CPU runs
of every cell."""
import os

import pytest

from chipbench import harness, trace_reduce
from chipbench.layer_metrics import _spans

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1e-6
NEW = {"sched_idle_ms.prog", "sched_host_ms.prog", "launch_idle_ms.prog",
       "sample_idle_ms.decode"}


def _profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture(scope="module")
def pd():
    return _profile("program_spans.pbtxt")


@pytest.fixture
def data(pd, monkeypatch):
    window, spans = _spans.window_and_spans(pd)
    monkeypatch.setattr(_spans, "of_run", lambda data: spans)
    return harness.RunData(cell=None, peaks=None, devices=[0],
                           records={"completed": 1, "decode_steps": 2},
                           counters={}, work={},
                           trace=trace_reduce.from_profile(pd))


def read(name, data):
    return harness.load_module("layer_metrics", name).read(data)


def test_program_spans_come_from_the_host_planes(pd):
    window, spans = _spans.window_and_spans(pd)
    assert [(e.start_ns, e.end_ns) for e in window] == [(0.0, 100000.0)]
    assert [e.name for e in spans][:4] == [
        "repro.submit", "repro.admission", "repro.drain", "repro.placement"]
    assert len(spans) == 9
    assert all(e.name.startswith("repro.") for e in spans)


def test_sched_idle_is_submit_and_drain_less_launch_and_wait(data):
    # [0, 48] less [8, 44] = [0, 8] + [44, 48]; busy [44, 45]
    assert read("sched_idle_ms.prog", data) == pytest.approx(11 * US * 1e3)


def test_sched_host_is_the_same_region_on_the_host_clock(data):
    # [0, 8] + [44, 48], busy or not
    assert read("sched_host_ms.prog", data) == pytest.approx(12 * US * 1e3)
    assert read("sched_idle_ms.prog", data) <= read("sched_host_ms.prog",
                                                    data)


def test_launch_idle(data):
    # [8, 20], busy [10, 20]
    assert read("launch_idle_ms.prog", data) == pytest.approx(2 * US * 1e3)


def test_sample_idle_counts_only_samples_inside_decode_steps(data):
    # [70, 74] busy throughout, [96, 99] idle; [48, 50] is in no step
    assert read("sample_idle_ms.decode", data) == pytest.approx(
        3 * US * 1e3 / 2)


def test_program_metrics_within_the_idle_of_their_harness_span(data):
    red = trace_reduce.reduce(data.trace, devices=[0])
    assert red.gaps_by_host["cb.request"] == pytest.approx(25 * US)
    assert red.gaps_by_host["cb.decode_step"] == pytest.approx(25 * US)
    prog = read("sched_idle_ms.prog", data) + read("launch_idle_ms.prog",
                                                   data)
    assert prog <= 1e3 * red.gaps_by_host["cb.request"]
    assert read("sample_idle_ms.decode", data) * 2 <= (
        1e3 * red.gaps_by_host["cb.decode_step"])


def test_absent_spans_read_none(data, monkeypatch):
    monkeypatch.setattr(_spans, "of_run", lambda data: [])
    assert all(read(m, data) is None for m in NEW)
    monkeypatch.setattr(_spans, "of_run", lambda data: None)
    assert all(read(m, data) is None for m in NEW)
    data.trace = None
    monkeypatch.undo()
    assert _spans.of_run(data) is None


@pytest.mark.parametrize("a,b,overlap,less", [
    ([(0, 10)], [(2, 4), (6, 8)], [(2, 4), (6, 8)],
     [(0, 2), (4, 6), (8, 10)]),
    ([(0, 5), (10, 15)], [(4, 11)], [(4, 5), (10, 11)],
     [(0, 4), (11, 15)]),
    ([(0, 5)], [(5, 9)], [], [(0, 5)]),
    ([(3, 4)], [(0, 10)], [(3, 4)], []),
    ([], [(0, 1)], [], []),
    ([(0, 1)], [], [], [(0, 1)]),
])
def test_interval_overlap_and_less(a, b, overlap, less):
    assert _spans._overlap(a, b) == overlap
    assert _spans._overlap(b, a) == overlap
    assert _spans._less(a, b) == less


def test_program_spans_leave_the_reduction_as_it_was():
    """two_kernels.pbtxt with repro.* events added to its host line
    reduces exactly as the file without them."""
    with open(os.path.join(DATA, "two_kernels.pbtxt")) as f:
        text = f.read()
    last = "    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 1000000 }"
    meta = '  event_metadata { key: 5 value { id: 5 name: "PjitFunction(step)" } }'
    assert last in text and meta in text
    spans = text.replace(last, last + """
    events { metadata_id: 6 offset_ps: 0 duration_ps: 11000000 }
    events { metadata_id: 7 offset_ps: 12000000 duration_ps: 57000000 }
    events { metadata_id: 8 offset_ps: 13000000 duration_ps: 40000000 }""")
    spans = spans.replace(meta, meta + """
  event_metadata { key: 6 value { id: 6 name: "repro.submit" } }
  event_metadata { key: 7 value { id: 7 name: "repro.drain" } }
  event_metadata { key: 8 value { id: 8 name: "repro.launch" } }""")
    from jax.profiler import ProfileData
    before = trace_reduce.from_profile(ProfileData.from_text_proto(text))
    after_pd = ProfileData.from_text_proto(spans)
    after = trace_reduce.from_profile(after_pd)
    assert len(_spans.window_and_spans(after_pd)[1]) == 3
    assert after == before
    for devs in ([0], None):
        red_b = trace_reduce.reduce(before, devices=devs)
        red_a = trace_reduce.reduce(after, devices=devs)
        assert red_a == red_b
        assert trace_reduce.breakdown(red_a) == trace_reduce.breakdown(red_b)


def _session(tmp_path, name, spans):
    """One profiler session into ``tmp_path/name``: a ``cb.window``
    holding the program's host spans named in ``spans``."""
    import time

    import jax
    from repro.obs import trace as obs_trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tmp_path / name
    with jax.profiler.trace(str(d), profiler_options=opts):
        with jax.profiler.TraceAnnotation("cb.window"):
            for span in spans:
                with obs_trace.host_span(span):
                    time.sleep(0.001)
    return trace_reduce.load(str(d))


def _traced(trace, **records):
    return harness.RunData(cell=None, peaks=None, devices=[0],
                           records=records, counters={}, work={},
                           trace=trace)


def test_of_run_finds_the_trace_whose_window_is_the_runs(tmp_path,
                                                        monkeypatch):
    """The run's trace file is the newest ``chipbench_trace_*`` one whose
    ``cb.window`` matches; a newer trace of another window is passed
    over."""
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    mine = _session(tmp_path, "chipbench_trace_a", ["drain"])
    _session(tmp_path, "chipbench_trace_b", ["sample"])  # newer, not ours
    assert [e.name for e in _spans.of_run(_traced(mine))] == [
        "repro.drain"]
    assert not mine.host_spans[0].name.startswith("repro.")


def test_trace_without_program_spans_reads_none(tmp_path, monkeypatch):
    """A program that writes no spans (one older than them): the trace
    is found, holds none, and every reader gives None."""
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    data = _traced(_session(tmp_path, "chipbench_trace_a", []),
                   completed=3, decode_steps=3)
    assert _spans.of_run(data) == []
    assert all(read(m, data) is None for m in NEW)


def test_traced_run_whose_trace_is_not_found_raises(tmp_path, monkeypatch):
    """A traced run whose trace file the search misses fails, rather
    than dropping the metrics as if the program wrote no spans."""
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    mine = _session(tmp_path, "elsewhere", ["drain"])   # not searched
    _session(tmp_path, "chipbench_trace_b", ["drain"])  # another window
    data = _traced(mine, completed=3)
    with pytest.raises(FileNotFoundError, match="cb.window"):
        _spans.of_run(data)
    with pytest.raises(FileNotFoundError):
        read("launch_idle_ms.prog", data)


def _new_metrics_of(name):
    return {m["name"] for m in harness.load_benchmark()["per_layer"]
            if m["name"] in NEW and name in m.get("workloads", [name])}


CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_traced_tiny_run_reports_the_program_span_metrics(name,
                                                          monkeypatch):
    from test_cb_run_cpu import run_tiny
    res = run_tiny(name, monkeypatch, trace=True)
    want = _new_metrics_of(name)
    assert set(res["metrics"]) & NEW == want
    got = {m: res["metrics"][m]["value"] for m in want}
    assert all(v >= 0 for v in got.values())
    gaps = dict(res["breakdown"]["idle_gaps"])
    if "sched_idle_ms.prog" in want:
        per_req = 1e3 * gaps["cb.request"] / res["window"]["requests"]
        assert got["sched_idle_ms.prog"] + got["launch_idle_ms.prog"] \
            <= per_req * (1 + 1e-9)
        assert got["sched_idle_ms.prog"] <= got["sched_host_ms.prog"] * (
            1 + 1e-9)
    if "sample_idle_ms.decode" in want:
        per_step = 1e3 * gaps["cb.decode_step"] / res["window"][
            "decode_steps"]
        assert got["sample_idle_ms.decode"] <= per_step * (1 + 1e-9)
