"""Unified observability (ISSUE 7, DESIGN.md §15).

Covers :mod:`repro.obs` end to end: tracer parenting/nesting, virtual-
clock byte-stable JSONL and Chrome-trace exports, the NULL_SPAN off
path, the metrics registry (exact counter round-trips, le-inclusive
histogram bucket edges, label escaping, Prometheus text exposition and
the HTTP endpoint), the registry-backed ``DISPATCH_STATS`` view and its
test-isolation window, span wiring through queue → scheduler →
program dispatch (sweep AND disk-hit negotiate outcomes), drift
record/rank/format plus the cost-model feed, plan-cache GC (entry and
byte bounds, LRU order, load-touch, keep-newest) and EWMA-correction
persistence — including a REAL fresh subprocess warm-starting its
predictions from a parent-populated cache dir.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401 — registers the ISA
from repro.core import artifact, isa
from repro.core import program as prog_mod
from repro.memhier import TPU_V5E
from repro.obs import critical as obs_critical
from repro.obs import drift as obs_drift
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import Slo, SloMonitor, SloShedder
from repro.obs.tail import TailSampler
from repro.roofline import dispatch_cache_report
from repro.sched import CostModel, RequestQueue, Scheduler

F32 = jnp.float32


@pytest.fixture
def tracer():
    """A fresh active tracer; deactivated afterwards."""
    t = obs_trace.Tracer()
    with obs_trace.using_tracer(t):
        yield t


@pytest.fixture
def cache_dir(tmp_path):
    prog_mod.clear_dispatch_caches()
    with artifact.using_plan_cache(tmp_path):
        yield tmp_path
    prog_mod.clear_dispatch_caches()


def _operands(n=5000):
    rng = np.random.default_rng(0)
    return (2.0,
            jnp.asarray(rng.standard_normal(n), F32),
            jnp.asarray(rng.standard_normal(n), F32))


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_parents_and_finish(self):
        t = obs_trace.Tracer()
        with t.span("a") as a:
            assert t.current() is a
            with t.span("b", k=1) as b:
                assert b.parent_id == a.span_id
            assert b.end is not None and b.end >= b.start
        assert a.parent_id is None
        assert t.current() is None
        assert [s.name for s in t.children_of(a)] == ["b"]
        assert t.subtree_names(a) == ["a", "b"]

    def test_explicit_parent_and_under(self):
        t = obs_trace.Tracer()
        root = t.start_span("request", parent=None)
        with t.span("sibling"):
            with t.under(root):
                with t.span("child") as c:
                    pass
        assert c.parent_id == root.span_id
        assert root.end is None          # under() never finishes it
        t.finish(root, lane=0)
        assert root.end is not None and root.attrs["lane"] == 0

    def test_exception_marks_span_and_pops_stack(self):
        t = obs_trace.Tracer()
        with pytest.raises(RuntimeError):
            with t.span("outer"):
                with t.span("boom"):
                    raise RuntimeError("x")
        boom = t.named("boom")[0]
        assert "RuntimeError" in boom.attrs["error"]
        assert boom.end is not None
        assert t.current() is None       # stack unwound cleanly

    def test_max_spans_drops_not_grows(self):
        t = obs_trace.Tracer(max_spans=2)
        for i in range(5):
            with t.span(f"s{i}"):
                pass
        assert len(t.spans) == 2 and t.dropped == 3

    def test_virtual_clock_deterministic(self):
        c = obs_trace.VirtualClock()
        assert (c(), c(), c()) == (0.0, 1e-6, 2e-6)

    def test_jsonl_byte_stable_and_sorted(self):
        def run():
            t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
            with t.span("a", z=1, n="x"):
                with t.span("b"):
                    pass
            return t.export_jsonl()

        a, b = run(), run()
        assert a == b and a
        lines = a.strip().splitlines()
        assert [json.loads(ln)["span_id"] for ln in lines] == [1, 2]
        # sorted keys within each object => byte stability is structural
        for ln in lines:
            keys = list(json.loads(ln))
            assert keys == sorted(keys)

    def test_chrome_export_valid(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        with t.span("a", lane=2):
            with t.span("b", arr=np.float32(1.5)):
                pass
        doc = json.loads(t.export_chrome())
        ev = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(ev) == 2
        assert ev[0]["tid"] == 3         # lane+1
        assert ev[1]["args"]["parent_id"] == 1
        assert isinstance(ev[1]["args"]["arr"], float)  # jsonable attrs

    def test_null_span_when_off(self):
        assert obs_trace.get_tracer() is None
        ctx = obs_trace.span("anything", k=1)
        assert ctx is obs_trace.NULL_SPAN
        with ctx as sp:
            assert sp is None

    def test_module_span_routes_to_active(self, tracer):
        with obs_trace.span("x") as sp:
            assert sp is not None
        assert tracer.named("x")


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_exact_roundtrip(self):
        r = MetricsRegistry()
        c = r.counter("t_requests_total", "help text")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert r.counter("t_requests_total") is c     # get-or-create
        text = r.expose_text()
        assert "# HELP t_requests_total help text" in text
        assert "# TYPE t_requests_total counter" in text
        assert "\nt_requests_total 5\n" in text
        snap = json.loads(r.snapshot_json())
        fam = snap["t_requests_total"]
        assert fam["kind"] == "counter"
        assert fam["series"][0]["value"] == 5

    def test_gauge_set_and_dec(self):
        r = MetricsRegistry()
        g = r.gauge("t_depth")
        g.set(7)
        g.dec(2)
        assert g.value == 5
        assert "# TYPE t_depth gauge" in r.expose_text()

    def test_histogram_bucket_edges_le_inclusive(self):
        r = MetricsRegistry()
        h = r.histogram("t_lat", buckets=(0.1, 1.0, 10.0))
        h.observe(0.1)                   # exactly ON an edge: le=0.1
        h.observe(0.1000001)             # just past it: le=1.0
        h.observe(100.0)                 # +Inf overflow bucket
        assert h.cumulative() == [1, 2, 2, 3]
        assert h.count == 3
        assert h.sum == pytest.approx(100.2000001)
        assert h.quantile(0.50) == 1.0
        assert h.quantile(0.99) == float("inf")
        lines = h.sample_lines()
        assert 't_lat_bucket{le="0.1"} 1' in lines
        assert 't_lat_bucket{le="+Inf"} 3' in lines
        assert "t_lat_count 3" in lines

    def test_histogram_empty_quantile_nan(self):
        h = MetricsRegistry().histogram("t_e", buckets=(1.0,))
        assert h.count == 0 and h.quantile(0.5) != h.quantile(0.5)  # NaN

    def test_histogram_all_overflow_quantile_nan(self):
        """Every observation past the last finite edge: no finite edge
        bounds ANY quantile, so the answer is NaN (not inf — inf is for
        a quantile that lands in a populated overflow of an otherwise
        informative histogram, see the le-inclusive test above)."""
        h = MetricsRegistry().histogram("t_of", buckets=(0.1, 1.0))
        h.observe(5.0)
        h.observe(50.0)
        for q in (0.01, 0.5, 0.99):
            assert h.quantile(q) != h.quantile(q)    # NaN

    def test_labels_distinct_and_escaped(self):
        r = MetricsRegistry()
        r.counter("t_total", labels={"tenant": "a"}).inc()
        r.counter("t_total", labels={"tenant": "b"}).inc(2)
        assert r.get("t_total", {"tenant": "b"}).value == 2
        r.counter("t_esc_total", labels={"v": 'q"\\\n'}).inc()
        text = r.expose_text()
        assert 't_total{tenant="a"} 1' in text
        assert 't_total{tenant="b"} 2' in text
        assert 't_esc_total{v="q\\"\\\\\\n"} 1' in text

    def test_kind_and_bucket_conflicts_raise(self):
        r = MetricsRegistry()
        r.counter("t_x")
        with pytest.raises(TypeError):
            r.histogram("t_x")
        r.histogram("t_h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError):
            r.histogram("t_h", buckets=(5.0,))

    def test_exposition_parses(self):
        """Every non-comment line is `name[{labels}] value`, every
        family has exactly one HELP and one TYPE line before it."""
        r = MetricsRegistry()
        r.counter("t_a_total", "a").inc(3)
        r.histogram("t_b_seconds", "b", labels={"k": "v"},
                    buckets=(0.5,)).observe(0.25)
        r.gauge("t_c", "c").set(-1.5)
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
            r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? '
            r'(NaN|[-+]?(Inf|[0-9.eE+-]+))$')
        seen_meta = set()
        for ln in r.expose_text().splitlines():
            if not ln:
                continue
            if ln.startswith("#"):
                kind, name = ln.split()[1:3]
                seen_meta.add((kind, name))
                continue
            assert sample.match(ln), f"unparseable sample line: {ln!r}"
        for name in ("t_a_total", "t_b_seconds", "t_c"):
            assert ("HELP", name) in seen_meta
            assert ("TYPE", name) in seen_meta

    def test_http_endpoint(self):
        r = MetricsRegistry()
        r.counter("t_served_total").inc(9)
        httpd = obs_metrics.start_http_server(0, registry=r)
        try:
            host, port = httpd.server_address[:2]
            base = f"http://{host}:{port}"
            with urllib.request.urlopen(f"{base}/metrics") as resp:
                body = resp.read().decode()
                assert resp.headers["Content-Type"].startswith("text/plain")
            assert "t_served_total 9" in body
            with urllib.request.urlopen(f"{base}/metrics.json") as resp:
                doc = json.loads(resp.read().decode())
            assert doc["t_served_total"]["series"][0]["value"] == 9
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/other")
        finally:
            httpd.shutdown()
            httpd.server_close()


# ---------------------------------------------------------------------------
# DISPATCH_STATS: registry-backed view + isolation window (satellite c)
# ---------------------------------------------------------------------------

class TestDispatchStatsView:
    def test_view_is_registry_backed(self):
        before = obs_metrics.REGISTRY.counter(
            "repro_dispatch_geometry_misses_total").value
        prog_mod.DISPATCH_STATS.geometry_misses += 3
        after = obs_metrics.REGISTRY.counter(
            "repro_dispatch_geometry_misses_total").value
        assert after - before == 3
        assert prog_mod.DISPATCH_STATS.geometry_misses == after

    def test_snapshot_is_frozen_and_comparable(self):
        s = prog_mod.DISPATCH_STATS.snapshot()
        assert isinstance(s, prog_mod.DispatchStats)
        assert prog_mod.DISPATCH_STATS == s
        prog_mod.DISPATCH_STATS.disk_hit += 1
        assert prog_mod.DISPATCH_STATS != s
        with pytest.raises(AttributeError):
            prog_mod.DISPATCH_STATS.not_a_counter

    def test_window_isolates_from_ambient_state(self):
        prog_mod.DISPATCH_STATS.geometry_hits += 7   # ambient noise
        with prog_mod.dispatch_stats_window() as w:
            prog_mod.DISPATCH_STATS.geometry_hits += 2
            prog_mod.DISPATCH_STATS.disk_miss += 1
            assert w.delta("geometry_hits") == 2
        d = w.deltas()
        assert d.geometry_hits == 2 and d.disk_miss == 1
        assert d.kernel_traces == 0

    def test_reset_zeroes_in_place(self):
        view = prog_mod.DISPATCH_STATS
        view.batch_calls += 5
        prog_mod.reset_dispatch_stats()
        assert view.batch_calls == 0
        assert prog_mod.DISPATCH_STATS is view      # no global rebind


class TestRooflineReport:
    def test_dispatch_cache_report_counters_and_rates(self):
        prog_mod.reset_dispatch_stats()
        prog_mod.DISPATCH_STATS.geometry_hits += 3
        prog_mod.DISPATCH_STATS.geometry_misses += 1
        prog_mod.DISPATCH_STATS.disk_hit += 1
        prog_mod.DISPATCH_STATS.disk_miss += 1
        rep = dispatch_cache_report()
        assert rep["geometry_hits"] == 3
        assert rep["geometry_misses"] == 1
        assert rep["geometry_hit_rate"] == pytest.approx(0.75)
        assert rep["disk_hit_rate"] == pytest.approx(0.5)
        json.dumps(rep)                              # JSON-able


# ---------------------------------------------------------------------------
# Span wiring: queue -> scheduler -> program dispatch
# ---------------------------------------------------------------------------

class TestSpanWiring:
    def test_submit_emits_request_and_admission(self, tracer):
        fused = isa.fuse("c0_scale", "c0_add")
        q = RequestQueue()
        it = q.submit(fused, _operands(), tenant="t0", arrival=0.0)
        (root,) = tracer.named("request")
        assert it.span is root and root.end is None
        assert root.attrs["tenant"] == "t0"
        (adm,) = tracer.named("admission")
        assert adm.parent_id == root.span_id and adm.end is not None
        assert "c0_scale" in adm.attrs["coalesce_key"]

    def test_wall_run_builds_one_connected_tree(self, tracer):
        prog_mod.clear_dispatch_caches()
        fused = isa.fuse("c0_scale", "c0_add")
        q = RequestQueue()
        q.submit(fused, _operands(), tenant="t0", arrival=0.0)
        with artifact.using_plan_cache(None):
            Scheduler(q, cost=CostModel(hierarchy=TPU_V5E), policy="fifo",
                      n_lanes=1, clock="wall", mode="interpret").drain()
        (root,) = [s for s in tracer.spans if s.parent_id is None]
        names = tracer.subtree_names(root)
        for want in ("request", "admission", "coalesce", "placement",
                     "dispatch", "negotiate", "pallas_build"):
            assert want in names, f"{want} missing from {names}"
        assert len(names) == len(tracer.spans)       # fully connected
        assert all(s.end is not None for s in tracer.spans)
        assert root.attrs["observed_s"] > 0
        assert root.attrs["lane"] == 0
        # cost pricing and dispatch may each negotiate (distinct memory
        # models => distinct geometry keys); all are cold sweeps here
        negs = tracer.named("negotiate")
        assert negs
        assert all(s.attrs["outcome"] == "sweep" for s in negs)
        assert re.fullmatch(r"[0-9a-f]{12,}", negs[0].attrs["fingerprint"])

    def test_negotiate_outcome_disk_hit(self, cache_dir, tracer):
        fused = isa.fuse("c0_scale", "c0_add")
        fused.program.negotiate_geometry(5000, F32)   # publish
        prog_mod.clear_dispatch_caches()
        isa.fuse("c0_scale", "c0_add").program.negotiate_geometry(5000, F32)
        outcomes = [s.attrs["outcome"] for s in tracer.named("negotiate")]
        assert outcomes[-1] == "disk_hit"

    def test_coalesced_batch_single_span_per_dispatch(self, tracer):
        fused = isa.fuse("c0_scale", "c0_add")
        ops_ = _operands(2048)
        q = RequestQueue()
        for _ in range(4):
            q.submit(fused, ops_, tenant="t0", arrival=0.0)
        Scheduler(q, policy="fifo", n_lanes=1, clock="wall",
                  mode="interpret").drain()
        (co,) = tracer.named("coalesce")
        assert co.attrs["n_items"] == 4 and co.attrs["coalesced"]
        dispatches = tracer.named("dispatch")
        assert len(dispatches) == 1                  # one stacked launch
        assert dispatches[0].attrs["n_items"] == 4
        assert len(tracer.named("request")) == 4     # all roots finished
        assert all(s.end is not None for s in tracer.named("request"))

    def test_no_tracer_no_spans_no_crash(self):
        assert obs_trace.get_tracer() is None
        fused = isa.fuse("c0_scale", "c0_add")
        q = RequestQueue()
        it = q.submit(fused, _operands(), arrival=0.0)
        assert it.span is None
        Scheduler(q, policy="fifo", n_lanes=1, clock="wall",
                  mode="interpret").drain()


# ---------------------------------------------------------------------------
# Drift
# ---------------------------------------------------------------------------

class TestDrift:
    def test_record_rank_and_format(self):
        tr = obs_drift.DriftTracker()
        assert tr.record("k1", 1e-3, 3e-3, name="worst") == 3.0
        tr.record("k1", 1e-3, 3e-3)
        tr.record("k2", 1e-3, 1.2e-3, name="mild")
        tr.record("k3", 0.0, 1.0) is None            # unusable pair
        rep = tr.report()
        assert [r["name"] for r in rep] == ["worst", "mild"]
        assert rep[0]["drift"] == pytest.approx(2.0)
        assert rep[0]["samples"] == 2
        assert rep[1]["mean_ratio"] == pytest.approx(1.2)
        assert tr.report(min_samples=2) == rep[:1]
        text = tr.format_report()
        assert "worst" in text and "obs/model" in text
        assert rep[0]["fingerprint"] in text

    def test_cell_overflow_counted(self):
        tr = obs_drift.DriftTracker(max_cells=1)
        tr.record("a", 1.0, 1.0)
        assert tr.record("b", 1.0, 1.0) is None
        assert tr.overflow == 1 and len(tr) == 1

    def test_cost_model_feeds_drift(self):
        cost = CostModel(hierarchy=TPU_V5E)
        fused = isa.fuse("c0_scale", "c0_add")
        est = cost.estimate(fused, n_elems=5000, dtype=F32)
        for _ in range(3):
            cost.observe(fused, n_elems=5000, dtype=F32,
                         seconds=2.0 * est.modeled_s)
        (cell,) = cost.drift_report(min_samples=1)
        assert cell["samples"] == 3
        assert cell["drift"] == pytest.approx(1.0)
        assert cell["name"] == "c0_scale+c0_add"
        assert cell["ewma_ratio"] == pytest.approx(2.0)

    def test_watch_programs_bare_calls(self):
        tr = obs_drift.DriftTracker()
        fused = isa.fuse("c0_scale", "c0_add")
        with obs_drift.watch_programs(tr):
            fused(*_operands(), mode="interpret")
        (cell,) = tr.report(min_samples=1)
        assert cell["samples"] == 1 and cell["mean_ratio"] > 0


class TestDriftAction:
    """Observe→act loop (ISSUE 9): a cell that chronically exceeds the
    drift threshold forces a fresh geometry sweep on its next dispatch
    (DISPATCH_STATS.drift_renegotiated), consuming the flag."""

    def test_chronic_drift_renegotiates_next_dispatch(self):
        prog_mod.clear_dispatch_caches()
        prog_mod.reset_dispatch_stats()
        cost = CostModel(hierarchy=TPU_V5E, drift_threshold=0.4)
        fused = isa.fuse("c0_scale", "c0_add")
        ops_ = _operands()
        fused(*ops_, mode="interpret")              # warm geometry memo
        base = prog_mod.DISPATCH_STATS.drift_renegotiated
        est = cost.estimate(fused, n_elems=5000, dtype=F32)
        for _ in range(2):                          # chronic, not one-off
            cost.observe(fused, n_elems=5000, dtype=F32,
                         seconds=est.modeled_s * 10)
        fused(*ops_, mode="interpret")              # flagged shape re-sweeps
        assert prog_mod.DISPATCH_STATS.drift_renegotiated == base + 1
        fused(*ops_, mode="interpret")              # flag consumed: no loop
        assert prog_mod.DISPATCH_STATS.drift_renegotiated == base + 1

    def test_no_threshold_no_renegotiation(self):
        prog_mod.clear_dispatch_caches()
        prog_mod.reset_dispatch_stats()
        cost = CostModel(hierarchy=TPU_V5E)         # reporting only
        fused = isa.fuse("c0_scale", "c0_add")
        ops_ = _operands()
        fused(*ops_, mode="interpret")
        base = prog_mod.DISPATCH_STATS.drift_renegotiated
        est = cost.estimate(fused, n_elems=5000, dtype=F32)
        for _ in range(3):
            cost.observe(fused, n_elems=5000, dtype=F32,
                         seconds=est.modeled_s * 10)
        fused(*ops_, mode="interpret")
        assert prog_mod.DISPATCH_STATS.drift_renegotiated == base


# ---------------------------------------------------------------------------
# Plan-cache GC (satellite a)
# ---------------------------------------------------------------------------

class TestPlanCacheGC:
    def _fill(self, cache, keys, t0):
        for i, k in enumerate(keys):
            assert cache.store("geom", k, {"i": i})
            os.utime(cache.entry_path("geom", k), (t0 + i, t0 + i))

    def test_entry_bound_evicts_oldest(self, tmp_path):
        cache = artifact.PlanCache(tmp_path, max_entries=3)
        e0 = prog_mod.DISPATCH_STATS.disk_evict
        self._fill(cache, ["a", "b", "c"], 1_000_000.0)
        assert len(list(tmp_path.glob("*.json"))) == 3
        cache.store("geom", "d", {"i": 3})            # 4th: sweep on store
        left = {p.name for p in tmp_path.glob("*.json")}
        assert len(left) == 3
        assert os.path.basename(cache.entry_path("geom", "a")) not in left
        assert os.path.basename(cache.entry_path("geom", "d")) in left
        assert prog_mod.DISPATCH_STATS.disk_evict - e0 == 1

    def test_byte_bound(self, tmp_path):
        cache = artifact.PlanCache(tmp_path, max_bytes=1)
        cache.store("geom", "a", {"i": 0})
        os.utime(cache.entry_path("geom", "a"), (1_000_000.0,) * 2)
        cache.store("geom", "b", {"i": 1})            # over: sweep
        left = [p.name for p in tmp_path.glob("*.json")]
        # the just-published entry is never evicted, everything else is
        assert left == [os.path.basename(cache.entry_path("geom", "b"))]

    def test_load_touches_mtime_lru(self, tmp_path):
        cache = artifact.PlanCache(tmp_path, max_entries=3)
        self._fill(cache, ["a", "b", "c"], 1_000_000.0)
        assert cache.load("geom", "a") == {"i": 0}    # touch: now newest
        cache.store("geom", "d", {"i": 3})
        left = {p.name for p in tmp_path.glob("*.json")}
        assert os.path.basename(cache.entry_path("geom", "a")) in left
        assert os.path.basename(cache.entry_path("geom", "b")) not in left

    def test_sweep_never_evicts_published(self, tmp_path):
        unbounded = artifact.PlanCache(tmp_path)
        unbounded.store("geom", "a", {"i": 0})
        unbounded.store("geom", "b", {"i": 1})
        keep = unbounded.entry_path("geom", "b")
        # make the entry to protect the OLDEST on disk, then sweep a
        # bounded view around it: "a" goes, the published one survives
        os.utime(keep, (1.0, 1.0))
        bounded = artifact.PlanCache(tmp_path, max_entries=1)
        assert bounded._sweep(keep=keep) == 1
        assert os.path.exists(keep)
        assert not os.path.exists(unbounded.entry_path("geom", "a"))

    def test_unbounded_never_sweeps(self, tmp_path):
        cache = artifact.PlanCache(tmp_path)
        for k in "abcdefgh":
            cache.store("geom", k, {})
        assert len(list(tmp_path.glob("*.json"))) == 8
        assert cache._sweep() == 0

    def test_env_bounds(self, tmp_path, monkeypatch):
        monkeypatch.setenv(artifact.ENV_MAX_ENTRIES, "2")
        monkeypatch.setenv(artifact.ENV_MAX_BYTES, "12345")
        cache = artifact.PlanCache(tmp_path)
        assert cache.max_entries == 2 and cache.max_bytes == 12345
        monkeypatch.setenv(artifact.ENV_MAX_ENTRIES, "junk")
        assert artifact.PlanCache(tmp_path).max_entries is None
        assert artifact.PlanCache(tmp_path, max_entries=7).max_entries == 7


# ---------------------------------------------------------------------------
# EWMA persistence (satellite b, kind="ewma")
# ---------------------------------------------------------------------------

_EWMA_CHILD = textwrap.dedent("""
    import json
    import jax.numpy as jnp
    import repro.kernels
    from repro.core import isa
    from repro.memhier import TPU_V5E
    from repro.sched import CostModel

    fused = isa.fuse("c0_scale", "c0_add")
    cost = CostModel(hierarchy=TPU_V5E)
    est = cost.estimate(fused, n_elems=5000, dtype=jnp.float32)
    print(json.dumps({"correction": est.correction}))
""")


class TestEwmaPersistence:
    def _train(self, ratio=2.0):
        cost = CostModel(hierarchy=TPU_V5E)
        fused = isa.fuse("c0_scale", "c0_add")
        est = cost.estimate(fused, n_elems=5000, dtype=F32)
        for _ in range(2):               # 2nd observation replaces the 1st
            cost.observe(fused, n_elems=5000, dtype=F32,
                         seconds=ratio * est.modeled_s)
        return cost, fused, est

    def test_roundtrip_in_process(self, cache_dir):
        cost, fused, est = self._train(ratio=2.0)
        assert any(p.name.startswith("ewma-")
                   for p in cache_dir.iterdir()), "no ewma artifact"
        fresh = CostModel(hierarchy=TPU_V5E)
        e2 = fresh.estimate(fused, n_elems=5000, dtype=F32)
        assert e2.correction == pytest.approx(2.0)
        assert e2.seconds == pytest.approx(2.0 * est.modeled_s)
        # ...and the observation count rode along: the next observe
        # blends instead of replacing (count > 1 on the warmed key)
        key = fresh.ewma_key(fused, 5000, F32)
        assert fresh._count.get(key, 0) >= 2

    def test_one_disk_probe_per_key(self, cache_dir):
        cost, fused, _ = self._train()
        fresh = CostModel(hierarchy=TPU_V5E)
        fresh.estimate(fused, n_elems=5000, dtype=F32)
        with prog_mod.dispatch_stats_window() as w:
            fresh.estimate(fused, n_elems=5000, dtype=F32)
            fresh.estimate(fused, n_elems=5000, dtype=F32)
        assert w.delta("disk_hit") == 0 and w.delta("disk_miss") == 0

    def test_malformed_payload_ignored(self, cache_dir):
        cost = CostModel(hierarchy=TPU_V5E)
        fused = isa.fuse("c0_scale", "c0_add")
        key = cost.ewma_key(fused, 5000, F32)
        cache = artifact.plan_cache()
        for bad in ({"ratio": -2.0, "abs": None, "count": 1},
                    {"ratio": float("nan"), "abs": None, "count": 1},
                    {"ratio": True, "abs": None, "count": 1},
                    {"ratio": None, "abs": None, "count": "many"},
                    "not even a dict"):
            cache.store("ewma", key, bad)
            fresh = CostModel(hierarchy=TPU_V5E)
            est = fresh.estimate(fused, n_elems=5000, dtype=F32)
            assert est.correction == 1.0, f"accepted {bad!r}"

    def test_no_cache_no_persistence(self):
        with artifact.using_plan_cache(None):
            cost, fused, _ = self._train()
            fresh = CostModel(hierarchy=TPU_V5E)
            est = fresh.estimate(fused, n_elems=5000, dtype=F32)
            assert est.correction == 1.0

    def test_subprocess_warm_starts_predictions(self, cache_dir):
        self._train(ratio=3.0)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        src = os.path.join(root, "src")
        env = dict(os.environ)
        env[artifact.ENV_VAR] = str(cache_dir)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else src)
        proc = subprocess.run([sys.executable, "-c", _EWMA_CHILD],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.splitlines()[-1])
        assert out["correction"] == pytest.approx(3.0), (
            "fresh process did not warm-start its EWMA correction")


class TestSampling:
    """Head-based per-request sampling (ISSUE 8): the keep decision is
    made once at the root and inherited by the whole request tree."""

    def test_rate_one_keeps_everything(self):
        t = obs_trace.Tracer(sample_rate=1.0)
        for i in range(5):
            t.finish(t.start_span("request", parent=None, i=i))
        assert len(t.spans) == 5 and t.unsampled == 0

    def test_rate_zero_keeps_nothing(self):
        t = obs_trace.Tracer(sample_rate=0.0)
        for i in range(5):
            s = t.start_span("request", parent=None, i=i)
            assert not s.sampled and s.span_id == 0
            t.finish(s)
        assert len(t.spans) == 0 and t.unsampled == 5

    def test_fractional_rate_deterministic_cadence(self):
        t = obs_trace.Tracer(sample_rate=0.25)
        kept = []
        for i in range(8):
            root = t.start_span("request", parent=None, i=i)
            if root.sampled:
                kept.append(i)
            t.finish(root)
        # credit accumulator: first root sampled, then every 4th
        assert kept == [0, 4]
        assert t.unsampled == 6
        assert len(t.spans) == 2

    def test_children_inherit_root_decision(self):
        t = obs_trace.Tracer(sample_rate=0.5)
        n_stored = 0
        for i in range(4):
            root = t.start_span("request", parent=None)
            child = t.start_span("admission", parent=root)
            grand = t.start_span("dispatch", parent=child)
            assert child.sampled == root.sampled == grand.sampled
            for s in (grand, child, root):
                t.finish(s)
            n_stored += 3 * root.sampled
        assert len(t.spans) == n_stored
        # dropped trees leave no orphans: every stored parent_id resolves
        ids = {s.span_id for s in t.spans}
        assert all(s.parent_id in ids for s in t.spans
                   if s.parent_id is not None)

    def test_unsampled_spans_skip_exports(self):
        t = obs_trace.Tracer(sample_rate=0.5)
        for i in range(4):
            root = t.start_span("request", parent=None)
            t.finish(t.start_span("work", parent=root))
            t.finish(root)
        for line in t.export_jsonl().splitlines():
            assert json.loads(line)["span_id"] != 0

    def test_rate_validated(self):
        for bad in (-0.1, 1.5):
            with pytest.raises(ValueError):
                obs_trace.Tracer(sample_rate=bad)


class TestDriftThreshold:
    """Threshold wiring (ISSUE 8): chronic drift is queryable via
    exceeding() and counted in repro_drift_exceeded_total."""

    def _counter(self):
        return obs_metrics.REGISTRY.counter("repro_drift_exceeded_total")

    def test_counter_needs_two_samples(self):
        base = self._counter().value
        t = obs_drift.DriftTracker(threshold=0.5)
        t.record("k", 1.0, 10.0)  # one huge outlier: not chronic yet
        assert self._counter().value == base
        t.record("k", 1.0, 10.0)
        assert self._counter().value == base + 1

    def test_within_tolerance_never_counts(self):
        base = self._counter().value
        t = obs_drift.DriftTracker(threshold=0.5)
        for _ in range(5):
            t.record("k", 1.0, 1.2)  # 20% drift < 50% threshold
        assert self._counter().value == base
        assert t.exceeding() == []

    def test_exceeding_lists_offenders_worst_first(self):
        t = obs_drift.DriftTracker(threshold=0.25)
        for _ in range(3):
            t.record("bad", 1.0, 2.0, name="bad")
            t.record("worse", 1.0, 4.0, name="worse")
            t.record("fine", 1.0, 1.1, name="fine")
        rows = t.exceeding()
        assert [r["name"] for r in rows] == ["worse", "bad"]
        # explicit threshold overrides the constructor's
        assert {r["name"] for r in t.exceeding(threshold=0.05)} == {
            "worse", "bad", "fine"}

    def test_no_threshold_anywhere_raises(self):
        t = obs_drift.DriftTracker()
        t.record("k", 1.0, 2.0)
        with pytest.raises(ValueError):
            t.exceeding()

    def test_threshold_validated(self):
        with pytest.raises(ValueError):
            obs_drift.DriftTracker(threshold=0.0)

    def test_cost_model_plumbs_threshold(self):
        cost = CostModel(hierarchy=TPU_V5E, drift_threshold=0.4)
        assert cost.drift.threshold == 0.4
        fused = isa.fuse("c0_scale", "c0_add")
        est = cost.estimate(fused, n_elems=5000, dtype=F32)
        for _ in range(2):
            cost.observe(fused, n_elems=5000, dtype=F32,
                         seconds=est.seconds * 10)
        assert cost.drift.exceeding()


# ---------------------------------------------------------------------------
# §19: critical-path blame attribution
# ---------------------------------------------------------------------------

def _blame_run(n=6, arrival_step=1e-4):
    """A small virtual-clock scheduled run under the ACTIVE tracer:
    ``n`` requests, two tenants, distinct scalars (separate batches)."""
    fused = isa.fuse("c0_scale", "c0_add")
    _, x, b = _operands(2048)
    q = RequestQueue()
    for i in range(n):
        q.submit(fused, (2.0 + i, x, b), tenant=f"t{i % 2}",
                 arrival=i * arrival_step)
    Scheduler(q, cost=CostModel(hierarchy=TPU_V5E), policy="fifo",
              n_lanes=1, clock="virtual").drain()


class TestBlame:
    def test_virtual_conservation_and_buckets(self, tracer):
        _blame_run(n=6)
        blames = obs_critical.attribute(tracer)
        assert [b.seq for b in blames] == list(range(6))
        assert obs_critical.max_residual(blames) <= 1e-9
        for b in blames:
            # VirtualClock span ticks are synthetic span counts, not
            # scheduler time: the carved buckets must stay exactly zero
            assert b.buckets["negotiate"] == 0.0
            assert b.buckets["pallas_build"] == 0.0
            assert b.buckets["compute"] > 0.0
            assert b.buckets["queue_wait"] >= 0.0
            assert b.total_s == pytest.approx(b.finish - b.arrival)
            assert b.critical_path[0] == "request"
            assert len(b.critical_path) >= 2
            assert b.top() in obs_critical.BUCKETS

    def test_report_ranked_and_formatted(self, tracer):
        _blame_run(n=4)
        blames = obs_critical.attribute(tracer)
        rep = obs_critical.blame_report(blames)
        assert sorted(rep) == ["t0", "t1"]
        for ranked in rep.values():
            assert {k for k, _ in ranked} == set(obs_critical.BUCKETS)
            totals = [v for _, v in ranked]
            assert totals == sorted(totals, reverse=True)
        text = obs_critical.format_report(blames)
        assert "blame[t0]:" in text and "blame[t1]:" in text

    def test_export_jsonl_byte_stable_and_id_free(self):
        def run():
            t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
            with obs_trace.using_tracer(t):
                _blame_run(n=4)
            return obs_critical.export_jsonl(obs_critical.attribute(t))

        run()                            # warm geometry/dispatch state
        a, b = run(), run()
        assert a == b and a
        for line in a.strip().splitlines():
            d = json.loads(line)
            assert "span_id" not in d and "trace_id" not in d
            assert set(d["buckets"]) == set(obs_critical.BUCKETS)

    def test_shed_and_unfinished_roots_skipped(self, tracer):
        root = tracer.start_span("request", parent=None, seq=0,
                                 tenant="a", arrival=0.0)
        tracer.finish(root, shed=True)   # finished without blame inputs
        tracer.start_span("request", parent=None, seq=1, arrival=0.0)
        assert obs_critical.attribute(tracer) == []

    def test_wall_clock_carves_negotiate(self, tracer):
        prog_mod.clear_dispatch_caches()
        fused = isa.fuse("c0_scale", "c0_add")
        q = RequestQueue()
        q.submit(fused, _operands(), arrival=0.0)
        with artifact.using_plan_cache(None):
            Scheduler(q, cost=CostModel(hierarchy=TPU_V5E), policy="fifo",
                      n_lanes=1, clock="wall", mode="interpret").drain()
        (b,) = obs_critical.attribute(tracer)
        assert b.clock == "wall"
        assert abs(b.residual_s) <= 1e-9
        assert b.buckets["negotiate"] > 0.0      # cold sweep carved out
        assert b.buckets["pallas_build"] >= 0.0
        assert b.buckets["compute"] >= 0.0       # carve-out never negative


# ---------------------------------------------------------------------------
# §19: tail-based sampling
# ---------------------------------------------------------------------------

def _finish_request(t, latency, tenant="default", error=False):
    """Open + finish one synthetic request tree on tracer ``t`` with a
    scheduler-style stamped latency (``finish - arrival``)."""
    root = t.start_span("request", parent=None, tenant=tenant, arrival=0.0)
    child = t.start_span("placement", parent=root)
    if error:
        child.attrs["error"] = "RuntimeError: boom"
    t.finish(child)
    t.finish(root, start=0.0, finish=latency)
    return root


class TestTailSampler:
    def test_requires_full_head_rate(self):
        with pytest.raises(ValueError):
            TailSampler(obs_trace.Tracer(sample_rate=0.5))

    def test_parameter_validation(self):
        t = obs_trace.Tracer()
        with pytest.raises(ValueError):
            TailSampler(t, ring=0)
        with pytest.raises(ValueError):
            TailSampler(t, sample_rate=1.5)
        with pytest.raises(ValueError):
            TailSampler(t, quantile=1.0)

    def test_error_beats_slo_beats_head(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        ts = TailSampler(t, sample_rate=1.0, slo_s=1e-3)
        e = _finish_request(t, 5e-3, error=True)   # breaches AND errors
        s = _finish_request(t, 5e-3)               # just breaches
        f = _finish_request(t, 1e-4)               # fast: head keep
        assert ts.kept[e.span_id] == "error"
        assert ts.kept[s.span_id] == "slo"
        assert ts.kept[f.span_id] == "head"
        assert ts.stats()["by_reason"] == {
            "error": 1, "slo": 1, "p99": 0, "head": 1}

    def test_per_tenant_slo_dict(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        ts = TailSampler(t, slo_s={"gold": 1e-3})
        g = _finish_request(t, 2e-3, tenant="gold")
        _finish_request(t, 2e-3, tenant="free")    # no SLO: not kept
        assert list(ts.kept) == [g.span_id]
        assert ts.kept[g.span_id] == "slo"

    def test_head_credit_deterministic(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        ts = TailSampler(t, sample_rate=0.5)
        kept = []
        for i in range(6):
            root = _finish_request(t, 1e-4)
            if root.span_id in ts.kept:
                kept.append(i)
        assert kept == [0, 2, 4]                   # first kept, then 1-in-2

    def test_p99_threshold_is_causal(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        ts = TailSampler(t, p99_min=2)
        _finish_request(t, 1e-3)                   # window unarmed
        _finish_request(t, 1e-3)                   # still judging blind
        slow = _finish_request(t, 5e-3)            # >= p99 of {1ms, 1ms}
        assert list(ts.kept.values()) == ["p99"]
        assert list(ts.kept) == [slow.span_id]

    def test_ring_eviction_prunes_tracer(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        ts = TailSampler(t, ring=2)
        roots = [_finish_request(t, 1e-4) for _ in range(5)]
        assert ts.kept == {} and ts.evicted == 3
        alive = {s.span_id for s in t.spans}
        assert all(r.span_id not in alive for r in roots[:3])
        assert all(r.span_id in alive for r in roots[3:])
        assert ts.stats()["provisional"] == 2

    def test_export_jsonl_byte_stable(self):
        def run():
            t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
            ts = TailSampler(t, slo_s=1e-3, sample_rate=0.5)
            _finish_request(t, 5e-3)
            _finish_request(t, 1e-4)
            _finish_request(t, 2e-3, error=True)
            return ts.export_jsonl()

        a, b = run(), run()
        assert a == b and a
        reasons = [json.loads(ln).get("keep_reason")
                   for ln in a.strip().splitlines()]
        assert [r for r in reasons if r] == ["slo", "head", "error"]


# ---------------------------------------------------------------------------
# §19: SLO burn rate + admission feedback
# ---------------------------------------------------------------------------

class TestSlo:
    def _slo(self, **kw):
        kw.setdefault("objective", 0.9)
        kw.setdefault("fast_s", 1.0)
        kw.setdefault("slow_s", 10.0)
        return Slo("a", 1e-3, **kw)

    def test_burn_rate_algebra(self):
        s = self._slo()
        assert s.burn_rate() == 0.0                # no events
        assert s.record(2e-3, now=100.0) is True
        assert s.record(0.5e-3, now=100.5) is False
        # 1 bad of 2 in the fast window, over a 0.1 budget
        assert s.burn_rate(now=100.5, window="fast") == pytest.approx(5.0)

    def test_effective_now_never_rewinds(self):
        s = self._slo()
        s.record(2e-3, now=100.0)
        assert s.burn_rate(now=0.0, window="fast") == \
            s.burn_rate(now=None, window="fast")

    def test_burning_requires_both_windows(self):
        s = self._slo()
        for i in range(18):                        # healthy history
            s.record(1e-4, now=i * 0.5)
        s.record(5e-3, now=9.4)
        s.record(5e-3, now=9.6)
        # fast window saturated, slow window still diluted: not burning
        assert s.burn_rate(now=9.6, window="fast") > 2.0
        assert s.burn_rate(now=9.6, window="slow") <= 2.0
        assert not s.burning(now=9.6, threshold=2.0)
        for k in range(8):                         # sustained breach
            s.record(5e-3, now=9.61 + k * 0.01)
        assert s.burning(now=9.7, threshold=2.0)

    def test_validation(self):
        with pytest.raises(ValueError):
            Slo("a", 0.0)
        with pytest.raises(ValueError):
            Slo("a", 1e-3, objective=1.0)
        with pytest.raises(ValueError):
            Slo("a", 1e-3, fast_s=10.0, slow_s=1.0)
        with pytest.raises(ValueError):
            self._slo().burn_rate(window="weird")

    def test_max_events_sweeps_old(self):
        s = self._slo(max_events=4)
        for i in range(10):
            s.record(1e-4, now=float(i * 100))     # far apart in time
        assert len(s._events) <= 4


class TestSloMonitor:
    def _burning_monitor(self, tenant="b"):
        mon = SloMonitor(threshold=2.0)
        mon.add(tenant, target_s=1e-3, objective=0.9,
                fast_s=1.0, slow_s=10.0)
        for i in range(30):
            mon.record(tenant, 5e-3, now=0.1 + i * 0.3)
        return mon

    def test_add_get_and_duplicates(self):
        mon = SloMonitor()
        slo = mon.add("a", target_s=1e-3)
        assert mon.get("a") is slo and mon.tenants() == ["a"]
        with pytest.raises(ValueError):
            mon.add("a", target_s=2e-3)
        assert mon.get("nope") is None

    def test_record_unregistered_is_noop(self):
        mon = SloMonitor()
        mon.record("ghost", 1.0, now=0.0)          # must not raise
        mon.record_shed("ghost", now=0.0)
        assert mon.burn_rates() == {}

    def test_burning_and_report(self):
        mon = self._burning_monitor()
        mon.add("ok", target_s=1.0)
        mon.record("ok", 1e-4, now=9.0)
        assert mon.burning(now=9.1) == ["b"]
        text = mon.report(now=9.1)
        assert "slo[b]:" in text and "BURNING" in text
        assert "slo[ok]:" in text and "(ok)" in text

    def test_gauges_exported(self):
        mon = self._burning_monitor(tenant="gauge_t")
        g = obs_metrics.REGISTRY.get(
            "repro_slo_burn_rate", {"tenant": "gauge_t", "window": "fast"})
        assert g is not None and g.value > 2.0

    def test_record_shed_holds_burn_signal(self):
        mon = self._burning_monitor()
        before = mon.get("b").burn_rate(now=9.1, window="fast")
        mon.record_shed("b", now=9.2)              # shed = served-zero
        assert mon.get("b").burn_rate(now=9.2, window="fast") >= before


class TestSloShedder:
    def test_validation(self):
        mon = SloMonitor()
        with pytest.raises(ValueError):
            SloShedder(mon, mode="drop")
        with pytest.raises(ValueError):
            SloShedder(mon, weight_factor=0.0)

    def test_accepts_unregistered_and_healthy(self):
        mon = SloMonitor()
        mon.add("a", target_s=1.0)
        shed = SloShedder(mon)
        assert shed.admit("ghost", now=0.0) == "accept"
        assert shed.admit("a", now=0.0) == "accept"

    def test_shed_records_bad_event(self):
        mon = TestSloMonitor()._burning_monitor()
        shed = SloShedder(mon, mode="shed")
        n0 = len(mon.get("b")._events)
        assert shed.admit("b", now=9.1) == "shed"
        assert len(mon.get("b")._events) == n0 + 1  # signal holds

    def test_deprioritise_does_not_record(self):
        mon = TestSloMonitor()._burning_monitor()
        shed = SloShedder(mon, mode="deprioritise", weight_factor=0.5)
        n0 = len(mon.get("b")._events)
        assert shed.admit("b", now=9.1) == "deprioritise"
        assert len(mon.get("b")._events) == n0

    def test_queue_sheds_burning_tenant(self):
        mon = TestSloMonitor()._burning_monitor()
        q = RequestQueue(admission=SloShedder(mon))
        fused = isa.fuse("c0_scale", "c0_add")
        base = obs_metrics.REGISTRY.counter(
            "repro_sched_shed_total", labels={"tenant": "b"}).value
        it = q.submit(fused, _operands(), tenant="b", arrival=9.1)
        assert it.shed and len(q) == 0
        assert obs_metrics.REGISTRY.counter(
            "repro_sched_shed_total",
            labels={"tenant": "b"}).value == base + 1
        ok = q.submit(fused, _operands(), tenant="healthy", arrival=9.1)
        assert not ok.shed and len(q) == 1

    def test_queue_shed_finishes_root_span(self, tracer):
        mon = TestSloMonitor()._burning_monitor()
        q = RequestQueue(admission=SloShedder(mon))
        it = q.submit(isa.fuse("c0_scale", "c0_add"), _operands(),
                      tenant="b", arrival=9.1)
        assert it.span is not None and it.span.end is not None
        assert it.span.attrs["shed"] is True
        assert obs_critical.attribute(tracer) == []  # no blame inputs

    def test_queue_deprioritises_weight(self):
        mon = TestSloMonitor()._burning_monitor()
        q = RequestQueue(admission=SloShedder(
            mon, mode="deprioritise", weight_factor=0.5))
        base = obs_metrics.REGISTRY.counter(
            "repro_sched_deprioritised_total",
            labels={"tenant": "b"}).value
        it = q.submit(isa.fuse("c0_scale", "c0_add"), _operands(),
                      tenant="b", weight=2.0, arrival=9.1)
        assert not it.shed and len(q) == 1
        assert it.weight == pytest.approx(1.0)
        assert obs_metrics.REGISTRY.counter(
            "repro_sched_deprioritised_total",
            labels={"tenant": "b"}).value == base + 1


# ---------------------------------------------------------------------------
# §19: the scheduler's blame/SLO span attributes, read back
# ---------------------------------------------------------------------------

class TestOtlpBlameAttrs:
    """The blame/SLO attrs round-trip through :meth:`Tracer.export_jsonl`
    (what ``serve.py --obs-tail`` writes): typed floats and ints, and
    integer span/parent ids stable across identical runs.  The class and
    its id test keep the names they had when they read the attrs through
    the OTLP exporter, since removed."""

    def _run_doc(self):
        t = obs_trace.Tracer(clock=obs_trace.VirtualClock())
        with obs_trace.using_tracer(t):
            _blame_run(n=3)
        return t.export_jsonl()

    def test_blame_inputs_typed(self):
        spans = [json.loads(line) for line in self._run_doc().splitlines()]
        reqs = [s for s in spans if s["name"] == "request"]
        assert len(reqs) == 3
        for s in reqs:
            attrs = s["attrs"]
            for k in ("solo_s", "batch_s", "swap_s", "contention_s",
                      "dram_busy_s", "channel_busy_s"):
                assert type(attrs[k]) is float, (k, attrs[k])
            assert attrs["clock"] == "virtual"
            assert type(attrs["channel"]) is int and attrs["channel"] == 0
            assert type(attrs["lane"]) is int

    def test_hex_ids_stable_across_identical_runs(self):
        self._run_doc()                  # warm geometry/dispatch state
        a, b = self._run_doc(), self._run_doc()
        assert a == b                    # span and parent ids included
        first = json.loads(a.splitlines()[0])
        assert type(first["span_id"]) is int and first["span_id"] == 1
        assert first["parent_id"] is None


# ---------------------------------------------------------------------------
# Spans on the profiler's clock: the three modes of the gate
# ---------------------------------------------------------------------------

def _profiled(fn, log_dir):
    """Run ``fn`` inside a ``cb.request`` annotation under a JAX profiler
    session; returns the host events named ``repro.*`` or ``cb.*`` as
    ``(name, start_ns, end_ns, stats)``, by start."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        with jax.profiler.TraceAnnotation("cb.request"):
            fn()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("repro.", "cb.")):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _request_and_sample():
    """One scheduled request (the prefix sum), then one greedy token
    sample."""
    from repro.kernels import ops
    from repro.launch import serve
    q = RequestQueue()
    q.submit(ops.prefix_sum, (jnp.arange(1024, dtype=F32),),
             tenant="t0", arrival=0.0)
    Scheduler(q, policy="fifo", n_lanes=1, clock="wall").drain()
    serve.sample(jnp.ones((2, 16), F32), None, 0.0)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class TestProfilerSpans:
    def test_off_builds_no_span_and_no_annotation(self, monkeypatch):
        def boom(*a, **k):
            raise AssertionError("built while off")
        monkeypatch.setattr(obs_trace, "_Annotation", boom)
        monkeypatch.setattr(obs_trace, "Span", boom)
        assert not obs_trace.enabled()
        assert obs_trace.span("placement", lane=0) is obs_trace.NULL_SPAN
        assert obs_trace.host_span("launch") is obs_trace.NULL_SPAN
        _request_and_sample()        # every site runs through the gate

    def test_off_gate_is_cheap(self):
        import timeit
        n = 20000
        per_call = min(timeit.repeat(
            lambda: obs_trace.span("dispatch", n_items=1), number=n,
            repeat=3)) / n
        assert per_call < 10e-6

    def test_profiler_alone_writes_nested_program_spans(self, tmp_path):
        assert obs_trace.get_tracer() is None
        ev = _profiled(_request_and_sample, tmp_path)
        names = [e[0] for e in ev]
        for want in ("submit", "admission", "drain", "placement",
                     "launch", "wait", "sample"):
            assert "repro." + want in names, (want, names)
        assert "repro.request" not in names      # the root cannot nest
        by = {}
        for e in ev:
            by.setdefault(e[0], []).append(e)
        (cb,) = by["cb.request"]
        assert all(_inside(e, cb) for e in ev)   # one clock for both
        (submit,), (drain,) = by["repro.submit"], by["repro.drain"]
        assert _inside(by["repro.admission"][0], submit)
        assert submit[2] <= drain[1]
        (place,) = by["repro.placement"]
        assert _inside(place, drain)
        assert place[3]["lane"] == 0
        (launch,), (wait,) = by["repro.launch"], by["repro.wait"]
        assert _inside(launch, place) and _inside(wait, place)
        assert launch[2] <= wait[1]
        assert by["repro.admission"][0][3]["seq"] >= 0
        (sample,) = by["repro.sample"]
        assert drain[2] <= sample[1]
        # spans on one thread nest properly: no two overlap partially
        for i, a in enumerate(ev):
            for b in ev[i + 1:]:
                assert b[1] >= a[2] or _inside(b, a), (a, b)

    def test_tracer_and_profiler_both_get_the_spans(self, tmp_path,
                                                    tracer):
        ev = _profiled(_request_and_sample, tmp_path)
        names = {e[0] for e in ev}
        for want in ("admission", "coalesce", "placement"):
            assert tracer.named(want), want
            assert "repro." + want in names, want
        assert "repro.launch" in names and "repro.sample" in names
        # the tracer records as without a profiler: one connected tree,
        # and no host spans
        (root,) = [s for s in tracer.spans if s.parent_id is None]
        assert root.name == "request" and root.attrs["observed_s"] > 0
        assert len(tracer.subtree_names(root)) == len(tracer.spans)
        assert not {"submit", "drain", "launch", "sample"} & {
            s.name for s in tracer.spans}

    def test_tracer_without_profiler_opens_no_annotation(self, tracer,
                                                          monkeypatch):
        def boom(*a, **k):
            raise AssertionError("annotation opened with no profiler")
        monkeypatch.setattr(obs_trace, "_Annotation", boom)
        _request_and_sample()
        assert tracer.named("placement") and tracer.named("admission")
