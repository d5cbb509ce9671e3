"""Scheduling runtime (ISSUE 5): queue, cost model, scheduler, replay.

Covers the DESIGN.md §13 contracts:

  * queue/admission — arity validated at submit; coalesce keys group
    same-structure+scalars+shape requests and nothing else;
  * batch coalescing — ``Program.call_batch`` bit-identical to N solo
    calls (including padding and multi-output programs), scalar/shape
    mismatches rejected, counters tick;
  * cost-aware warm buckets — drifted sizes re-negotiate and update the
    bucket (``DISPATCH_STATS.rebucketed``), repeats stay warm;
  * cost model — memhier-seeded estimates, EWMA correction converges to
    observed reality (cold-start observation discarded), contention
    makespan bounded by [max individual, serial sum];
  * scheduler — EDF and WFQ orderings, deterministic placements,
    contention-aware virtual makespan, plans scheduled at part
    granularity, shard_map lane dispatch matching the oracle;
  * replay — byte-identical JSONL round-trip, placements reproduced.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401 — registers the ISA
from repro.core import isa
from repro.core import program as prog_mod
from repro.core.burst_model import BurstModel
from repro.core.program import Program
from repro.graph import partition
from repro.kernels.ops import c0_pipeline_graph
from repro.launch.mesh import make_mesh
from repro.memhier import TPU_V5E, contended_makespan, predict_program
from repro.sched import (CostModel, RequestQueue, Scheduler, TraceRecorder,
                         coalesce_key, placements_match, replay,
                         sharded_program_call)

N = 4096


@pytest.fixture
def fresh_caches():
    prog_mod.clear_dispatch_caches()
    prog_mod.reset_dispatch_stats()
    yield


def vecs(*seeds, n=N, shape=None):
    rng = [np.random.default_rng(s) for s in seeds]
    out = [jnp.asarray(r.standard_normal(shape if shape else n), jnp.float32)
           for r in rng]
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# queue + coalescing
# ---------------------------------------------------------------------------

class TestQueue:
    def test_admission_rejects_bad_arity(self):
        q = RequestQueue()
        fused = isa.fuse("c0_scale", "c0_add")
        with pytest.raises(TypeError, match="expected 3 operands"):
            q.submit(fused, (2.0, vecs(0)))
        assert len(q) == 0

    def test_admission_rejects_shape_mismatch(self):
        q = RequestQueue()
        fused = isa.fuse("c0_scale", "c0_add")
        x, = [vecs(0)]
        y = vecs(1, n=2 * N)
        with pytest.raises(ValueError, match="agree on"):
            q.submit(fused, (2.0, x, y))

    def test_admission_rejects_non_target(self):
        with pytest.raises(TypeError, match="unsupported work target"):
            RequestQueue().submit(42, ())

    def test_coalesce_key_groups_equal_requests(self):
        fused = isa.fuse("c0_scale", "c0_add")
        x, b = vecs(0, 1)
        k1 = coalesce_key(fused, (2.0, x, b))
        k2 = coalesce_key(fused, (2.0, b, x))      # same shapes/scalars
        assert k1 == k2 and k1 is not None

    def test_coalesce_key_splits_on_shape_dtype_not_scalar_values(self):
        fused = isa.fuse("c0_scale", "c0_add")
        x, b = vecs(0, 1)
        base = coalesce_key(fused, (2.0, x, b))
        # scalar VALUES no longer split the key: call_batch stacks mixed
        # scalars into per-item SMEM vectors (scalar-batched coalescing)
        assert coalesce_key(fused, (3.0, x, b)) == base
        # scalar dtype still splits (the stacked SMEM vector is typed)
        assert coalesce_key(fused, (jnp.float32(2.0), x, b)) != base
        y = vecs(2, n=2 * N)
        assert coalesce_key(fused, (2.0, y, vecs(3, n=2 * N))) != base
        xi = jnp.asarray(np.arange(N), jnp.int32)
        assert coalesce_key(isa.fuse("c0_copy"), (xi,)) != \
            coalesce_key(isa.fuse("c0_copy"), (x,))

    def test_plan_and_callable_never_coalesce(self):
        plan = partition(c0_pipeline_graph("saxpby"), model=TPU_V5E,
                         n_elems=N)
        assert coalesce_key(plan, ()) is None
        assert coalesce_key(lambda: None, ()) is None

    def test_pop_ready_batches_and_arrival_filter(self):
        q = RequestQueue()
        fused = isa.fuse("c0_scale", "c0_add")
        x, b = vecs(0, 1)
        q.submit(fused, (2.0, x, b), arrival=0.0)
        q.submit(fused, (2.0, b, x), arrival=0.0)
        q.submit(fused, (2.0, x, b), arrival=5.0)     # not arrived yet
        batches = q.pop_ready(1.0)
        assert len(batches) == 1 and len(batches[0].items) == 2
        assert batches[0].coalesced
        assert len(q) == 1
        assert q.next_arrival(1.0) == 5.0


# ---------------------------------------------------------------------------
# batch-coalesced dispatch (core/program.py)
# ---------------------------------------------------------------------------

class TestCallBatch:
    def test_bit_identical_with_padding_and_2d(self, fresh_caches):
        fused = isa.fuse("c0_scale", "c0_add")
        prog = fused.program
        reqs = [(2.0, vecs(10 + i, shape=(4, 1000)),
                 vecs(20 + i, shape=(4, 1000))) for i in range(5)]
        outs = prog.call_batch(reqs, interpret=True)
        for ops, got in zip(reqs, outs):
            want = fused(*ops, mode="interpret")
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_multi_output_program(self, fresh_caches):
        from repro.core.template import Stage

        def body(scalars, ins, outs, carry, step):
            outs[0][...] = ins[0][...] * 2.0
            outs[1][...] = ins[0][...] + 1.0

        prog = Program([Stage(name="twin", body=body, n_vec_in=1,
                              n_vec_out=2)])
        reqs = [(vecs(i),) for i in range(3)]
        outs = prog.call_batch(reqs, interpret=True)
        for ops, got in zip(reqs, outs):
            want = prog(*ops, interpret=True)
            assert isinstance(got, tuple) and len(got) == 2
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_counters_and_single_item_passthrough(self, fresh_caches):
        prog = isa.fuse("c0_scale", "c0_add").program
        x, b = vecs(0, 1)
        with prog_mod.dispatch_stats_window() as w:
            prog.call_batch([(2.0, x, b)], interpret=True)
            assert w.delta("batch_calls") == 0
            prog.call_batch([(2.0, x, b), (2.0, b, x)], interpret=True)
            assert w.delta("batch_calls") == 1
            assert w.delta("batch_items") == 2

    def test_mixed_scalars_coalesce_bit_identical(self, fresh_caches):
        prog = isa.fuse("c0_scale", "c0_add").program
        x, b = vecs(0, 1)
        with prog_mod.dispatch_stats_window() as w:
            outs = prog.call_batch([(2.0, x, b), (3.0, x, b)],
                                   interpret=True)
        assert w.delta("batch_calls") == 1
        assert w.delta("batch_mixed") == 1
        for s, out in zip((2.0, 3.0), outs):
            ref = prog(s, x, b, interpret=True)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref))

    def test_uniform_scalars_keep_shared_path(self, fresh_caches):
        prog = isa.fuse("c0_scale", "c0_add").program
        x, b = vecs(0, 1)
        with prog_mod.dispatch_stats_window() as w:
            prog.call_batch([(2.0, x, b), (2.0, x, b)], interpret=True)
        assert w.delta("batch_calls") == 1
        assert w.delta("batch_mixed") == 0

    def test_mismatched_scalar_dtypes_rejected(self, fresh_caches):
        prog = isa.fuse("c0_scale", "c0_add").program
        x, b = vecs(0, 1)
        with pytest.raises(ValueError, match="scalar"):
            prog.call_batch([(np.float64(2.0), x, b),
                             (np.float32(3.0), x, b)], interpret=True)

    def test_mismatched_shapes_rejected(self, fresh_caches):
        prog = isa.fuse("c0_copy").program
        with pytest.raises(ValueError, match="shape"):
            prog.call_batch([(vecs(0),), (vecs(1, n=2 * N),)],
                            interpret=True)

    def test_shape_changing_program_rejected(self, fresh_caches):
        from repro.core.template import Stage

        def body(scalars, ins, outs, carry, step):
            outs[0][...] = ins[0][...]

        shapes = lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype),)  # noqa: E731
        p = Program([Stage(name="reshaper", body=body, n_vec_in=1,
                           n_vec_out=1, out_shapes=shapes)])
        with pytest.raises(ValueError, match="batch-coalesced"):
            p.call_batch([(vecs(0),), (vecs(1),)], interpret=True)

    def test_observed_hook_reports_batch(self, fresh_caches):
        prog = isa.fuse("c0_scale", "c0_add").program
        x, b = vecs(0, 1)
        seen = []
        hook = lambda p, n, dt, s, k: seen.append((n, dt, s, k))  # noqa: E731
        prog_mod.push_observed_time_hook(hook)
        try:
            prog(2.0, x, b, interpret=True)
            prog.call_batch([(2.0, x, b), (2.0, b, x)], interpret=True)
        finally:
            prog_mod.pop_observed_time_hook(hook)
        assert [e[3] for e in seen] == [1, 2]
        assert all(e[0] == N and e[1] == "float32" and e[2] > 0
                   for e in seen)


# ---------------------------------------------------------------------------
# cost-aware warm-dispatch bucketing (core/program.py satellite)
# ---------------------------------------------------------------------------

class TestRebucketing:
    def mk(self):
        stages = [isa.get("c0_scale").template.stage(),
                  isa.get("c0_add").template.stage()]
        # burst law where wide blocks win at the bucket top but padding
        # waste dominates at half size + 1
        return Program(stages, model=BurstModel(peak_bw=1e9,
                                                overhead_s=1e-6))

    def test_drifted_size_rebuckets(self, fresh_caches):
        prog = self.mk()
        br, bc = prog._resolve_geometry(65536, jnp.float32)
        assert bc == 8192                      # widest block, zero padding
        with prog_mod.dispatch_stats_window() as w:
            br2, bc2 = prog._resolve_geometry(32769, jnp.float32)
            assert bc2 < bc                    # re-negotiated narrower
            assert w.delta("rebucketed") == 1

    def test_repeat_size_stays_warm_after_rebucket(self, fresh_caches):
        prog = self.mk()
        prog._resolve_geometry(65536, jnp.float32)
        prog._resolve_geometry(32769, jnp.float32)
        with prog_mod.dispatch_stats_window() as w:
            prog._resolve_geometry(32769, jnp.float32)
            assert w.delta("geometry_misses") == 0
            assert w.delta("rebucketed") == 0

    def test_same_size_never_checks(self, fresh_caches):
        prog = self.mk()
        prog._resolve_geometry(65536, jnp.float32)
        with prog_mod.dispatch_stats_window() as w:
            for _ in range(3):
                prog._resolve_geometry(65536, jnp.float32)
            assert w.deltas() == prog_mod.DispatchStats()

    def test_undrifted_size_marks_checked_once(self, fresh_caches):
        prog = self.mk()
        prog._resolve_geometry(65536, jnp.float32)
        # 65024 pads to the same single wide block: within the band
        with prog_mod.dispatch_stats_window() as w:
            prog._resolve_geometry(65024, jnp.float32)
            prog._resolve_geometry(65024, jnp.float32)
            assert w.delta("rebucketed") == 0
            assert w.delta("geometry_misses") == 0


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_memhier_seed_matches_predict_program(self):
        cost = CostModel(hierarchy=TPU_V5E)
        fused = isa.fuse("c0_scale", "c0_add")
        est = cost.estimate(fused, n_elems=1 << 16, dtype=jnp.float32)
        prog = fused.program
        import copy
        neg = copy.copy(prog)
        neg.model = TPU_V5E
        neg._model_fp = None
        br, bc, _ = neg.negotiate_geometry(1 << 16, jnp.float32)
        pred = predict_program(TPU_V5E, prog, 1 << 16, jnp.float32,
                               block_rows=br, block_cols=bc,
                               n_buffers=prog.n_buffers)
        assert est.modeled_s == pred.time_s
        assert est.dram_busy_s == pred.dram_busy_s
        assert est.source == "memhier"

    def test_ewma_correction_converges(self):
        cost = CostModel(hierarchy=TPU_V5E, alpha=0.5)
        fused = isa.fuse("c0_copy")
        base = cost.estimate(fused, n_elems=N, dtype=jnp.float32)
        # machine consistently 3x slower than the model
        for _ in range(8):
            cost.observe(fused, n_elems=N, dtype=jnp.float32,
                         seconds=3.0 * base.modeled_s)
        est = cost.estimate(fused, n_elems=N, dtype=jnp.float32)
        assert est.seconds == pytest.approx(3.0 * base.modeled_s, rel=0.1)
        assert est.dram_busy_s == pytest.approx(3.0 * base.dram_busy_s,
                                                rel=0.1)

    def test_cold_start_observation_discarded(self):
        cost = CostModel(hierarchy=TPU_V5E)
        fused = isa.fuse("c0_copy")
        base = cost.estimate(fused, n_elems=N, dtype=jnp.float32)
        cost.observe(fused, n_elems=N, dtype=jnp.float32,
                     seconds=500 * base.modeled_s)       # jit compile
        cost.observe(fused, n_elems=N, dtype=jnp.float32,
                     seconds=2.0 * base.modeled_s)       # steady state
        est = cost.estimate(fused, n_elems=N, dtype=jnp.float32)
        assert est.seconds == pytest.approx(2.0 * base.modeled_s, rel=1e-6)

    def test_callable_target_uses_observed_ewma(self):
        cost = CostModel()
        fn = lambda: None  # noqa: E731
        key = ("my_step",)
        assert cost.estimate(fn, cost_key=key).source == "default"
        cost.observe(fn, seconds=0.5, cost_key=key)
        est = cost.estimate(fn, cost_key=key)
        assert est.source == "ewma" and est.seconds == 0.5

    def test_seed_cache_keys_on_model_and_buffers(self):
        # structurally identical programs with different n_buffers (or a
        # rebound model) must not share a stale seed
        cost = CostModel(hierarchy=TPU_V5E)
        stages = lambda: [isa.get("c0_scale").template.stage(),  # noqa: E731
                          isa.get("c0_add").template.stage()]
        p1 = Program(stages(), n_buffers=1)
        p2 = Program(stages(), n_buffers=2)
        e1 = cost.estimate(p1, n_elems=N, dtype=jnp.float32)
        e2 = cost.estimate(p2, n_elems=N, dtype=jnp.float32)
        assert e1.modeled_s != e2.modeled_s

    def test_contention_bounds(self):
        cost = CostModel(hierarchy=TPU_V5E)
        copy1 = isa.fuse("c0_copy")
        e = cost.estimate(copy1, n_elems=1 << 20, dtype=jnp.float32)
        m = cost.contended_makespan([e, e, e])
        assert m >= e.seconds
        assert m <= 3 * e.seconds + 1e-18
        assert cost.contended_makespan([]) == 0.0
        assert cost.contended_makespan([e]) == e.seconds

    def test_memhier_contended_makespan_properties(self):
        copy1 = isa.fuse("c0_copy").program
        p1 = predict_program(TPU_V5E, copy1, 1 << 20, jnp.float32)
        p2 = predict_program(TPU_V5E, copy1, 1 << 18, jnp.float32)
        m = contended_makespan([p1, p2])
        assert m >= max(p1.time_s, p2.time_s)
        assert m <= p1.time_s + p2.time_s + 1e-18
        assert contended_makespan([]) == 0.0


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _mixed_queue(arrive=0.0):
    q = RequestQueue()
    fused = isa.fuse("c0_scale", "c0_add")
    copy1 = isa.fuse("c0_copy")
    x, b = vecs(0, 1)
    q.submit(fused, (2.0, x, b), deadline=1e-3, tenant="A", arrival=arrive)
    q.submit(fused, (2.0, b, x), deadline=2e-3, tenant="A", arrival=arrive)
    q.submit(copy1, (x,), tenant="B", weight=2.0, arrival=arrive)
    q.submit(copy1, (b,), tenant="B", arrival=arrive)
    return q


class TestScheduler:
    def test_edf_orders_by_deadline(self):
        q = RequestQueue()
        scale = isa.fuse("c0_scale")
        x = vecs(0)
        # distinct scalar dtypes → distinct coalesce keys → 3 batches
        # (values alone no longer split — scalar-batched coalescing)
        late = q.submit(scale, (np.float64(2.0), x), deadline=9.0)
        none = q.submit(scale, (np.float32(3.0), vecs(1)))
        soon = q.submit(scale, (np.int32(4), vecs(2)), deadline=1.0)
        rep = Scheduler(q, cost=CostModel(hierarchy=TPU_V5E), policy="edf",
                        n_lanes=1, clock="virtual").drain()
        order = [p.seq for p in sorted(rep.placements,
                                       key=lambda p: p.round)]
        assert order == [soon.seq, late.seq, none.seq]

    def test_wfq_prefers_heavier_tenant(self):
        q = RequestQueue()
        scale = isa.fuse("c0_scale")
        # distinct scalar dtypes → no coalescing; identical service size
        a = q.submit(scale, (np.float64(2.0), vecs(0)), tenant="light",
                     weight=1.0)
        b = q.submit(scale, (np.float32(3.0), vecs(1)), tenant="heavy",
                     weight=4.0)
        rep = Scheduler(q, cost=CostModel(hierarchy=TPU_V5E), policy="wfq",
                        n_lanes=1, clock="virtual").drain()
        first = min(rep.placements, key=lambda p: p.round)
        assert first.seq == b.seq      # 4x weight → earlier virtual finish

    def test_wfq_bills_every_tenant_of_a_coalesced_batch(self):
        # a cross-tenant coalesced batch must advance BOTH tenants'
        # virtual time — nobody rides free on a shared launch.
        from repro.sched import WeightedFairPolicy
        q = RequestQueue()
        copy1 = isa.fuse("c0_copy")
        x = vecs(0)
        q.submit(copy1, (x,), tenant="A", arrival=0.0)
        q.submit(copy1, (vecs(1),), tenant="B", arrival=0.0)
        batches = q.pop_ready(0.0)
        assert len(batches) == 1 and batches[0].coalesced
        policy = WeightedFairPolicy()
        cost = CostModel(hierarchy=TPU_V5E)
        policy.order(batches, 0.0, lambda it: cost.estimate_item(it))
        assert policy._tenant_tag["A"] > 0.0
        assert policy._tenant_tag["B"] > 0.0

    def test_virtual_contention_bounds_and_determinism(self):
        cost = CostModel(hierarchy=TPU_V5E)
        copy1 = isa.fuse("c0_copy")
        solo = cost.estimate(copy1, n_elems=N, dtype=jnp.float32).seconds

        def run():
            q = RequestQueue()
            q.submit(copy1, (vecs(0),))
            q.submit(copy1, (vecs(1),))
            return Scheduler(q, cost=CostModel(hierarchy=TPU_V5E),
                             policy="edf", n_lanes=2,
                             clock="virtual").drain()

        r1, r2 = run(), run()
        assert placements_match(r1.placements, r2.placements)
        assert r1.makespan >= solo - 1e-18
        assert r1.makespan <= 2 * solo + 1e-18

    def test_wall_results_match_oracle(self):
        q = _mixed_queue()
        fused = isa.fuse("c0_scale", "c0_add")
        copy1 = isa.fuse("c0_copy")
        x, b = vecs(0, 1)
        rep = Scheduler(q, policy="fifo", n_lanes=2, clock="wall",
                        mode="interpret").drain()
        np.testing.assert_allclose(
            np.asarray(rep.results[0]),
            np.asarray(fused(2.0, x, b, mode="ref")), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(rep.results[2]),
            np.asarray(copy1(x, mode="ref")), rtol=1e-5)
        assert len(rep.placements) == 4

    def test_deadline_miss_reported(self):
        q = RequestQueue()
        copy1 = isa.fuse("c0_copy")
        hit = q.submit(copy1, (vecs(0),), deadline=10.0)
        miss = q.submit(copy1, (vecs(1),), deadline=1e-12)
        rep = Scheduler(q, cost=CostModel(hierarchy=TPU_V5E), policy="edf",
                        n_lanes=1, clock="virtual").drain()
        assert miss.seq in rep.missed and hit.seq not in rep.missed

    def test_virtual_arrivals_advance_clock(self):
        q = RequestQueue()
        copy1 = isa.fuse("c0_copy")
        q.submit(copy1, (vecs(0),), arrival=0.0)
        q.submit(copy1, (vecs(1),), arrival=0.5)
        rep = Scheduler(q, cost=CostModel(hierarchy=TPU_V5E),
                        policy="fifo", n_lanes=2, clock="virtual").drain()
        late = max(rep.placements, key=lambda p: p.seq)
        assert late.start >= 0.5

    def test_plan_parts_schedule_with_contention(self):
        plan = partition(c0_pipeline_graph("axpby_residual"),
                         model=TPU_V5E, n_elems=1 << 16, method="beam")
        units = plan.units()
        assert all(u.predicted_s is not None and u.dram_busy_s is not None
                   for u in units)
        assert tuple(u.deps for u in units) == plan.part_deps()
        q = RequestQueue()
        rng = np.random.default_rng(0)
        from repro.graph.ir import Value
        ops = [jnp.asarray(rng.standard_normal(1 << 16), jnp.float32)
               if isinstance(key, Value) else 2.0
               for _, key in plan.graph.free_inputs()]
        q.submit(plan, tuple(ops))
        rep = Scheduler(q, cost=CostModel(hierarchy=TPU_V5E),
                        clock="virtual", n_lanes=2).drain()
        # contention-aware plan duration ≥ the free-overlap critical path
        assert rep.makespan >= plan.predicted_time() - 1e-18
        assert rep.makespan <= plan.predicted_time(overlap=False) + 1e-18

    def test_sharded_lanes_match_oracle(self):
        fused = isa.fuse("c0_scale", "c0_add")
        x, b = vecs(0, 1)
        mesh = make_mesh((1,), ("parts",))
        reqs = [(2.0, x, b), (3.0, b, x), (1.5, x, x)]
        outs = sharded_program_call(fused, reqs, mesh)
        for ops, got in zip(reqs, outs):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(fused(*ops, mode="ref")),
                rtol=1e-6)

    def test_sharded_scheduler_run(self):
        mesh = make_mesh((1,), ("parts",))
        q = RequestQueue()
        fused = isa.fuse("c0_scale", "c0_add")
        x, b = vecs(0, 1)
        q.submit(fused, (2.0, x, b))
        q.submit(fused, (2.0, b, x))
        rep = Scheduler(q, mesh=mesh, policy="fifo", clock="wall").drain()
        np.testing.assert_allclose(
            np.asarray(rep.results[0]),
            np.asarray(fused(2.0, x, b, mode="ref")), rtol=1e-6)

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="unknown policy"):
            Scheduler(RequestQueue(), policy="srtf")
        with pytest.raises(ValueError, match="clock"):
            Scheduler(RequestQueue(), clock="sundial")


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------

class TestReplay:
    def record_run(self, policy="wfq"):
        rec = TraceRecorder()
        rep = Scheduler(_mixed_queue(), cost=CostModel(hierarchy=TPU_V5E),
                        policy=policy, n_lanes=2, clock="virtual",
                        recorder=rec).drain()
        return rec, rep

    def test_jsonl_roundtrip_bit_identical(self, tmp_path):
        rec, _ = self.record_run()
        text = rec.dumps()
        p = tmp_path / "trace.jsonl"
        rec.dump(str(p))
        loaded = TraceRecorder.load(str(p))
        assert loaded.dumps() == text
        for line in text.splitlines():
            json.loads(line)               # every line is valid JSON

    def test_replay_reproduces_placements(self):
        for policy in ("fifo", "edf", "wfq"):
            rec, rep = self.record_run(policy)
            rep2 = replay(TraceRecorder.loads(rec.dumps()))
            assert placements_match(rep.placements, rep2.placements), policy

    def test_plan_replay_reproduces_with_cache_bound_parts(self):
        # a hierarchy whose FIRST level is the bottleneck: part time_s >
        # dram_busy_s, so the plan's contention-priced duration differs
        # from the naive sum — the recorded estimate must carry it.
        import dataclasses as dc
        slow0 = dc.replace(TPU_V5E.levels[0],
                           bandwidth=TPU_V5E.levels[0].bandwidth / 1000)
        hier = dc.replace(TPU_V5E, levels=(slow0,) + TPU_V5E.levels[1:])
        plan = partition(c0_pipeline_graph("axpby_residual"), model=hier,
                         n_elems=1 << 14, method="beam")
        from repro.graph.ir import Value
        rng = np.random.default_rng(0)
        ops = [jnp.asarray(rng.standard_normal(1 << 14), jnp.float32)
               if isinstance(key, Value) else 2.0
               for _, key in plan.graph.free_inputs()]

        def run(rec):
            q = RequestQueue()
            q.submit(plan, tuple(ops))
            return Scheduler(q, cost=CostModel(hierarchy=hier),
                             policy="edf", n_lanes=1, clock="virtual",
                             recorder=rec).drain()

        rec = TraceRecorder()
        rep = run(rec)
        rep2 = replay(TraceRecorder.loads(rec.dumps()))
        assert placements_match(rep.placements, rep2.placements)

    def test_replay_with_policy_override_differs(self):
        rec, rep = self.record_run("edf")
        alt = replay(rec, policy="wfq")
        assert len(alt.placements) == len(rep.placements)

    def test_replay_empty_trace_rejected(self):
        with pytest.raises(ValueError, match="no submit events"):
            replay(TraceRecorder())


# ---------------------------------------------------------------------------
# noise-aware regression gating (benchmarks/regression.py satellite)
# ---------------------------------------------------------------------------

class TestRegressionMedians:
    def rows(self, name, us, samples=None):
        r = {"name": name, "us_per_call": us, "derived": ""}
        if samples is not None:
            r["samples"] = samples
        return {name: r}

    def test_wall_row_gates_on_median(self):
        from benchmarks.regression import DEFAULT_PATTERNS, compare
        old = self.rows("suite_wall_us", 100.0,
                        [100.0, 101.0, 99.0, 100.0, 102.0])
        new = self.rows("suite_wall_us", 100.0,
                        [300.0, 301.0, 299.0, 300.0, 302.0])
        fails = compare(old, new, 0.25, DEFAULT_PATTERNS,
                        wall_threshold=0.60)
        assert len(fails) == 1 and "wall-gated" in fails[0]

    def test_wall_row_median_ignores_outlier(self):
        from benchmarks.regression import DEFAULT_PATTERNS, compare
        old = self.rows("suite_wall_us", 100.0,
                        [100.0, 101.0, 99.0, 100.0, 102.0])
        # one 10x outlier sample; median unchanged → no failure
        new = self.rows("suite_wall_us", 100.0,
                        [100.0, 1000.0, 99.0, 101.0, 100.0])
        assert compare(old, new, 0.25, DEFAULT_PATTERNS,
                       wall_threshold=0.60) == []

    def test_unsampled_wall_row_never_gates(self):
        from benchmarks.regression import DEFAULT_PATTERNS, compare
        old = self.rows("suite_wall_us", 100.0)
        new = self.rows("suite_wall_us", 1000.0)
        assert compare(old, new, 0.25, DEFAULT_PATTERNS) == []

    def test_too_few_samples_never_gates(self):
        from benchmarks.regression import DEFAULT_PATTERNS, compare
        old = self.rows("suite_wall_us", 100.0, [100.0, 100.0, 100.0])
        new = self.rows("suite_wall_us", 900.0, [900.0, 900.0, 900.0])
        assert compare(old, new, 0.25, DEFAULT_PATTERNS) == []

    def test_modeled_row_behaviour_unchanged(self):
        from benchmarks.regression import DEFAULT_PATTERNS, compare
        old = self.rows("graph_axpby_predicted_us", 10.0)
        new = self.rows("graph_axpby_predicted_us", 14.0)
        fails = compare(old, new, 0.25, DEFAULT_PATTERNS)
        assert len(fails) == 1 and "gated" in fails[0]
        ok = compare(old, self.rows("graph_axpby_predicted_us", 11.0),
                     0.25, DEFAULT_PATTERNS)
        assert ok == []

    def test_sampled_row_helper_records_samples(self):
        from benchmarks import common
        common.reset_results()
        common.sampled_row("t_wall_us", lambda: 1, iters=5)
        rec = common.RESULTS[-1]
        assert len(rec["samples"]) == 5
        assert rec["us_per_call"] == common.median(rec["samples"])
