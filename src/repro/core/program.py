"""Fused instruction programs: N registered instructions, ONE pallas_call.

The paper's wide-operand I'/S' encodings exist to do more work per
instruction issue; the TPU analogue of "one issue" is one ``pallas_call``.
Chaining unfused ops round-trips every intermediate through HBM — exactly
the traffic the paper's reconfigurable region avoids by keeping values in
the datapath. A :class:`Program` is the software form of a *larger*
reconfigurable region: it takes the :class:`~repro.core.template.Stage`
of each instruction, negotiates one common block geometry (picked with the
:mod:`~repro.core.burst_model` burst-efficiency law, bounded by the VMEM
budget check in :class:`~repro.core.stream.StreamConfig`), and emits a
single ``pallas_call`` whose kernel runs the stage bodies back to back,
threading intermediates through VMEM scratch refs instead of HBM.

Chaining rule (the "register bypass network"):
  * stage *i*'s vector outputs feed the FIRST ``n_vec_out`` vector inputs
    of stage *i+1*;
  * every remaining vector input, and every scalar input, comes from the
    program's external operand list.

External operand order (user-facing): for each stage in chain order, its
scalar operands then its non-chained vector operands. E.g.
``fuse("c0_scale", "c0_add")`` is called as ``fused(s, x, b)`` and computes
``add(scale(s, x), b)``.

The merged external operand list is the fused program's "encoding": it is
validated against the widened P'-type budget in :mod:`repro.core.isa` at
``fuse()`` time (per-stage I'/S' limits were already enforced when each
instruction registered).

A Program is one *chain*; whole instruction DAGs are partitioned into
chains by the :mod:`repro.graph` dataflow compiler (DESIGN.md §11),
whose candidate chains are compiled through the same
:func:`repro.core.isa.fuse_chain` primitive as ``fuse()``.

Hot-path caching (DESIGN.md §12): geometry negotiation is memoised per
``(program identity, n_elems, dtype, model fingerprint)`` in a shared
module-level cache (so the partitioner's many equivalent candidate
Programs share negotiated geometries), ``__call__`` resolves a warm
dispatch through a per-instance ``(n_elems bucket, dtype, model
fingerprint)`` table without re-entering negotiation at all, and the
built ``pallas_call`` is wrapped in ``jax.jit`` and cached per operand
signature so a warm call never re-traces. :data:`DISPATCH_STATS` counts
hits/misses/traces; ``benchmarks/bench_hotpath.py`` gates zero
renegotiation and zero re-trace on the warm path. Warm buckets are
*cost-aware*: a warm hit at a size whose modeled time has drifted > 10%
from the bucket's negotiated geometry triggers a re-negotiation and
updates the bucket (``DISPATCH_STATS.rebucketed``).

Persistent artifacts (DESIGN.md §14): when a plan cache is active
(:mod:`repro.core.artifact`), an in-process geometry miss first consults
the content-addressed on-disk cache — keyed identically to the memo —
and every completed negotiation (including "no-fit" verdicts) is
atomically published back, so a fresh worker pointed at a populated
cache dir re-negotiates NOTHING (``DISPATCH_STATS.disk_*`` counts the
traffic; ``benchmarks/bench_aot.py`` gates the warm subprocess).

Observability (DESIGN.md §15): the dispatch path emits structured
spans — ``dispatch`` around every ``__call__``/``call_batch``,
``negotiate`` around a memo-miss sweep (outcome ``disk_hit`` vs
``sweep``), ``pallas_build`` around a cold jit build — through
:mod:`repro.obs.trace` (no-ops when no tracer is installed and no
profiler collects; written into the profiler trace when one does), and
:data:`DISPATCH_STATS` is a thin view over registry-backed
``repro_dispatch_*_total`` counters in :mod:`repro.obs.metrics`;
``bench_hotpath`` gates the instrumented warm path at ≤ 3% overhead.

Serving entry points (DESIGN.md §13): :meth:`Program.call_batch`
coalesces N same-structure requests into ONE launch sharing one warm
dispatch (the :mod:`repro.sched` queue's batch path), and observed-time
hooks (:func:`push_observed_time_hook`) report measured wall seconds per
call back to online cost models.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
import weakref
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

from . import artifact as _artifact
from .burst_model import BurstModel, TPU_V5E_HBM
from .stream import (LANES, VMEM_BYTES, StreamConfig, _bits,
                     flatten_to_blocks, round_up)
from .template import Stage, emit_stage

# Candidate fused block widths (lanes-aligned powers of two). The burst
# model picks among these: wide enough to amortise DMA issue overhead
# (paper §3.1.2: very wide LLC blocks), small enough for the VMEM budget
# (paper §3.1.3: BRAM capacity).
_BLOCK_COL_CANDIDATES = tuple(LANES * (1 << k) for k in range(7))


# ---------------------------------------------------------------------------
# dispatch caching (DESIGN.md §12)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchStats:
    """Frozen snapshot of the warm-dispatch counters.

    Since ISSUE 7 the live counters are registry-backed
    (``repro.obs.metrics``, one ``repro_dispatch_<field>_total`` counter
    per field — DESIGN.md §15); :data:`DISPATCH_STATS` is a thin
    attribute view over them whose :meth:`_DispatchStatsView.snapshot`
    returns an instance of this dataclass. Diff two snapshots (or use
    :func:`dispatch_stats_window`) instead of reading ambient values —
    the counters are process-global.
    """

    geometry_hits: int = 0       # negotiations answered from the cache
    geometry_misses: int = 0     # negotiations that ran the candidate loop
    call_builds: int = 0         # pallas_call callables constructed
    kernel_traces: int = 0       # times a fused kernel body was traced
    kernel_launches: int = 0     # call_blocks launches compiled for the chip
    interpret_launches: int = 0  # call_blocks launches in interpret mode
    rebucketed: int = 0          # warm buckets re-negotiated on cost drift
    batch_calls: int = 0         # coalesced call_batch launches
    batch_items: int = 0         # work items those coalesced launches served
    batch_mixed: int = 0         # coalesced launches with per-item scalars
    # persistent-artifact cache (core.artifact, DESIGN.md §14):
    disk_hit: int = 0            # artifacts loaded + verified from disk
    disk_miss: int = 0           # disk consults that found no entry
    disk_invalidated: int = 0    # stale/wrong-key/version-drift entries dropped
    disk_corrupt: int = 0        # unreadable/truncated entries dropped
    disk_store: int = 0          # artifacts atomically published to disk
    disk_evict: int = 0          # artifacts removed by the LRU size sweep
    # obs→cost action loop (DESIGN.md §15/§18):
    drift_renegotiated: int = 0  # geometry sweeps re-run on chronic drift


_STAT_FIELDS = tuple(f.name for f in dataclasses.fields(DispatchStats))


class _DispatchStatsView:
    """Attribute view over the registry-backed dispatch counters.

    Preserves the historical mutable-dataclass API —
    ``DISPATCH_STATS.geometry_hits += 1`` works unchanged at every call
    site — while the authoritative values live in
    ``repro.obs.metrics.REGISTRY`` as ``repro_dispatch_<field>_total``
    counters (visible to the Prometheus exposition and JSON snapshot).
    """

    __slots__ = ("_counters",)

    def __init__(self):
        counters = {}
        for f in _STAT_FIELDS:
            counters[f] = _metrics.REGISTRY.counter(
                f"repro_dispatch_{f}_total",
                help=f"dispatch counter {f} (core/program.py)")
        object.__setattr__(self, "_counters", counters)

    def __getattr__(self, name):
        try:
            return self._counters[name].value
        except KeyError:
            raise AttributeError(name) from None

    def __setattr__(self, name, value):
        try:
            self._counters[name].set(value)
        except KeyError:
            raise AttributeError(name) from None

    def snapshot(self) -> DispatchStats:
        return DispatchStats(**{f: c.value
                                for f, c in self._counters.items()})

    def reset(self) -> None:
        for c in self._counters.values():
            c.reset()

    def __eq__(self, other):
        if isinstance(other, (DispatchStats, _DispatchStatsView)):
            return all(getattr(self, f) == getattr(other, f)
                       for f in _STAT_FIELDS)
        return NotImplemented

    def __repr__(self):
        return repr(self.snapshot()).replace("DispatchStats",
                                             "DispatchStatsView", 1)


DISPATCH_STATS = _DispatchStatsView()


class StatsWindow:
    """Scoped delta reader over :data:`DISPATCH_STATS`.

    The counters are process-global, so a test asserting "this block
    negotiated nothing" must compare against a baseline taken at block
    entry, never against ambient values. ``w.delta(field)`` is the
    change since the window opened; ``w.deltas()`` the full snapshot
    diff."""

    def __init__(self, view: _DispatchStatsView):
        self._view = view
        self.start = view.snapshot()

    def delta(self, field: str) -> int:
        return getattr(self._view, field) - getattr(self.start, field)

    def deltas(self) -> DispatchStats:
        now = self._view.snapshot()
        return DispatchStats(**{f: getattr(now, f) - getattr(self.start, f)
                                for f in _STAT_FIELDS})


class _StatsWindowCtx:
    __slots__ = ("_window",)

    def __enter__(self) -> StatsWindow:
        self._window = StatsWindow(DISPATCH_STATS)
        return self._window

    def __exit__(self, *a):
        return False


def dispatch_stats_window() -> _StatsWindowCtx:
    """``with dispatch_stats_window() as w: ...; w.delta("disk_hit")`` —
    the test-isolation primitive for counter assertions."""
    return _StatsWindowCtx()

# Observed-time hooks (DESIGN.md §13): callables
#   hook(program, n_elems, dtype_name, seconds, n_items)
# invoked after a __call__ / call_batch whose outputs were blocked on, so
# ``seconds`` is honest wall time including execution, not just async
# dispatch. With no hook registered the dispatch path pays one falsy
# check. ``n_items`` > 1 marks a coalesced batch (``n_elems`` stays the
# per-item size so online models key consistently with solo calls).
_OBSERVED_HOOKS: list = []


def push_observed_time_hook(hook) -> None:
    _OBSERVED_HOOKS.append(hook)


def pop_observed_time_hook(hook) -> None:
    _OBSERVED_HOOKS.remove(hook)


# Cost-aware warm bucketing: re-negotiate a warm bucket when the cached
# geometry's modeled time at the actual n_elems drifts more than this
# fraction from the best geometry for that size (DESIGN.md §12/§13).
REBUCKET_DRIFT = 0.10
# Per-bucket bound on remembered already-checked sizes (a sweep touching
# many sizes in one bucket must not grow the entry monotonically).
_CHECKED_MAX = 64


class _WarmEntry:
    """One warm-dispatch bucket: geometry + the drift anchor.

    ``anchor_n``/``anchor_t`` are the size and modeled time the geometry
    was (re-)negotiated at; ``checked`` remembers sizes already found
    within the drift band so repeat calls skip the check entirely.
    """

    __slots__ = ("block_rows", "block_cols", "anchor_n", "anchor_t",
                 "checked")

    def __init__(self, block_rows: int, block_cols: int,
                 anchor_n: int, anchor_t: float):
        self.block_rows = block_rows
        self.block_cols = block_cols
        self.anchor_n = anchor_n
        self.anchor_t = anchor_t
        self.checked: dict = {}

    def mark_checked(self, n: int) -> None:
        if len(self.checked) >= _CHECKED_MAX:
            self.checked.pop(next(iter(self.checked)))
        self.checked[n] = True

# (program identity, n_elems, dtype, model fp, budget, n_buffers)
#   -> (block_rows, block_cols, StreamConfig) | ("no-fit", message)
# Bounded FIFO: negotiations are cheap enough to redo that a dropped old
# entry only costs one candidate sweep, while the bound keeps long-lived
# processes (serving, size sweeps) from growing the cache monotonically.
_GEOMETRY_CACHE: dict = {}
_GEOMETRY_CACHE_MAX = 4096
# Per-Program executable-cache bound: each entry pins a jitted
# pallas_call, so a long-lived Program sweeping many operand shapes must
# not accumulate one forever (same monotonic-growth concern as above).
_EXE_CACHE_MAX = 64
# Per-Program warm-dispatch table bound (entries are tiny, but a served
# Program whose model is re-bound repeatedly would otherwise grow it).
_DISPATCH_CACHE_MAX = 256


def reset_dispatch_stats() -> None:
    DISPATCH_STATS.reset()


def clear_dispatch_caches() -> None:
    """Drop every warm dispatch cache: the shared geometry cache, the
    registry's memoised FusedPrograms, and the per-instance tables of the
    Programs those kept alive (other Program instances' tables die with
    the instances)."""
    _GEOMETRY_CACHE.clear()
    from . import isa as _isa          # deferred: isa imports us lazily
    for fused in _isa.registry._fuse_cache.values():
        fused.program._dispatch_cache.clear()
        fused.program._exe_cache.clear()
    _isa.registry._fuse_cache.clear()


def _dtype_name(dtype) -> str:
    return np.dtype(dtype).name


def _n_bucket(n: int) -> int:
    """Warm-dispatch size bucket: next power of two. Calls within one
    bucket reuse the first negotiated geometry (any legal geometry is
    numerically identical; only the modeled time moves within a 2×
    band), so a sweep over nearby sizes stays on the warm path."""
    n = int(n)
    return 1 << max(0, n - 1).bit_length()


# Identity tokens for models without a fingerprint(): weak-keyed so a
# token lives exactly as long as its model — a dead model's token is
# never reissued (a raw id() could be recycled by the allocator and
# alias a different model's cached geometry). Unweakrefable models are
# pinned in _MODEL_PIN instead: a deliberate (tiny, rare) leak that
# buys the same no-aliasing guarantee.
_MODEL_TOKENS = weakref.WeakKeyDictionary()
_MODEL_PIN: dict = {}
_MODEL_COUNTER = itertools.count().__next__


def _model_fingerprint(model) -> tuple:
    """Hashable identity of the memory model's predictions.

    BurstModel and Hierarchy provide value-based fingerprints (model
    edits — a ``dataclasses.replace``d LLC block, a policy change — make
    new frozen objects, hence new fingerprints, invalidating cached
    geometries). Unknown models fall back to a per-object token: correct
    for distinct objects, no value-level invalidation.
    """
    fp = getattr(model, "fingerprint", None)
    if fp is not None:
        return fp()
    try:
        tok = _MODEL_TOKENS.get(model)
        if tok is None:
            tok = _MODEL_COUNTER()
            _MODEL_TOKENS[model] = tok
    except TypeError:                   # unhashable/unweakrefable model
        key = id(model)
        pinned = _MODEL_PIN.get(key)
        if pinned is None or pinned[0] is not model:
            pinned = (model, _MODEL_COUNTER())
            _MODEL_PIN[key] = pinned    # strong ref: id can't recycle
        tok = pinned[1]
    return ("token", tok)


def _cache_geometry(key, value) -> None:
    """Insert with a FIFO bound: oldest entries evict first (redoing an
    evicted negotiation costs one candidate sweep, nothing correctness-
    relevant)."""
    if len(_GEOMETRY_CACHE) >= _GEOMETRY_CACHE_MAX:
        _GEOMETRY_CACHE.pop(next(iter(_GEOMETRY_CACHE)))
    _GEOMETRY_CACHE[key] = value


# -- drift-triggered re-negotiation (obs → cost action loop, §15) -----------
# Pending (program identity, n_elems bucket, dtype name) cells whose
# chronic modeled-vs-observed drift asked for a fresh geometry sweep;
# consumed (and cleared) by the next _resolve_geometry on that cell.
_RENEGOTIATE: set = set()


def request_renegotiation(identity, bucket: int, dtype_name: str) -> None:
    """Ask the next dispatch of ``(identity, bucket, dtype)`` to re-run
    its geometry sweep from scratch — memo and disk consult skipped,
    warm bucket and cached sweeps purged. This is the *action half* of
    drift tracking (DESIGN.md §15): :meth:`repro.sched.cost.CostModel.
    observe` calls it when a cell's accumulated drift stays past the
    tracker threshold, closing the loop from observation back into the
    dispatch path. Idempotent until consumed; consumption is counted in
    ``DISPATCH_STATS.drift_renegotiated``."""
    _RENEGOTIATE.add((identity, int(bucket), str(dtype_name)))


def _purge_geometry(identity, bucket: int, dtype_name: str) -> None:
    """Drop memoised sweeps for one (identity, size bucket, dtype) cell."""
    stale = [k for k in _GEOMETRY_CACHE
             if k[0] == identity and _n_bucket(k[1]) == bucket
             and k[2] == dtype_name]
    for k in stale:
        _GEOMETRY_CACHE.pop(k, None)


class _ItemScalarRef:
    """Per-item view over a batch-stacked SMEM scalar ref.

    Scalar-batched coalescing (DESIGN.md §13) stacks each scalar operand
    slot's per-item values into one ``(k_items, ...)`` SMEM array; stage
    bodies keep indexing ``scalars[j][0]`` / ``scalars[j][...]`` exactly
    as if the scalar were solo — this view routes those reads to the row
    of the item owning the current row block.
    """

    __slots__ = ("_ref", "_item")

    def __init__(self, ref, item):
        self._ref = ref
        self._item = item

    def __getitem__(self, idx):
        if idx is Ellipsis:
            return self._ref[self._item]
        return self._ref[self._item, idx]


# -- persistent geometry artifacts (core.artifact, DESIGN.md §14) -----------
# Payload of one "geom" disk entry: the memo value serialised flat. The
# StreamConfig is stored by its three defining ints (its derived
# geometry is recomputed), "no-fit" verdicts persist too — a fresh
# process skips the doomed candidate sweep as well as the successful
# ones.

def _geometry_payload(value) -> dict:
    if value[0] == "no-fit":
        return {"no_fit": str(value[1])}
    br, bc, cfg, t = value
    return {"block_rows": int(br), "block_cols": int(bc),
            "vlen_bits": int(cfg.vlen_bits),
            "block_bits": int(cfg.block_bits),
            "n_buffers": cfg.n_buffers, "time_s": float(t)}


def _geometry_from_payload(payload):
    """Decode + validate one disk payload back to the memo value; None
    marks the entry stale (counted/dropped by PlanCache.load). The
    StreamConfig constructor re-runs its own geometry invariants, so a
    tampered payload that would produce an illegal config dies here
    instead of reaching a kernel launch."""
    if not isinstance(payload, dict):
        return None
    if "no_fit" in payload:
        return ("no-fit", str(payload["no_fit"]))
    try:
        br, bc = int(payload["block_rows"]), int(payload["block_cols"])
        cfg = StreamConfig(vlen_bits=int(payload["vlen_bits"]),
                           block_bits=int(payload["block_bits"]),
                           n_buffers=payload["n_buffers"])
        t = float(payload["time_s"])
    except (KeyError, TypeError, ValueError):
        return None
    if br < 1 or bc < 1 or bc % LANES:
        return None
    return (br, bc, cfg, t)


def _stage_identity(st: Stage) -> tuple:
    return (st.name, st.n_scalar_in, st.n_vec_in, st.n_vec_out,
            st.block_rows, st.block_cols, st.carry_cols,
            _dtype_name(st.carry_dtype), st.carry_init,
            st.out_shapes is None)


class Program:
    """A chain of Stages compiled to one pallas_call.

    Parameters
    ----------
    stages: the per-instruction Stages, in dataflow order.
    name:   display name ("c0_scale+c0_add").
    model:  memory model used to negotiate the fused block size — either
            a one-term :class:`BurstModel` (the legacy law) or a
            :class:`repro.memhier.hierarchy.Hierarchy`, in which case
            candidates are scored by the trace-driven simulator
            (:func:`repro.memhier.predict.predict_program`, running the
            phase-structured fast engine).
    vmem_budget: VMEM capacity bound for resident operand blocks.
    n_buffers: DMA double-buffering depth: enters the VMEM footprint
            (each resident operand block is held ``ceil(n_buffers)``
            times) AND the hierarchy timing term (≥ 2 overlaps fill with
            compute; 1 serialises; fractional depths in (1, 2) model the
            fill/drain transients in between — see
            :mod:`repro.memhier.predict`).
    """

    def __init__(self, stages: Sequence[Stage], name: Optional[str] = None,
                 model=TPU_V5E_HBM,
                 vmem_budget: int = VMEM_BYTES,
                 n_buffers: float = 2):
        stages = tuple(stages)
        if not stages:
            raise ValueError("a Program needs at least one stage")
        self.stages = stages
        self.name = name or "+".join(st.name for st in stages)
        self.model = model
        self.vmem_budget = vmem_budget
        self.n_buffers = n_buffers
        # structural identity: the shared geometry-cache key component —
        # equivalent Programs (same stages/budget) share negotiations.
        self._identity = tuple(_stage_identity(st) for st in stages)
        self._dispatch_cache: dict = {}   # warm __call__ geometry table
        self._exe_cache: dict = {}        # operand signature -> jitted call
        self._model_fp: Optional[tuple] = None   # (model, fingerprint) memo

        # -- chain validation (raises at fuse() time) ----------------------
        self._n_chained = [0]
        self._n_ext = [stages[0].n_vec_in]
        for prev, st in zip(stages, stages[1:]):
            if not prev.shape_preserving:
                raise ValueError(
                    f"{self.name}: stage {prev.name!r} has shape-changing "
                    f"outputs and cannot feed a chained stage")
            if prev.n_vec_out > st.n_vec_in:
                raise ValueError(
                    f"{self.name}: stage {prev.name!r} produces "
                    f"{prev.n_vec_out} vector outputs but {st.name!r} "
                    f"accepts only {st.n_vec_in} vector inputs")
            self._n_chained.append(prev.n_vec_out)
            self._n_ext.append(st.n_vec_in - prev.n_vec_out)
        if len(stages) > 1 and not stages[-1].shape_preserving:
            raise ValueError(
                f"{self.name}: shape-changing final stage "
                f"{stages[-1].name!r} is only supported in single-stage "
                f"programs")

    # -- merged operand list ------------------------------------------------
    @property
    def n_scalar_in(self) -> int:
        return sum(st.n_scalar_in for st in self.stages)

    @property
    def n_ext_vec_in(self) -> int:
        return sum(self._n_ext)

    @property
    def n_vec_out(self) -> int:
        return self.stages[-1].n_vec_out

    @property
    def n_intermediates(self) -> int:
        return sum(st.n_vec_out for st in self.stages[:-1])

    @property
    def n_inputs(self) -> int:
        return self.n_scalar_in + self.n_ext_vec_in

    def pipeline_depth(self) -> int:
        """Chained latency: grid steps before the first fused block lands."""
        return sum(st.pipeline_depth() for st in self.stages)

    def _current_model_fp(self) -> tuple:
        """The model fingerprint, memoised per model *object* so the warm
        dispatch path pays a single identity check, not a per-call
        ``fingerprint()`` rebuild. Rebinding ``self.model`` (the only way
        to change a frozen model) invalidates via the identity check."""
        memo = self._model_fp
        if memo is not None and memo[0] is self.model:
            return memo[1]
        fp = _model_fingerprint(self.model)
        self._model_fp = (self.model, fp)
        return fp

    def split_operands(self, operands):
        """User-order flat operands → per-stage (scalars, ext_vectors).

        The single place the external operand convention is defined; ref
        composition (isa.FusedProgram) and the kernel path both use it, so
        they cannot disagree.
        """
        if len(operands) != self.n_inputs:
            raise TypeError(
                f"{self.name}: expected {self.n_inputs} operands "
                f"({self.n_scalar_in} scalar + {self.n_ext_vec_in} vector, "
                f"per-stage order), got {len(operands)}")
        out, i = [], 0
        for st, ne in zip(self.stages, self._n_ext):
            sc = tuple(operands[i:i + st.n_scalar_in])
            i += st.n_scalar_in
            ext = tuple(operands[i:i + ne])
            i += ne
            out.append((sc, ext))
        return out

    # -- cost model (roofline inputs) ---------------------------------------
    def flops(self, n_elems: int) -> float:
        return float(n_elems) * sum(st.cost_flops_per_elem
                                    for st in self.stages)

    def hbm_bytes_fused(self, n_elems: int, dtype) -> int:
        """HBM traffic of THIS program: externals + final outputs only."""
        return (self.n_ext_vec_in + self.n_vec_out) * n_elems * _bits(dtype) // 8

    def hbm_bytes_unfused(self, n_elems: int, dtype) -> int:
        """HBM traffic of the same chain as N separate pallas_calls: every
        stage re-reads its inputs from and spills its outputs to HBM."""
        per_elem = sum(st.n_vec_in + st.n_vec_out for st in self.stages)
        return per_elem * n_elems * _bits(dtype) // 8

    # -- geometry negotiation ----------------------------------------------
    def negotiate_geometry(self, n_elems: int, dtype):
        """Pick one (block_rows, block_cols) for the whole fused region.

        block_rows is the lcm of the stage row granularities. block_cols is
        chosen by the memory model: the candidate minimising modeled DMA
        time for the program's total streamed bytes (wider blocks amortise
        issue overhead; padding waste and the VMEM budget push back — the
        paper's Fig. 3 trade-off at TPU scale). With a BurstModel the
        score is the one-term burst law; with a memhier Hierarchy each
        candidate is simulated trace-driven (per-level traffic included,
        intermediates elided) by the fast engine. Returns (block_rows,
        block_cols, StreamConfig).

        Results are memoised in a module-level cache keyed on the
        program's structural identity, (n_elems, dtype), the model
        fingerprint and the budget/buffer knobs (DESIGN.md §12): a
        repeated negotiation — same Program warm, or an equivalent
        candidate chain inside the partitioner's beam search — costs one
        dict lookup instead of a simulated candidate sweep. Model edits
        change the fingerprint and miss correctly. With an active plan
        cache (:mod:`repro.core.artifact`), a memo miss additionally
        consults the same key on disk and publishes the sweep's result,
        so negotiations persist across processes (DESIGN.md §14).
        """
        return self._negotiate_scored(n_elems, dtype)[:3]

    def negotiated_time(self, n_elems: int, dtype) -> float:
        """Modeled seconds of one launch at the negotiated geometry —
        the scheduling runtime's model seed (:mod:`repro.sched.cost`).
        Shares the negotiation memo, so a warm call is one dict hit."""
        return self._negotiate_scored(n_elems, dtype)[3]

    def _score_geometry(self, n_elems: int, dtype, block_rows: int,
                        block_cols: int) -> float:
        """Modeled seconds of ONE candidate geometry at ``n_elems`` —
        the negotiation's per-candidate scoring term, exposed so the
        cost-aware warm-bucket check can price a cached geometry at a
        new size without re-running the whole candidate sweep."""
        bits = _bits(dtype)
        if not isinstance(self.model, BurstModel):
            # deferred: memhier imports core.stream / core.template
            from repro.memhier.predict import predict_program
            return predict_program(self.model, self, n_elems, dtype,
                                   block_rows=block_rows,
                                   block_cols=block_cols,
                                   n_buffers=self.n_buffers).time_s
        block_elems = block_rows * block_cols
        n_io = self.n_ext_vec_in + self.n_vec_out
        padded = round_up(max(n_elems, 1), block_elems)
        return n_io * self.model.time_for(padded * bits / 8,
                                          block_elems * bits / 8)

    def _negotiate_scored(self, n_elems: int, dtype, fresh: bool = False):
        """The negotiation loop; returns (block_rows, block_cols,
        StreamConfig, modeled seconds of the winner). ``fresh`` skips
        the memo and the disk consult — the drift-triggered
        re-negotiation path distrusts the cached answer, so it must pay
        the sweep — while the result is still published to both."""
        model_fp = self._current_model_fp()
        key = (self._identity, int(n_elems), _dtype_name(dtype),
               model_fp, self.vmem_budget,
               self.n_buffers)
        hit = None if fresh else _GEOMETRY_CACHE.get(key)
        if hit is not None:
            DISPATCH_STATS.geometry_hits += 1
            if hit[0] == "no-fit":
                raise ValueError(hit[1])
            return hit
        # memo miss: everything below is span-worthy work (DESIGN.md
        # §15 — "negotiate" span, outcome disk_hit | sweep | no_fit).
        # The verdict is raised after the span closes, so a no-fit
        # span records its outcome and no error.
        with (_trace.span("negotiate", program=self.name,
                          n_elems=int(n_elems), dtype=_dtype_name(dtype),
                          bucket=_n_bucket(n_elems),
                          fingerprint=_artifact.key_hash(key))
              if _trace.enabled() else _trace.NULL_SPAN) as sp:
            verdict = self._negotiate_miss(key, model_fp, n_elems, dtype,
                                           fresh, sp)
        if verdict[0] == "no-fit":
            raise ValueError(verdict[1])
        return verdict

    def _negotiate_miss(self, key, model_fp, n_elems: int, dtype,
                        fresh: bool, sp):
        """A memo miss: the disk artifact, else the candidate sweep.
        Returns the geometry or the ``("no-fit", msg)`` verdict, and
        stamps the outcome on the tracer's span ``sp`` (None when no
        tracer records it)."""
        # in-process miss: consult the persistent artifact cache before
        # paying the candidate sweep (DESIGN.md §14). Token-fingerprinted
        # models are process-local and never share disk entries.
        disk = _artifact.plan_cache()
        if disk is not None and not _artifact.persistable_fingerprint(model_fp):
            disk = None
        if disk is not None and not fresh:
            loaded = disk.load("geom", key, decode=_geometry_from_payload)
            if loaded is not None:
                DISPATCH_STATS.geometry_hits += 1
                _cache_geometry(key, loaded)
                if sp is not None:
                    sp.attrs.update(outcome="disk_hit",
                                    no_fit=loaded[0] == "no-fit")
                return loaded
        DISPATCH_STATS.geometry_misses += 1
        block_rows = 1
        for st in self.stages:
            block_rows = math.lcm(block_rows, st.block_rows)
        bits = _bits(dtype)
        # resident per grid step: external ins + outs + VMEM intermediates
        # and carries (the fused region's whole operand footprint).
        n_resident = (self.n_ext_vec_in + self.n_vec_out
                      + self.n_intermediates
                      + sum(1 for st in self.stages if st.carry_cols))

        candidates = sorted(set(_BLOCK_COL_CANDIDATES)
                            | {st.block_cols for st in self.stages})
        best = None
        for bc in candidates:
            block_elems = block_rows * bc
            cfg = StreamConfig(vlen_bits=LANES * bits,
                               block_bits=block_elems * bits,
                               n_buffers=self.n_buffers)
            try:
                cfg.check_vmem_budget(n_resident, budget=self.vmem_budget)
            except ValueError:
                continue
            t = self._score_geometry(n_elems, dtype, block_rows, bc)
            if best is None or t < best[0]:
                best = (t, bc, cfg)
        if best is None:
            msg = (f"{self.name}: no block geometry fits {n_resident} "
                   f"resident operands in the {self.vmem_budget}-byte "
                   f"VMEM budget")
            verdict = ("no-fit", msg)
            _cache_geometry(key, verdict)
            if disk is not None:
                disk.store("geom", key, _geometry_payload(verdict))
            if sp is not None:
                sp.attrs.update(outcome="sweep", no_fit=True)
            return verdict
        t, bc, cfg = best
        result = (block_rows, bc, cfg, t)
        _cache_geometry(key, result)
        if disk is not None:
            disk.store("geom", key, _geometry_payload(result))
        if sp is not None:
            sp.attrs.update(outcome="sweep", block=[block_rows, bc],
                            modeled_s=t)
        return result

    # -- kernel emission ----------------------------------------------------
    def _fused_kernel(self, block_rows: int, block_cols: int,
                      scalar_items: int = 0):
        """Build the single kernel running all stage bodies back to back.

        ``scalar_items`` > 0 marks a scalar-batched coalesced launch: the
        scalar operands arrive stacked per item and each row block reads
        its owning item's row (``scalar_items`` = row blocks per item,
        DESIGN.md §13)."""
        stages, n_ext = self.stages, self._n_ext
        ns, nv, no = self.n_scalar_in, self.n_ext_vec_in, self.n_vec_out
        n_inter = self.n_intermediates

        def kernel(*refs):
            # trace-time side effect: runs once per (re)trace, never at
            # execution — the bench_hotpath zero-retrace gate reads it.
            DISPATCH_STATS.kernel_traces += 1
            scalar_refs = refs[:ns]
            if scalar_items:
                item = pl.program_id(0) // scalar_items
                scalar_refs = tuple(_ItemScalarRef(r, item)
                                    for r in scalar_refs)
            vec_refs = refs[ns:ns + nv]
            out_refs = refs[ns + nv:ns + nv + no]
            scratch = refs[ns + nv + no:]
            inter_refs = scratch[:n_inter]
            carry_refs = scratch[n_inter:]
            step = pl.program_id(1)

            prev_outs: tuple = ()
            si = vi = ii = ci = 0
            for k, st in enumerate(stages):
                sc = scalar_refs[si:si + st.n_scalar_in]
                si += st.n_scalar_in
                ext = vec_refs[vi:vi + n_ext[k]]
                vi += n_ext[k]
                ins = tuple(prev_outs) + tuple(ext)
                if k < len(stages) - 1:
                    outs = inter_refs[ii:ii + st.n_vec_out]
                    ii += st.n_vec_out
                else:
                    outs = out_refs
                carry = None
                if st.carry_cols:
                    carry = carry_refs[ci]
                    ci += 1
                emit_stage(st, sc, ins, outs, carry, step)
                prev_outs = outs

        kernel.__name__ = f"{self.name.replace('+', '_')}_kernel"
        return kernel

    def call_blocks(self, *operands, block_rows: Optional[int] = None,
                    block_cols: Optional[int] = None,
                    scalar_items: int = 0,
                    interpret: bool = False):
        """Launch on pre-normalised 2D operands (the strict template path).

        Vector operands must already be (rows, cols) with rows/cols
        divisible by the block geometry; defaults to the stages' declared
        geometry (single stage: exactly the old KernelTemplate behaviour).
        ``scalar_items`` > 0 is the scalar-batched coalesced path: scalar
        operands are ``(k_items, ...)`` stacks and each group of
        ``scalar_items`` row blocks reads its own item's values.
        """
        stages = self.stages
        last = stages[-1]
        if block_rows is None:
            block_rows = max(st.block_rows for st in stages)
        if block_cols is None:
            block_cols = max(st.block_cols for st in stages)

        per_stage = self.split_operands(operands)
        scalars = tuple(s for sc, _ in per_stage for s in sc)
        vectors = tuple(v for _, ext in per_stage for v in ext)
        for v in vectors:
            if v.ndim != 2:
                raise ValueError(f"{self.name}: vector operands must be 2D "
                                 f"(rows, cols); got shape {v.shape}")
        rows, cols = vectors[0].shape
        if len(stages) > 1:
            for v in vectors[1:]:
                if v.shape != (rows, cols):
                    raise ValueError(
                        f"{self.name}: fused operands must agree on shape; "
                        f"got {v.shape} vs {(rows, cols)}")
        if rows % block_rows or cols % block_cols:
            raise ValueError(
                f"{self.name}: operand shape {(rows, cols)} not divisible by "
                f"block ({block_rows}, {block_cols}); pad upstream")
        grid = (rows // block_rows, cols // block_cols)

        if last.out_shapes is not None:
            out_shape = tuple(last.out_shapes(*vectors))
        else:
            out_shape = tuple(
                jax.ShapeDtypeStruct(vectors[0].shape, vectors[0].dtype)
                for _ in range(last.n_vec_out))

        if interpret:
            DISPATCH_STATS.interpret_launches += 1
        else:
            DISPATCH_STATS.kernel_launches += 1
        # warm dispatch: one jitted pallas_call per operand signature —
        # a repeat call with the same shapes re-traces nothing.
        if scalar_items:
            scalars = tuple(jnp.asarray(s) for s in scalars)
        else:
            scalars = tuple(jnp.asarray(s).reshape(-1) for s in scalars)
        sig = (block_rows, block_cols, bool(interpret), int(scalar_items),
               tuple((tuple(s.shape), _dtype_name(s.dtype))
                     for s in scalars),
               tuple((tuple(v.shape), _dtype_name(v.dtype))
                     for v in vectors),
               tuple((tuple(o.shape), _dtype_name(o.dtype))
                     for o in out_shape))
        cached = self._exe_cache.get(sig)
        if cached is not None:
            return cached(*scalars, *vectors)
        DISPATCH_STATS.call_builds += 1
        _sp = _trace.span("pallas_build", program=self.name,
                          block=[block_rows, block_cols],
                          interpret=bool(interpret))
        with _sp:
            fn = self._build_call(stages, scalars, vectors, out_shape,
                                  block_rows, block_cols, grid, cols,
                                  interpret, scalar_items)
        if len(self._exe_cache) >= _EXE_CACHE_MAX:
            self._exe_cache.pop(next(iter(self._exe_cache)))
        self._exe_cache[sig] = fn
        return fn(*scalars, *vectors)

    def _build_call(self, stages, scalars, vectors, out_shape, block_rows,
                    block_cols, grid, cols, interpret, scalar_items=0):
        """Construct the jitted ``pallas_call`` for one operand
        signature (the cold half of :meth:`call_blocks`)."""
        blockspec = pl.BlockSpec((block_rows, block_cols),
                                 lambda r, c: (r, c))
        in_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars)
                    + [blockspec] * len(vectors))
        out_specs = tuple(
            pl.BlockSpec(
                (block_rows,
                 block_cols * s.shape[1] // cols if cols else block_cols),
                lambda r, c: (r, c))
            for s in out_shape)
        scratch: list = []
        # intermediates: chained values live in VMEM, never touching HBM.
        for st in stages[:-1]:
            scratch.extend(
                pltpu.VMEM((block_rows, block_cols), vectors[0].dtype)
                for _ in range(st.n_vec_out))
        for st in stages:
            if st.carry_cols:
                scratch.append(pltpu.VMEM((block_rows, st.carry_cols),
                                          st.carry_dtype))

        compiler_params = None
        if not interpret:
            # rows are independent ("parallel"); cols carry state in order.
            compiler_params = pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"))

        fn = jax.jit(pl.pallas_call(
            self._fused_kernel(block_rows, block_cols, scalar_items),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if len(out_shape) > 1 else out_specs[0],
            out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=compiler_params,
        ))
        return fn

    def _check_vectors(self, per_stage):
        """Validate external vector operand consistency: identical shapes
        and dtypes. Identical SHAPES (not just sizes) so ref-mode oracle
        composition (which runs on the original shapes, where numpy
        broadcasting would silently diverge) and the flattened kernel path
        accept exactly the same operand lists. Returns the external
        vectors in program order."""
        flat_vecs = [v for _, ext in per_stage for v in ext]
        if not flat_vecs:
            raise TypeError(f"{self.name}: a program needs at least one "
                            f"vector operand")
        shape = jnp.shape(flat_vecs[0])
        dtype = jnp.result_type(flat_vecs[0])
        for v in flat_vecs[1:]:
            if jnp.shape(v) != shape:
                raise ValueError(
                    f"{self.name}: fused vector operands must agree on "
                    f"shape; got {jnp.shape(v)} vs {shape}")
            if jnp.result_type(v) != dtype:
                raise ValueError(
                    f"{self.name}: fused vector operands must share a "
                    f"dtype; got {jnp.result_type(v)} vs {dtype}")
        return flat_vecs

    def check_vector_operands(self, operands):
        return self._check_vectors(self.split_operands(operands))

    # ------------------------------------------------------------------
    def _resolve_geometry(self, n: int, dtype) -> tuple[int, int]:
        """Warm-dispatch geometry for ``n`` elements: the per-instance
        bucket table, with the cost-aware drift check (DESIGN.md §12).

        A repeat size is a pure dict hit. A NEW size landing in a warm
        bucket first prices the cached geometry at that size (one model
        evaluation, no candidate sweep); only when its per-element
        modeled time drifted > :data:`REBUCKET_DRIFT` beyond the
        negotiation anchor does the full (memoised) negotiation re-run —
        and if the best geometry beats the cached one by more than the
        drift band, the bucket is updated (``DISPATCH_STATS.rebucketed``).
        So sweeps stay warm while the bucket approximation stays bounded.

        A pending drift re-negotiation request for this (identity,
        bucket, dtype) cell (:func:`request_renegotiation` — filed by
        the cost model when chronic modeled-vs-observed drift exceeds
        its tracker threshold) is consumed here: the warm bucket and the
        memoised sweeps are purged and the negotiation re-runs fresh
        (``DISPATCH_STATS.drift_renegotiated``).
        """
        dkey = (_n_bucket(n), _dtype_name(dtype),
                self._current_model_fp(), self.vmem_budget,
                self.n_buffers)
        entry = self._dispatch_cache.get(dkey)
        fresh = False
        if _RENEGOTIATE:
            rkey = (self._identity, _n_bucket(n), _dtype_name(dtype))
            if rkey in _RENEGOTIATE:
                _RENEGOTIATE.discard(rkey)
                DISPATCH_STATS.drift_renegotiated += 1
                _purge_geometry(*rkey)
                self._dispatch_cache.pop(dkey, None)
                entry, fresh = None, True
        if entry is None:
            br, bc, _, t = self._negotiate_scored(n, dtype, fresh=fresh)
            if len(self._dispatch_cache) >= _DISPATCH_CACHE_MAX:
                self._dispatch_cache.pop(next(iter(self._dispatch_cache)))
            entry = _WarmEntry(br, bc, n, t)
            self._dispatch_cache[dkey] = entry
        elif n != entry.anchor_n and n not in entry.checked:
            self._maybe_rebucket(entry, n, dtype)
        return entry.block_rows, entry.block_cols

    def _maybe_rebucket(self, entry: _WarmEntry, n: int, dtype) -> None:
        t_cached = self._score_geometry(n, dtype, entry.block_rows,
                                        entry.block_cols)
        band = 1.0 + REBUCKET_DRIFT
        allowed = band * entry.anchor_t * (n / entry.anchor_n)
        if t_cached <= allowed:
            entry.mark_checked(n)
            return
        # per-element efficiency drifted: run the (memoised) full sweep
        # and keep whichever geometry actually wins at this size.
        br, bc, _, t_best = self._negotiate_scored(n, dtype)
        if t_cached > band * t_best:
            entry.block_rows, entry.block_cols = br, bc
            entry.anchor_n, entry.anchor_t = n, t_best
            entry.checked.clear()
            DISPATCH_STATS.rebucketed += 1
        else:
            # the drift is inherent to the size (every geometry pays it);
            # re-anchor so nearby sizes compare against this one.
            entry.anchor_n, entry.anchor_t = n, t_cached
            entry.mark_checked(n)

    def _notify_observed(self, outs, n: int, dtype, t0: float,
                         n_items: int) -> None:
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        for hook in list(_OBSERVED_HOOKS):
            hook(self, n, _dtype_name(dtype), dt, n_items)

    def __call__(self, *operands, interpret: bool = False):
        """The shared streaming entry path: normalise arbitrary-shaped
        vector operands to padded 2D blocks, negotiate the fused geometry,
        launch the single pallas_call, restore the caller's shapes.

        Warm calls hit the per-instance dispatch table — keyed on the
        power-of-two ``n_elems`` bucket, dtype and model fingerprint —
        and skip negotiation entirely (with the cost-aware drift check of
        :meth:`_resolve_geometry` bounding the bucket approximation); the
        jitted ``pallas_call`` is reused per operand signature, so a
        repeat call does zero Python negotiation and zero kernel
        re-tracing (DESIGN.md §12).
        """
        per_stage = self.split_operands(operands)
        flat_vecs = self._check_vectors(per_stage)
        ref_v = flat_vecs[0]
        n = ref_v.size
        # traced calls (inside jit / shard_map) have no wall time to report
        t0 = (time.perf_counter()
              if _OBSERVED_HOOKS and not isinstance(ref_v, jax.core.Tracer)
              else None)

        with _trace.span("dispatch", program=self.name, n_elems=int(n),
                         dtype=_dtype_name(ref_v.dtype),
                         bucket=_n_bucket(n), n_items=1) as _sp:
            block_rows, block_cols = self._resolve_geometry(n, ref_v.dtype)
            if _sp is not None:
                _sp.attrs["block"] = [block_rows, block_cols]
            norm = []
            for sc, ext in per_stage:
                norm.extend(sc)
                norm.extend(flatten_to_blocks(v, block_cols, block_rows)[0]
                            for v in ext)
            out = self.call_blocks(*norm, block_rows=block_rows,
                                   block_cols=block_cols,
                                   interpret=interpret)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        outs = tuple(o.reshape(-1)[:n].reshape(ref_v.shape) for o in outs)
        result = outs[0] if len(outs) == 1 else outs
        if t0 is not None:
            self._notify_observed(result, n, ref_v.dtype, t0, 1)
        return result

    # ------------------------------------------------------------------
    def call_batch(self, batch: Sequence[Sequence[Any]], *,
                   interpret: bool = False):
        """Coalesced dispatch: N same-structure requests, ONE launch.

        ``batch`` is a sequence of operand tuples that must agree on
        scalar operand shapes/dtypes and on vector shapes/dtype (the
        :func:`repro.sched.queue.coalesce_key` grouping invariant), and
        every stage must be shape-preserving. Each item is normalised to
        whole blocks exactly as a solo :meth:`__call__` would be, the
        padded 2-D operands are stacked along the *parallel* row axis,
        and one ``pallas_call`` covers them all — so per-item results are
        bit-identical to N individual calls (blocks never straddle an
        item boundary; carried state is per row-block in both paths)
        while the per-launch Python/dispatch overhead is paid once.
        Returns the per-item results in order.

        Scalar operand *values* may differ between items: batches whose
        scalars are not all equal take the scalar-batched path
        (``DISPATCH_STATS.batch_mixed``) — each scalar slot is stacked
        into one ``(k_items,)`` SMEM vector and every row block indexes
        its owning item's value inside the kernel, so e.g. sixteen
        ``scale(s_k, x_k)`` requests with sixteen distinct ``s_k`` still
        coalesce into ONE launch with bit-identical per-item results.
        Batches whose scalars are all equal keep the exact pre-existing
        shared-scalar launch path.
        """
        batch = [tuple(ops) for ops in batch]
        if not batch:
            return []
        if not all(st.shape_preserving for st in self.stages):
            raise ValueError(
                f"{self.name}: shape-changing programs cannot be "
                f"batch-coalesced (per-item output shapes differ)")
        if len(batch) == 1:
            return [self(*batch[0], interpret=interpret)]
        t0 = time.perf_counter() if _OBSERVED_HOOKS else None

        items = [self.split_operands(ops) for ops in batch]
        ref_vecs = [self._check_vectors(per) for per in items]
        shape = jnp.shape(ref_vecs[0][0])
        dtype = jnp.result_type(ref_vecs[0][0])
        scalars0 = [np.asarray(s) for sc, _ in items[0] for s in sc]
        mixed = False
        for k, per in enumerate(items[1:], start=1):
            if jnp.shape(ref_vecs[k][0]) != shape:
                raise ValueError(
                    f"{self.name}: batched items must agree on vector "
                    f"shape; item {k} has {jnp.shape(ref_vecs[k][0])} "
                    f"vs {shape}")
            if jnp.result_type(ref_vecs[k][0]) != dtype:
                raise ValueError(
                    f"{self.name}: batched items must share a dtype")
            sc_k = [np.asarray(s) for sc, _ in per for s in sc]
            for a, b in zip(scalars0, sc_k):
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise ValueError(
                        f"{self.name}: batched items must agree on "
                        f"scalar operand shapes/dtypes (item {k} "
                        f"differs)")
                if not np.array_equal(a, b):
                    mixed = True

        n = ref_vecs[0][0].size
        with _trace.span("dispatch", program=self.name, n_elems=int(n),
                         dtype=_dtype_name(dtype), bucket=_n_bucket(n),
                         n_items=len(batch)) as _sp:
            block_rows, block_cols = self._resolve_geometry(n, dtype)
            if _sp is not None:
                _sp.attrs["block"] = [block_rows, block_cols]
            # Per-item normalised rows (identical across items — same
            # shape): cols padded up to whole blocks exactly as
            # flatten_to_blocks.
            rows_raw = -(-n // block_cols)
            rows_per_item = round_up(rows_raw, block_rows)
            padded_n = rows_per_item * block_cols

            def stack_slot(vs):
                """Stack one operand slot's per-item vectors into the
                padded 2-D batch layout — the same bytes a vstack of
                per-item ``flatten_to_blocks`` results would hold, in
                O(1) jax ops per slot instead of O(items)."""
                flat = jnp.stack(vs).reshape(len(vs), n)
                if padded_n != n:
                    flat = jnp.pad(flat, ((0, 0), (0, padded_n - n)))
                return flat.reshape(len(vs) * rows_per_item, block_cols)

            # rebuild program operand order: per stage, scalars then
            # stacked external vectors. Equal scalars pass through from
            # item 0 (the exact shared-scalar path); mixed scalars stack
            # per slot into (k_items, ...) SMEM vectors and the kernel
            # indexes each row block's owning item (scalar_items = row
            # blocks per item along the parallel grid axis).
            scalar_items = rows_per_item // block_rows if mixed else 0
            scal_slots = [[per[si][0][ki] for per in items]
                          for si, (sc0, _) in enumerate(items[0])
                          for ki in range(len(sc0))]
            per_slot = [[per[si][1][vi] for per in items]
                        for si, (_, ext0) in enumerate(items[0])
                        for vi in range(len(ext0))]
            norm = []
            slot = 0
            sslot = 0
            for sc, ext in items[0]:
                for _ in sc:
                    if mixed:
                        norm.append(jnp.stack([
                            jnp.asarray(v).reshape(-1)
                            for v in scal_slots[sslot]]))
                    else:
                        norm.append(scal_slots[sslot][0])
                    sslot += 1
                for _ in ext:
                    norm.append(stack_slot(per_slot[slot]))
                    slot += 1
            out = self.call_blocks(*norm, block_rows=block_rows,
                                   block_cols=block_cols,
                                   scalar_items=scalar_items,
                                   interpret=interpret)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        # un-stack in O(1) jax ops per output, then view out the items
        k_items = len(batch)
        unstacked = [o.reshape(k_items, padded_n)[:, :n].reshape(
                         (k_items,) + tuple(shape)) for o in outs]
        results = []
        for k in range(k_items):
            per_out = tuple(o[k] for o in unstacked)
            results.append(per_out[0] if len(per_out) == 1 else per_out)
        DISPATCH_STATS.batch_calls += 1
        DISPATCH_STATS.batch_items += len(batch)
        if mixed:
            DISPATCH_STATS.batch_mixed += 1
        if t0 is not None:
            self._notify_observed(results, n, dtype, t0, len(batch))
        return results
