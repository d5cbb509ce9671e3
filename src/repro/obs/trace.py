"""Structured spans over the request lifecycle (DESIGN.md §15).

Tree spans (parent ← child), recorded by an installed :class:`Tracer`::

    request                     one submitted WorkItem, root (Tracer only)
    ├── admission               arity validation + coalesce key
    ├── coalesce                batch formation (parented to the batch's
    │                           first member; attrs name the rest)
    ├── reconfig                a region load charged to a lane
    └── placement               one lane batch run by the scheduler
        └── dispatch            Program.__call__ / call_batch
            ├── negotiate       geometry sweep on memo miss
            │                   (outcome: disk_hit | sweep)
            ├── pallas_build    cold jit build of the pallas_call
            └── part            one Plan part (graph plans only)

Host spans, the host time the device may wait on (profiler only),
each read by a per-layer metric of the chip benchmark
(``chipbench/layer_metrics/``)::

    submit                      RequestQueue.submit, the whole call
    drain                       Scheduler.drain, the whole call
    └── placement ─┬─ launch    the host issuing one lane batch's work
                   └─ wait      jax.block_until_ready on its outputs
    sample                      serve.sample, the per-step token pick

``sched_idle_ms.prog`` reads the device idle inside ``submit`` and
``drain`` but outside ``launch`` and ``wait`` (``sched_host_ms.prog``
the host time there); ``launch_idle_ms.prog`` the device idle inside
``launch``; ``sample_idle_ms.decode`` inside ``sample``.

Three modes, decided per span by one gate (:func:`span`,
:func:`host_span`):

* **Off** — no :class:`Tracer` installed and the JAX profiler not
  collecting.  The gate reads the :data:`ACTIVE` global and
  ``jax.profiler.TraceAnnotation.is_enabled()`` and returns the
  singleton :data:`NULL_SPAN`: no :class:`Span`, no annotation.  What
  remains is the call and its keyword packing, a few hundred
  nanoseconds a span on a CPU; ``tests/test_obs.py`` times it, and the
  chip benchmark's untraced runs (``chipbench/run.py --trace 0``)
  carry it in every end-to-end number.
* **Profiler collecting** (``jax.profiler.trace`` / ``start_trace``,
  e.g. ``chipbench/run.py --trace 1``), no Tracer — each span is a
  ``jax.profiler.TraceAnnotation`` named ``repro.<span>``, with its
  scalar attrs (``seq``, ``lane``, ``name``, …) as event metadata.  It
  lands on the host line of the same ``.xplane.pb`` as the device ops,
  on the same clock, so device idle can be put down to the span the
  host was in.  Nothing else runs: no blame stamps, no ``under``, no
  root listeners.  What this mode costs is read by running a traced
  cell on the parent and on the change with the same seeds and
  comparing the window's requests or steps and its idle share.
* **Tracer installed** (``serve.py --obs-trace/--obs-tail``, the
  analysis tier of §19) — tree spans are recorded exactly as without a
  profiler, on the tracer's clock; when the profiler also collects they
  are mirrored into it.  Host spans never enter the Tracer: several
  would be roots there (``submit``, ``drain``, ``sample``) and change
  its sampling, tail decisions and blame.  The request root is opened
  in ``submit`` and finished by the scheduler, so it cannot nest on
  the host line and stays in the Tracer only.

Determinism: a :class:`Tracer` built on :class:`VirtualClock` assigns
sequential span ids and synthetic timestamps, so
:meth:`Tracer.export_jsonl` is byte-stable across identical runs — the
same contract as ``sched/replay.py``'s TraceRecorder.
:meth:`Tracer.export_chrome` emits Chrome-trace/Perfetto JSON
(``traceEvents`` with complete ``"X"`` events, µs timestamps).
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

#: Prefix of every span name written into a profiler trace.
PROFILER_PREFIX = "repro."
#: ``True`` while a JAX profiler session collects (one C++ flag read).
_collecting = _Annotation.is_enabled


class Span:
    """One timed operation.  ``attrs`` is a plain dict the owning site
    may mutate until :meth:`Tracer.finish`."""

    __slots__ = ("name", "span_id", "parent_id", "start", "end", "attrs",
                 "sampled")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 start: float, attrs: Dict[str, Any], sampled: bool = True):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end: Optional[float] = None
        self.attrs = attrs
        self.sampled = sampled

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "end": self.end,
            "attrs": {k: _chromable(v) for k, v in self.attrs.items()},
        }

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, id={self.span_id}, "
                f"parent={self.parent_id})")


class VirtualClock:
    """Deterministic clock: each read advances by ``step``.  Pairing
    this with a fresh tracer makes exports byte-stable across runs."""

    def __init__(self, start: float = 0.0, step: float = 1e-6):
        self._t = float(start)
        self.step = float(step)

    def __call__(self) -> float:
        t = self._t
        self._t += self.step
        return t


def _metadata(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """The attrs a profiler event carries: scalars only (lists, keys and
    arrays stay in the Tracer)."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (str, int, float, bool))}


class _SpanCtx:
    """Context manager for one span: pushes onto the tracer's stack so
    nested instrumentation sites parent correctly, and writes the span
    into the profiler as well when ``mirror`` is set."""

    __slots__ = ("_tracer", "_span", "_ann")

    def __init__(self, tracer: "Tracer", span: Span, mirror: bool = False):
        self._tracer = tracer
        self._span = span
        self._ann = _ProfilerSpan(span.name, span.attrs) if mirror else None

    def __enter__(self) -> Span:
        self._tracer._stack.append(self._span)
        if self._ann is not None:
            self._ann.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        st = self._tracer._stack
        if st and st[-1] is self._span:
            st.pop()
        if exc_type is not None:
            self._span.attrs.setdefault("error", exc_type.__name__)
        self._tracer.finish(self._span)
        return False


class _ProfilerSpan:
    """A span written only into the profiler trace.  Enters to ``None``
    like :data:`NULL_SPAN`, so sites skip their attribute writes."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self._ann = _Annotation(PROFILER_PREFIX + name, **_metadata(attrs))

    def __enter__(self):
        self._ann.__enter__()
        return None

    def __exit__(self, exc_type, exc, tb):
        self._ann.__exit__(exc_type, exc, tb)
        return False


class _UnderCtx:
    """Re-parents nested spans under an existing (still-open) span
    without finishing it on exit — the scheduler uses this to hang
    placement/dispatch work off a request's root span."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> Span:
        self._tracer._stack.append(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        st = self._tracer._stack
        if st and st[-1] is self._span:
            st.pop()
        return False


class _NullSpan:
    """Singleton no-op stand-in used when tracing is disabled.  Enters
    to ``None`` so call sites guard attribute writes with
    ``if sp is not None``."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


NULL_SPAN = _NullSpan()

_CURRENT = object()  # sentinel: parent = top of stack


class Tracer:
    """Collects spans with parent/child links.

    ``clock`` defaults to ``time.perf_counter``; pass a
    :class:`VirtualClock` for byte-stable exports.  Span ids are
    sequential from 1 in creation order.  ``max_spans`` bounds memory;
    overflow increments :attr:`dropped` instead of growing.

    ``sample_rate`` enables head-based per-request sampling so tracing
    can stay on under sustained traffic: the keep/drop decision is made
    once per ROOT span (a request) and inherited by every descendant,
    so kept requests keep their *whole* span tree — unlike ``max_spans``
    overflow, which truncates the tail of the run.  The decision is a
    deterministic credit accumulator (no RNG): at rate ``r`` exactly
    every ``1/r``-th root is kept, starting with the first, so tests
    and replays see stable output.  Unsampled spans are never stored
    (they cost one branch + counter); :attr:`unsampled` counts them.
    """

    def __init__(self, clock=None, max_spans: int = 1_000_000,
                 sample_rate: float = 1.0):
        if not (0.0 <= sample_rate <= 1.0):
            raise ValueError(f"sample_rate must be in [0, 1], got "
                             f"{sample_rate}")
        self.clock = clock or time.perf_counter
        self.max_spans = max_spans
        self.sample_rate = float(sample_rate)
        self.spans: List[Span] = []
        self.dropped = 0
        self.unsampled = 0
        #: callbacks fired once per sampled ROOT span, at its first
        #: finish — the attach point for tail-based sampling
        #: (:class:`repro.obs.tail.TailSampler`, DESIGN.md §19), which
        #: must see the whole tree only after its outcome is known.
        self.root_listeners: List = []
        self._stack: List[Span] = []
        self._next_id = 1
        # first root always sampled (when rate > 0): start one credit
        # short of the keep threshold
        self._credit = 1.0 - self.sample_rate

    # -- recording ---------------------------------------------------
    def current(self) -> Optional[Span]:
        return self._stack[-1] if self._stack else None

    def start_span(self, name: str, parent=_CURRENT, **attrs) -> Span:
        """Create an open span.  ``parent``: the sentinel default means
        "current top of stack"; pass ``None`` for an explicit root or a
        :class:`Span` for an explicit parent."""
        if parent is _CURRENT:
            parent = self.current()
        if isinstance(parent, Span):
            pid, sampled = parent.span_id, parent.sampled
        else:
            pid, sampled = None, self._sample_root()
        if not sampled:
            self.unsampled += 1
            return Span(name, 0, pid, self.clock(), attrs, sampled=False)
        sp = Span(name, self._next_id, pid, self.clock(), attrs)
        self._next_id += 1
        if len(self.spans) < self.max_spans:
            self.spans.append(sp)
        else:
            self.dropped += 1
        return sp

    def _sample_root(self) -> bool:
        """Head-based keep/drop for a new root (see class docstring)."""
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        self._credit += self.sample_rate
        if self._credit >= 1.0 - 1e-12:
            self._credit -= 1.0
            return True
        return False

    def finish(self, span: Span, **attrs):
        if attrs:
            span.attrs.update(attrs)
        first = span.end is None
        if first:
            span.end = self.clock()
        if (first and span.parent_id is None and span.sampled
                and self.root_listeners):
            for cb in list(self.root_listeners):
                cb(span)

    def span(self, name: str, parent=_CURRENT, **attrs) -> _SpanCtx:
        """``with tracer.span("negotiate", ...) as sp:`` — starts,
        stacks, and finishes a span around the body."""
        return _SpanCtx(self, self.start_span(name, parent=parent, **attrs))

    def under(self, span: Span) -> _UnderCtx:
        return _UnderCtx(self, span)

    # -- queries (tests / reports) ----------------------------------
    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def children_of(self, span: Span) -> List[Span]:
        return [s for s in self.spans if s.parent_id == span.span_id]

    def subtree_names(self, root: Span) -> List[str]:
        """Names of every span reachable from ``root`` (inclusive),
        in span-id order — the connectivity check for the one-request
        span-tree acceptance gate."""
        by_parent: Dict[Optional[int], List[Span]] = {}
        for s in self.spans:
            by_parent.setdefault(s.parent_id, []).append(s)
        out, todo = [], [root]
        while todo:
            s = todo.pop()
            out.append(s)
            todo.extend(by_parent.get(s.span_id, ()))
        return [s.name for s in sorted(out, key=lambda s: s.span_id)]

    # -- exports -----------------------------------------------------
    def export_jsonl(self) -> str:
        """One sorted-key JSON object per line, span-id order.
        Byte-stable for a given (clock, workload) pair."""
        return "".join(
            json.dumps(s.to_dict(), sort_keys=True,
                       separators=(",", ":")) + "\n"
            for s in sorted(self.spans, key=lambda s: s.span_id))

    def export_chrome(self, process_name: str = "repro") -> str:
        """Chrome-trace / Perfetto JSON: complete ``"X"`` events with
        microsecond timestamps; span ids/parents ride in ``args``."""
        t0 = min((s.start for s in self.spans), default=0.0)
        events: List[dict] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 1,
            "args": {"name": process_name},
        }]
        for s in sorted(self.spans, key=lambda s: s.span_id):
            end = s.end if s.end is not None else s.start
            args = {"span_id": s.span_id, "parent_id": s.parent_id}
            args.update({k: _chromable(v) for k, v in s.attrs.items()})
            events.append({
                "name": s.name,
                "ph": "X",
                "ts": round((s.start - t0) * 1e6, 3),
                "dur": round(max(end - s.start, 0.0) * 1e6, 3),
                "pid": 1,
                "tid": int(s.attrs.get("lane", 0)) + 1,
                "args": args,
            })
        return json.dumps({"traceEvents": events,
                           "displayTimeUnit": "ms"}, sort_keys=True)


def _chromable(v):
    """Attrs down to JSON scalars: numpy 0-d values unwrap, anything
    else non-JSON falls back to its repr (exports must never throw)."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_chromable(x) for x in v]
    if getattr(v, "ndim", None) == 0 and hasattr(v, "item"):
        try:
            return _chromable(v.item())
        except (TypeError, ValueError):  # pragma: no cover - exotic dtypes
            pass
    return repr(v)


# ---------------------------------------------------------------------------
# process-global activation
# ---------------------------------------------------------------------------

#: The active tracer, or ``None`` (tracing off).  Instrumentation sites
#: read this once per operation.
ACTIVE: Optional[Tracer] = None


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or clear, with ``None``) the process tracer; returns
    the previous one."""
    global ACTIVE
    prev, ACTIVE = ACTIVE, tracer
    return prev


def get_tracer() -> Optional[Tracer]:
    return ACTIVE


class _UsingTracer:
    __slots__ = ("_tracer", "_prev")

    def __init__(self, tracer):
        self._tracer = tracer

    def __enter__(self) -> Optional[Tracer]:
        self._prev = set_tracer(self._tracer)
        return self._tracer

    def __exit__(self, *a):
        set_tracer(self._prev)
        return False


def using_tracer(tracer: Optional[Tracer]) -> _UsingTracer:
    """``with using_tracer(Tracer()) as tr: ...`` — scoped activation
    with restore (tests, benches)."""
    return _UsingTracer(tracer)


def enabled() -> bool:
    """Whether a span opened now would be recorded anywhere: a Tracer is
    installed or the profiler collects.  Sites whose attrs are costly to
    build test this first."""
    return ACTIVE is not None or _collecting()


def span(name: str, /, parent=_CURRENT, **attrs):
    """The gate for a tree span (module docstring): on the active tracer
    (mirrored into the profiler while it collects), a profiler event
    alone when no tracer is installed, else :data:`NULL_SPAN`.  Enters
    to the :class:`Span` only when a tracer records it, so sites guard
    attribute writes with ``if sp is not None``."""
    tr = ACTIVE
    if tr is None:
        if not _collecting():
            return NULL_SPAN
        return _ProfilerSpan(name, attrs)
    return _SpanCtx(tr, tr.start_span(name, parent=parent, **attrs),
                    _collecting())


def host_span(name: str, /, **attrs):
    """The gate for a host span (module docstring): a profiler event
    while the profiler collects, else :data:`NULL_SPAN`.  A Tracer never
    records it.  Enters to ``None``."""
    if not _collecting():
        return NULL_SPAN
    return _ProfilerSpan(name, attrs)
