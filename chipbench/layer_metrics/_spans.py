"""Device idle inside the program's own host spans.

The program (``repro.obs.trace``) writes its spans into the profiler
trace while the profiler collects: host events named ``repro.<span>`` on
the same clock as the device ops and the harness's ``cb.*`` spans. The
readers here put the device's idle time down to them: the idle seconds
inside a union of program spans, less another union, optionally only
where a harness span is open too, clipped to ``cb.window`` and averaged
over the run's devices.

``trace_reduce.Trace`` keeps only the harness's spans, so this module
reads the program's spans from the run's trace file itself: the newest
``chipbench_trace_*`` directory under the temporary directory (where
``run.py`` writes the trace, and which it deletes only after the
per-layer readers have run) whose ``cb.window`` is the run's. A traced
run whose trace file cannot be found raises, so that a broken search
fails the run instead of dropping its metrics; a trace that holds no
program spans (a program that writes none) gives ``None``, as the other
readers give where what they read is absent.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile
from typing import Optional

from chipbench import trace_reduce

PREFIX = "repro."
TRACE_DIRS = "chipbench_trace_*"        # run.py's mkdtemp prefix


def window_and_spans(pd) -> tuple[list, list]:
    """(the ``cb.window`` events, the ``repro.*`` events) on the host
    planes of a ``ProfileData``, as ``trace_reduce.Event``s by start."""
    window, spans = [], []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(trace_reduce.Event(e.name, e.start_ns,
                                                    e.end_ns))
                elif e.name == trace_reduce.WINDOW:
                    window.append(trace_reduce.Event(e.name, e.start_ns,
                                                     e.end_ns))
    window.sort(key=lambda e: e.start_ns)
    spans.sort(key=lambda e: e.start_ns)
    return window, spans


@functools.lru_cache(maxsize=2)
def _read(path: str) -> tuple[Optional[tuple], list]:
    from jax.profiler import ProfileData
    window, spans = window_and_spans(ProfileData.from_file(path))
    if not window:
        return None, spans
    return (window[0].start_ns, window[-1].end_ns), spans


def of_run(data) -> Optional[list]:
    """The run's program spans (``[]`` where its trace holds none), or
    None where the run was not traced.  Raises ``FileNotFoundError``
    where no trace file under the temporary directory has the run's
    window."""
    if data.trace is None:
        return None
    window = trace_reduce.window_of(data.trace)
    pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                           "*.xplane.pb")
    paths = glob.glob(pattern, recursive=True)
    paths.sort(key=os.path.getmtime, reverse=True)
    for path in paths:
        got, spans = _read(path)
        if got == window:
            return spans
    raise FileNotFoundError(
        f"no trace file {pattern} has the run's {trace_reduce.WINDOW} "
        f"{window} (of {len(paths)} tried)")


def _overlap(a: list, b: list) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _less(a: list, b: list) -> list:
    """``a`` less ``b``, both sorted disjoint interval lists."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def _union(events, names) -> list:
    return trace_reduce.merge((e.start_ns, e.end_ns) for e in events
                              if e.name in names)


def region(data, spans: list, inside, less=(), within=()) -> list:
    """The union of the program spans named in ``inside``, less those
    named in ``less``, and (where ``within`` names harness spans) only
    while one of those is open; clipped to ``cb.window``."""
    lo, hi = trace_reduce.window_of(data.trace)
    out = trace_reduce.clip(_union(spans, inside), lo, hi)
    if less:
        out = _less(out, _union(spans, less))
    if within:
        out = _overlap(out, _union(data.trace.host_spans, within))
    return out


def idle_seconds(data, spans: list, inside, less=(), within=()
                 ) -> Optional[float]:
    """Device idle seconds inside :func:`region`, averaged over
    ``data.devices``; None where the region is empty."""
    r = region(data, spans, inside, less, within)
    if not r or not data.devices:
        return None
    busy, total = trace_reduce.busy_within(data.trace, r, data.devices)
    return total - busy


def host_seconds(data, spans: list, inside, less=(), within=()
                 ) -> Optional[float]:
    """Host seconds inside :func:`region`; None where it is empty.  Read
    on the host's clock alone, so unlike :func:`idle_seconds` it does not
    move with the trace's host-to-device clock offset."""
    r = region(data, spans, inside, less, within)
    return sum(b - a for a, b in r) * 1e-9 if r else None


def _ms_per(data, count: str, seconds, inside, less, within
            ) -> Optional[float]:
    n = data.records.get(count, 0)
    spans = of_run(data)
    if not spans or n <= 0:
        return None
    got = seconds(data, spans, inside, less, within)
    return None if got is None else 1e3 * got / n


def idle_ms_per(data, count: str, inside, less=(), within=()
                ) -> Optional[float]:
    """:func:`idle_seconds` in milliseconds per ``data.records[count]``
    (completed requests, decode steps)."""
    return _ms_per(data, count, idle_seconds, inside, less, within)


def host_ms_per(data, count: str, inside, less=(), within=()
                ) -> Optional[float]:
    """:func:`host_seconds` in milliseconds per ``data.records[count]``."""
    return _ms_per(data, count, host_seconds, inside, less, within)
