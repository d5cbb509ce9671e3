"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy and idle share of the traced window, device time per
operation and per kernel, and the device's idle time split by what the
host was doing then (the harness's own ``cb.*`` annotations), and device
time by the op-name path of each op (the names of its
``jax.named_scope`` blocks, and ``while/body`` inside a scan or loop).

Only the process that held the chip can write the trace; this module
only reads it, with ``jax.profiler.ProfileData`` and, for the op-name
paths, a reader of the serialized trace's event metadata: on a TPU v5e
each ``XLA Ops`` event's metadata carries the path as the stat ``tf_op``
(``jit(<lambda>)/while/body/closed_call/bsd,de->bse/dot_general:`` in a
Mamba2 decode step), and ``ProfileData`` gives an event only the stats
of its own, not those of its metadata.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Hashable, Iterable, Optional

ANNOTATION_PREFIX = "cb."        # host spans the harness writes
WINDOW = "cb.window"             # the span around the traced window
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"             # event-metadata stat with the op-name path


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    scope: str = ""          # a device op's op-name path ("" where unknown)


@dataclasses.dataclass
class Trace:
    device_ops: dict[int, list[Event]]      # device id → ops, by start
    host_spans: list[Event]                  # cb.* annotations


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """The short name of a device op from the HLO text the trace carries:
    ``%merge_sorted_pallas.1 = (...) custom-call(...)`` →
    ``merge_sorted_pallas``."""
    name = hlo.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", name)


def self_times(events: list[Event], lo: float, hi: float,
               key: Callable[[Event], Hashable] = lambda e: op_name(e.name)
               ) -> dict:
    """Device seconds per op name (or per ``key`` of each op) inside
    [lo, hi], each op's own time: the ops of a loop body nest inside the
    loop's event on the same line, and only the innermost op is
    running."""
    out: collections.Counter = collections.Counter()
    stack: list[list] = []          # [event, child seconds]

    def close(entry):
        e, child = entry
        own = (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9 - child
        if own > 0:
            out[key(e)] += own
        if stack:
            stack[-1][1] += (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9

    for e in events:
        if e.end_ns <= lo or e.start_ns >= hi:
            continue
        while stack and stack[-1][0].end_ns <= e.start_ns:
            close(stack.pop())
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized protobuf
    message: an int for a varint, a ``memoryview`` otherwise."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield tag >> 3, value


def op_scopes(xspace: bytes) -> dict[str, dict[str, str]]:
    """device plane name → {op event name → its ``SCOPE_STAT`` path}, read
    from a serialized ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``:
    a plane's ``event_metadata`` map is field 4 and ``stat_metadata`` field
    5; an event metadata's ``name`` is field 2 and its ``stats`` field 5; a
    stat's ``metadata_id`` is field 1, ``str_value`` 5 and ``ref_value``
    7). The ops' lines are skipped unread. An event name whose metadata
    entries carry different paths, or none, maps to ``""``."""
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, stat_names, metas = "", {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f in (4, 5):
                entry = dict(_fields(v))
                if 2 not in entry:
                    continue
                value = dict(_fields(entry[2]))
                if f == 5:
                    stat_names[value.get(1, 0)] = bytes(
                        value.get(2, b"")).decode()
                else:
                    stats = [dict(_fields(st)) for k, st in _fields(entry[2])
                             if k == 5]
                    metas.append((bytes(value.get(2, b"")).decode(), stats))
        if not DEVICE_PLANE.match(name):
            continue
        paths: dict[str, set] = {}
        for op, stats in metas:
            path = next(((bytes(st[5]).decode() if 5 in st
                          else stat_names.get(st.get(7), ""))
                         for st in stats
                         if stat_names.get(st.get(1)) == SCOPE_STAT), "")
            paths.setdefault(op, set()).add(path)
        out[name] = {op: p.pop() if len(p) == 1 else ""
                     for op, p in paths.items()}
    return out


def from_profile(pd, scopes: Optional[dict] = None) -> Trace:
    """Collect device ops (the ``XLA Ops`` line of each device plane) and
    the harness's host annotations from a ``ProfileData``; ``scopes``
    (``op_scopes`` of the same trace) gives each device op its path."""
    device_ops: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                paths = (scopes or {}).get(plane.name, {})
                evs = device_ops.setdefault(int(m.group(2)), [])
                evs.extend(Event(e.name, e.start_ns, e.end_ns,
                                 paths.get(e.name, ""))
                           for e in line.events)
            elif not m:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(ANNOTATION_PREFIX))
    for evs in device_ops.values():
        evs.sort(key=lambda e: (e.start_ns, -e.end_ns))
    host.sort(key=lambda e: e.start_ns)
    return Trace(device_ops, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    with open(path, "rb") as f:
        xspace = f.read()
    return from_profile(ProfileData.from_serialized_xspace(xspace),
                        op_scopes(xspace))


def window_of(trace: Trace) -> tuple[float, float]:
    spans = [e for e in trace.host_spans if e.name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return spans[0].start_ns, spans[-1].end_ns


def merge(intervals: Iterable[tuple[float, float]]
          ) -> list[tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by sorted disjoint intervals."""
    return sum(b - a for a, b in clip(intervals, lo, hi))


def busy_intervals(trace: Trace, device: int, lo: float, hi: float):
    return clip(merge((e.start_ns, e.end_ns)
                      for e in trace.device_ops.get(device, ())), lo, hi)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                             # mean over the devices
    per_device_busy_s: dict[int, float]
    op_seconds: dict[str, float]              # device self time by op name
    gaps_by_host: dict[str, float]            # idle seconds by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _host_split(spans: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Seconds of [lo, hi] by the innermost harness span covering each
    part of it (outside every span: ``host: no harness span``)."""
    inside = [s for s in spans if s.name != WINDOW
              and s.end_ns > lo and s.start_ns < hi]
    cuts = sorted({lo, hi, *(max(lo, min(hi, t)) for s in inside
                             for t in (s.start_ns, s.end_ns))})
    out: collections.Counter = collections.Counter()
    for a, b in zip(cuts, cuts[1:]):
        cover = [s for s in inside if s.start_ns <= a and s.end_ns >= b]
        who = (min(cover, key=lambda s: s.end_ns - s.start_ns).name
               if cover else "host: no harness span")
        out[who] += (b - a) * 1e-9
    return out


def reduce(trace: Trace, devices: Optional[list[int]] = None,
           window: Optional[tuple[float, float]] = None) -> Reduction:
    lo, hi = window if window is not None else window_of(trace)
    devices = sorted(trace.device_ops) if devices is None else devices
    if not devices:
        raise ValueError("the trace has no device ops")
    per_busy, ops = {}, collections.Counter()
    gaps_by_host: collections.Counter = collections.Counter()
    for d in devices:
        busy = busy_intervals(trace, d, lo, hi)
        per_busy[d] = sum(b - a for a, b in busy) * 1e-9
        for name, sec in self_times(trace.device_ops.get(d, []), lo,
                                    hi).items():
            ops[name] += sec / len(devices)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                for who, sec in _host_split(trace.host_spans, a, b).items():
                    gaps_by_host[who] += sec / len(devices)
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(per_busy.values()) / len(devices),
        per_device_busy_s=per_busy,
        op_seconds=dict(ops),
        gaps_by_host=dict(gaps_by_host))


def busy_within(trace: Trace, spans: list[tuple[float, float]],
                devices: list[int]) -> tuple[float, float]:
    """(busy seconds, span seconds) over host spans such as decode steps,
    averaged over ``devices``."""
    spans = merge(spans)
    total = sum(b - a for a, b in spans) * 1e-9
    busy = 0.0
    for d in devices:
        iv = merge((e.start_ns, e.end_ns)
                   for e in trace.device_ops.get(d, ()))
        busy += sum(covered(iv, a, b) for a, b in spans) * 1e-9
    return busy / max(len(devices), 1), total


def kernel_seconds(red: Reduction, patterns: dict[str, str]
                   ) -> dict[str, float]:
    """Device seconds per kernel, where ``patterns`` maps a kernel to the
    regular expression its ops' names match in the trace."""
    out = {}
    for kernel, pat in patterns.items():
        rx = re.compile(pat)
        t = sum(s for name, s in red.op_seconds.items() if rx.fullmatch(name))
        if t > 0:
            out[kernel] = t
    return out


def scope_seconds(trace: Trace, devices: list[int], component: str
                  ) -> Optional[float]:
    """Device self time inside ``cb.window``, averaged over ``devices``, of
    the ops whose op-name path has ``component`` as one of its parts (a
    ``jax.named_scope``'s name, or ``while`` for the body of a scan or
    loop), nesting as in ``self_times``. None where no op in the window
    has it, as in a trace whose ops carry no paths."""
    lo, hi = window_of(trace)
    parts: dict[str, bool] = {}

    def inside(e: Event) -> bool:
        if e.scope not in parts:
            parts[e.scope] = component in re.split(r"[/:]", e.scope)
        return parts[e.scope]

    total = sum(self_times(trace.device_ops.get(d, []), lo, hi,
                           inside).get(True, 0.0) for d in devices)
    return total / len(devices) if total > 0 else None


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time,
    and the host spans in which the device sat idle longest."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gaps_by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
