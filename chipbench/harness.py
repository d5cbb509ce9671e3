"""Pieces every driver shares: the checkout's layout, loading a cell's
files by name, host spans on the profiler's clock, the compile counter,
and the record of one run that the per-layer readers read."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent          # chipbench/
ROOT = HERE.parent                              # the checkout
SRC = ROOT / "src"


def module_name(name: str) -> str:
    """A benchmark name as a file stem: ``-`` and ``.`` become ``_``."""
    return name.replace("-", "_").replace(".", "_")


@functools.cache
def load_module(kind: str, name: str):
    """``chipbench/<kind>/<module_name(name)>.py``, loaded by path, once."""
    path = HERE / kind / f"{module_name(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{module_name(name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its configuration and traffic."""
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file's contents
    traffic_name: str
    traffic: dict           # the traffic mix's parameters
    end_to_end: list        # the end-to-end metric entries this cell reports
    per_layer: list         # the per-layer metric entries this cell reports


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_from(bench: dict, name: str) -> Cell:
    from chipbench import traffic as traffic_mod
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"],
        traffic=traffic_mod.load(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


# ---------------------------------------------------------------------------
# host spans and counters
# ---------------------------------------------------------------------------

class Spans:
    """Host spans on the profiler's clock. Off, they cost one attribute
    test; on, each is a ``jax.profiler.TraceAnnotation`` named
    ``cb.<name>`` that the trace reduction reads."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return self._annotation("cb." + name)


class CompileCounter:
    """Counts programs built while armed: each is a compilation or a load
    from the persistent cache. The window should see neither."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.built = 0
        self.cache_hits = 0
        self.armed = False
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    @property
    def compiles(self) -> int:
        return self.built - self.cache_hits

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if self.armed and event == self.BUILD:
            self.built += 1

    def _on_event(self, event: str, **kw) -> None:
        if self.armed and event == self.CACHE_HIT:
            self.cache_hits += 1


@dataclasses.dataclass
class Compared:
    """One number the correctness check compares, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class RunData:
    """What a per-layer reader may read about one run."""
    cell: Cell
    peaks: Any                              # peaks.Peaks
    devices: list                           # device ids the cell used
    records: dict                           # the driver's host records
    counters: dict                          # program counters over the window
    work: dict                              # kernel → [flops, bytes] in window
    reduction: Optional[Any] = None         # trace_reduce.Reduction
    trace: Optional[Any] = None             # trace_reduce.Trace
    kernel_patterns: dict = dataclasses.field(default_factory=dict)


def kernel_patterns(names) -> dict:
    """kernel → the pattern its ops' names match in the trace, read from
    each kernel's work file."""
    return {k: load_module("work", k).TRACE for k in names}


def ensure_src_on_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
