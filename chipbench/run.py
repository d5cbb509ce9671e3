"""Run one benchmark cell once, on the chip this process is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``. One run is: set-up (weights or operand pools from the
seed, warm-up of the cell's own shapes), the measured window of
``--seconds``, then the check of what the window produced against the
plain reference. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the JAX
profiler and the result carries its per-layer metrics instead.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``: each number the check compared,
beside its limit (also the last lines of standard error).

The run refuses to start, and prints no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


class NoChip(RuntimeError):
    pass


def device_info(chips: int) -> tuple[list, dict]:
    """The cell's devices and how JAX reports them; raises unless JAX runs
    on a TPU with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    devs = devs[:chips]
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devs if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def configure_jax() -> None:
    """Compile cache inside the checkout (or where the environment says),
    every program cached however fast it compiled."""
    import jax
    harness.ensure_src_on_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer_metrics(cell: harness.Cell, data: harness.RunData) -> dict:
    out = {}
    for m in cell.per_layer:
        value = harness.load_module("layer_metrics", m["name"]).read(data)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: harness.Cell, seed: int, seconds: float,
             trace: bool) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    from chipbench import peaks as peaks_mod
    from chipbench import trace_reduce

    devs, device = device_info(cell.chips)
    peaks = peaks_mod.peaks_for(device["kind"])
    driver = harness.load_module(
        "drivers", cell.config["program"]["driver"]).Driver(cell, seed, devs)
    spans = harness.Spans(trace)
    compiles = harness.CompileCounter()

    t_setup = time.perf_counter()
    driver.setup(seconds)
    setup_s = time.perf_counter() - T_START

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    compiles.armed = True
    try:
        driver.window(seconds, spans)
    finally:
        compiles.armed = False
        if trace:
            jax.profiler.stop_trace()
    device["memory_peak_bytes"] = memory_peak(devs)

    data = harness.RunData(cell=cell, peaks=peaks,
                           devices=[d.id for d in devs],
                           records=driver.records, counters=driver.counters,
                           work=driver.work)
    result = {"correct": None, "attempted": driver.attempted,
              "failed": driver.failed}
    breakdown = None
    if trace:
        tr = trace_reduce.load(log_dir)
        red = trace_reduce.reduce(tr, devices=data.devices)
        data.trace, data.reduction = tr, red
        data.kernel_patterns = harness.kernel_patterns(driver.kernels)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = trace_reduce.breakdown(red)
        metrics = per_layer_metrics(cell, data)
        _rmtree(log_dir)
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = (setup_s if m["name"] == "setup_s"
                     else driver.end_to_end[m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    driver.release()
    compared = driver.check()
    result["correct"] = bool(compared) and all(c.ok for c in compared)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = dict(driver.summary, compiles=compiles.compiles,
                            cache_loads=compiles.cache_hits,
                            start_s=t_setup - T_START)
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in compared}
    return result


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    try:
        cell = harness.cell_from(harness.load_benchmark(), args.workload)
        configure_jax()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
