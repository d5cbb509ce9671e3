"""CPU rehearsal of every cell: the whole run at a tiny size, with the
look for a chip skipped from here; the same run with its timed path
broken underneath, which the check must catch; and the refusals."""
import copy
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench import harness, run

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SEED = 2**31 + 424242

TINY_SIZES = {"copy": 4096, "scale": 4096, "add": 4096, "triad": 4096,
              "prefix_sum": 65536, "mergesort": 4096}


def tiny_cell(name):
    """The benchmark's cell with its sizes cut for the CPU."""
    cell = harness.cell_from(harness.load_benchmark(), name)
    cell.config = copy.deepcopy(cell.config)
    if cell.config["program"]["driver"] == "sched_programs":
        for k, n in TINY_SIZES.items():
            cell.config["programs"][k]["n"] = n
    else:
        cell.traffic = dict(cell.traffic, batch=2, prompt_len=16,
                            gen=min(cell.traffic["gen"], 6), check_rows=3,
                            check_block=2, prefill_rows=1)
    return cell


def tiny_model(cell, **over):
    """The cell's own architecture (its configuration's ``program.arch``)
    at the program's reduced size, with two layers and ``over``."""
    from repro.configs import get_config
    arch = cell.config["program"]["arch"]
    return dataclasses.replace(get_config(arch).reduced(),
                               **({"n_layers": 2} | over))


def on_the_cpu(monkeypatch, cell, cfg=None):
    """Skip the harness's look for a TPU (the CPU's devices stand in, with
    the v5e's peaks), and serve a tiny model of the cell's architecture in
    place of the cell's."""
    from chipbench import peaks
    devs = jax.devices()[:cell.chips]
    monkeypatch.setattr(run, "device_info", lambda chips: (devs, {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}))
    monkeypatch.setattr(peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    mod = harness.load_module("drivers", cell.config["program"]["driver"])
    if hasattr(mod, "program_config"):
        model = cfg if cfg is not None else tiny_model(cell)
        monkeypatch.setattr(mod, "program_config", lambda program: model)
    return mod


CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def run_tiny(name, monkeypatch, trace=False):
    cell = tiny_cell(name)
    on_the_cpu(monkeypatch, cell)
    return run.run_cell(cell, SEED, 1.0, trace)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_tiny_on_the_cpu(name, monkeypatch):
    res = run_tiny(name, monkeypatch)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = harness.cell_from(harness.load_benchmark(), name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    json.dumps(res)


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics_only(name, monkeypatch):
    res = run_tiny(name, monkeypatch, trace=True)
    cell = harness.cell_from(harness.load_benchmark(), name)
    allowed = {m["name"] for m in cell.per_layer}
    assert set(res["metrics"]) <= allowed
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_answers(monkeypatch):
    """Every batch's first answer is off by one in its first element,
    where the scheduler produces it."""
    from repro.sched.scheduler import Scheduler
    real = Scheduler._dispatch_batch

    def broken(self, batch):
        outs = list(real(self, batch))
        first = outs[0]
        if isinstance(first, (tuple, list)):
            first = type(first)([first[0].at[0].add(1)] + list(first[1:]))
        else:
            first = first.at[0].add(1)
        outs[0] = first
        return outs

    monkeypatch.setattr(Scheduler, "_dispatch_batch", broken)


def _state_unchanged(monkeypatch):
    """A decode step that returns its cache as it got it."""
    from repro.models import model as M
    real = M.decode_step

    def broken(cfg, params, cache, tokens, pos):
        logits, _ = real(cfg, params, cache, tokens, pos)
        return logits, cache

    monkeypatch.setattr(M, "decode_step", broken)


def _token_altered(monkeypatch):
    """Every row's token is one past the greedy choice, where it is
    sampled, so that whichever rows the check samples hold it."""
    from repro.launch import serve
    real = serve.sample

    def broken(logits, rng, temperature):
        return (real(logits, rng, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(serve, "sample", broken)


# the faults the timed path of each driver can have
DRIVER_FAULTS = {"sched_programs": ["answer_altered"],
                 "lm_serve": ["state_unchanged", "token_altered"]}


def driver_of(name):
    cell = harness.cell_from(harness.load_benchmark(), name)
    return cell.config["program"]["driver"]


FAULTS = [(name, fault) for name in CELLS
          for fault in DRIVER_FAULTS[driver_of(name)]]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_check_catches_a_broken_timed_path(name, fault, monkeypatch):
    {"answer_altered": _alter_answers, "state_unchanged": _state_unchanged,
     "token_altered": _token_altered}[fault](monkeypatch)
    res = run_tiny(name, monkeypatch)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("name", ["mamba2-1.3b.prefill",
                                  "mamba2-1.3b.decode"])
def test_tiny_model_is_the_cells_own_architecture(name):
    from repro.configs import get_config
    cell = tiny_cell(name)
    assert tiny_model(cell) == dataclasses.replace(
        get_config("mamba2-1.3b").reduced(), n_layers=2)
    cell.config["program"]["arch"] = "hymba-1.5b"
    model = tiny_model(cell, vocab=256)
    assert model.family == "hybrid"
    assert (model.n_layers, model.vocab) == (2, 256)


def test_faults_follow_the_cells_driver():
    today = {("stream-apps.bulk", "answer_altered"),
             ("mamba2-1.3b.prefill", "state_unchanged"),
             ("mamba2-1.3b.prefill", "token_altered"),
             ("mamba2-1.3b.decode", "state_unchanged"),
             ("mamba2-1.3b.decode", "token_altered")}
    names = {name for name, _ in today}
    assert {f for f in FAULTS if f[0] in names} == today


def test_decode_work_gets_the_mean_context_of_the_window(monkeypatch):
    cell = tiny_cell("mamba2-1.3b.decode")
    drv = on_the_cpu(monkeypatch, cell).Driver(cell, SEED, jax.devices()[:1])
    work = harness.load_module("work", cell.config["program"]["work"]["decode"])
    got = {}
    real_work = work.work

    def counting_work(**kw):
        got.update(kw)
        return real_work(**kw)

    monkeypatch.setattr(work, "work", counting_work)
    drv.setup(1.0)
    try:
        stepped = []
        real_step = drv._decode_one

        def step():
            stepped.append(drv.pos)
            real_step()

        drv._decode_one = step
        drv.window(1.0, harness.Spans(False))
    finally:
        drv.release()
    rec = drv.records
    assert len(stepped) == rec["decode_steps"] > 0
    assert rec["decode_context"] == pytest.approx(sum(stepped) / len(stepped))
    assert rec["decode_context"] >= cell.traffic["prompt_len"]
    assert got["context"] == rec["decode_context"]


def test_stream_control_in_bfloat16_fails_the_check(monkeypatch):
    import ml_dtypes
    cell = tiny_cell("stream-apps.bulk")
    drv = on_the_cpu(monkeypatch, cell).Driver(cell, SEED, jax.devices()[:1])
    drv.setup(1.0)
    drv.window(1.0, harness.Spans(False))
    got = drv.answers(control_dtype=ml_dtypes.bfloat16)
    limits = cell.config["check"]
    failed = [n for n, v in got.items()
              if n != "sort_mismatches" and v > limits[n]]
    assert failed, got


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        CELLS[0], "--seed", str(SEED), "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    bench = harness.load_benchmark()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_benchmark_name_has_its_files():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.load_module("layer_metrics", m["name"]).read)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        harness.load_module("reference", c["name"])
    for w in bench["workloads"]:
        cell = harness.cell_from(bench, w["name"])
        mod = harness.load_module("drivers", cell.config["program"]["driver"])
        for k in mod.kernels_of(cell.config):
            assert harness.load_module("work", k).TRACE


def test_mamba2_control_in_float8_fails_the_check(monkeypatch):
    """The control at a size a CPU test run holds: the served tokens of a
    16-layer, d_model 256 Mamba2 pass; float8 in the program's place does
    not (on the chip at the cell's size it reads 1.7–2.7, PERF.md)."""
    cell = tiny_cell("mamba2-1.3b.prefill")
    cell.traffic = dict(cell.traffic, batch=4, prompt_len=256, gen=16,
                        check_rows=4, check_block=4)
    cfg = tiny_model(cell, n_layers=16, d_model=256, ssm_state=32,
                     ssm_headdim=32, ssm_chunk=64, vocab=2048,
                     param_dtype="bfloat16", act_dtype="bfloat16")
    mod = on_the_cpu(monkeypatch, cell, cfg)
    drv = mod.Driver(cell, SEED, jax.devices()[:1])
    drv.setup(1.0)
    drv.window(1.0, harness.Spans(False))
    drv.release()
    limit = cell.config["check"]["logit_gap"]
    (program,) = drv.check()
    assert program.ok, program
    assert drv.control()["logit_gap"] > limit


def test_serving_set_up_refuses_a_model_that_is_not_the_configured_one():
    """The configuration's ``program.runs`` is checked against the program."""
    mod = harness.load_module("drivers", "lm_serve")
    cell = harness.cell_from(harness.load_benchmark(), "mamba2-1.3b.decode")
    program = copy.deepcopy(cell.config["program"])
    assert mod.program_config(program).n_layers == 48
    program["runs"]["n_layers"] = 24
    with pytest.raises(ValueError, match="n_layers"):
        mod.program_config(program)
