"""What the chip path rests on, checked on the CPU: mesh construction,
the dispatch default, the compile-cache location, and chip_smoke.py's
refusal to run off the chip plus its phases at a tiny size in interpret
mode."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

import repro.kernels  # noqa: F401 — registers the instruction set
from repro.core import isa
from repro.distributed.sharding import current_mesh
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.mesh import make_elastic_mesh, make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def test_mesh_helper_builds_auto_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    elastic = make_elastic_mesh(n_devices=1, model_parallel=4)
    assert dict(elastic.shape) == {"data": 1, "model": 1}
    assert set(elastic.axis_types) == {AxisType.Auto}


def test_current_mesh_follows_set_mesh():
    assert current_mesh() is None
    mesh = make_mesh((1,), ("parts",))
    with jax.set_mesh(mesh):
        assert current_mesh().axis_names == ("parts",)
        seen = []
        jax.jit(lambda x: seen.append(current_mesh()) or x)(1.0)
        assert seen[0].axis_names == ("parts",)
    assert current_mesh() is None


def test_dispatch_default_resolves_to_ref_on_cpu():
    assert isa.current_mode() == "auto"
    assert isa.resolve_auto(isa.current_mode()) == "ref"
    counts = isa.registry.dispatch_counts
    before = counts[("c3_prefixsum", "ref")]
    repro.kernels.ops.prefix_sum(jax.numpy.ones((8, 128)))
    assert counts[("c3_prefixsum", "ref")] == before + 1


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == os.path.join(ROOT,
                                                             ".jax_cache")


def _run_smoke(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    out = _run_smoke(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run_smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


SERVE_TINY = ["--arch", "mamba2-1.3b", "--reduced", "--batch", "2",
              "--prompt-len", "64", "--gen", "4"]

PHASES = {
    "stream": lambda: chip_smoke.phase_stream(4096, "interpret"),
    "sort": lambda: chip_smoke.phase_sort(8192, "interpret",
                                          merge_width=256),
    "prefix": lambda: chip_smoke.phase_prefix(8192, "interpret"),
    "statescan": lambda: chip_smoke.phase_statescan(
        [(2, 2, 4, 8, 16), (1, 8, 4, 8, 16)], "interpret"),
    "fused": lambda: chip_smoke.phase_fused(4096, "interpret"),
    "graphs": lambda: chip_smoke.phase_graphs(4096, "interpret"),
    "scheduler": lambda: chip_smoke.phase_scheduler(4096, "interpret"),
    "serve": lambda: chip_smoke.phase_serve(SERVE_TINY, "interpret"),
    "sharded_serve": lambda: chip_smoke.phase_sharded_serve(
        SERVE_TINY, "interpret", 1),
    "sharded_programs": lambda: chip_smoke.phase_sharded_programs(
        4096, "interpret", 1),
}


@pytest.mark.parametrize("name", sorted(PHASES))
def test_chip_smoke_phase_tiny_interpret(name, capsys):
    rec = PHASES[name]()
    assert rec["dispatches"] > 0
    assert rec["max_err"] <= rec.get("tol", rec.get("rtol", 1e-6))
    assert '"phase"' in capsys.readouterr().out


def test_chip_smoke_phase_fails_on_wrong_answer(monkeypatch):
    """A phase whose kernel disagrees with the oracle raises — nothing
    catches it to carry on."""
    monkeypatch.setattr(chip_smoke.ops, "prefix_sum",
                        lambda x, mode=None: x * (2.0 if mode != "ref"
                                                  else 1.0))
    with pytest.raises(chip_smoke.PhaseFailed):
        chip_smoke.phase_prefix(1024, "interpret")


GRAD_CASES = {
    "c4_statescan": (lambda x, m: ops.chunk_scan_state(
        jnp.full((2, 2, 4), 0.9), x, axis=1, mode=m), (2, 2, 4, 8, 16)),
    "c5_topk": (lambda x, m: ops.topk(x, 2, mode=m)[0], (16, 8)),
    "c3_prefixsum": (lambda x, m: ops.prefix_sum(x, mode=m), (8, 256)),
}


@pytest.mark.parametrize("name", sorted(GRAD_CASES))
def test_kernel_dispatch_differentiates_through_oracle(name):
    """With the kernel dispatched by default on the chip, training still
    has gradients: the backward pass differentiates the oracle."""
    fn, shape = GRAD_CASES[name]
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    grad = {m: jax.grad(lambda v: jnp.sum(fn(v, m)))(x)
            for m in ("interpret", "ref")}
    np.testing.assert_allclose(grad["interpret"], grad["ref"], rtol=1e-6)
