"""End-to-end behaviour: training drivers, serving, dry-run machinery."""
import contextlib
import io
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_training_loss_decreases():
    """~200 steps of a reduced model on synthetic data: loss must drop."""
    from repro.launch import train
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        final = train.main(["--arch", "llama3-8b", "--reduced",
                            "--steps", "200", "--batch", "8",
                            "--seq", "128", "--log-every", "20"])
    lines = [ln for ln in buf.getvalue().splitlines() if ln.startswith("step")]
    losses = [float(ln.split()[3]) for ln in lines]
    assert losses[-1] < losses[0] - 0.1, losses
    assert np.isfinite(final)


def test_train_resume_continues(tmp_path):
    from repro.launch import train
    with contextlib.redirect_stdout(io.StringIO()):
        train.main(["--arch", "mamba2-1.3b", "--reduced", "--steps", "6",
                    "--batch", "4", "--seq", "64", "--ckpt-dir",
                    str(tmp_path), "--ckpt-every", "3", "--log-every", "3"])
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        train.main(["--arch", "mamba2-1.3b", "--reduced", "--steps", "9",
                    "--batch", "4", "--seq", "64", "--ckpt-dir",
                    str(tmp_path), "--ckpt-every", "3", "--log-every", "3"])
    assert "resumed from step 6" in buf.getvalue()


def test_serve_driver_generates():
    from repro.launch import serve
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        gen = serve.main(["--arch", "hymba-1.5b", "--reduced",
                          "--batch", "2", "--prompt-len", "32",
                          "--gen", "8"])
    assert gen.tokens.shape == (2, 8)
    assert gen.prefill_logits.shape == (2, 512)


def test_greedy_decode_is_deterministic():
    from repro.launch import serve
    outs = []
    for _ in range(2):
        with contextlib.redirect_stdout(io.StringIO()):
            outs.append(serve.main(["--arch", "llama3-8b", "--reduced",
                                    "--batch", "1", "--prompt-len", "16",
                                    "--gen", "6"]).tokens)
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    """One real dry-run cell end-to-end in a fresh 512-device process."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "musicgen-medium", "--shape", "decode_32k", "--outdir",
         str(tmp_path)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": SRC},
        cwd=os.path.dirname(SRC))
    assert out.returncode == 0, out.stdout + out.stderr
    files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(files) == 1
    rep = json.load(open(os.path.join(tmp_path, files[0])))
    assert rep["n_chips"] == 256
    assert rep["terms"]["dominant"] in ("compute_s", "memory_s",
                                        "collective_s")
    assert rep["flops_per_chip"] > 0


def test_long500k_skips_full_attention():
    from repro.configs import SHAPES, cell_applicable, get_config
    ok, why = cell_applicable(get_config("llama3_8b"), SHAPES["long_500k"])
    assert not ok and "quadratic" in why
    ok, _ = cell_applicable(get_config("mamba2_1p3b"), SHAPES["long_500k"])
    assert ok
    ok, _ = cell_applicable(get_config("hymba_1p5b"), SHAPES["long_500k"])
    assert ok


def test_collective_parser():
    from repro.roofline.analysis import collective_bytes
    hlo = """
  %ar = f32[1024]{0} all-reduce(%x), replica_groups=[16,16]<=[256]
  %ag = bf16[64,128]{1,0} all-gather(%y), replica_groups=[16,16]<=[256]
  %done = f32[8] all-reduce-done(%z)
  %tup = (f32[256]{0}, f32[256]{0}) all-reduce(%a, %b), replica_groups=[1,4]<=[4]
"""
    got = collective_bytes(hlo)
    assert got["counts"]["all-reduce"] == 2
    assert got["counts"]["all-gather"] == 1
    ar1 = 1024 * 4 * 2 * 15 / 16
    ag = 64 * 128 * 2 * 15 / 16
    ar2 = 2 * 256 * 4 * 2 * 3 / 4
    assert abs(got["total"] - (ar1 + ag + ar2)) < 1e-6


def test_roofline_terms():
    from repro.roofline.analysis import roofline_terms
    t = roofline_terms(197e12, 819e9 * 2, 0.0)
    assert t["dominant"] == "memory_s"
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["roofline_fraction"] == pytest.approx(0.5)
