"""Multi-device semantics (8 fake CPU devices via subprocess isolation):
compressed collectives, GPipe pipeline, MoE EP parity, elastic restore,
sharded train-step parity with single-device."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(body: str, n: int = 8) -> str:
    """Run `body` in a subprocess with n fake devices; body must print OK."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path.insert(0, {SRC!r})
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    assert "OK" in out.stdout, out.stdout
    return out.stdout


def test_compressed_ring_allreduce_matches_psum():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_ring_allreduce
        mesh = make_mesh((8,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4096)),
                        jnp.float32)
        def body(xl):
            return compressed_ring_allreduce(xl, "d"), jax.lax.psum(xl, "d")
        got, want = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P("d")),
            check_vma=False))(x)
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        # per-hop int8 error bound: ~n_hops × absmax/254
        assert err / scale < 8 / 127, (err, scale)
        print("OK", err / scale)
    """)


def test_error_feedback_reduces_bias():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.collectives import (ErrorFeedback,
            quantize_blockwise, dequantize_blockwise, _pad_to)
        rng = np.random.default_rng(1)
        g_true = jnp.asarray(rng.standard_normal(512), jnp.float32) * 1e-3
        # identical tiny gradient each step: EF must recover the mean
        def lossy(g):
            q, s = quantize_blockwise(_pad_to(g, 256)[0])
            return dequantize_blockwise(q, s)[:g.size]
        ef = ErrorFeedback.init({"g": g_true})
        acc_ef = jnp.zeros_like(g_true)
        acc_naive = jnp.zeros_like(g_true)
        for _ in range(64):
            sent, ef = ef.apply({"g": g_true}, lambda x: x)
            acc_ef = acc_ef + sent["g"]
            acc_naive = acc_naive + lossy(g_true)
        err_ef = float(jnp.mean(jnp.abs(acc_ef / 64 - g_true)))
        err_naive = float(jnp.mean(jnp.abs(acc_naive / 64 - g_true)))
        assert err_ef < err_naive * 0.5 or err_naive == 0.0, (err_ef, err_naive)
        print("OK", err_ef, err_naive)
    """)


def test_gpipe_forward_matches_sequential():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.pipeline import gpipe_forward
        mesh = make_mesh((4,), ("stage",))
        S, M, D = 4, 6, 16
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((S, D, D)) / np.sqrt(D),
                        jnp.float32)
        mbs = jnp.asarray(rng.standard_normal((M, 2, D)), jnp.float32)
        def stage(wl, x):
            return jnp.tanh(x @ wl[0])
        def run(w_all, mbs):
            out = gpipe_forward(stage, w_all, mbs, "stage", S)
            return jax.lax.psum(out, "stage")  # valid only on last stage
        got = jax.jit(jax.shard_map(run, mesh=mesh,
            in_specs=(P("stage"), P()), out_specs=P(),
            check_vma=False))(w, mbs)
        want = mbs
        for s in range(S):
            want = jnp.tanh(want @ w[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_moe_ep_matches_dense_oracle():
    run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import moe
        from repro.models.params import init_params
        cfg = dataclasses.replace(
            get_config("kimi_k2_1t").reduced(),
            n_experts=8, top_k=2, capacity_factor=8.0)  # no drops
        mesh = make_mesh((4, 2), ("data", "model"))
        rng = jax.random.PRNGKey(0)
        p = init_params(cfg, rng)["layers"]["moe"]
        p = jax.tree.map(lambda x: x[0], p)  # one layer
        x = jax.random.normal(rng, (4, 8, cfg.d_model), jnp.float32)
        dense_out, aux_d = moe._moe_dense(cfg, p, x)
        with jax.set_mesh(mesh):
            ep_out, aux_e = jax.jit(
                lambda xx: moe._moe_sharded(cfg, p, xx, mesh, use_ep=True))(x)
        np.testing.assert_allclose(np.asarray(ep_out),
                                   np.asarray(dense_out),
                                   rtol=2e-3, atol=2e-3)
        with jax.set_mesh(mesh):
            tp_out, _ = jax.jit(
                lambda xx: moe._moe_sharded(cfg, p, xx, mesh, use_ep=False))(x)
        np.testing.assert_allclose(np.asarray(tp_out),
                                   np.asarray(dense_out),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch import api
        from repro.distributed.sharding import tree_shardings
        cfg = get_config("llama3_8b").reduced()
        state = api.init_train_state(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 32), 0, cfg.vocab),
                 "targets": jax.random.randint(jax.random.PRNGKey(2),
                                               (8, 32), 0, cfg.vocab)}
        step = api.make_train_step(cfg)
        _, m1 = jax.jit(step)(jax.tree.map(jnp.copy, state),
                              jax.tree.map(jnp.copy, batch))
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.configs.base import SHAPES
        shp = dataclasses.replace(SHAPES["train_4k"], seq_len=32,
                                  global_batch=8)
        st_sh = tree_shardings(api.train_state_logical(cfg),
                               jax.eval_shape(lambda: state), mesh)
        b_sh = tree_shardings(api.batch_logical(cfg, shp),
                              jax.eval_shape(lambda: batch), mesh)
        with jax.set_mesh(mesh):
            _, m2 = jax.jit(step, in_shardings=(st_sh, b_sh),
                            out_shardings=(st_sh, None))(state, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-4)
        print("OK", float(m1["loss"]), float(m2["loss"]))
    """)


def test_elastic_checkpoint_restore_other_mesh(tmp_path):
    run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_sharded
        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        mesh8 = make_mesh((8,), ("data",))
        placed = jax.device_put(tree, NamedSharding(mesh8, P("data")))
        save_checkpoint({str(tmp_path)!r}, 5, placed)
        # restore onto a DIFFERENT mesh (4×2)
        mesh42 = make_mesh((4, 2), ("data", "model"))
        sh = {{"w": NamedSharding(mesh42, P("data", "model"))}}
        tmpl = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
        got, m = restore_sharded({str(tmp_path)!r}, tmpl, sh)
        assert m["step"] == 5
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.arange(64).reshape(8, 8))
        print("OK")
    """)


def test_pod_sync_averages_params():
    """DiLoCo-style compressed pod sync: params converge to the pod mean."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.train import make_pod_sync
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        sync = make_pod_sync(mesh)
        # per-pod divergent params (replicated within pod by construction)
        with jax.set_mesh(mesh):
            p = {"w": jnp.ones((4, 256), jnp.float32)}
            out = sync(p)
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0, rtol=1e-2)
        print("OK")
    """)


def test_elastic_train_resume_smaller_mesh(tmp_path):
    """Train on a 4x2 mesh, checkpoint, resume on 2x2 — elastic re-mesh."""
    run_with_devices(f"""
        import contextlib, io
        from repro.launch import train
        with contextlib.redirect_stdout(io.StringIO()):
            train.main(["--arch", "llama3-8b", "--reduced", "--steps", "4",
                        "--batch", "8", "--seq", "64", "--model-parallel",
                        "2", "--ckpt-dir", {str(tmp_path)!r},
                        "--ckpt-every", "2", "--log-every", "2"])
        print("OK phase1 done")
    """, n=8)
    run_with_devices(f"""
        import contextlib, io
        from repro.launch import train
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            train.main(["--arch", "llama3-8b", "--reduced", "--steps", "6",
                        "--batch", "8", "--seq", "64", "--model-parallel",
                        "2", "--ckpt-dir", {str(tmp_path)!r},
                        "--ckpt-every", "2", "--log-every", "2"])
        assert "resumed from step 4" in buf.getvalue(), buf.getvalue()
        print("OK resumed on smaller mesh")
    """, n=4)


def test_chip_smoke_four_device_phases():
    """chip_smoke.py --chips 4's phases on four virtual devices, in
    interpret mode: Mamba2 served on a (1, 4) mesh and a 4-lane
    sharded_program_call, each against device 0."""
    root = os.path.dirname(SRC)
    run_with_devices(f"""
        import sys; sys.path.insert(0, {root!r})
        import chip_smoke as cs
        argv = ["--arch", "mamba2-1.3b", "--reduced", "--batch", "2",
                "--prompt-len", "64", "--gen", "4"]
        rec = cs.phase_sharded_serve(argv, "interpret", 4)
        assert rec["max_err"] <= cs.LOGIT_TOL_BF16, rec
        assert rec["max_err_f32"] <= cs.LOGIT_TOL_F32, rec
        rec = cs.phase_sharded_programs(4096, "interpret", 4)
        assert rec["max_err"] == 0.0, rec
        print("OK")
    """, n=4)
