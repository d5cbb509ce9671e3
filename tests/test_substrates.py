"""Optimizers, checkpointing, data pipeline, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, load_checkpoint,
                              save_checkpoint)
from repro.checkpoint.ckpt import latest_step
from repro.data import SyntheticLMData
from repro.distributed.sharding import logical_spec, shard_fit
from repro.optim import Adafactor, AdamW, clip_by_global_norm, warmup_cosine


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _quadratic_converges(opt, steps=400):
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3), "m": jnp.zeros((2, 3))}

    def loss(p):
        return (jnp.sum((p["w"] - target) ** 2)
                + jnp.sum((p["m"] - 1.0) ** 2))

    state = opt.init(params)
    for step in range(steps):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params, step)
    return float(loss(params))


def test_adamw_converges():
    assert _quadratic_converges(AdamW(lr=5e-2, weight_decay=0.0)) < 1e-3


def test_adafactor_converges():
    assert _quadratic_converges(Adafactor(lr=5e-2)) < 1e-2


def test_adafactor_state_is_factored():
    p = {"w": jnp.zeros((64, 32))}
    st = Adafactor().init(p)
    assert st["f"]["w"]["vr"].shape == (64,)
    assert st["f"]["w"]["vc"].shape == (32,)


def test_state_logical_axes_follow_params():
    ax = {"w": ("embed", "ffn")}
    assert AdamW().state_logical_axes(ax) == {"m": ax, "v": ax}
    f = Adafactor().state_logical_axes(ax)["f"]["w"]
    assert f["vr"] == ("embed",) and f["vc"] == ("ffn",)


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == 20.0
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(clipped["a"])), 1.0, rtol=1e-5)


def test_warmup_cosine_shape():
    lr = warmup_cosine(1.0, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1.0)
    assert float(lr(100)) == pytest.approx(0.1, rel=1e-2)
    assert float(lr(55)) < float(lr(20))


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.bfloat16)}}
    save_checkpoint(str(tmp_path), 7, tree, extra={"note": "x"})
    got, manifest = load_checkpoint(str(tmp_path), template=tree)
    assert manifest["step"] == 7 and manifest["extra"]["note"] == "x"
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(6).reshape(2, 3))
    assert got["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_gc_keeps_last(tmp_path):
    tree = {"x": jnp.zeros(2)}
    for s in range(5):
        save_checkpoint(str(tmp_path), s, tree, keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_00000003", "step_00000004"]
    assert latest_step(str(tmp_path)) == 4


def test_async_manager(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_async(3, {"x": jnp.arange(4)})
    mgr.wait()
    got, m = load_checkpoint(str(tmp_path))
    assert m["step"] == 3


def test_preemption_handler_saves(tmp_path):
    import signal
    mgr = CheckpointManager(str(tmp_path))
    mgr.install_preemption_handler()
    mgr.observe(11, {"x": jnp.arange(3)})
    os.kill(os.getpid(), signal.SIGTERM)
    assert latest_step(str(tmp_path)) == 11


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_batches_differ_by_step():
    d = SyntheticLMData(vocab=100, seq_len=8, global_batch=4)
    assert not np.array_equal(d.host_batch(0)["tokens"],
                              d.host_batch(1)["tokens"])


def test_token_file_data(tmp_path):
    from repro.data import TokenFileData
    path = str(tmp_path / "toks.bin")
    np.arange(10_000, dtype=np.int32).tofile(path)
    d = TokenFileData(path, seq_len=16, global_batch=4)
    b = d.host_batch(3)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


# ---------------------------------------------------------------------------
# sharding rules (no mesh needed beyond 1 device: test the solver logic)
# ---------------------------------------------------------------------------

# the two-pod production mesh, without devices
FakeMesh = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_shard_fit_picks_first_divisible():
    assert shard_fit(256, [("pod", "data"), ("data",), None],
                     FakeMesh, set()) == ("pod", "data")
    assert shard_fit(16, [("pod", "data"), ("data",), None],
                     FakeMesh, set()) == ("data",)
    assert shard_fit(7, [("pod", "data"), ("data",), None],
                     FakeMesh, set()) is None


def test_shard_fit_respects_used_axes():
    assert shard_fit(256, [("model",), None], FakeMesh, {"model"}) is None


def test_logical_spec_no_axis_reuse():
    # q_heads takes model; kv_heads must then fall to replicated
    spec = logical_spec(("embed", "q_heads", "kv_heads"),
                        (4096, 32, 16), FakeMesh)
    assert spec == jax.sharding.PartitionSpec("data", "model", None)


def test_logical_spec_head_fallback():
    # 40 heads % 16 != 0 → replicated (the qwen3 CP case)
    spec = logical_spec(("embed", "q_heads", "head_dim"),
                        (5120, 40, 128), FakeMesh)
    assert spec == jax.sharding.PartitionSpec("data", None, None)
