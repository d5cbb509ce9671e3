"""Per-arch smoke: reduced config, one forward/train step, shapes + no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch import api
from repro.models import model as M

RNG = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch(cfg, with_targets=True):
    b = {}
    if cfg.frontend != "none":
        b["embeddings"] = jax.random.normal(RNG, (B, S, cfg.d_model),
                                            jnp.float32)
    else:
        b["tokens"] = jax.random.randint(RNG, (B, S), 0, cfg.vocab)
    if with_targets:
        b["targets"] = jax.random.randint(RNG, (B, S), 0, cfg.vocab)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, RNG)
    x, aux = M.forward(cfg, params, _batch(cfg, False), train=False)
    assert x.shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    state = api.init_train_state(cfg, RNG)
    step = jax.jit(api.make_train_step(cfg))
    mid_state, metrics = step(state, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # step 0 has lr=0 (warmup); params must move on step 1
    new_state, metrics = step(mid_state, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(new_state["step"]) == 2
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        mid_state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch):
    cfg = get_config(arch).reduced()
    if cfg.frontend != "none":
        cfg = dataclasses.replace(cfg, frontend="none")
    params = M.init_params(cfg, RNG)
    cache = M.init_cache(cfg, B, S)
    logits, new_cache = M.decode_step(cfg, params, cache,
                                      jnp.zeros((B, 1), jnp.int32),
                                      jnp.int32(0))
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # cache structurally unchanged
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ["llama3_8b", "granite_20b", "qwen3_14b",
                                  "mamba2_1p3b", "hymba_1p5b", "kimi_k2_1t",
                                  "musicgen_medium", "falcon_h1_34b"])
def test_prefill_decode_consistency(arch):
    """Decode from a prefill cache == full forward (the serving invariant)."""
    cfg = get_config(arch).reduced()
    if cfg.frontend != "none":
        cfg = dataclasses.replace(cfg, frontend="none")
    params = M.init_params(cfg, RNG)
    toks = jax.random.randint(RNG, (B, S + 1), 0, cfg.vocab)
    x, _ = M.forward(cfg, params, {"tokens": toks}, train=False)
    want = M.logits(cfg, M._unembed_w(cfg, params), x[:, -1])
    _, cache = M.prefill(cfg, params, {"tokens": toks[:, :S]})
    cache = M.grow_cache(cfg, cache, S, S + 4)
    got, _ = M.decode_step(cfg, params, cache, toks[:, S:S + 1],
                           jnp.int32(S))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_published(arch):
    """Full configs land on their published parameter counts (±7%)."""
    published = {
        "internlm2_20b": 19.9e9, "llama3_8b": 8.0e9, "granite_20b": 20.1e9,
        "qwen3_14b": 14.8e9, "mamba2_1p3b": 1.35e9, "internvl2_76b": 70e9,
        "kimi_k2_1t": 1.03e12, "grok1_314b": 314e9,
        "musicgen_medium": 1.4e9, "hymba_1p5b": 1.52e9,
        "falcon_h1_34b": 33.6e9,
    }
    n = get_config(arch).n_params()
    assert abs(n - published[arch]) / published[arch] < 0.07, n


def test_moe_active_params():
    kimi = get_config("kimi_k2_1t")
    assert 28e9 < kimi.n_active_params() < 36e9   # "a32b"
    grok = get_config("grok1_314b")
    assert 75e9 < grok.n_active_params() < 95e9
