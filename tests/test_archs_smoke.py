"""Per-arch smoke: reduced config, one forward/train step, shapes + no NaNs."""
import dataclasses
import functools

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


@pytest.mark.parametrize("arch,prefill_len,steps", [
    ("llama3_8b", S, 4), ("granite_20b", S, 4), ("qwen3_14b", S, 4),
    ("mamba2_1p3b", S, 4), ("falcon_h1_34b", S, 5),
    # SWA window 32: the first step writes slot 0 (the wrap) and drops
    # position 0; a 48-token prefill comes back rolled by grow_cache
    ("hymba_1p5b", S, 6), ("hymba_1p5b", S + 16, 6)])
def test_multi_step_decode_matches_forward(arch, prefill_len, steps):
    """Every step of a decode from a prefill cache gives the logits the
    full forward gives at that position: the step's own key and value
    count once, the cache's filled slots count, and under SWA the slot a
    step overwrites no longer counts."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, RNG)
    n = prefill_len + steps
    toks = jax.random.randint(RNG, (B, n), 0, cfg.vocab)
    _, cache = M.prefill(cfg, params, {"tokens": toks[:, :prefill_len]})
    cache = M.grow_cache(cfg, cache, prefill_len, n)
    x, _ = M.forward(cfg, params, {"tokens": toks}, train=False)
    want = M.logits(cfg, M._unembed_w(cfg, params), x)
    step = jax.jit(functools.partial(M.decode_step, cfg))
    for pos in range(prefill_len, n):
        got, cache = step(params, cache, toks[:, pos:pos + 1],
                          jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want[:, pos]),
                                   rtol=2e-4, atol=2e-4, err_msg=str(pos))


@pytest.mark.parametrize("arch,prefill_len", [
    ("falcon_h1_34b", S), ("hymba_1p5b", S + 16), ("llama3_8b", S)])
def test_kv_cache_layout_agrees(arch, prefill_len):
    """abstract_cache, prefill's cache, grow_cache and cache_logical_axes
    agree on (L, B, KV, T, hd); growing pads (or, under SWA, rolls) the
    positions axis and leaves the SSM entries as they were."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, RNG)
    capacity = prefill_len + 8
    toks = jax.random.randint(RNG, (B, prefill_len), 0, cfg.vocab)
    _, cache = M.prefill(cfg, params, {"tokens": toks})
    grown = M.grow_cache(cfg, cache, prefill_len, capacity)
    want = M.abstract_cache(cfg, B, capacity)
    t = cfg.swa_window or capacity
    kv = (cfg.n_layers, B, cfg.n_kv_heads, t, cfg.head_dim)
    assert want["k"].shape == want["v"].shape == kv
    assert jax.tree.map(lambda a: (a.shape, a.dtype), grown) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    axes = M.cache_logical_axes(cfg)
    assert axes["k"] == axes["v"] == (
        "layers", "batch", "kv_heads", "cache_seq", "head_dim")
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(want)
    for n in ("k", "v"):
        c, g = np.asarray(cache[n]), np.asarray(grown[n])
        if cfg.swa_window:
            # prefill keeps the window's last t positions in order; after
            # growing, position p sits at slot p % t
            first = prefill_len - t
            for p in range(first, prefill_len):
                np.testing.assert_array_equal(g[:, :, :, p % t],
                                              c[:, :, :, p - first])
        else:
            np.testing.assert_array_equal(g[:, :, :, :prefill_len], c)
            assert not g[:, :, :, prefill_len:].any()
    for n in set(cache) - {"k", "v"}:
        for a, b in zip(jax.tree.leaves(cache[n]), jax.tree.leaves(grown[n])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
