"""Step builders + abstract input specs + shardings for every cell.

One place defines, for each (arch × shape):
  * the step function that gets lowered (train_step / prefill / serve_step)
  * ShapeDtypeStruct stand-ins for every input (no allocation)
  * NamedShardings from the logical-axis rules
This is what dryrun.py, train.py and serve.py all consume.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import tree_shardings, logical_sharding
from repro.models import model as M
from repro.models.params import abstract_params, logical_axes
from repro.optim import clip_by_global_norm, get_optimizer


# ---------------------------------------------------------------------------
# batches
# ---------------------------------------------------------------------------

def batch_abstract(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        if cfg.frontend != "none":
            return {"embeddings": jax.ShapeDtypeStruct(
                        (b, s, cfg.d_model), jnp.dtype(cfg.act_dtype)),
                    "targets": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                "targets": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if shape.kind == "prefill":
        if cfg.frontend != "none":
            return {"embeddings": jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), jnp.dtype(cfg.act_dtype))}
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def batch_logical(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    if shape.kind == "train":
        key = "embeddings" if cfg.frontend != "none" else "tokens"
        ax = {key: ("batch", None, "act_embed")[:3 if key == "embeddings"
                                                else 2],
              "targets": ("batch", None)}
        return ax
    if shape.kind == "prefill":
        key = "embeddings" if cfg.frontend != "none" else "tokens"
        return {key: ("batch", None, "act_embed")[:3 if key == "embeddings"
                                                  else 2]}
    return {"tokens": ("batch", None), "pos": ()}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_state_abstract(cfg: ModelConfig):
    opt = get_optimizer(cfg.optimizer, state_dtype=cfg.opt_state_dtype)
    params = abstract_params(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    return {"params": params, "opt": opt_state,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def train_state_logical(cfg: ModelConfig):
    opt = get_optimizer(cfg.optimizer, state_dtype=cfg.opt_state_dtype)
    pax = logical_axes(cfg)
    return {"params": pax, "opt": opt.state_logical_axes(pax), "step": ()}


def init_train_state(cfg: ModelConfig, rng):
    opt = get_optimizer(cfg.optimizer, state_dtype=cfg.opt_state_dtype)
    params = M.init_params(cfg, rng)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(cfg: ModelConfig, grad_accum: int = 0,
                    clip_norm: float = 1.0):
    grad_accum = grad_accum or cfg.grad_accum
    opt = get_optimizer(cfg.optimizer, state_dtype=cfg.opt_state_dtype)

    def loss(params, batch):
        return M.loss_fn(cfg, params, batch)

    def step(state, batch):
        if grad_accum > 1:
            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = jax.value_and_grad(loss, has_aux=True)(
                    state["params"], mb)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None
            mbs = jax.tree.map(
                lambda x: x.reshape((grad_accum, -1) + x.shape[1:]), batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (grads, l_sum), _ = jax.lax.scan(
                micro, (zero, 0.0), mbs,
                unroll=grad_accum if cfg.scan_unroll > 1 else 1)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            metrics = {"loss": l_sum / grad_accum}
        else:
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
                state["params"], batch)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        new_params, new_opt = opt.update(grads, state["opt"],
                                         state["params"], state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    return step


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_prefill(cfg: ModelConfig):
    def fn(params, batch):
        return M.prefill(cfg, params, batch)
    return fn


def make_serve_step(cfg: ModelConfig):
    def fn(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch["tokens"],
                             batch["pos"])
    return fn


# ---------------------------------------------------------------------------
# cell assembly: (fn, abstract args, in/out shardings)
# ---------------------------------------------------------------------------

def _rules(cfg: ModelConfig):
    """(param_rules, opt_rules): fsdp shards both over data; zero2 keeps
    params replicated (no per-layer gathers) but shards optimizer states
    (one u-gather per step — ZeRO-2); off replicates both over data."""
    if cfg.fsdp:
        return None, None
    if cfg.zero2:
        return {"embed": [None]}, None
    return {"embed": [None]}, {"embed": [None]}


def _shardings(tree_logical, tree_abstract, mesh, rules=None):
    return tree_shardings(tree_logical, tree_abstract, mesh, rules)


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               grad_accum: int = 0):
    """Returns (fn, args_abstract tuple, in_shardings, out_shardings,
    donate_argnums)."""
    pax = logical_axes(cfg)
    params_abs = abstract_params(cfg)
    batch_abs = batch_abstract(cfg, shape)
    batch_ax = batch_logical(cfg, shape)

    rules, opt_rules = _rules(cfg)
    if shape.kind == "train":
        state_abs = make_train_state_abstract(cfg)
        state_ax = train_state_logical(cfg)
        fn = make_train_step(cfg, grad_accum=grad_accum)
        state_sh = {
            "params": _shardings(state_ax["params"], state_abs["params"],
                                 mesh, rules),
            "opt": _shardings(state_ax["opt"], state_abs["opt"], mesh,
                              opt_rules),
            "step": _shardings(state_ax["step"], state_abs["step"], mesh),
        }
        in_sh = (state_sh,
                 _shardings(batch_ax, batch_abs, mesh, rules))
        out_sh = (in_sh[0], None)          # metrics unconstrained
        return fn, (state_abs, batch_abs), in_sh, out_sh, (0,)

    if shape.kind == "prefill":
        fn = make_prefill(cfg)
        cache_abs = M.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cache_sh = _shardings(M.cache_logical_axes(cfg), cache_abs, mesh)
        in_sh = (_shardings(pax, params_abs, mesh, rules),
                 _shardings(batch_ax, batch_abs, mesh, rules))
        out_sh = (None, cache_sh)
        return fn, (params_abs, batch_abs), in_sh, out_sh, ()

    # decode
    fn = make_serve_step(cfg)
    cache_abs = M.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cache_sh = _shardings(M.cache_logical_axes(cfg), cache_abs, mesh)
    in_sh = (_shardings(pax, params_abs, mesh, rules), cache_sh,
             _shardings(batch_ax, batch_abs, mesh, rules))
    out_sh = (None, cache_sh)
    return fn, (params_abs, cache_abs, batch_abs), in_sh, out_sh, (1,)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, **kw):
    fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh, **kw)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        return jitted.lower(*args)
