"""Mixture-of-Experts with sorting-network routing + prefix-sum dispatch.

This layer is where the paper's two showcase instructions live in a
modern LM (DESIGN.md §4):

  * c5_topk — per-token expert selection is a key/payload bitonic network
    (ONE multi-operand instruction vs. the min/max/shuffle zoo, §6);
  * c3_prefixsum — the position-in-expert slot of every token is an
    exclusive prefix sum over assignment masks, the paper's own cited
    database use-case (radix partitioning / parallel filtering [48]).

Three dispatch implementations:
  'dense' — every expert on every token (oracle for tests; tiny configs);
  'ep'    — expert parallelism: capacity-bucketed all_to_all over the
            `data` axis under shard_map (E % data_size == 0; kimi-k2);
  'tp'    — experts replicated, FFN dim TP-sharded (E < axis size; grok-1).

Production details: fixed per-expert capacity (token dropping, standard),
partial sums routed *back* through the reverse all_to_all before the
model-axis psum (collective on (t,d), not (E,cap,d) — a 10× saving, see
EXPERIMENTS.md §Perf), and a dispatch-microbatch knob that bounds buffer
memory.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current_mesh
from repro.kernels import ops as kops


def _route(cfg: ModelConfig, logits: jax.Array):
    """logits (t, E) fp32 → (gates (t,k) fp32, ids (t,k) int32, aux)."""
    vals, ids = kops.topk(logits, cfg.top_k)
    gates = jax.nn.softmax(vals, axis=-1)
    # load-balance aux (Switch-style): E · Σ_e f_e · p_e
    probs = jax.nn.softmax(logits, axis=-1)
    e = cfg.n_experts
    frac = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    return gates, ids, aux


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)


def _slots(cfg: ModelConfig, ids: jax.Array, cap: int):
    """Position-in-expert via exclusive prefix sum (c3_prefixsum)."""
    tk = ids.size
    flat = ids.reshape(tk)
    onehot = jax.nn.one_hot(flat, cfg.n_experts, dtype=jnp.float32)  # (tk,E)
    # scan along the token axis, one row per expert → our carried-scan op
    exc = kops.exclusive_prefix_sum(onehot.T).T                      # (tk,E)
    slot = jnp.take_along_axis(exc, flat[:, None], axis=1)[:, 0]
    slot = slot.astype(jnp.int32)
    valid = slot < cap
    dst = jnp.where(valid, flat * cap + slot, cfg.n_experts * cap)
    return dst  # (tk,) flat (expert, slot) index; overflow row = E*cap


def _expert_ffn(cfg: ModelConfig, recv: jax.Array, w: dict) -> jax.Array:
    """recv (E_loc, C, D) × local expert weights → PARTIAL (E_loc, C, D)
    (partial over the model axis: f is f_loc)."""
    h = jnp.einsum("ecd,edf->ecf", recv, w["w_in"])
    if cfg.mlp_gated:
        g = jnp.einsum("ecd,edf->ecf", recv, w["w_gate"])
        a = jax.nn.silu(g.astype(jnp.float32)).astype(recv.dtype) * h
    else:
        a = jax.nn.gelu(h.astype(jnp.float32)).astype(recv.dtype)
    return jnp.einsum("ecf,efd->ecd", a, w["w_out"])


def _moe_dense(cfg: ModelConfig, p: dict, x: jax.Array):
    """Oracle: compute every expert on every token (tiny configs only)."""
    b, s, d = x.shape
    toks = x.reshape(-1, d)
    logits = (toks @ p["router"]).astype(jnp.float32)
    gates, ids, aux = _route(cfg, logits)
    weights = jnp.zeros_like(logits).at[
        jnp.arange(toks.shape[0])[:, None], ids].set(gates)      # (t,E)
    h = jnp.einsum("td,edf->tef", toks, p["w_in"])
    if cfg.mlp_gated:
        g = jnp.einsum("td,edf->tef", toks, p["w_gate"])
        a = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    else:
        a = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("tef,efd->ted", a, p["w_out"])
    out = jnp.einsum("ted,te->td", y, weights.astype(x.dtype))
    return out.reshape(b, s, d), aux


def _dispatch_combine(cfg: ModelConfig, toks: jax.Array, p: dict,
                      ep_axis: str | None, tp_axis: str | None,
                      n_ep: int):
    """Shared EP/TP dispatch for one token block. toks: (t, D) local."""
    t, d = toks.shape
    logits = (toks @ p["router"]).astype(jnp.float32)
    gates, ids, aux = _route(cfg, logits)
    cap = _capacity(cfg, t)
    e = cfg.n_experts
    dst = _slots(cfg, ids, cap)

    rep = jnp.repeat(toks, cfg.top_k, axis=0)                     # (tk, D)
    send = jnp.zeros((e * cap + 1, d), toks.dtype).at[dst].add(rep)
    send = send[:e * cap]

    if ep_axis is not None:                                       # EP a2a
        recv = jax.lax.all_to_all(send.reshape(e * cap, d), ep_axis,
                                  split_axis=0, concat_axis=0, tiled=True)
        e_loc = e // n_ep
        recv = recv.reshape(n_ep, e_loc, cap, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(e_loc, n_ep * cap, d)
    else:                                                         # TP-local
        recv = send.reshape(e, cap, d)

    part = _expert_ffn(cfg, recv, p)                              # partial/f

    if ep_axis is not None:
        e_loc = e // n_ep
        back = part.reshape(e_loc, n_ep, cap, d).transpose(1, 0, 2, 3)
        back = back.reshape(e * cap, d)
        ret = jax.lax.all_to_all(back, ep_axis, split_axis=0,
                                 concat_axis=0, tiled=True)       # (E*cap, d)
    else:
        ret = part.reshape(e * cap, d)

    padded = jnp.concatenate([ret, jnp.zeros((1, d), ret.dtype)], axis=0)
    gathered = padded[dst].reshape(t, cfg.top_k, d)
    comb = jnp.sum(gathered.astype(jnp.float32)
                   * gates[..., None], axis=1)                    # (t, D)
    if tp_axis is not None:  # finish TP partial sums on the small tensor
        comb = jax.lax.psum(comb, tp_axis)
    return comb.astype(toks.dtype), aux


def _moe_sharded(cfg: ModelConfig, p: dict, x: jax.Array, mesh,
                 use_ep: bool):
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ep_axis = "data" if use_ep else None
    tp_axis = "model" if "model" in mesh.axis_names else None
    n_ep = mesh.shape["data"] if use_ep else 1

    wspecs = {
        "router": P(None, None),
        "w_in": P("data" if use_ep else None, None, "model"),
        "w_out": P("data" if use_ep else None, "model", None),
    }
    if cfg.mlp_gated:
        wspecs["w_gate"] = wspecs["w_in"]
    p = {k: p[k] for k in wspecs}  # drop anything extra

    def body(x_l, p_l):
        b_l, s, d = x_l.shape
        toks = x_l.reshape(-1, d)
        mb = cfg.dispatch_microbatch
        if mb > 1 and toks.shape[0] % mb == 0:
            # bound dispatch-buffer memory: scan over token sub-blocks
            def step(_, blk):
                out, aux = _dispatch_combine(cfg, blk, p_l, ep_axis,
                                             tp_axis, n_ep)
                return None, (out, aux)
            _, (outs, auxs) = jax.lax.scan(
                step, None, toks.reshape(mb, -1, d),
                unroll=mb if cfg.scan_unroll > 1 else 1)  # cost probes
            out, aux = outs.reshape(-1, d), jnp.mean(auxs)
        else:
            out, aux = _dispatch_combine(cfg, toks, p_l, ep_axis,
                                         tp_axis, n_ep)
        return out.reshape(b_l, s, d), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes if batch_axes else None, None, None), wspecs),
        out_specs=(P(batch_axes if batch_axes else None, None, None), P()),
        check_vma=False,
    )
    return fn(x, p)


def moe_layer(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (B, S, D) → (out (B,S,D), aux load-balance loss)."""
    mesh = current_mesh()
    if mesh is None or cfg.moe_impl == "dense":
        return _moe_dense(cfg, p, x)
    use_ep = (cfg.moe_impl == "ep"
              and "data" in mesh.axis_names
              and cfg.n_experts % mesh.shape["data"] == 0)
    return _moe_sharded(cfg, p, x, mesh, use_ep)
