"""End-to-end training driver (fault-tolerant).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --reduced --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ck

Features: mesh scaled to available devices (elastic), sharded train
state, synthetic or file-backed data, async checkpointing + preemption
handler, resume-from-latest (on ANY divisor mesh), optional compressed
cross-pod parameter sync (DiLoCo-style outer step, see
distributed/collectives.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager, restore_sharded
from repro.checkpoint.ckpt import latest_step
from repro.configs import SHAPES, get_config
from repro.data import SyntheticLMData, TokenFileData, make_global_batch
from repro.distributed.collectives import compressed_ring_allreduce
from repro.distributed.sharding import tree_shardings
from repro.launch import api
from repro.launch.mesh import make_elastic_mesh, mesh_name


def make_pod_sync(mesh):
    """Compressed cross-pod parameter averaging (outer sync step)."""
    if "pod" not in mesh.axis_names:
        return None
    n_pods = mesh.shape["pod"]

    def avg(p):
        def one(x):
            s = compressed_ring_allreduce(x.astype(jnp.float32), "pod")
            return (s / n_pods).astype(x.dtype)
        return jax.tree.map(one, p)

    spec = P()  # params replicated over pod in-spec handled per-leaf below

    def sync(params):
        return jax.shard_map(
            avg, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: spec, params),),
            out_specs=jax.tree.map(lambda _: spec, params),
            check_vma=False)(params)

    return jax.jit(sync)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3-8b")
    p.add_argument("--reduced", action="store_true",
                   help="tiny same-family config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--data", default=None, help="token .bin file (else synthetic)")
    p.add_argument("--pod-sync-every", type=int, default=0,
                   help=">0: DiLoCo-style compressed cross-pod parameter "
                        "averaging every N steps (needs a 'pod' mesh axis)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, attn_impl="chunked")

    mesh = make_elastic_mesh(model_parallel=args.model_parallel)
    print(f"mesh {mesh_name(mesh)} axes {mesh.axis_names} "
          f"({mesh.devices.size} devices)")

    shape = dataclasses.replace(
        SHAPES["train_4k"], seq_len=args.seq, global_batch=args.batch)

    state_abs = api.make_train_state_abstract(cfg)
    state_ax = api.train_state_logical(cfg)
    state_sh = tree_shardings(state_ax, state_abs, mesh)
    batch_abs = api.batch_abstract(cfg, shape)
    batch_sh = tree_shardings(api.batch_logical(cfg, shape), batch_abs, mesh)

    step_fn = api.make_train_step(cfg, grad_accum=args.grad_accum)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=0)

        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            state, manifest = restore_sharded(args.ckpt_dir, state_abs,
                                              state_sh)
            start = manifest["step"]
            print(f"resumed from step {start} on mesh {mesh_name(mesh)}")
        else:
            state = jax.jit(
                lambda r: api.init_train_state(cfg, r),
                out_shardings=state_sh)(jax.random.PRNGKey(args.seed))

        if args.data:
            data = TokenFileData(args.data, shape.seq_len,
                                 shape.global_batch, args.seed)
        else:
            data = SyntheticLMData(cfg.vocab, shape.seq_len,
                                   shape.global_batch, args.seed)

        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        if mgr:
            mgr.install_preemption_handler()
        pod_sync = (make_pod_sync(mesh)
                    if args.pod_sync_every > 0 else None)

        t0 = time.time()
        tokens_per_step = shape.tokens
        for step in range(start, args.steps):
            batch = make_global_batch(data.host_batch(step), batch_sh)
            state, metrics = jitted(state, batch)
            if mgr:
                mgr.observe(step + 1, state)
            if (step + 1) % args.log_every == 0:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                tps = tokens_per_step * args.log_every / dt
                print(f"step {step+1:6d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"{tps:9.0f} tok/s")
                t0 = time.time()
            if pod_sync and (step + 1) % args.pod_sync_every == 0:
                state["params"] = pod_sync(state["params"])
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save_async(step + 1, state)
        if mgr:
            mgr.save_async(args.steps, state)
            mgr.wait()
        final = float(metrics["loss"])
        print(f"done: final loss {final:.4f}")
        return final


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
