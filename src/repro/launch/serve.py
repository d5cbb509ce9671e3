"""Batched serving driver: prefill a prompt batch, then decode tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \
        --reduced --batch 4 --prompt-len 64 --gen 32

With ``--sched`` the decode steps are driven through the
:mod:`repro.sched` predictive scheduling runtime (DESIGN.md §13): each
step is submitted to the request queue with a per-token latency deadline
(``--slo-ms``), executed by the cost-driven scheduler on the wall clock,
and its observed time fed back to the EWMA cost model — so later steps
are predicted from the machine's actual behaviour, deadline misses are
reported, and ``--sched-trace`` records the whole run as a replayable
JSONL trace (``python -m repro.sched.replay`` it offline to compare
policies on the production arrival sequence).

Observability (DESIGN.md §15): ``--metrics PORT`` serves the process
metrics registry over HTTP — Prometheus text at ``/metrics``, JSON
snapshot at ``/metrics.json`` — for the whole run (``--metrics-hold``
keeps the process alive afterwards so external scrapers can fetch a
final state; CI's smoke step curls it). ``--obs-trace PATH`` activates the
span tracer and writes the run's Chrome-trace/Perfetto JSON to PATH,
and a modeled-vs-observed drift report is printed after a ``--sched``
run when any completions were recorded.

Analysis tier (DESIGN.md §19): ``--obs-tail PATH`` keeps every
SLO-breaching / erroring / p99 request tree at a 1% baseline rate and
writes them to PATH; ``--slo-shed`` closes the SLO loop — completions
feed per-tenant burn-rate windows and a burning tenant's new arrivals
are shed at admission; with a tracer active a per-tenant blame report
(queue-wait / swap / coalesce / contention / compute) is printed after
the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import tree_shardings
from repro.launch import api
from repro.launch.mesh import make_elastic_mesh, mesh_name
from repro.models import model as M
from repro.models.params import abstract_params, logical_axes
from repro.obs import trace as _trace


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray          # (batch, gen) generated ids, greedy or sampled
    prefill_logits: jax.Array   # (batch, vocab) logits at the last prompt position
    compile_s: float            # seconds compiling the prefill and decode steps


def grow_cache_fn(cfg, prefill_len, capacity):
    """Close over the static sizes so the cache growth can be jitted."""
    def f(cache):
        return M.grow_cache(cfg, cache, prefill_len, capacity)
    return f


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3-8b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--devices", type=int, default=None, metavar="N",
                   help="serve on the first N devices (default: all)")
    p.add_argument("--dtype", default=None, choices=("bfloat16", "float32"),
                   help="parameter and activation dtype (default: the "
                        "config's)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sched", action="store_true",
                   help="drive decode steps through the repro.sched "
                        "runtime (queue + cost model + scheduler)")
    p.add_argument("--sched-policy", default="edf",
                   help="scheduling policy with --sched (edf|wfq|fifo)")
    p.add_argument("--sched-trace", default=None, metavar="PATH",
                   help="record the scheduling run as replayable JSONL")
    p.add_argument("--sched-lanes", type=int, default=1, metavar="N",
                   help="with --sched: scheduler lane count (decode steps "
                        "are sequential, so >1 only widens rounds for "
                        "concurrent tenants)")
    p.add_argument("--sched-channels", type=int, default=None, metavar="N",
                   help="with --sched: model N HBM channels — lanes map "
                        "round-robin onto channels and a round's DRAM "
                        "demand serialises per channel instead of on one "
                        "shared interface (DESIGN.md §18)")
    p.add_argument("--slo-ms", type=float, default=50.0,
                   help="per-token latency deadline with --sched")
    p.add_argument("--plan-cache", default=None, metavar="DIR",
                   help="persistent compiled-plan artifact dir (DESIGN.md "
                        "§14): negotiated geometries and partitioned plans "
                        "are loaded from / published to DIR, so a restarted "
                        "or replicated server skips the cold compile work; "
                        "equivalent to REPRO_PLAN_CACHE in the environment")
    p.add_argument("--metrics", type=int, default=None, metavar="PORT",
                   help="serve the metrics registry over HTTP on PORT: "
                        "Prometheus text at /metrics, JSON snapshot at "
                        "/metrics.json (DESIGN.md §15)")
    p.add_argument("--metrics-hold", type=float, default=0.0, metavar="SEC",
                   help="with --metrics: keep the process (and endpoint) "
                        "alive SEC seconds after the run so scrapers can "
                        "fetch the final state")
    p.add_argument("--obs-trace", default=None, metavar="PATH",
                   help="activate the span tracer and write the run's "
                        "Chrome-trace JSON to PATH (open in Perfetto / "
                        "chrome://tracing)")
    p.add_argument("--obs-tail", default=None, metavar="PATH",
                   help="tail-based trace sampling (DESIGN.md §19): record "
                        "every request tree provisionally, keep the ones "
                        "that breach the --slo-ms target, error, or land "
                        "in the rolling p99 (plus a 1%% head baseline), "
                        "and write the kept trees' JSONL to PATH; implies "
                        "the span tracer")
    p.add_argument("--slo-shed", action="store_true",
                   help="with --sched: feed completions into a per-tenant "
                        "SLO burn-rate monitor (--slo-ms target) and shed "
                        "new arrivals of any tenant burning its error "
                        "budget on both the fast and slow windows "
                        "(DESIGN.md §19); off by default")
    p.add_argument("--region-slots", type=int, default=None, metavar="N",
                   help="with --sched: bound each lane to N configured-"
                        "region slots (repro.regions, DESIGN.md §16); "
                        "non-resident placements charge a measured "
                        "reconfiguration penalty. 0 tracks residency "
                        "without bounding; omit to disable regions")
    p.add_argument("--region-policy", default="lru",
                   choices=("lru", "reuse"),
                   help="residency eviction policy with --region-slots: "
                        "lru baseline or EWMA predicted-reuse")
    args = p.parse_args(argv)

    if args.plan_cache:
        from repro.core.artifact import set_plan_cache
        set_plan_cache(args.plan_cache)

    httpd = None
    if args.metrics is not None:
        from repro.obs import metrics as obs_metrics
        httpd = obs_metrics.start_http_server(args.metrics)
        host, port = httpd.server_address[:2]
        print(f"metrics http://{host}:{port}/metrics "
              f"(+ /metrics.json)")
    tracer = None
    sampler = None
    if args.obs_trace or args.obs_tail:
        tracer = _trace.Tracer()
        _trace.set_tracer(tracer)
        if args.obs_tail:
            from repro.obs.tail import TailSampler
            sampler = TailSampler(tracer, sample_rate=0.01,
                                  slo_s=args.slo_ms * 1e-3)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, attn_impl="chunked")
    if args.dtype:
        cfg = dataclasses.replace(cfg, param_dtype=args.dtype,
                                  act_dtype=args.dtype)

    mesh = make_elastic_mesh(args.devices,
                             model_parallel=args.model_parallel)
    print(f"mesh {mesh_name(mesh)}")
    capacity = args.prompt_len + args.gen
    rng = jax.random.PRNGKey(args.seed)

    with jax.set_mesh(mesh):
        params_sh = tree_shardings(logical_axes(cfg), abstract_params(cfg),
                                   mesh)
        params = jax.jit(lambda r: M.init_params(cfg, r),
                         out_shardings=params_sh)(rng)
        prompts = jax.random.randint(
            rng, (args.batch, args.prompt_len), 0, cfg.vocab)

        prefill = jax.jit(lambda pp, b: M.prefill(cfg, pp, b))
        decode = jax.jit(
            lambda pp, c, t, pos: M.decode_step(cfg, pp, c, t, pos))

        # compile ahead of the first call, so the timings below exclude it
        # (the jitted functions reuse these executables)
        batch_in = {"tokens": prompts}
        t0 = time.time()
        prefill.lower(params, batch_in).compile()
        t_compile = time.time() - t0

        t0 = time.time()
        logits, cache = prefill(params, batch_in)
        prefill_logits = logits
        cache = jax.jit(grow_cache_fn(cfg, args.prompt_len, capacity))(cache)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        print(f"prefill {args.batch}×{args.prompt_len} in "
              f"{t_prefill*1e3:.1f} ms "
              f"({args.batch*args.prompt_len/t_prefill:.0f} tok/s)")

        out_tokens = []
        tok = sample(logits, rng, args.temperature)
        out_tokens.append(np.asarray(tok))
        if args.gen > 1:
            t0 = time.time()
            decode.lower(params, cache, tok,
                         jnp.int32(args.prompt_len)).compile()
            t_compile += time.time() - t0
        print(f"compiled prefill + decode in {t_compile:.1f} s")
        if args.sched:
            gen, dt = _decode_scheduled(args, decode, sample, params, cache,
                                        tok, rng, out_tokens)
        else:
            t0 = time.time()
            for i in range(args.gen - 1):
                pos = jnp.int32(args.prompt_len + i)
                logits, cache = decode(params, cache, tok, pos)
                rng = jax.random.fold_in(rng, i)
                tok = sample(logits, rng, args.temperature)
                out_tokens.append(np.asarray(tok))
            jax.block_until_ready(tok)
            dt = time.time() - t0
            gen = np.concatenate(out_tokens, axis=1)
        print(f"decoded {args.gen} tokens × batch {args.batch} in "
              f"{dt*1e3:.1f} ms ({args.batch*(args.gen-1)/max(dt,1e-9):.0f} tok/s)")
        print("sample row:", gen[0][:16], "...")
        if tracer is not None and args.obs_trace:
            with open(args.obs_trace, "w") as f:
                f.write(tracer.export_chrome())
            print(f"obs trace ({len(tracer.spans)} spans) -> "
                  f"{args.obs_trace}")
        if sampler is not None:
            with open(args.obs_tail, "w") as f:
                f.write(sampler.export_jsonl())
            st = sampler.stats()
            print(f"obs tail: kept {st['kept']}/{st['seen']} trees "
                  f"({st['by_reason']}) -> {args.obs_tail}")
        if tracer is not None and args.sched:
            from repro.obs import critical
            blames = critical.attribute(tracer)
            if blames:
                print(critical.format_report(blames))
        if httpd is not None and args.metrics_hold > 0:
            print(f"holding metrics endpoint {args.metrics_hold:.0f}s",
                  flush=True)
            time.sleep(args.metrics_hold)
        return ServeResult(tokens=gen, prefill_logits=prefill_logits,
                           compile_s=t_compile)


def _decode_scheduled(args, decode, sample_fn, params, cache, tok, rng,
                      out_tokens):
    """The decode loop as scheduling-runtime clients (DESIGN.md §13).

    Decode steps are sequentially dependent (KV cache, sampled token),
    so each is submitted as it becomes ready and drained immediately —
    what the runtime adds is admission, deadline accounting against the
    ``--slo-ms`` per-token budget, EWMA-corrected per-step predictions,
    and the replayable trace.
    """
    from repro.sched import CostModel, RequestQueue, Scheduler, TraceRecorder

    slo = args.slo_ms * 1e-3
    monitor = None
    if args.slo_shed:
        # SLO feedback loop (DESIGN.md §19): completions feed per-tenant
        # burn-rate windows; a tenant burning both windows has its NEW
        # arrivals shed at admission. Windows scale with the per-token
        # target so the fast window holds ~20 steps of signal.
        from repro.obs.slo import SloMonitor, SloShedder
        monitor = SloMonitor(threshold=2.0)
        monitor.add("decode", target_s=slo, objective=0.9,
                    fast_s=20 * slo, slow_s=200 * slo)
        queue = RequestQueue(admission=SloShedder(monitor))
    else:
        queue = RequestQueue()
    cost = CostModel()
    recorder = TraceRecorder() if args.sched_trace else None
    sched = Scheduler(queue, cost=cost, policy=args.sched_policy,
                      n_lanes=args.sched_lanes, clock="wall",
                      recorder=recorder,
                      region_slots=args.region_slots,
                      region_policy=args.region_policy,
                      n_channels=args.sched_channels,
                      slo=monitor)

    state = {"cache": cache, "tok": tok, "rng": rng}

    def step(i):
        pos = jnp.int32(args.prompt_len + i)
        logits, state["cache"] = decode(params, state["cache"],
                                        state["tok"], pos)
        state["rng"] = jax.random.fold_in(state["rng"], i)
        state["tok"] = sample_fn(logits, state["rng"], args.temperature)
        return state["tok"]

    t0 = time.time()
    shed_steps = 0
    for i in range(args.gen - 1):
        now = sched.now()
        it = queue.submit(step, (i,), deadline=now + slo, tenant="decode",
                          arrival=now, cost_key=("decode_step", args.arch))
        if it.shed:
            # admission dropped the step: no token this position — the
            # decode chain resumes at the next admitted step
            shed_steps += 1
            continue
        sched.drain()
        out_tokens.append(np.asarray(state["tok"]))
    dt = time.time() - t0

    rep = sched.report()
    if rep.placements:
        obs = sorted(p.observed_s for p in rep.placements)
        tail = rep.placements[len(rep.placements) // 2:]
        err = sorted(abs(p.predicted_s - p.observed_s)
                     / max(p.observed_s, 1e-9) for p in tail)
        print(f"sched[{args.sched_policy}]: {len(rep.placements)} steps, "
              f"{len(rep.missed)} past the {args.slo_ms:.0f} ms SLO, "
              f"median step {obs[len(obs)//2]*1e3:.1f} ms, "
              f"EWMA prediction error (2nd half) "
              f"{err[len(err)//2]*100:.0f}%")
    if sched.regions is not None:
        r = sched.regions.report()
        lane0 = r["lanes"][0]
        print(f"regions[{r['policy']}]: {r['slots'] or 'unbounded'} "
              f"slots/lane, lane0 hit ratio {lane0['hit_ratio']:.2f} "
              f"({lane0['hits']} hits / {lane0['loads']} loads / "
              f"{lane0['evictions']} evictions), "
              f"{r['swap_seconds']*1e3:.2f} ms charged to reconfig")
    if monitor is not None:
        print(monitor.report(now=sched.now()))
        if shed_steps:
            print(f"slo-shed: {shed_steps} decode steps shed at "
                  f"admission")
    if recorder is not None:
        recorder.dump(args.sched_trace)
        print(f"sched trace ({len(recorder.events)} events) -> "
              f"{args.sched_trace}")
    if cost.drift_report(min_samples=1):
        print(cost.drift.format_report(top=5, min_samples=1))
    return np.concatenate(out_tokens, axis=1), dt


def sample(logits, rng, temperature):
    with _trace.host_span("sample"):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / temperature, axis=-1)[:, None].astype(jnp.int32)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
