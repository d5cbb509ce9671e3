"""Smoke run of the main path on the chip, checked against the oracles.

    python3 chip_smoke.py             # one TPU chip
    python3 chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip runs, in this one process:
  1. the instruction programs in ``kernel`` mode at the paper's sizes —
     STREAM copy/scale/add/triad over 128 Mi f32 elements (4× VMEM),
     the §4.3.1 sortnet mergesort over 2^24 int32 keys, the §4.3.2 prefix
     sum over 2^26 f32 values, the c4_statescan recurrence at Mamba2-1.3B's
     prefill shapes, one fused c0 chain, the three
     ``c0_pipeline_graph`` plans, and one coalescing ``Scheduler.drain()``
     — each compared with its ref oracle on the chip;
  2. Mamba2-1.3B serving at its published widths through
     ``repro.launch.serve.main`` (random weights from ``--seed``): batch 4,
     512-token prompts, 32 greedy tokens, with the kernel-dispatch prefill
     logits compared with a ref-dispatch run of the same step, in bf16
     and again in f32.

``--chips 4`` runs only what exists across chips: Mamba2-1.3B served on a
(1, 4) data×model mesh against the same serve on ``devices()[0]``, and a
``sharded_program_call`` over a 4-lane ``parts`` mesh against the same
requests on ``devices()[0]``.

Every phase prints one JSON line. The last line of stdout is
``{"ok": true, "device": {...}}`` and appears only when JAX runs on a TPU
and every phase passed; otherwise the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.kernels  # noqa: E402,F401 — registers the instruction set
from repro.core import isa  # noqa: E402
from repro.core.program import DISPATCH_STATS  # noqa: E402
from repro.graph import partition  # noqa: E402
from repro.graph.ir import Value  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.sched import RequestQueue, Scheduler, sharded_program_call  # noqa: E402

STREAM_N = 128 << 20        # f32 elements: 512 MiB per array, 4× VMEM
SORT_N = 1 << 24            # int32 keys (paper §4.3.1)
SCAN_N = 1 << 26            # f32 values (paper §4.3.2)
SCHED_N = 16 << 20          # f32 elements per scheduler request
SCAN_RTOL = 1e-3            # max |kernel - ref| / max |ref| for the scans
# Mamba2 last-position prefill logits, kernel vs ref dispatch and sharded
# vs one chip: max |Δ| ≤ tol · max |ref|. With random weights the 48
# layers amplify any flipped rounding — a different XLA fusion around the
# kernel or a different reduction order across shards is enough. Measured
# on the CPU at d_model 256, 48 layers: in bf16, (1, 4)-sharded vs one
# device differs by 0.35, more than a real fault (out_proj scaled by 0.75,
# one of four partial sums lost: 0.20), so the bf16 bound only catches
# gross faults (all layers skipped: 2.7). In f32 with f32 matmuls the same
# two differ by 3.2e-4 and 0.25, so the f32 run is the discriminating one.
LOGIT_TOL_BF16 = 0.5
LOGIT_TOL_F32 = 1e-2
# c4_statescan shapes (batch, chunks, heads, headdim, state) of Mamba2-1.3B
# prefill: the served batch 4 × 512 tokens, and one 2048-token prompt
STATESCAN_SHAPES = ((4, 2, 64, 64, 128), (1, 8, 64, 64, 128))
SERVE_ARGV = ["--arch", "mamba2-1.3b", "--batch", "4", "--prompt-len", "512",
              "--gen", "32"]


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _launches() -> dict:
    """Dispatches per Pallas mode: named instructions (isa) plus program
    launches (fused chains, plans, coalesced batches)."""
    return {m: isa.registry.mode_dispatches(m) + getattr(DISPATCH_STATS,
                                                f"{m}_launches")
            for m in ("kernel", "interpret")}


class Phase:
    """Collects one phase's record and checks that it dispatched Pallas
    kernels in ``mode`` and none in the other Pallas mode."""

    def __init__(self, name: str, mode: str, **sizes):
        self.rec = {"phase": name, "mode": mode, **sizes,
                    "compile_s": 0.0, "max_err": 0.0}
        self.mode = mode

    def __enter__(self):
        self._start = _launches()
        self._isa = isa.use(self.mode)
        self._isa.__enter__()
        return self

    def compile(self, fn, *args):
        """AOT-compile ``fn`` for ``args``; counts its Pallas custom calls."""
        t = time.perf_counter()
        exe = jax.jit(fn).lower(*args).compile()
        self.rec["compile_s"] += time.perf_counter() - t
        self.rec["custom_calls"] = (self.rec.get("custom_calls", 0)
                                    + exe.as_text().count("tpu_custom_call"))
        return exe

    def err(self, e: float) -> None:
        self.rec["max_err"] = max(self.rec["max_err"], float(e))

    def __exit__(self, *exc):
        self._isa.__exit__(*exc)
        if exc[0] is not None:
            return False
        end = _launches()
        got = {m: end[m] - self._start[m] for m in end}
        other = "interpret" if self.mode == "kernel" else "kernel"
        self.rec["dispatches"] = got[self.mode]
        check(got[self.mode] > 0,
              f"{self.rec['phase']}: no {self.mode} dispatch")
        check(got[other] == 0,
              f"{self.rec['phase']}: {got[other]} {other} dispatches")
        print(json.dumps(self.rec), flush=True)
        return False


def _uniform(seed: int, n: int, dtype=jnp.float32):
    return jax.jit(lambda k: jax.random.uniform(k, (n,), dtype),
                   )(jax.random.PRNGKey(seed))


def _rel_err(got, want) -> float:
    """max |got - want| / max |want|, computed on device 0."""
    dev = jax.devices()[0]
    got = jax.device_put(got, dev).astype(jnp.float32)
    want = jax.device_put(want, dev).astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


# ---------------------------------------------------------------------------
# phase 1 — instruction programs
# ---------------------------------------------------------------------------

def phase_stream(n: int, mode: str, seed: int = 0) -> dict:
    """STREAM copy, scale, add and triad (paper Fig. 4)."""
    a, b = _uniform(seed, n), _uniform(seed + 1, n)
    s = jnp.float32(3.0)
    kernels = {
        "copy": (lambda x, y, c, m: ops.stream_copy(x, mode=m), "exact"),
        "scale": (lambda x, y, c, m: ops.stream_scale(x, c, mode=m), "close"),
        "add": (lambda x, y, c, m: ops.stream_add(x, y, mode=m), "close"),
        "triad": (lambda x, y, c, m: ops.stream_triad(x, y, c, mode=m),
                  "close"),
    }
    with Phase("stream", mode, elems=n, bytes_per_array=4 * n) as ph:
        for name, (fn, how) in kernels.items():
            out = ph.compile(lambda x, y, c: fn(x, y, c, mode), a, b, s)(
                a, b, s)
            want = jax.jit(lambda x, y, c: fn(x, y, c, "ref"))(a, b, s)
            if how == "exact":
                check(bool(jnp.array_equal(out, want)), f"{name} differs")
            else:
                check(bool(jnp.allclose(out, want, rtol=1e-6, atol=0)),
                      f"{name} not within rtol 1e-6")
            ph.err(jnp.max(jnp.abs(out - want)))
            del out, want
    return ph.rec


def phase_sort(n: int, mode: str, seed: int = 0, merge_width: int = 2048
               ) -> dict:
    """§4.3.1: c2_sort and c1_merge alone, then the sortnet mergesort,
    whose levels wider than one kernel block run as merge-path windows
    on c1_merge; each is checked exactly."""
    x = jax.jit(lambda k: jax.random.randint(
        k, (n,), jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max,
        jnp.int32))(jax.random.PRNGKey(seed))
    pairs = jnp.sort(x.reshape(-1, 2, merge_width), axis=-1)
    a, b = pairs[:, 0], pairs[:, 1]
    # (kernel path, oracle): the whole mergesort's oracle is jnp.sort —
    # its ref-mode composition reshapes to 8-wide rows, which pad 128× in
    # the chip's (8, 128) tiles.
    steps = {
        "c2_sort": (lambda v, w: ops.sort_chunks(v, width=8, mode=mode),
                    lambda v, w: ref.sort_chunks(v, width=8), (x, x)),
        "c1_merge": (lambda v, w: ops.merge_sorted(v, w, mode=mode),
                     ref.merge_sorted, (a, b)),
        "mergesort": (lambda v, w: ops.sortnet_mergesort(v, mode=mode),
                      lambda v, w: ref.mergesort(v), (x, x)),
    }
    with Phase("sort", mode, elems=n, merge_width=merge_width) as ph:
        for name, (fn, oracle, args) in steps.items():
            out = ph.compile(fn, *args)(*args)
            want = jax.jit(oracle)(*args)
            bad = sum(int(jnp.sum(o != r)) for o, r in
                      zip(jax.tree.leaves(out), jax.tree.leaves(want)))
            ph.rec[f"{name}_mismatches"] = bad
            check(bad == 0, f"{name}: {bad} keys differ from the oracle")
    return ph.rec


def phase_prefix(n: int, mode: str, seed: int = 0) -> dict:
    """§4.3.2: c3_prefixsum over one long vector."""
    x = _uniform(seed, n)
    with Phase("prefix_sum", mode, elems=n, rtol=SCAN_RTOL) as ph:
        out = ph.compile(lambda v: ops.prefix_sum(v, mode=mode), x)(x)
        want = jax.jit(lambda v: ops.prefix_sum(v, mode="ref"))(x)
        e = _rel_err(out, want)
        ph.err(e)
        check(e <= SCAN_RTOL, f"prefix sum rel err {e} > {SCAN_RTOL}")
    return ph.rec


def phase_statescan(shapes, mode: str, seed: int = 0) -> dict:
    """c4_statescan, the SSD inter-chunk recurrence, at Mamba2's shapes:
    decays in (e^-1, 1], normal f32 chunk states."""
    with Phase("statescan", mode, shapes=[list(s) for s in shapes],
               rtol=SCAN_RTOL) as ph:
        for i, shape in enumerate(shapes):
            ka, ks = jax.random.split(jax.random.PRNGKey(seed + i))
            a = jnp.exp(-jax.random.uniform(ka, shape[:3], jnp.float32))
            s = jax.random.normal(ks, shape, jnp.float32)
            out = ph.compile(lambda x, y: ops.chunk_scan_state(
                x, y, axis=1, mode=mode), a, s)(a, s)
            want = jax.jit(lambda x, y: ops.chunk_scan_state(
                x, y, axis=1, mode="ref"))(a, s)
            e = _rel_err(out, want)
            ph.err(e)
            check(e <= SCAN_RTOL, f"statescan {shape}: {e} > {SCAN_RTOL}")
    return ph.rec


def phase_fused(n: int, mode: str, seed: int = 0) -> dict:
    """One fused chain: scale then add in one pallas_call."""
    fused = isa.fuse("c0_scale", "c0_add")
    x, b = _uniform(seed, n), _uniform(seed + 1, n)
    s = jnp.float32(2.5)
    with Phase("fused_scale_add", mode, elems=n) as ph:
        out = ph.compile(lambda *o: fused(*o, mode=mode), s, x, b)(s, x, b)
        want = jax.jit(lambda *o: fused(*o, mode="ref"))(s, x, b)
        check(bool(jnp.allclose(out, want, rtol=1e-6, atol=0)),
              "fused chain not within rtol 1e-6")
        ph.err(jnp.max(jnp.abs(out - want)))
    return ph.rec


def phase_graphs(n: int, mode: str, seed: int = 0) -> dict:
    """The three c0_pipeline_graph DAGs, partitioned into plans."""
    with Phase("c0_pipeline_graphs", mode, elems=n,
               plans=list(ops.C0_PIPELINES)) as ph:
        for i, kind in enumerate(ops.C0_PIPELINES):
            g = ops.c0_pipeline_graph(kind)
            plan = partition(g, n_elems=n, dtype=jnp.float32)
            args = [_uniform(seed + 10 * i + k, n)
                    if isinstance(v, Value) else jnp.float32(1.5 + k)
                    for k, (_, v) in enumerate(g.free_inputs())]
            outs = ph.compile(lambda *o: plan(*o, mode=mode), *args)(*args)
            want = jax.jit(lambda *o: plan.ref(*o))(*args)
            for o, w in zip(jax.tree.leaves(outs), jax.tree.leaves(want)):
                check(bool(jnp.allclose(o, w, rtol=1e-6, atol=0)),
                      f"plan {kind} not within rtol 1e-6")
                ph.err(jnp.max(jnp.abs(o - w)))
    return ph.rec


def phase_scheduler(n: int, mode: str, seed: int = 0) -> dict:
    """Two tenants' same-structure requests, coalesced into call_batch."""
    fused = isa.fuse("c0_scale", "c0_add")
    reqs = [(jnp.float32(2.0), _uniform(seed, n), _uniform(seed + 1, n)),
            (jnp.float32(3.0), _uniform(seed + 2, n), _uniform(seed + 3, n))]
    with Phase("scheduler_drain", mode, elems=n, requests=len(reqs)) as ph:
        batches0 = DISPATCH_STATS.batch_calls
        for run in ("first_drain_s", "second_drain_s"):   # cold, then warm
            q = RequestQueue()
            for tenant, ops_ in zip(("tenant_a", "tenant_b"), reqs):
                q.submit(fused, ops_, tenant=tenant)
            t = time.perf_counter()
            rep = Scheduler(q, policy="fifo", clock="wall", mode=mode).drain()
            jax.block_until_ready(rep.results)
            ph.rec[run] = time.perf_counter() - t
        ph.rec["batch_calls"] = DISPATCH_STATS.batch_calls - batches0
        check(ph.rec["batch_calls"] >= 1, "requests were not coalesced")
        for i, ops_ in enumerate(reqs):
            want = fused(*ops_, mode="ref")
            check(bool(jnp.allclose(rep.results[i], want, rtol=1e-6,
                                    atol=0)), f"request {i} differs")
            ph.err(jnp.max(jnp.abs(rep.results[i] - want)))
    return ph.rec


def run_programs(mode: str, stream_n: int, sort_n: int, scan_n: int,
                 statescan_shapes, sched_n: int, seed: int) -> list:
    return [phase_stream(stream_n, mode, seed),
            phase_sort(sort_n, mode, seed),
            phase_prefix(scan_n, mode, seed),
            phase_statescan(statescan_shapes, mode, seed),
            phase_fused(stream_n, mode, seed),
            phase_graphs(sched_n, mode, seed),
            phase_scheduler(sched_n, mode, seed)]


# ---------------------------------------------------------------------------
# phase 2 — model serving
# ---------------------------------------------------------------------------

def _logit_err(got, want) -> float:
    check(bool(jnp.all(jnp.isfinite(got))), "non-finite prefill logits")
    return _rel_err(got, want)


def _one_token(argv: list) -> list:
    i = argv.index("--gen")
    return argv[:i] + ["--gen", "1"] + argv[i + 2:]


def _f32_logits(argv: list) -> jax.Array:
    """Prefill logits of the same serve in f32 with f32 matmuls (the
    chip's default runs an f32 dot as one bf16 pass)."""
    with jax.default_matmul_precision("highest"):
        return serve.main(_one_token(argv)
                          + ["--dtype", "float32"]).prefill_logits


def _check_logits(ph: Phase, what: str, bf16: tuple, f32: tuple) -> None:
    """(got, want) prefill logits in bf16 and in f32, each within its
    bound; the bf16 error is the phase's ``max_err``."""
    e16, e32 = _logit_err(*bf16), _logit_err(*f32)
    ph.err(e16)
    ph.rec["max_err_f32"] = e32
    check(e16 <= LOGIT_TOL_BF16,
          f"{what} bf16 prefill logits: {e16} > {LOGIT_TOL_BF16}")
    check(e32 <= LOGIT_TOL_F32,
          f"{what} f32 prefill logits: {e32} > {LOGIT_TOL_F32}")


def phase_serve(argv: list, mode: str) -> dict:
    """Serve with the ISA in ``mode`` and compare the prefill logits with
    the same step under ref dispatch, in bf16 and in f32."""
    gen = int(argv[argv.index("--gen") + 1])
    batch = int(argv[argv.index("--batch") + 1])
    with Phase("serve", mode, argv=argv, tol=LOGIT_TOL_BF16,
               tol_f32=LOGIT_TOL_F32) as ph:
        t = time.perf_counter()
        res = serve.main(argv)
        ph.rec["serve_s"] = time.perf_counter() - t     # compile included
        ph.rec["compile_s"] = res.compile_s
        check(res.tokens.shape == (batch, gen),
              f"generated {res.tokens.shape}, want {(batch, gen)}")
        ph.rec["tokens"] = int(res.tokens.size)
        got32 = _f32_logits(argv)
        with isa.use("ref"):
            ref = serve.main(_one_token(argv))
            want32 = _f32_logits(argv)
        _check_logits(ph, "kernel vs ref",
                      (res.prefill_logits, ref.prefill_logits),
                      (got32, want32))
    return ph.rec


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_sharded_serve(argv: list, mode: str, n_dev: int) -> dict:
    """(1, n_dev) data×model mesh against the same serve on device 0."""
    sharded_argv = argv + ["--model-parallel", str(n_dev)]
    one_argv = argv + ["--devices", "1"]
    with Phase("serve_sharded", mode, argv=argv, chips=n_dev,
               tol=LOGIT_TOL_BF16, tol_f32=LOGIT_TOL_F32) as ph:
        t = time.perf_counter()
        sharded = serve.main(sharded_argv)
        ph.rec["serve_s"] = time.perf_counter() - t
        ph.rec["compile_s"] = sharded.compile_s
        one = serve.main(one_argv)
        ph.rec["token_agreement"] = float(np.mean(sharded.tokens
                                                  == one.tokens))
        _check_logits(ph, "sharded vs one-chip",
                      (sharded.prefill_logits, one.prefill_logits),
                      (_f32_logits(sharded_argv), _f32_logits(one_argv)))
    return ph.rec


def phase_sharded_programs(n: int, mode: str, n_dev: int,
                           seed: int = 0) -> dict:
    """sharded_program_call over an n_dev-lane parts mesh against the
    same requests dispatched one by one on device 0."""
    fused = isa.fuse("c0_scale", "c0_add")
    reqs = [(jnp.float32(1.0 + i), _uniform(seed + 2 * i, n),
             _uniform(seed + 2 * i + 1, n)) for i in range(2 * n_dev)]
    mesh = make_mesh((n_dev,), ("parts",),
                     devices=jax.devices()[:n_dev])
    with Phase("sharded_program_call", mode, elems=n, requests=len(reqs),
               chips=n_dev) as ph:
        t = time.perf_counter()
        outs = jax.block_until_ready(sharded_program_call(fused, reqs, mesh))
        ph.rec["compile_s"] = time.perf_counter() - t
        for o, ops_ in zip(outs, reqs):
            want = fused(*ops_)
            check(bool(jnp.array_equal(np.asarray(o), np.asarray(want))),
                  "sharded result differs from device 0")
            ph.err(jnp.max(jnp.abs(jnp.asarray(np.asarray(o))
                                   - jnp.asarray(np.asarray(want)))))
    return ph.rec


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    dev = jax.devices()
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    if info["platform"] != "tpu" or len(dev) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(dev)} {info['platform']} device(s)", file=sys.stderr)
        return 1
    print(json.dumps({"devices": info,
                      "compile_cache": enable_compile_cache()}), flush=True)

    serve_argv = SERVE_ARGV + ["--seed", str(args.seed)]
    if args.chips == 1:
        run_programs("kernel", STREAM_N, SORT_N, SCAN_N, STATESCAN_SHAPES,
                     SCHED_N, args.seed)
        phase_serve(serve_argv, "kernel")
    else:
        phase_sharded_serve(serve_argv, "kernel", args.chips)
        phase_sharded_programs(SCHED_N, "kernel", args.chips, args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
