"""Driver for a deployment of instruction programs: every request goes
through the program's ``RequestQueue.submit`` and a wall-clock
``Scheduler`` (policy and lanes from the configuration, the dispatch mode
left at ``auto``: the Pallas kernels on a TPU).

Each program kind is a file of its own, ``chipbench/programs/<kind>.py``,
found by the name the configuration gives it. It holds ``VECTORS`` (the
operand vectors its requests read), ``KEYS`` (int32 keys,
else float32 in [0, 1)), ``NUMBER`` (the name its answers are checked
under), ``KERNELS``, ``target(n)``, ``operands(vecs, scalar)``,
``work(n)`` (kernel → [flops, bytes] one request needs from the Pallas
kernels, from ``chipbench/work``) and ``reference(operands, dtype)``, its
answer in NumPy.

Closed loop: one client sends a request, waits until its result is
ready, and sends the next, in whole cycles of the traffic mix (the seed
draws each cycle's order and scalars), until ``seconds`` have passed and
the cycle in flight has finished. Each kind's operands are made once on
the device from the seed; the scheduler's results and placements are
dropped after every request, so what it holds stays bounded.

End to end: ``prog_req_ms``, the window's length over the requests it
completed. Checked: ``check_per_kind`` answers of each kind, drawn from
the seed among all the window's requests, against the plain reference.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, traffic

harness.ensure_src_on_path()

import repro.kernels  # noqa: E402,F401 — registers the instruction set
from repro.core import isa  # noqa: E402
from repro.core.program import DISPATCH_STATS  # noqa: E402
from repro.sched import RequestQueue, Scheduler  # noqa: E402


def kind(name: str):
    return harness.load_module("programs", name)


def kernels_of(config: dict) -> tuple:
    """The Pallas kernels the configuration's program kinds launch."""
    return tuple(sorted({k for name in config["programs"]
                         for k in kind(name).KERNELS}))


def pallas_launches() -> int:
    """Program launches plus named instructions dispatched to a kernel."""
    named = set(isa.names())
    return DISPATCH_STATS.kernel_launches + sum(
        n for (name, mode), n in isa.registry.dispatch_counts.items()
        if mode == "kernel" and name in named)


@functools.partial(jax.jit, static_argnums=1)
def _random_floats(key, n: int):
    return jax.random.uniform(key, (n,), jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def _random_keys(key, n: int):
    info = jnp.iinfo(jnp.int32)
    return jax.random.randint(key, (n,), info.min, info.max, jnp.int32)


class Driver:
    def __init__(self, cell: harness.Cell, seed: int, devices):
        self.cell = cell
        self.seed = seed
        self.devices = devices
        self.ref = harness.load_module("reference", cell.config_name)
        self.sizes = {name: int(p["n"])
                      for name, p in cell.config["programs"].items()}
        self.kernels = kernels_of(cell.config)
        self.records: dict = {}
        self.counters: dict = {}
        self.work: dict = {}
        self.attempted = 0
        self.failed = 0

    # -- set-up ---------------------------------------------------------------
    def _pool(self, name: str) -> tuple:
        """The operand vectors every request of a kind reads, made on the
        device from the seed (STREAM, too, reuses its arrays)."""
        mod, n = kind(name), self.sizes[name]
        key = jax.random.key(traffic.jax_seed(
            self.seed, 3, sorted(self.sizes).index(name)))
        if mod.KEYS:
            return tuple(_random_keys(kk, n)
                         for kk in jax.random.split(key, mod.VECTORS))
        return tuple(_random_floats(kk, n)
                     for kk in jax.random.split(key, mod.VECTORS))

    def operands(self, req) -> tuple:
        """The request's operand tuple in the program's order."""
        return kind(req.kind).operands(self.pools[req.kind], req.scalar)

    def setup(self, seconds: float) -> None:
        conf = self.cell.config
        t0 = time.perf_counter()
        self.targets = {name: kind(name).target(n)
                        for name, n in self.sizes.items()}
        self.pools = {name: self._pool(name) for name in self.sizes}
        self.queue = RequestQueue()
        sc = conf["scheduler"]
        self.sched = Scheduler(self.queue, policy=sc["policy"],
                               n_lanes=sc["lanes"], clock="wall")
        jax.block_until_ready(self.pools)
        t1 = time.perf_counter()
        # warm-up: every kind once, through the same queue and scheduler
        for name in sorted(self.sizes):
            self._send(traffic.Request(0, -1, name, 1.0))
            self._forget()
        self.setup_parts = {"pools_s": t1 - t0,
                            "warm_up_s": time.perf_counter() - t1}

    def _send(self, req):
        """Submit one request, drain the scheduler, return its placement
        and result (ready on the device)."""
        arrival = self.sched.now()
        self.queue.submit(self.targets[req.kind], self.operands(req),
                          tenant=req.kind, arrival=arrival)
        self.sched.drain()
        (p,) = self.sched.placements
        return arrival, p, self.sched.results[p.seq]

    def _forget(self) -> None:
        self.sched.results.clear()
        self.sched.placements.clear()

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float, spans) -> None:
        spec = self.cell.traffic
        sched = self.sched
        keep = traffic.Reservoir(spec["check_per_kind"], self.seed)
        launches0, batch0 = pallas_launches(), DISPATCH_STATS.batch_calls
        latency, wait, done = [], [], []
        c = 0
        with spans("window"):
            t0 = sched.now()
            while sched.now() - t0 < seconds:
                for req in traffic.cycle(spec, self.seed, c, len(done)):
                    with spans("request"):
                        arrival, p, out = self._send(req)
                    latency.append(p.finish - arrival)
                    wait.append(p.start - arrival)
                    done.append(req)
                    keep.offer(req.kind, (req, out))
                    self._forget()
                c += 1
            t_end = sched.now() - t0
        self.kept = keep.items()
        self.attempted = len(done)
        self.failed = 0
        self.records.update(
            latency_s=latency, sched_wait_s=wait, completed=len(done),
            cycles=c, window_s=t_end)
        self.counters = {
            "pallas_launches": pallas_launches() - launches0,
            "batch_calls": DISPATCH_STATS.batch_calls - batch0}
        work: dict = {}
        for req in done:
            for k, (f, b) in kind(req.kind).work(self.sizes[req.kind]).items():
                acc = work.setdefault(k, [0.0, 0.0])
                acc[0] += f
                acc[1] += b
        self.work = work

    @property
    def end_to_end(self) -> dict:
        rec = self.records
        if not rec.get("completed"):
            return {}
        return {"prog_req_ms": 1e3 * rec["window_s"] / rec["completed"]}

    @property
    def summary(self) -> dict:
        rec = self.records
        return {"requests": self.attempted, "cycles": rec["cycles"],
                "window_s": rec["window_s"],
                "latency_max_s": max(rec["latency_s"], default=0.0),
                "pallas_launches": self.counters["pallas_launches"],
                "batch_calls": self.counters["batch_calls"],
                **self.setup_parts}

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        self.sched = self.queue = None

    def answers(self, control_dtype=None) -> dict:
        """number → its worst value over the sampled requests: the
        program's answers, or with ``control_dtype`` the reference
        computed in that precision in the program's place."""
        out: dict = {}
        for req, got in self.kept:
            ops_ = tuple(np.asarray(v) for v in self.operands(req))
            want = self.ref.answer(req.kind, ops_)
            if control_dtype is None:
                if not isinstance(got, (tuple, list)):
                    got = (got,)
                got = tuple(np.asarray(g) for g in got)
            else:
                got = self.ref.answer(req.kind, ops_, control_dtype)
            name = kind(req.kind).NUMBER
            out[name] = max(out.get(name, 0.0),
                            self.ref.compare(req.kind, got, want))
        return out

    def control(self) -> dict:
        """The check's numbers with the reference computed in bfloat16 in
        the program's place (int32 keys have no lower precision)."""
        import ml_dtypes
        got = self.answers(control_dtype=ml_dtypes.bfloat16)
        exact = {kind(k).NUMBER for k in self.sizes if kind(k).KEYS}
        return {n: v for n, v in got.items() if n not in exact}

    def check(self) -> list:
        limits = self.cell.config["check"]
        got = self.answers()
        out = []
        for name in sorted({kind(k).NUMBER for k in self.sizes}):
            value = got.get(name, math.inf)     # none answered: a fault
            out.append(harness.Compared(name, value, limits[name]))
        self.kept = []
        return out
