"""Serving driver for the program's language models: the program's own
jitted ``prefill`` and ``decode_step`` under its mesh and parameter
shardings, its ``grow_cache`` and ``sample``, as ``launch/serve.py``
builds them, with every token copied to the host as it is produced (what
a streaming client sees).

Everything that belongs to one model is in its configuration file's
``program`` block: ``arch`` (the program's config name), ``runs`` (the
program's ``ModelConfig`` values that set-up checks), ``sizes`` (size
name → ``ModelConfig`` attribute, for the reference and the work counts)
and ``work`` (the ``chipbench/work`` files of a prefill and of a decode
step; the prefill's ``KERNELS`` and ``kernels()`` name and count the
kernels read in the trace). The reference, ``chipbench/reference/
<config>.py``, draws the weights and runs the plain forward.

A prefill work file's ``work`` takes ``batch``, ``seq``, ``weight_bytes``
and the sizes as keywords; a decode work file's ``work`` takes ``batch``,
``weight_bytes``, ``context`` (the mean over the window's decode steps of
the cache positions filled when each step ran, so that a cache that grows
with the context, such as attention's keys and values, can be counted)
and the sizes. Each returns (operations, bytes) of one call and ignores
the keywords it does not need.

Closed loop: one client sends a batch of prompts, takes ``gen`` greedy
tokens, and sends the next batch. With ``"prefill_in_setup": true`` the
first batch is prefilled during set-up and the window only decodes it (a
new batch's prefill, should one be needed, counts in no decode time).

End to end: ``ttft_ms`` (mean over the batches started in the window of
the time from sending the batch to its first token on the host) and
``tpot_ms`` (all decode time in the window over all decode steps).
Checked: a sample of served rows, drawn from the seed, against the plain
float32 reference run over each prompt with its served tokens.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, traffic

harness.ensure_src_on_path()

from repro.configs import get_config  # noqa: E402
from repro.distributed.sharding import tree_shardings  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.mesh import make_elastic_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.params import abstract_params, logical_axes  # noqa: E402


def sizes_of(program: dict, cfg) -> dict:
    """The sizes the reference and the work counts take: each named in
    the configuration's ``program.sizes`` after the program's
    ``ModelConfig`` attribute that holds it."""
    return {name: getattr(cfg, attr) for name, attr in program["sizes"].items()}


def program_config(program: dict):
    """The program's ``ModelConfig`` for ``program.arch``; set-up refuses
    one that does not run what ``program.runs`` says it runs."""
    cfg = get_config(program["arch"])
    for attr, value in program["runs"].items():
        if getattr(cfg, attr) != value:
            raise ValueError(f"{program['arch']}: the program runs {attr}="
                             f"{getattr(cfg, attr)}, the configuration file "
                             f"says {value}")
    return cfg


def kernels_of(config: dict) -> tuple:
    """The Pallas kernels whose device time the prefill's work file
    counts (its ``KERNELS``)."""
    return tuple(harness.load_module(
        "work", config["program"]["work"]["prefill"]).KERNELS)


WARMUP_BATCH = 1 << 30


class Driver:
    def __init__(self, cell: harness.Cell, seed: int, devices):
        self.cell = cell
        self.seed = seed
        self.devices = devices
        program = cell.config["program"]
        self.cfg = program_config(program)
        self.sizes = sizes_of(program, self.cfg)
        self.kernels = kernels_of(cell.config)
        self.work_files = program["work"]
        self.ref = harness.load_module("reference", cell.config_name)
        t = cell.traffic
        self.batch, self.prompt_len, self.gen = (t["batch"], t["prompt_len"],
                                                 t["gen"])
        self.check_rows = t["check_rows"]
        self.records = {"ttft_s": [], "decode_s": 0.0, "decode_steps": 0,
                        "decode_positions": 0}
        self.counters = {}
        self.work = {}
        self.served: dict[int, np.ndarray] = {}     # batch → (B, tokens)
        self.attempted = 0
        self.failed = 0

    # -- set-up ---------------------------------------------------------------
    def _weights_key(self):
        return jax.random.key(traffic.jax_seed(self.seed, 1))

    def _prompts(self, index: int):
        key = jax.random.fold_in(
            jax.random.key(traffic.jax_seed(self.seed, 2)), index)
        return self._draw_prompts(key)

    def setup(self, seconds: float) -> None:
        cfg = self.cfg
        t0 = time.perf_counter()
        self.mesh = make_elastic_mesh(len(self.devices), model_parallel=1)
        self._mesh_ctx = jax.set_mesh(self.mesh)
        self._mesh_ctx.__enter__()
        shard = tree_shardings(logical_axes(cfg), abstract_params(cfg),
                               self.mesh)
        self.params = self.ref.weights(self.sizes, self._weights_key(),
                                       jnp.dtype(cfg.param_dtype), shard)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract_params(cfg))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if want != got:
            raise ValueError("the reference's weights do not have the "
                             "program's parameter shapes")
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        b, s, vocab = self.batch, self.prompt_len, cfg.vocab
        self._draw_prompts = jax.jit(
            lambda k: jax.random.randint(k, (b, s), 0, vocab))
        self.prefill = jax.jit(lambda pp, bt: M.prefill(cfg, pp, bt))
        self.decode = jax.jit(
            lambda pp, c, t, pos: M.decode_step(cfg, pp, c, t, pos))
        self.grow = jax.jit(serve.grow_cache_fn(cfg, s, s + self.gen))
        # warm-up: one request of the cell's shapes, whose prompts no
        # window batch draws; its state is dropped
        self._start_batch(WARMUP_BATCH)
        for _ in range(2):
            self._decode_one()
        self.cache = self.tok = None
        t2 = time.perf_counter()
        if self.cell.traffic.get("prefill_in_setup"):
            self._start_batch(0)
        self.setup_parts = {"weights_s": t1 - t0, "warm_up_s": t2 - t1,
                            "first_batch_s": time.perf_counter() - t2}

    def _start_batch(self, index: int) -> float:
        """Send batch ``index``: prefill, grow the cache, first token to
        the host. Returns the seconds to the first token."""
        prompts = self._prompts(index)
        jax.block_until_ready(prompts)
        t0 = time.perf_counter()
        rows = self.cell.traffic.get("prefill_rows", self.batch)
        if rows < self.batch:
            # a batch whose prefill does not fit the chip at once is
            # prefilled in row blocks and its caches joined on the batch axis
            parts = [self.prefill(self.params,
                                  {"tokens": prompts[r:r + rows]})
                     for r in range(0, self.batch, rows)]
            logits = jnp.concatenate([p[0] for p in parts])
            cache = jax.tree.map(lambda *c: jnp.concatenate(c, axis=1),
                                 *[p[1] for p in parts])
            del parts
        else:
            logits, cache = self.prefill(self.params, {"tokens": prompts})
        self.cache = self.grow(cache)
        self.tok = serve.sample(logits, None, 0.0)
        first = np.asarray(self.tok)
        dt = time.perf_counter() - t0
        self.index, self.pos, self.tokens = index, self.prompt_len, [first]
        return dt

    def _decode_one(self) -> None:
        logits, self.cache = self.decode(self.params, self.cache, self.tok,
                                         jnp.int32(self.pos))
        self.tok = serve.sample(logits, None, 0.0)
        self.tokens.append(np.asarray(self.tok))
        self.pos += 1

    def _finish_batch(self) -> None:
        self.served[self.index] = np.concatenate(self.tokens, axis=1)
        self.attempted += self.batch

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float, spans) -> None:
        """Closed loop until ``seconds`` have passed. A batch sent in the
        window runs to its last token; where the batch was prefilled in
        set-up, the window ends with the time instead (decode only)."""
        rec = self.records
        cut = bool(self.cell.traffic.get("prefill_in_setup"))
        t_open = time.perf_counter()
        end = t_open + seconds
        index = 0 if self.cache is None else 1
        t_dec = t_open
        with spans("window"):
            while True:
                now = time.perf_counter()
                done = self.cache is None or len(self.tokens) >= self.gen
                if done or (cut and now >= end):
                    if self.cache is not None:
                        rec["decode_s"] += now - t_dec
                        self._finish_batch()
                        self.cache = None
                    if now >= end:
                        break
                    with spans("prefill"):
                        ttft = self._start_batch(index)
                    rec["ttft_s"].append(ttft)
                    index += 1
                    t_dec = time.perf_counter()
                    continue
                rec["decode_positions"] += self.pos
                with spans("decode_step"):
                    self._decode_one()
                rec["decode_steps"] += 1
        rec["window_s"] = time.perf_counter() - t_open
        self._account()

    def _account(self) -> None:
        rec, s = self.records, self.sizes
        weight_bytes = float(sum(a.size * a.dtype.itemsize
                                 for a in jax.tree.leaves(self.params)))
        rec["weight_bytes"] = weight_bytes
        w_pre = harness.load_module("work", self.work_files["prefill"])
        w_dec = harness.load_module("work", self.work_files["decode"])
        n_prefill = len(rec["ttft_s"])
        f, _ = w_pre.work(batch=self.batch, seq=self.prompt_len,
                          weight_bytes=weight_bytes, **s)
        rec["prefill_flops"] = f * n_prefill
        rec["decode_context"] = (rec["decode_positions"] / rec["decode_steps"]
                                 if rec["decode_steps"] else 0.0)
        f, b = w_dec.work(batch=self.batch, weight_bytes=weight_bytes,
                          context=rec["decode_context"], **s)
        rec["decode_step_flops"], rec["decode_step_bytes"] = f, b
        self.work = {k: [f * n_prefill, b * n_prefill] for k, (f, b) in
                     w_pre.kernels(batch=self.batch, seq=self.prompt_len,
                                   **s).items()} if n_prefill else {}

    @property
    def end_to_end(self) -> dict:
        rec = self.records
        out = {}
        if rec["ttft_s"]:
            out["ttft_ms"] = 1e3 * sum(rec["ttft_s"]) / len(rec["ttft_s"])
        if rec["decode_steps"]:
            out["tpot_ms"] = 1e3 * rec["decode_s"] / rec["decode_steps"]
        return out

    @property
    def summary(self) -> dict:
        rec = self.records
        return {"batches": len(self.served), "prefills": len(rec["ttft_s"]),
                "decode_steps": rec["decode_steps"],
                "window_s": rec.get("window_s"), **self.setup_parts}

    # -- check ----------------------------------------------------------------
    def release(self) -> None:
        self.params = self.cache = self.tok = None
        self.prefill = self.decode = self.grow = None
        self._mesh_ctx.__exit__(None, None, None)

    def sample_rows(self) -> list[tuple[int, int]]:
        """(batch, row) pairs to check, drawn from the seed."""
        pairs = [(bi, r) for bi in sorted(self.served)
                 for r in range(self.batch)]
        return [pairs[i] for i in traffic.sample_indices(
            len(pairs), self.check_rows, self.seed)]

    def reference_gaps(self, control: bool = False) -> np.ndarray:
        """The gap of every served token of the sampled rows, from the
        plain reference over each prompt with its served tokens (or, with
        ``control``, of the tokens the float8 reference puts first)."""
        s = self.sizes
        params = self.ref.weights(s, self._weights_key(),
                                  jnp.dtype(self.cfg.param_dtype))
        rows = self.sample_rows()
        n = min(self.served[b].shape[1] for b, _ in rows)
        need = self.prompt_len + n - 1          # the last token is not read
        length = -(-need // 512) * 512          # few shapes to compile
        pos = np.arange(self.prompt_len - 1, need)
        out = []
        block = self.cell.traffic["check_block"]
        for lo in range(0, len(rows), block):
            part = rows[lo:lo + block]
            toks = np.zeros((len(part), length), np.int32)
            served = np.stack([self.served[b][r, :n] for b, r in part])
            for k, (b, r) in enumerate(part):
                toks[k, :self.prompt_len] = np.asarray(self._prompts(b))[r]
                toks[k, self.prompt_len:need] = served[k, :n - 1]
            g = self.ref.gaps_at(s, params, jnp.asarray(toks),
                                 jnp.asarray(np.tile(pos, (len(part), 1))),
                                 jnp.asarray(served), control)
            out.append(np.asarray(g))
        return np.concatenate(out)

    def control(self) -> dict:
        """The check's number with float8 in the program's place."""
        return {"logit_gap": float(self.reference_gaps(control=True).max())}

    def check(self) -> list:
        limit = self.cell.config["check"]["logit_gap"]
        if not self.served:
            return [harness.Compared("logit_gap", float("inf"), limit)]
        g = self.reference_gaps()
        self.records["check_tokens"] = int(g.size)
        return [harness.Compared("logit_gap", float(g.max()), limit)]
