"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``chipbench/traffic/<name>.json``; this module reads it and draws
the requests of one run from ``--seed``.

Every mix is a closed loop: one client sends a request (or a batch of
prompts), waits for its result, and sends the next. The seed draws the
order and the data, never the amount of work:

- a program mix (``"cycle"``) names how many requests of each program
  kind make one cycle; every cycle holds exactly that multiset, in an
  order the seed draws afresh for each cycle, with a scalar operand per
  request drawn from the seed;
- a serving mix (``"batch"``, ``"prompt_len"``, ``"gen"``) sends batches
  of prompts the seed draws (see the serving driver).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Independent host streams from one seed of any size."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def jax_seed(seed: int, *stream: int) -> int:
    """A 31-bit seed for ``jax.random.key`` drawn from ``seed``."""
    return int(rng(seed, 7, *stream).integers(1 << 31))


@dataclasses.dataclass(frozen=True)
class Request:
    index: int          # position in the order sent
    cycle: int          # the cycle it belongs to
    kind: str           # the program kind (``chipbench/programs/<kind>.py``)
    scalar: float       # the request's scalar operand, where it has one


def cycle(spec: dict, seed: int, c: int, first_index: int = 0
          ) -> list[Request]:
    """The requests of cycle ``c``: the mix's multiset of kinds in an
    order drawn from the seed, each with a scalar uniform in [0.5, 2)."""
    kinds = [k for k, count in spec["cycle"].items() for _ in range(count)]
    g = rng(seed, 1, c)
    order = g.permutation(len(kinds))
    scalars = g.uniform(0.5, 2.0, size=len(kinds))
    return [Request(first_index + i, c, kinds[j], float(s))
            for i, (j, s) in enumerate(zip(order, scalars))]


class Reservoir:
    """A uniform sample of ``k`` items of each kind from a stream of
    unknown length, drawn from the seed (reservoir sampling): what a run
    keeps for its check stays bounded however long the window runs."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.g = rng(seed, 100)
        self.seen: dict = {}
        self.kept: dict = {}

    def offer(self, kind: str, item) -> None:
        j = self.seen.get(kind, 0)
        self.seen[kind] = j + 1
        slots = self.kept.setdefault(kind, [])
        if j < self.k:
            slots.append(item)
            return
        r = int(self.g.integers(j + 1))
        if r < self.k:
            slots[r] = item

    def items(self) -> list:
        return [it for kind in sorted(self.kept) for it in self.kept[kind]]


def sample_indices(n: int, k: int, seed: int, stream: int = 2) -> list[int]:
    """``k`` of ``n`` indices drawn from the seed, in order."""
    if n <= k:
        return list(range(n))
    return sorted(int(i) for i in rng(seed, stream).choice(n, size=k,
                                                           replace=False))
