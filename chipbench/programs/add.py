"""STREAM Add, ``c = a + b``: the ``c0_add`` instruction as a one-stage
fused program, float32. Two vectors in, one out."""
import numpy as np

from chipbench import harness

VECTORS = 2
KEYS = False
NUMBER = "stream_rel_err"
KERNELS = ("c0_program",)


def target(n: int):
    from repro.core import isa
    return isa.fuse("c0_add")


def operands(vecs: tuple, scalar: float) -> tuple:
    return (vecs[0], vecs[1])


def work(n: int) -> dict:
    return {"c0_program": list(harness.load_module("work", "c0_program").work(
        n=n, vec_in=2, vec_out=1, flops_per_elem=1))}


def reference(ops: tuple, dtype) -> tuple:
    a, b = (np.asarray(v).astype(dtype) for v in ops)
    return ((a + b).astype(dtype),)
