"""STREAM Copy, ``c = a``: the ``c0_copy`` instruction as a one-stage
fused program, float32. One vector in, one out, no arithmetic."""
import numpy as np

from chipbench import harness

VECTORS = 1                 # operand vectors a request takes from its pool
KEYS = False                # float32 operands uniform in [0, 1)
NUMBER = "stream_rel_err"   # the number its answers are checked under
KERNELS = ("c0_program",)


def target(n: int):
    from repro.core import isa
    return isa.fuse("c0_copy")


def operands(vecs: tuple, scalar: float) -> tuple:
    return (vecs[0],)


def work(n: int) -> dict:
    return {"c0_program": list(harness.load_module("work", "c0_program").work(
        n=n, vec_in=1, vec_out=1, flops_per_elem=0))}


def reference(ops: tuple, dtype) -> tuple:
    """The answer in NumPy, in ``dtype``."""
    (a,) = ops
    return (np.asarray(a).astype(dtype),)
