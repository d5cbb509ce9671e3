"""STREAM Triad, ``a = b + q·c``: the ``c0_triad`` instruction as a
one-stage fused program, float32. Two vectors and a scalar in, one vector
out."""
import numpy as np

from chipbench import harness

VECTORS = 2
KEYS = False
NUMBER = "stream_rel_err"
KERNELS = ("c0_program",)


def target(n: int):
    from repro.core import isa
    return isa.fuse("c0_triad")


def operands(vecs: tuple, scalar: float) -> tuple:
    import jax.numpy as jnp
    return (jnp.float32(scalar), vecs[0], vecs[1])


def work(n: int) -> dict:
    return {"c0_program": list(harness.load_module("work", "c0_program").work(
        n=n, vec_in=2, vec_out=1, flops_per_elem=2))}


def reference(ops: tuple, dtype) -> tuple:
    q, b, c = (np.asarray(v).astype(dtype) for v in ops)
    return ((b + (q * c).astype(dtype)).astype(dtype),)
