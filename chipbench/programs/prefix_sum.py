"""The prefix-sum application of arXiv:2106.07456 sec. 4.3.2:
``ops.prefix_sum``, the ``c3_prefixsum`` carried scan, over float32."""
import numpy as np

from chipbench import harness

VECTORS = 1
KEYS = False
NUMBER = "scan_rel_err"
KERNELS = ("c3_prefixsum",)


def target(n: int):
    from repro.kernels import ops
    return ops.prefix_sum


def operands(vecs: tuple, scalar: float) -> tuple:
    return (vecs[0],)


def work(n: int) -> dict:
    return {"c3_prefixsum": list(
        harness.load_module("work", "c3_prefixsum").work(n=n))}


def reference(ops: tuple, dtype) -> tuple:
    """The inclusive running sum, added in ``dtype`` one value at a time
    (NumPy's cumsum is sequential)."""
    (x,) = ops
    return (np.cumsum(np.asarray(x).astype(dtype), dtype=dtype),)
