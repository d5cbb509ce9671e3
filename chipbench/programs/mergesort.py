"""The sort application of arXiv:2106.07456 sec. 4.3.1:
``ops.sortnet_mergesort`` over int32 keys — ``c2_sort`` over chunks of
8, ``c1_merge`` levels up to 4096 keys wide, the wider levels on XLA's
sort. The answer is exact."""
import numpy as np

from chipbench import harness

VECTORS = 1
KEYS = True                 # int32 keys over the whole range
NUMBER = "sort_mismatches"
KERNELS = ("c1_merge", "c2_sort")
BASE, MAX_MERGE = 8, 4096   # ops.sortnet_mergesort's defaults


def target(n: int):
    from repro.kernels import ops
    return ops.sortnet_mergesort


def operands(vecs: tuple, scalar: float) -> tuple:
    return (vecs[0],)


def work(n: int) -> dict:
    """The Pallas kernels' share: the chunk sort and the merge levels up
    to ``MAX_MERGE`` (the wider levels run on XLA's sort, no kernel)."""
    w = lambda k, **kw: list(harness.load_module("work", k).work(**kw))  # noqa: E731
    merge = [0.0, 0.0]
    width = BASE
    while width < n and 2 * width <= MAX_MERGE:
        f, b = w("c1_merge", n=n, width=width)
        merge = [merge[0] + f, merge[1] + b]
        width *= 2
    return {"c2_sort": w("c2_sort", n=n, width=BASE), "c1_merge": merge}


def reference(ops: tuple, dtype) -> tuple:
    """Keys sorted ascending; int32 has no lower precision, so ``dtype``
    is not used."""
    (x,) = ops
    return (np.sort(np.asarray(x)),)
