"""c2_sort: bitonic sort of every ``width``-chunk of ``n`` keys. Each key
is read once and written once; the network has log2(w)(log2(w)+1)/2
layers of w/2 compare-exchanges per chunk, two operations each."""
import math

TRACE = r"sort_chunks_pallas"


def work(n: int, width: int, itemsize: int = 4) -> tuple[float, float]:
    lg = int(math.log2(width))
    layers = lg * (lg + 1) // 2
    return float(n * layers), float(2 * n * itemsize)
