"""c1_merge: merge sorted ``width``-chunks of two registers (a bitonic
merge network). ``n`` keys in all (``n / 2`` in each operand); every key is
read once and written once. A merge of two width-``w`` chunks is
log2(2w) layers of w compare-exchanges, two operations each."""
import math

TRACE = r"merge_sorted_pallas"


def work(n: int, width: int, itemsize: int = 4) -> tuple[float, float]:
    layers = int(math.log2(2 * width))
    return float(n * layers), float(2 * n * itemsize)
