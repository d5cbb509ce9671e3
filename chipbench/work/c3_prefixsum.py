"""c3_prefixsum: inclusive prefix sum over ``n`` values. The least work
is one add per value, each value read once and written once."""

TRACE = r"prefix_sum_pallas"


def work(n: int, itemsize: int = 4) -> tuple[float, float]:
    return float(n), float(2 * n * itemsize)
