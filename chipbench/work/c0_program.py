"""c0 streaming programs (STREAM scale/add/triad/copy, fused chains and
partitioned c0 plans): one pass over the operands.

``n`` elements per vector; ``vec_in`` vectors read and ``vec_out``
written once each, ``flops_per_elem`` arithmetic operations per element
(scale 1, add 1, triad 2, copy 0). A fused chain's intermediates stay on
chip, so only its external operands count."""

# The trace names a fused program's pallas_call only by its target.
TRACE = r"tpu_custom_call"


def work(n: int, vec_in: int, vec_out: int, flops_per_elem: int,
         itemsize: int = 4) -> tuple[float, float]:
    return float(flops_per_elem * n), float((vec_in + vec_out) * n * itemsize)
