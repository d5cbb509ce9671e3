"""Kernel layer (``kernels/ops.py`` ``c4_statescan``): the SSD chunk-state
scan's least time at the chip's peaks over its device time in the trace,
in percent. Moves ``ttft_ms``."""
from chipbench.layer_metrics._common import roofline_percent


def read(data):
    return roofline_percent(data, ("c4_statescan",))
