"""Kernel layer (``kernels/ops.py`` ``c3_prefixsum``, the carried scan
that shares ``_scan_blocks`` with ``c4_statescan``): the sec. 4.3.2
prefix sum's least time at the chip's peaks over its device time in the
trace, in percent. Moves ``prog_req_ms``."""
from chipbench.layer_metrics._common import roofline_percent


def read(data):
    return roofline_percent(data, ("c3_prefixsum",))
