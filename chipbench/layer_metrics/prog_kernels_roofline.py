"""Kernel layer (``kernels/*.py``): the program cells' Pallas kernels
together — c0 streaming programs, c1_merge, c2_sort, c3_prefixsum —
as the least time their work needs at the chip's peaks over their device
time in the trace. Moves ``prog_req_ms``."""
from chipbench.layer_metrics._common import roofline_percent

KERNELS = ("c0_program", "c1_merge", "c2_sort", "c3_prefixsum")


def read(data):
    return roofline_percent(data, KERNELS)
