"""Scheduler layer: host time in the spans ``sched_idle_ms.prog`` reads
(``repro.submit`` and ``repro.drain``, less ``repro.launch`` and
``repro.wait``), in milliseconds per completed request. Where the host
runs one request at a time, as in the closed loop, the device has
nothing queued there, so this bounds ``sched_idle_ms.prog`` from above;
read on the host's clock alone, it does not move with the trace's
host-to-device clock offset, which shifts device idle between adjacent
spans from run to run. Moves ``prog_req_ms``."""
from chipbench.layer_metrics._spans import host_ms_per
from chipbench.layer_metrics.sched_idle_ms_prog import INSIDE, LESS


def read(data):
    return host_ms_per(data, "completed", inside=INSIDE, less=LESS)
