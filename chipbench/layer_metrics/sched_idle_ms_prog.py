"""Scheduler layer (``sched/queue.py``, ``sched/scheduler.py``): device
idle while the host is in ``RequestQueue.submit`` or ``Scheduler.drain``
(the program's ``repro.submit`` and ``repro.drain`` spans) but not
launching a batch or waiting on it (``repro.launch``, ``repro.wait``):
admission, pricing and ordering, lane assignment and the bookkeeping
after each batch. Milliseconds per completed request. Moves
``prog_req_ms``."""
from chipbench.layer_metrics._spans import idle_ms_per

INSIDE = ("repro.submit", "repro.drain")
LESS = ("repro.launch", "repro.wait")


def read(data):
    return idle_ms_per(data, "completed", inside=INSIDE, less=LESS)
