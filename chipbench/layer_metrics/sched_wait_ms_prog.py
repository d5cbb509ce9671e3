"""Scheduler layer (``sched/queue.py``, ``sched/scheduler.py``): mean time
a request waited from its arrival to the start of the round that ran it,
from the scheduler's own ``Placement`` records, over every request of the
window: the host's pricing and ordering before the device starts.
Moves ``prog_req_ms``."""


def read(data):
    waits = data.records.get("sched_wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
