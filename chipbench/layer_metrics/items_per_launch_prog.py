"""Program layer (``core/isa.py``, ``core/program.py``, ``graph/plan.py``):
requests completed per Pallas launch in the window — program launches
(``DISPATCH_STATS.kernel_launches``) plus named instructions dispatched
to their kernel (``isa.registry.dispatch_counts``). Coalescing raises it;
a program that needs several launches per request lowers it. Moves
``prog_req_ms``."""


def read(data):
    launches = data.counters.get("pallas_launches", 0)
    done = data.records.get("completed", 0)
    if launches <= 0 or done <= 0:
        return None
    return done / launches
