"""Device: share of the decode steps' host spans (``cb.decode_step``) in
which no operation ran on the device, in percent. Moves ``tpot_ms``."""
from chipbench import trace_reduce


def read(data):
    if data.trace is None:
        return None
    spans = [(e.start_ns, e.end_ns) for e in data.trace.host_spans
             if e.name == "cb.decode_step"]
    if not spans:
        return None
    busy, total = trace_reduce.busy_within(data.trace, spans, data.devices)
    if total <= 0:
        return None
    return 100.0 * (1.0 - busy / total)
