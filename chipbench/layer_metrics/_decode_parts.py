"""Roofline shares of the parts of a decode step that the program names
with ``jax.named_scope`` (``attn``, ``ssm``, ``mlp``, ``models/model.py``)."""
from __future__ import annotations

from chipbench import harness, trace_reduce
from chipbench import peaks as peaks_mod


def scope_roofline(data, scope: str, part: str, **count_kw) -> float | None:
    """The window's decode steps times the least time of one step's
    ``part`` (the count of that name in the cell's decode work file, at
    the sizes of the configuration's ``program.runs``, the traffic's batch
    and the window's mean filled context, with ``count_kw``), over the
    device time of the ops in ``scope``, in percent; None where no op
    carries the scope."""
    rec = data.records
    steps = rec.get("decode_steps", 0)
    if data.trace is None or steps <= 0:
        return None
    secs = trace_reduce.scope_seconds(data.trace, data.devices, scope)
    if not secs:
        return None
    program = data.cell.config["program"]
    sizes = {name: program["runs"][attr]
             for name, attr in program["sizes"].items()}
    count = getattr(harness.load_module("work", program["work"]["decode"]),
                    part)
    f, b = count(batch=data.cell.traffic["batch"],
                 context=rec["decode_context"], **sizes, **count_kw)
    return 100.0 * steps * peaks_mod.least_seconds(f, b, data.peaks) / secs
