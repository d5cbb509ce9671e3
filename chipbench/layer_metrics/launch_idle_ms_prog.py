"""Program layer (``core/isa.py``, ``core/program.py``, ``kernels/``):
device idle while the host issues a batch's device work (the program's
``repro.launch`` span: ``Program.__call__``, named-instruction dispatch,
the eagerly dispatched levels of the mergesort). Milliseconds per
completed request. Moves ``prog_req_ms``."""
from chipbench.layer_metrics._spans import idle_ms_per


def read(data):
    return idle_ms_per(data, "completed", inside=("repro.launch",))
