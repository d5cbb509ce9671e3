"""Model step: device idle while the host samples the next token
(``launch/serve.py:sample``, the program's ``repro.sample`` span) inside
the decode steps' harness spans (``cb.decode_step``). Milliseconds per
decode step. Moves ``tpot_ms``."""
from chipbench.layer_metrics._spans import idle_ms_per


def read(data):
    return idle_ms_per(data, "decode_steps", inside=("repro.sample",),
                       within=("cb.decode_step",))
