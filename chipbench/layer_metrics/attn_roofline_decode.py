"""Mixers (``models/attention.py`` ``attention_decode``): the decode steps'
attention half at its roofline — the least time of its weights and the
keys and values at the window's mean filled context
(``chipbench/work/falcon_h1_decode.py`` ``attention``) over the device time
of the ops in the ``attn`` scope, in percent. Moves ``tpot_ms``."""
from chipbench.layer_metrics._decode_parts import scope_roofline


def read(data):
    return scope_roofline(data, "attn", "attention")
