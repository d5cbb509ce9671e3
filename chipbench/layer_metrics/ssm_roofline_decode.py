"""Mixers (``models/ssm.py`` ``ssd_decode``): the decode steps' SSD half at
its roofline — the least time of what the ops in the ``ssm`` scope must
move, its weights and one read of the f32 state and of the convolution
window (``chipbench/work/falcon_h1_decode.py`` ``ssd`` without
``written``: the layer scan writes the new state and window back into the
stacked cache outside the scope), over the device time of those ops, in
percent. Moves ``tpot_ms``."""
from chipbench.layer_metrics._decode_parts import scope_roofline


def read(data):
    return scope_roofline(data, "ssm", "ssd", written=False)
