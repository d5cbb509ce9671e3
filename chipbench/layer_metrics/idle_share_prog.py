"""Device: share of the traced window in which no operation ran on the
device, averaged over the cell's chips, in percent. Moves
``prog_req_ms``."""
from chipbench.layer_metrics._common import idle_percent


def read(data):
    return idle_percent(data)
