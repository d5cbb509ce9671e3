"""Model step (``models/model.py``, ``models/ssm.py``): the operations of
every prefill in the window (``chipbench/work/mamba2_prefill.py``, from
shapes) over the host-clock time from sending each batch to its first
token, against the chip's bf16 peak, in percent. Moves ``ttft_ms``."""


def read(data):
    flops = data.records.get("prefill_flops", 0.0)
    secs = sum(data.records.get("ttft_s", ()))
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / (secs * data.peaks.flops_bf16)
