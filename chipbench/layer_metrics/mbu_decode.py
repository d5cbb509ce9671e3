"""Model step: bytes one decode step must move (every weight, the f32 SSM
state read and written, the convolution window; from shapes,
``chipbench/work/mamba2_decode.py``) times the decode steps in the window,
over the host-clock decode time, against the chip's HBM bandwidth, in
percent. Moves ``tpot_ms``."""


def read(data):
    rec = data.records
    steps, secs = rec.get("decode_steps", 0), rec.get("decode_s", 0.0)
    if steps <= 0 or secs <= 0:
        return None
    return 100.0 * rec["decode_step_bytes"] * steps / (
        secs * data.peaks.hbm_bytes_s)
