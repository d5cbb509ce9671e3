"""One Mamba2 decode step for ``batch`` sequences (one token each),
counted from shapes.

Bytes: every weight read once (the tied embedding is read whole by the
unembedding), the f32 SSM state (batch, heads, headdim, state) of every
layer read and written, and the convolution window of x, B and C read and
written. Operations: the projections, convolution, state update and
readout per token and layer, and the unembedding. Sizes a count does not
need are ignored, and so is ``context``: the state and the convolution
window have the same size at every position."""


def work(batch: int, n_layers: int, d_model: int, d_inner: int, state: int,
         heads: int, headdim: int, conv_width: int, vocab: int,
         weight_bytes: float, act_itemsize: int = 2,
         **_sizes) -> tuple[float, float]:
    proj = (2 * d_model * (2 * d_inner + 2 * state + heads)
            + 2 * d_inner * d_model)
    conv = 2 * conv_width * (d_inner + 2 * state)
    ssm = 2 * 2 * heads * headdim * state     # the update, then C·state
    flops = batch * (n_layers * (proj + conv + ssm) + 2 * d_model * vocab)
    state_bytes = n_layers * batch * heads * headdim * state * 4 * 2
    conv_bytes = (n_layers * batch * (conv_width - 1) * (d_inner + 2 * state)
                  * act_itemsize * 2)
    return float(flops), float(weight_bytes + state_bytes + conv_bytes)
