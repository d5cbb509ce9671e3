"""One Mamba2 prefill over ``batch`` prompts of ``seq`` tokens (the
chunked SSD algorithm of arXiv:2405.21060, §6), counted from shapes.

Per token and layer: the input projections to z, x (``d_inner`` each),
B, C (``state`` each) and dt (``heads``), the output projection, and the
depthwise causal convolution over x, B and C. Per chunk of ``chunk``
tokens and layer: C·Bᵀ (chunk² · state), the intra-chunk outputs
(chunk² · heads · headdim), the chunk end-states and the inter-chunk
outputs (chunk · state · heads · headdim each), and the state recurrence.
Per sequence: the unembedding of the last position. A multiply-add is two
operations. Bytes: the weights read once, the activations between layers
written and read once, the final states written once.

The sizes are keyword arguments named as the configuration's
``program.sizes`` names them; sizes a count does not need are ignored."""
from chipbench import harness

# the kernels a prefill launches whose device time the trace shows
KERNELS = ("c4_statescan",)


def work(batch: int, seq: int, n_layers: int, d_model: int, d_inner: int,
         state: int, heads: int, headdim: int, chunk: int, conv_width: int,
         vocab: int, weight_bytes: float, act_itemsize: int = 2,
         **_sizes) -> tuple[float, float]:
    tokens = batch * seq
    proj = (2 * d_model * (2 * d_inner + 2 * state + heads)
            + 2 * d_inner * d_model)
    conv = 2 * conv_width * (d_inner + 2 * state)
    n_chunks = batch * (-(-seq // chunk))
    per_chunk = (2 * chunk * chunk * state
                 + 2 * chunk * chunk * heads * headdim
                 + 2 * 2 * chunk * state * heads * headdim
                 + 2 * heads * headdim * state)
    flops = n_layers * (tokens * (proj + conv) + n_chunks * per_chunk)
    flops += batch * 2 * d_model * vocab
    acts = n_layers * 2 * tokens * d_model * act_itemsize
    states = n_layers * batch * heads * headdim * state * 4
    return float(flops), float(weight_bytes + acts + states)


def kernels(batch: int, seq: int, n_layers: int, state: int, heads: int,
            headdim: int, chunk: int, **_sizes) -> dict:
    """kernel → [flops, bytes] of one prefill: one ``c4_statescan`` over
    the chunk states per layer."""
    f, b = harness.load_module("work", "c4_statescan").work(
        batch=batch, chunks=-(-seq // chunk), heads=heads, headdim=headdim,
        state=state)
    return {"c4_statescan": [f * n_layers, b * n_layers]}
