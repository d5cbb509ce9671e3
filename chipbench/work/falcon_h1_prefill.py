"""One Falcon-H1 prefill over ``batch`` prompts of ``seq`` tokens, counted
from shapes. Per token and layer: the attention projections, the Mamba-2
projections (z, x: ``d_inner``; B, C: ``groups`` · ``state``; dt) and
output projection, the depthwise convolution over x, B and C, and the
SwiGLU MLP. Per sequence and layer: causal attention over the prompt
(half of the seq² score and value products). Per chunk and layer the SSD
terms of ``mamba2_prefill``, with C·Bᵀ once per group. Per sequence the
LM head at the last position. A multiply-add is two operations. Bytes:
the weights read once, the activations between layers written and read
once, the keys, values and final states written once.

The sizes are keyword arguments named as the configuration's
``program.sizes`` names them; sizes a count does not need are ignored."""
from chipbench import harness

# the kernels a prefill launches whose device time the trace shows
KERNELS = ("c4_statescan",)


def work(batch: int, seq: int, n_layers: int, d_model: int, heads: int,
         kv_heads: int, head_dim: int, d_ff: int, d_inner: int,
         ssm_heads: int, headdim: int, groups: int, state: int, chunk: int,
         conv_width: int, vocab: int, weight_bytes: float,
         act_itemsize: int = 2, **_sizes) -> tuple[float, float]:
    tokens = batch * seq
    gn = groups * state
    attn = 2 * (d_model * (heads + 2 * kv_heads) * head_dim
                + heads * head_dim * d_model)
    ssm = (2 * d_model * (2 * d_inner + 2 * gn + ssm_heads)
           + 2 * d_inner * d_model + 2 * conv_width * (d_inner + 2 * gn))
    mlp = 3 * 2 * d_model * d_ff
    scores = batch * 2 * heads * head_dim * seq * seq   # causal QKᵀ and PV
    n_chunks = batch * (-(-seq // chunk))
    per_chunk = (2 * chunk * chunk * gn
                 + 2 * chunk * chunk * ssm_heads * headdim
                 + 2 * 2 * chunk * state * ssm_heads * headdim
                 + 2 * ssm_heads * headdim * state)
    flops = n_layers * (tokens * (attn + ssm + mlp) + scores
                        + n_chunks * per_chunk)
    flops += batch * 2 * d_model * vocab
    acts = n_layers * 2 * tokens * d_model * act_itemsize
    cache = n_layers * (tokens * 2 * kv_heads * head_dim * act_itemsize
                        + batch * ssm_heads * headdim * state * 4)
    return float(flops), float(weight_bytes + acts + cache)


def kernels(batch: int, seq: int, n_layers: int, state: int, ssm_heads: int,
            headdim: int, chunk: int, **_sizes) -> dict:
    """kernel → [flops, bytes] of one prefill: one ``c4_statescan`` over
    the chunk states per layer."""
    f, b = harness.load_module("work", "c4_statescan").work(
        batch=batch, chunks=-(-seq // chunk), heads=ssm_heads,
        headdim=headdim, state=state)
    return {"c4_statescan": [f * n_layers, b * n_layers]}
