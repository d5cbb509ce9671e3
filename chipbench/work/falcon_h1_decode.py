"""One Falcon-H1 decode step for ``batch`` sequences (one token each),
counted from shapes, by part of the block: ``attention`` (its weights and
the keys and values at the filled context), ``ssd`` (the Mamba-2 half: its
weights, the f32 state and the convolution window, each read and, with
``written``, written back), ``mlp``
and ``head`` (the LM head's slice of the vocabulary, read whole).
``work`` is their sum. A multiply-add is two operations; weights and
activations are ``itemsize`` bytes. Sizes a count does not need are
ignored, and so is ``weight_bytes``: the embedding table, which it
includes, is not read whole by a step.

``context`` is the mean number of cache positions filled when a step ran
(``records["decode_context"]``): attention reads that many keys and values
of each row, where the program reads its cache's whole capacity."""


def attention(batch: int, context: float, n_layers: int, d_model: int,
              heads: int, kv_heads: int, head_dim: int, itemsize: int = 2,
              **_sizes) -> tuple[float, float]:
    proj = d_model * (heads + 2 * kv_heads) * head_dim + heads * head_dim \
        * d_model
    flops = batch * n_layers * (2 * proj + 2 * 2 * heads * head_dim * context)
    kv = batch * n_layers * 2 * kv_heads * head_dim * (context + 1)
    return float(flops), float((n_layers * proj + kv) * itemsize)


def ssd(batch: int, n_layers: int, d_model: int, d_inner: int,
        ssm_heads: int, headdim: int, groups: int, state: int,
        conv_width: int, itemsize: int = 2, written: bool = True,
        **_sizes) -> tuple[float, float]:
    conv_dim = d_inner + 2 * groups * state
    proj = d_model * (2 * d_inner + 2 * groups * state + ssm_heads) \
        + d_inner * d_model
    params = proj + conv_dim * (conv_width + 1) + d_inner + 3 * ssm_heads
    flops = batch * n_layers * (2 * proj + 2 * conv_width * conv_dim
                                + 2 * 2 * ssm_heads * headdim * state)
    passes = 2 if written else 1
    state_bytes = batch * n_layers * ssm_heads * headdim * state * 4 * passes
    conv_bytes = (batch * n_layers * (conv_width - 1) * conv_dim * itemsize
                  * passes)
    return float(flops), float(n_layers * params * itemsize + state_bytes
                               + conv_bytes)


def mlp(batch: int, n_layers: int, d_model: int, d_ff: int,
        itemsize: int = 2, **_sizes) -> tuple[float, float]:
    mats = 3 * d_model * d_ff
    return float(batch * n_layers * 2 * mats), float(n_layers * mats
                                                      * itemsize)


def head(batch: int, d_model: int, vocab: int, vocab_rows: int,
         itemsize: int = 2, **_sizes) -> tuple[float, float]:
    return float(batch * 2 * d_model * vocab), float(d_model * vocab_rows
                                                     * itemsize)


def work(batch: int, context: float, weight_bytes: float = 0.0,
         **sizes) -> tuple[float, float]:
    parts = [attention(batch=batch, context=context, **sizes),
             ssd(batch=batch, **sizes), mlp(batch=batch, **sizes),
             head(batch=batch, **sizes)]
    return (float(sum(f for f, _ in parts)),
            float(sum(b for _, b in parts)))
