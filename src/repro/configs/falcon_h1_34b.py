"""Falcon-H1-34B-Instruct [huggingface.co/tiiuae/Falcon-H1-34B-Instruct,
config.json; equations: transformers' ``falcon_h1``] — every block runs a
Mamba-2 mixer (32 heads of 128, 2 groups of B/C, state 256, inner width
4096 decoupled from expand · hidden, convolution bias, gated RMSNorm per
group) and GQA attention (20/4 heads, RoPE θ 1e11) in parallel on the
same normed input and sums them, then a SwiGLU MLP; μP multipliers scale
the embedding, the SSM's input, both mixers' outputs, the keys, the SSM's
z, x, B, C and dt projections, the MLP's gate and down projection and the
LM head (the published attention input multiplier is 1). The RMSNorm
epsilon is the program's 1e-6 (published 1e-5)."""
import dataclasses

from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="falcon-h1-34b", family="hybrid",
        n_layers=72, d_model=5120, n_heads=20, n_kv_heads=4, head_dim=128,
        d_ff=21504, vocab=261120, rope_theta=1e11,
        ssm_state=256, ssm_headdim=128, ssm_inner=4096, ssm_groups=2,
        ssm_chunk=128, conv_width=4, conv_bias=True,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125,
        attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804,
        ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    )


def stage() -> ModelConfig:
    """One chip of an 8-stage pipeline on one v5e-8 host (9 whole layers a
    stage, the embedding and LM head vocabulary-parallel over the 8 chips):
    4 of its stage's layers and its 1/8 of the vocabulary, 32,640 rows.
    Every width is the published one."""
    return dataclasses.replace(config(), n_layers=4, vocab=261120 // 8)
