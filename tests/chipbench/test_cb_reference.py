"""The plain references agree with the program at small sizes on the CPU,
and their lower-precision controls do not."""
import dataclasses

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench import harness

R = harness.load_module("reference", "mamba2-1.3b")
S = harness.load_module("reference", "stream-apps")


@pytest.fixture(scope="module")
def small():
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("mamba2-1.3b").reduced(),
                              n_layers=3)
    program = harness.cell_from(harness.load_benchmark(),
                                "mamba2-1.3b.prefill").config["program"]
    lm = harness.load_module("drivers", "lm_serve")
    return cfg, lm.sizes_of(program, cfg)


def test_weights_have_the_programs_parameter_tree(small):
    from repro.models.params import abstract_params
    cfg, s = small
    p = R.weights(s, jax.random.key(0), jnp.float32)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract_params(cfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), p) == want


def test_mamba2_reference_matches_the_programs_f32_prefill_and_decode(small):
    from repro.models import model as M
    cfg, s = small
    p = R.weights(s, jax.random.key(3), jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        logits, cache = M.prefill(cfg, p, {"tokens": toks[:, :32]})
        outs = [logits]
        for i in range(32, 39):          # decode through the cache
            logits, cache = M.decode_step(cfg, p, cache, toks[:, i:i + 1],
                                          jnp.int32(i))
            outs.append(logits)
    prog = jnp.stack(outs, 1)
    pos = jnp.broadcast_to(jnp.arange(31, 39), (2, 8))
    ref = R.logits_at(s, p, toks[:, :39], pos)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(prog - ref))) <= 1e-5 * scale
    # the program's own greedy tokens lie on the reference's best
    gaps = R.gaps_at(s, p, toks[:, :39], pos, jnp.argmax(prog, -1))
    assert float(jnp.max(gaps)) == pytest.approx(0.0, abs=1e-6)


def test_mamba2_fp8_control_and_a_wrong_token_read_above_zero(small):
    cfg, s = small
    p = R.weights(s, jax.random.key(3), jnp.float32)
    toks = jax.random.randint(jax.random.key(2), (2, 48), 0, cfg.vocab)
    pos = jnp.broadcast_to(jnp.arange(16, 48), (2, 32))
    ctrl = R.gaps_at(s, p, toks, pos, jnp.zeros((2, 32), jnp.int32),
                     control=True)
    assert float(jnp.max(ctrl)) > 0.05
    wrong = R.gaps_at(s, p, toks, pos, jnp.zeros((2, 32), jnp.int32))
    assert float(jnp.max(wrong)) > 1.0


def test_ssd_chunks_agree_with_the_token_recurrence():
    """The chunked SSD (the paper's Listing 1) against the plain
    recurrence h_t = exp(A_t) h_{t-1} + B_t x_t, y_t = C_t h_t."""
    k = jax.random.split(jax.random.key(0), 4)
    b, t, h, p, n = 2, 12, 3, 2, 4
    X = jax.random.normal(k[0], (b, t, h, p))
    A = -jax.random.uniform(k[1], (b, t, h))
    B = jax.random.normal(k[2], (b, t, n))
    C = jax.random.normal(k[3], (b, t, n))
    got = R.ssd(X, A, B, C, 4)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for i in range(t):
        state = (jnp.exp(A[:, i])[..., None, None] * state
                 + jnp.einsum("bn,bhp->bhpn", B[:, i], X[:, i]))
        ys.append(jnp.einsum("bn,bhpn->bhp", C[:, i], state))
    np.testing.assert_allclose(got, jnp.stack(ys, 1), rtol=1e-5, atol=1e-5)


KINDS = ["copy", "scale", "add", "triad", "prefix_sum", "mergesort"]


@pytest.mark.parametrize("kind", KINDS)
def test_stream_references_against_the_programs_oracles(kind):
    """Each kind's reference against its program (the oracle off the
    chip), and its bfloat16 control well away from both."""
    import repro.kernels  # noqa: F401
    mod = harness.load_module("programs", kind)
    rng = np.random.default_rng(0)
    n = 4096
    if mod.KEYS:
        vecs = tuple(rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
                     for _ in range(mod.VECTORS))
    else:
        vecs = tuple(rng.uniform(size=n).astype(np.float32)
                     for _ in range(mod.VECTORS))
    ops_ = mod.operands(tuple(jnp.asarray(v) for v in vecs), 1.5)
    got = mod.target(n)(*ops_)
    got = tuple(got) if isinstance(got, (tuple, list)) else (got,)
    ops_ = tuple(np.asarray(v) for v in ops_)
    want = S.answer(kind, ops_)
    err = S.compare(kind, got, want)
    assert err <= (0 if mod.KEYS else 1e-6)
    if not mod.KEYS:
        low = S.answer(kind, ops_, ml_dtypes.bfloat16)
        assert S.compare(kind, low, want) > 1e-3


def test_program_kinds_of_the_configuration_have_their_files():
    cell = harness.cell_from(harness.load_benchmark(), "stream-apps.bulk")
    limits = cell.config["check"]
    for kind in cell.config["programs"]:
        mod = harness.load_module("programs", kind)
        assert mod.NUMBER in limits and mod.VECTORS >= 1
        assert set(mod.work(1024)) == set(mod.KERNELS)
