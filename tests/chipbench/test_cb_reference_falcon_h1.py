"""Falcon-H1: the plain reference agrees with the program's prefill and
decode at a small size on the CPU (two B/C groups, an SSM inner width
decoupled from expand · d_model, convolution bias, the published μP
multipliers) and its float8 control does not; the drawn queries' tie to
their own keys; the grouped SSD against the token recurrence; the
one-chip stage against the published config; the decode counts by part;
the readers of the attention and SSD halves on a hand-made trace."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, peaks

R = harness.load_module("reference", "falcon-h1-34b")
CELL = "falcon-h1-34b.long-ctx-decode"
DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def small():
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("falcon-h1-34b-stage").reduced(),
                              n_layers=3)
    program = harness.cell_from(harness.load_benchmark(),
                                CELL).config["program"]
    lm = harness.load_module("drivers", "lm_serve")
    return cfg, lm.sizes_of(program, cfg)


def test_the_small_model_keeps_what_falcon_h1_forces(small):
    cfg, s = small
    assert cfg.ssm_groups == 2 and cfg.ssm_heads == 4
    assert cfg.d_inner != cfg.ssm_expand * cfg.d_model
    assert cfg.conv_bias and cfg.family == "hybrid"
    assert cfg.key_multiplier != 1 and cfg.ssm_multipliers != (1.0,) * 5
    assert s["groups"] == 2 and s["d_inner"] == cfg.d_inner


def test_weights_have_the_programs_parameter_tree(small):
    from repro.models.params import abstract_params
    cfg, s = small
    p = R.weights(s, jax.random.key(0), jnp.float32)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract_params(cfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), p) == want


def test_queries_meet_their_own_keys_far_above_the_other_scores():
    """At the published head_dim, the drawn queries give scores of spread
    ``QUERY_SCALE`` against other tokens' keys and meet the token's own
    key ``QUERY_TIE · QUERY_SCALE · √hd`` above them, so that attention
    over a long context is peaked (the reference's docstring)."""
    d, heads, kv, hd = 512, 4, 2, 128
    s = dict(n_layers=1, d_model=d, d_ff=8, heads=heads, kv_heads=kv,
             head_dim=hd, d_inner=8, ssm_heads=2, groups=1, state=4,
             conv_width=2, vocab_rows=8)
    attn = R.weights(s, jax.random.key(7), jnp.float32)["layers"]["attn"]
    u = jax.random.normal(jax.random.key(8), (256, d))
    m = R.PUBLISHED["attention_in_multiplier"]
    q = jnp.einsum("td,dhk->thk", u * m, attn["wq"][0])
    k = jnp.einsum("td,dhk->thk", u * m, attn["wk"][0]) * R.PUBLISHED[
        "key_multiplier"]
    k = jnp.repeat(k, heads // kv, axis=1)
    sc = jnp.einsum("thk,shk->hts", q, k) / hd ** 0.5
    own = jnp.diagonal(sc, axis1=1, axis2=2)
    other = sc[:, ~np.eye(256, dtype=bool)]
    assert float(jnp.std(other)) == pytest.approx(R.QUERY_SCALE, rel=0.1)
    assert float(jnp.median(own)) == pytest.approx(
        R.QUERY_TIE * R.QUERY_SCALE * hd ** 0.5, rel=0.1)


def test_falcon_reference_matches_the_programs_f32_prefill_and_decode(small):
    from repro.models import model as M
    cfg, s = small
    p = R.weights(s, jax.random.key(3), jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        logits, cache = M.prefill(cfg, p, {"tokens": toks[:, :32]})
        cache = M.grow_cache(cfg, cache, 32, 40)
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


def test_falcon_fp8_control_and_a_wrong_token_read_above_zero(small):
    cfg, s = small
    p = R.weights(s, jax.random.key(3), jnp.float32)
    toks = jax.random.randint(jax.random.key(2), (2, 48), 0, cfg.vocab)
    pos = jnp.broadcast_to(jnp.arange(16, 48), (2, 32))
    ctrl = R.gaps_at(s, p, toks, pos, jnp.zeros((2, 32), jnp.int32),
                     control=True)
    assert float(jnp.max(ctrl)) > 0.05
    wrong = R.gaps_at(s, p, toks, pos, jnp.zeros((2, 32), jnp.int32))
    assert float(jnp.max(wrong)) > 1.0


def _recurrence(X, A, B, C):
    """h_t = exp(A_t) h_{t-1} + B_t x_t, y_t = C_t h_t, each head reading
    its group's B and C."""
    b, t, h, p = X.shape
    rep = h // B.shape[2]
    state = jnp.zeros((b, h, p, B.shape[-1]))
    ys = []
    for i in range(t):
        Bh = jnp.repeat(B[:, i], rep, axis=1)
        Ch = jnp.repeat(C[:, i], rep, axis=1)
        state = (jnp.exp(A[:, i])[..., None, None] * state
                 + jnp.einsum("bhn,bhp->bhpn", Bh, X[:, i]))
        ys.append(jnp.einsum("bhn,bhpn->bhp", Ch, state))
    return jnp.stack(ys, 1), state


def test_grouped_ssd_chunks_agree_with_the_token_recurrence():
    """The reference's chunked SSD with two groups of B and C (heads 0-1
    read group 0, heads 2-3 group 1) against the plain recurrence."""
    k = jax.random.split(jax.random.key(0), 4)
    b, t, h, p, g, n = 2, 12, 4, 2, 2, 3
    X = jax.random.normal(k[0], (b, t, h, p))
    A = -jax.random.uniform(k[1], (b, t, h))
    B = jax.random.normal(k[2], (b, t, g, n))
    C = jax.random.normal(k[3], (b, t, g, n))
    want, _ = _recurrence(X, A, B, C)
    np.testing.assert_allclose(R.ssd(X, A, B, C, 4), want, rtol=1e-5,
                               atol=1e-5)


def test_programs_grouped_ssd_prefill_agrees_with_its_token_steps(small):
    """The program's chunked SSD (``ssd_forward``) with two groups against
    its own one-token step (``ssd_decode``) run over the same inputs: the
    same outputs and the same final state."""
    from repro.models import ssm
    cfg, s = small
    p = jax.tree.map(lambda a: a[0],
                     R.weights(s, jax.random.key(5), jnp.float32)["layers"]
                     ["ssm"])
    u = jax.random.normal(jax.random.key(6), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, (state, conv) = ssm.ssd_forward(cfg, p, u, return_state=True)
        w = cfg.conv_width - 1
        gn = cfg.ssm_groups * cfg.ssm_state
        cache = {"x": jnp.zeros((2, w, cfg.d_inner)),
                 "B": jnp.zeros((2, w, gn)), "C": jnp.zeros((2, w, gn))}
        st = jnp.zeros_like(state)
        steps = []
        for i in range(32):
            y, cache, st = ssm.ssd_decode(cfg, p, u[:, i:i + 1], cache, st)
            steps.append(y)
    scale = float(jnp.max(jnp.abs(out)))
    np.testing.assert_allclose(jnp.concatenate(steps, 1), out, rtol=1e-4,
                               atol=1e-5 * scale)
    np.testing.assert_allclose(st, state, rtol=1e-4, atol=1e-5)
    for key in "xBC":     # the projections' last inputs, to rounding
        np.testing.assert_allclose(cache[key], conv[key], rtol=1e-4,
                                   atol=1e-5 * scale)


def test_stage_differs_from_the_published_config_only_in_depth_and_vocab():
    from repro.configs import ARCHS, STAGES, get_config
    full, stage = get_config("falcon-h1-34b"), get_config(
        "falcon-h1-34b-stage")
    differ = {f.name for f in dataclasses.fields(full)
              if getattr(full, f.name) != getattr(stage, f.name)}
    assert differ == {"n_layers", "vocab"}
    assert (full.n_layers, full.vocab) == (72, 261120)
    assert (stage.n_layers, stage.vocab, stage.vocab_padded) == (4, 32640,
                                                                  32768)
    assert "falcon_h1_34b" in ARCHS and "falcon_h1_34b_stage" in STAGES
    assert "falcon_h1_34b_stage" not in ARCHS
    assert abs(full.n_params() - 33.6e9) / 33.6e9 < 0.01


def test_configuration_file_is_what_the_program_runs():
    """The configuration's ``program.runs`` holds for the stage (set-up
    checks it) and its μP multiplier lists are the program's."""
    cell = harness.cell_from(harness.load_benchmark(), CELL)
    lm = harness.load_module("drivers", "lm_serve")
    cfg = lm.program_config(cell.config["program"])
    assert list(cfg.ssm_multipliers) == cell.config["ssm_multipliers"]
    assert list(cfg.mlp_multipliers) == cell.config["mlp_multipliers"]
    assert cell.config["num_hidden_layers"] == cfg.n_layers
    assert cell.config["vocab_size"] == cfg.vocab
    assert cell.config["mamba_d_ssm"] == cfg.d_inner
    assert cell.config["mamba_n_groups"] == cfg.ssm_groups
    assert R.PUBLISHED == cell.config


def work(part, **kw):
    return getattr(harness.load_module("work", "falcon_h1_decode"), part)(**kw)


def test_decode_parts_by_hand_at_a_tiny_shape():
    # attention: d 4, 1 query and 1 kv head of 2, one layer, context 3
    f, b = work("attention", batch=2, context=3.0, n_layers=1, d_model=4,
                heads=1, kv_heads=1, head_dim=2)
    proj = 4 * 3 * 2 + 2 * 4                            # q, k, v; out
    assert b == 2 * (proj + 2 * 2 * 1 * 2 * 4)          # weights, K/V
    assert f == 2 * (2 * proj + 4 * 1 * 2 * 3)
    # ssd: d 2, inner 2, 1 head of 2, 1 group of state 1, conv 2
    f, b = work("ssd", batch=1, n_layers=1, d_model=2, d_inner=2,
                ssm_heads=1, headdim=2, groups=1, state=1, conv_width=2)
    proj = 2 * (4 + 2 + 1) + 2 * 2                      # 18
    params = proj + 4 * 3 + 2 + 3                       # conv w and b, ...
    assert b == 2 * params + 2 * 4 * 2 + 1 * 4 * 2 * 2
    assert f == 2 * proj + 2 * 2 * 4 + 4 * 2
    # the sum, and the LM head read whole at its padded rows
    sizes = dict(n_layers=1, d_model=2, d_inner=2, ssm_heads=1, headdim=2,
                 groups=1, state=1, conv_width=2, heads=1, kv_heads=1,
                 head_dim=2, d_ff=3, vocab=5, vocab_rows=8)
    parts = [work(p, batch=1, context=4.0, **sizes)
             for p in ("attention", "ssd", "mlp", "head")]
    assert work("work", batch=1, context=4.0, weight_bytes=1e9, **sizes) \
        == (sum(f for f, _ in parts), sum(b for _, b in parts))
    assert work("head", batch=1, **sizes) == (2 * 2 * 5, 2 * 8 * 2)


def test_decode_step_at_the_cell_moves_six_and_a_half_gigabytes():
    cell = harness.cell_from(harness.load_benchmark(), CELL)
    program = cell.config["program"]
    sizes = {n: program["runs"][a] for n, a in program["sizes"].items()}
    kw = dict(batch=cell.traffic["batch"], context=17200.0, **sizes)
    total = work("work", weight_bytes=0.0, **kw)[1]
    assert 6.5e9 < total < 6.65e9
    shares = {p: work(p, **kw)[1] / total
              for p in ("attention", "ssd", "mlp", "head")}
    assert shares == pytest.approx({"attention": 0.38, "ssd": 0.17,
                                    "mlp": 0.40, "head": 0.05}, abs=0.01)
    # the KV part grows with the context, the rest does not
    more = dict(kw, context=18200.0)
    assert work("attention", **more)[1] > work("attention", **kw)[1]
    for part in ("ssd", "mlp", "head"):
        assert work(part, **more) == work(part, **kw)


def _scoped_run(trace_file, tmp_path_factory):
    from jax.profiler import ProfileData
    from chipbench import trace_reduce
    with open(os.path.join(DATA, trace_file)) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("trace") / "host.xplane.pb"
    path.write_bytes(xspace)
    trace = trace_reduce.load(str(path))
    # one layer of hand-countable sizes, one row, 100 steps at context 127
    sizes = dict(n_layers=1, d_model=64, heads=1, kv_heads=1, head_dim=64,
                 d_inner=8, ssm_heads=1, headdim=8, groups=1, state=4,
                 conv_width=2)
    cell = harness.Cell(
        name=CELL, chips=1, config_name="falcon-h1-34b",
        config={"program": {"runs": sizes, "sizes": {k: k for k in sizes},
                            "work": {"decode": "falcon_h1_decode"}}},
        traffic_name="long-ctx-decode", traffic={"batch": 1},
        end_to_end=[], per_layer=[])
    return harness.RunData(
        cell=cell, peaks=peaks.PEAKS["TPU v5 lite"], devices=[0],
        records={"decode_steps": 100, "decode_context": 127.0},
        counters={}, work={}, trace=trace,
        reduction=trace_reduce.reduce(trace, devices=[0]))


@pytest.mark.parametrize("metric,scope_us,step_bytes", [
    # attention: (64·3·64 + 64·64) weights + 2·64·128 K/V, 2 bytes each
    ("attn_roofline.decode", 13.0, 2 * (16384 + 16384)),
    # ssd: 64·(2·8 + 2·4 + 1) + 8·64 projections, 16·3 conv, 8 norm and
    # 3 per-head weights; the f32 state of 8·4 and the conv window of 16
    # read once (their write-back is outside the scope)
    ("ssm_roofline.decode", 15.0, 2 * (2112 + 48 + 8 + 3) + 8 * 4 * 4
     + 16 * 2),
])
def test_decode_half_rooflines_on_a_hand_made_trace(metric, scope_us,
                                                    step_bytes,
                                                    tmp_path_factory):
    """The ``attn`` and ``ssm`` ops of ``scoped_ops.pbtxt`` take 13 and
    15 us: 100 steps whose least time is their bytes at 819 GB/s."""
    data = _scoped_run("scoped_ops.pbtxt", tmp_path_factory)
    got = harness.load_module("layer_metrics", metric).read(data)
    bw = peaks.PEAKS["TPU v5 lite"].hbm_bytes_s
    assert got == pytest.approx(100 * 100 * step_bytes / bw
                                / (scope_us * 1e-6))


@pytest.mark.parametrize("metric", ["attn_roofline.decode",
                                    "ssm_roofline.decode"])
def test_decode_half_rooflines_read_none_where_no_op_has_the_scope(
        metric, tmp_path_factory):
    data = _scoped_run("two_kernels.pbtxt", tmp_path_factory)
    assert harness.load_module("layer_metrics", metric).read(data) is None
    data.trace = None
    assert harness.load_module("layer_metrics", metric).read(data) is None
