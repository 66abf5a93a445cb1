"""The lfm2_moe shaped block (models/lfm2_moe.py): the double-gated short
convolution, grouped-head attention with a norm on q and k, the
bias-selected sigmoid router with no shared expert (distributed/moe.py
DroplessMoELayer) and the tied head, at a small size on the CPU, against
the plain reference benchmark/reference/lfm2_moe_ref.py and plain einsum
code."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from benchmark import program_lfm2_moe as adapter
from benchmark.reference import lfm2_moe_ref as ref
from paddle_tpu.distributed import moe
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import FunctionalModule
from paddle_tpu.models import (GPTPretrainingCriterion, Lfm2MoeConfig,
                               Lfm2MoeForCausalLM, lfm2_moe)
from paddle_tpu.models.gpt import _local_attention_val
from paddle_tpu.models.lfm2_moe import Lfm2MoeDecoderLayer
from paddle_tpu.models.mla_moe import rms_norm
from paddle_tpu.ops.flash_attention import (_default_block,
                                            flash_attention_sharded_ok,
                                            flash_attention_val,
                                            flash_block_choice)

# The tests' size: the cell's pattern (one dense conv layer, then a period
# of attention and three convs), 8 query heads over 2 (the published group
# of 4). initializer_range 0.1: sqrt(h) * std ~ 0.8 as at the published
# widths (sqrt(2048) * 0.02 = 0.9), so that the blocks move the residual
# stream as they do there and a wrong block shows in the logits
PATTERN = ("conv", "full_attention", "conv", "conv", "conv")
TEST = dict(vocab_size=512, hidden_size=64, num_hidden_layers=5,
            layer_types=PATTERN, num_dense_layers=1, intermediate_size=128,
            moe_intermediate_size=32, num_attention_heads=8,
            num_key_value_heads=2, initializer_range=0.1, num_experts=32,
            num_experts_per_tok=4)


def _cfg(**kw):
    return Lfm2MoeConfig(**{**TEST, **kw})


def _ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in (
        "layer_types", "num_dense_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "norm_eps", "rope_theta",
        "num_experts_per_tok", "routed_scaling_factor", "experts_held")}


def _model(cfg, seed=3, bias=0.05):
    """The model with its vectors moved off their initial ones and zeros
    (a norm weight of one hides a norm left out of q and k, a zero bias a
    bias that weighs): the selection bias uniform in (-bias, bias)."""
    model = Lfm2MoeForCausalLM(cfg, seed=seed)
    r = np.random.RandomState(seed)
    for _, p in model.named_parameters():
        if p.ndim == 1:
            p.set_value((np.asarray(p._value, np.float32) + r.uniform(
                -0.3, 0.3, p.shape)).astype(np.float32))
    for m in model.model.moe_layers():
        m.select_bias.set_value(r.uniform(-bias, bias, m.select_bias.shape)
                                .astype(np.float32))
    return model


def _ids(cfg, b=2, s=48, seed=0):
    t = np.random.RandomState(seed).randint(0, cfg.vocab_size, (b, s + 1))
    return t[:, :-1], t[:, 1:]


def _compare(model, cfg, x, y):
    lines = []
    out = adapter.compare_with_reference(model, _ref_cfg(cfg), x, y,
                                         lines.append)
    return out, lines


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("held", [None, (8, 16)])
def test_logits_loss_and_three_layers_agree_with_the_reference(held):
    cfg = _cfg() if held is None else _cfg(
        router_outputs=32, num_experts=8, experts_held=held)
    model = _model(cfg)
    x, y = _ids(cfg)
    out, lines = _compare(model, cfg, x, y)
    assert out["ok"], lines
    assert out["router_flip_share"] == 0.0
    assert out["router_same_input_flip_share"] == 0.0
    assert out["abs_err"] < 1e-5 and out["max_abs_logit_err"] < 1e-4
    # the dense conv layer (0: 8 parameters), the attention expert layer
    # (1: 8 + 4) and the last conv expert layer (4: 5 + 4), and the inputs
    assert {k.split(".")[0] for k in out["grad_rel_err"]} == {"0", "1", "4"}
    assert len(out["grad_rel_err"]) == 8 + 12 + 9 + 3
    assert out["max_grad_rel_err"] < 2e-4
    assert out["sigma"] > 0.5      # the logits are no near-constant


def test_the_bfloat16_model_passes_the_cells_limits():
    cfg = _cfg(dtype="bfloat16", recompute="layer")
    model = _model(cfg, seed=5)
    x, y = _ids(cfg, b=1, s=64)
    out, lines = _compare(model, cfg, x, y)
    assert out["ok"], lines
    # ... and is no float32 model: its rounding shows
    assert out["max_abs_logit_err"] > 1e-3


def _reference_gradients(model, cfg, x, y, split_table=False):
    """jax.grad of the reference's loss by every weight, under the
    program's parameter names. `split_table`: (by the table as the
    embedding, by the table as the head) in place of their sum."""
    top, get_layer = adapter.reference_weights(model)
    layers = [get_layer(i) for i in range(cfg.num_hidden_layers)]
    rcfg = _ref_cfg(cfg)

    def ref_loss(w):
        out = ref.forward(x, w[0], lambda i: w[1][i], rcfg)
        return ref.next_token_loss(out["logits"], y)

    def two_tables(embed, head):
        h = jnp.asarray(embed)[jnp.asarray(x)]
        for i, kind in enumerate(cfg.layer_types):
            p = {k: jnp.asarray(v) for k, v in layers[i].items()}
            h, _ = ref.block(h, p, rcfg, kind, i < cfg.num_dense_layers)
        h = ref.rms_norm(h, jnp.asarray(top["embedding_norm"]), cfg.norm_eps)
        return ref.next_token_loss(h @ jnp.asarray(head).T, y)

    if split_table:
        return jax.grad(two_tables, argnums=(0, 1))(
            top["embed_tokens"], top["embed_tokens"])
    g_top, g_layers = jax.grad(ref_loss)((top, layers))
    want = {"model.embed_tokens": g_top["embed_tokens"],
            "model.final_norm_w": g_top["embedding_norm"]}
    for i, g in enumerate(g_layers):
        for n, r in adapter.reference_names(model.model.layers[i]).items():
            want[f"model.layers.{i}.{n}"] = g[r]
    return want


def _program_gradients(model, x, y):
    fm = FunctionalModule(model)
    crit = GPTPretrainingCriterion()

    def program_loss(pvals):
        out, _ = fm.call(pvals, fm.buffer_values(), jax.random.PRNGKey(0),
                         (jnp.asarray(x),), training=True)
        return crit(paddle.Tensor(out, _internal=True),
                    paddle.to_tensor(y, dtype="int64"))._value

    return dict(zip(fm.param_names, jax.grad(program_loss)(
        fm.param_values())))


def test_gradients_agree_with_jax_grad_of_the_reference_loss():
    cfg = _cfg()
    model = _model(cfg, seed=4)
    x, y = _ids(cfg, b=1, s=40)
    with jax.default_matmul_precision("highest"):
        got = _program_gradients(model, x, y)
        want = _reference_gradients(model, cfg, x, y)
    assert set(want) == set(got)
    assert "model.lm_head_w" not in got          # one table, no head matrix
    for name in got:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        err = float(jnp.max(jnp.abs(got[name] - want[name])))
        assert err <= 5e-4 * scale + 1e-7, (name, err, scale)
        assert scale > 1e-8, name          # every parameter has a gradient


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    cfg = _cfg()
    model = _model(cfg, seed=4)
    x, y = _ids(cfg, b=1, s=40)
    with jax.default_matmul_precision("highest"):
        got = _program_gradients(model, x, y)["model.embed_tokens"]
        as_embedding, as_head = _reference_gradients(model, cfg, x, y,
                                                     split_table=True)
    scale = float(jnp.max(jnp.abs(as_embedding + as_head)))
    np.testing.assert_allclose(got, as_embedding + as_head,
                               atol=5e-4 * scale)
    # each use counts: neither alone is the gradient
    for part in (as_embedding, as_head):
        assert float(jnp.max(jnp.abs(part))) > 0.05 * scale
        assert float(jnp.max(jnp.abs(got - part))) > 0.05 * scale
    # only the rows of tokens that occur have the embedding's part
    absent = np.setdiff1d(np.arange(cfg.vocab_size), np.unique(x))
    assert float(jnp.max(jnp.abs(as_embedding[absent]))) == 0.0


def test_train_step_on_the_tiny_model():
    cfg = _cfg(recompute="layer", router_outputs=32, num_experts=8,
               experts_held=(0, 8), initializer_range=0.02)
    model = Lfm2MoeForCausalLM(cfg, seed=1)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    x, y = _ids(cfg, b=2, s=40)
    table = np.asarray(model.model.embed_tokens._value).copy()
    losses = []
    for _ in range(8):
        losses.append(float(step(
            inputs=(paddle.to_tensor(x, dtype="int64"),),
            labels=(paddle.to_tensor(y, dtype="int64"),))._value))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.5, losses
    moes = model.model.moe_layers()
    assert len(moes) == 4
    for m in moes:
        # 8 steps x 80 tokens x 4 assignments in every layer's counters,
        # and the bias moved toward balance
        assert int(np.asarray(m.assign_count._value).sum()) == 8 * 80 * 4
        assert float(jnp.max(jnp.abs(m.select_bias._value))) > 0.0
        assert int(m.touched_count._value) > 0
    # rows of tokens that never occurred moved too: the table is the head
    absent = np.setdiff1d(np.arange(cfg.vocab_size), np.unique(x))
    moved = np.abs(np.asarray(model.model.embed_tokens._value) - table)
    assert moved[absent].max() > 0.0


def test_the_layer_pattern_and_the_parameters_of_each_kind():
    model = Lfm2MoeForCausalLM(_cfg(), seed=0)
    layers = model.model.layers
    assert [blk.layer_type for blk in layers] == list(PATTERN)
    assert [blk.moe is None for blk in layers] == [True] + [False] * 4
    dense, attn, conv = layers[0], layers[1], layers[2]
    assert dense.names == ("op_norm_w", "in_w", "conv_w", "out_w",
                           "ffn_norm_w", "gate_w", "up_w", "down_w")
    assert conv.names == dense.names[:5]
    assert attn.names == ("op_norm_w", "q_w", "k_w", "v_w", "q_norm_w",
                          "k_norm_w", "o_w", "ffn_norm_w")
    assert conv.in_w.shape == [64, 192] and conv.conv_w.shape == [64, 3]
    assert conv.out_w.shape == [64, 64] and dense.gate_w.shape == [64, 128]
    assert attn.q_w.shape == [64, 64] and attn.k_w.shape == [64, 16]
    assert attn.q_norm_w.shape == [8] and attn.o_w.shape == [64, 64]
    # the released initialisation: norms ones, no bias anywhere
    for blk in layers:
        for name in blk.names:
            if getattr(blk, name).ndim == 1:
                assert np.all(np.asarray(getattr(blk, name)._value) == 1.0)
    assert np.all(np.asarray(model.model.final_norm_w._value) == 1.0)
    assert conv.moe.router == "sigmoid" and conv.moe.names == conv.moe.PARAMS
    assert conv.moe.routed_scaling == 1.0 and conv.moe.top_k == 4
    names = [n for n, _ in model.named_parameters()]
    assert "model.embed_tokens" in names
    assert not [n for n in names if "lm_head" in n or "bias" in n]
    # the published pattern is the default: 18 convs, 6 attention layers
    full = Lfm2MoeConfig()
    assert full.layer_types.count("conv") == 18
    assert [i for i, k in enumerate(full.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert full.head_dim == 64 and full.router_outputs == 32
    with pytest.raises(ValueError, match="whole groups"):
        _cfg(num_attention_heads=3)
    with pytest.raises(ValueError, match="experts_held"):
        _cfg(experts_held=(0, 4))
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=("conv", "window") + PATTERN[2:])
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(num_hidden_layers=4)


# ------------------------------------------------------------- the mixers
def test_the_convolution_is_causal_and_tap_j_reaches_back():
    r = np.random.RandomState(0)
    z = jnp.asarray(r.randn(1, 7, 5), jnp.float32)
    w = jnp.asarray(r.randn(5, 3), jnp.float32)
    got = lfm2_moe.causal_taps(z, w)
    for t in range(7):
        acc = sum(np.asarray(w[:, j]) * (np.asarray(z[0, t - 2 + j])
                                         if t - 2 + j >= 0 else 0.0)
                  for j in range(3))
        np.testing.assert_allclose(got[0, t], acc, atol=1e-6)   # no SiLU
    np.testing.assert_allclose(got[0], ref.causal_conv(z[0], w), atol=1e-6)


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_each_mixer_against_the_reference(kind):
    cfg = _cfg()
    model = _model(cfg, seed=6)
    i = 1 if kind == "full_attention" else 3
    blk = model.model.layers[i]
    p = {n: getattr(blk, n)._value for n in blk.names}
    _, get_layer = adapter.reference_weights(model)
    pr = {k: jnp.asarray(v) for k, v in get_layer(i).items()}
    x = jnp.asarray(np.random.RandomState(2).randn(2, 80, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        if kind == "full_attention":
            got = lfm2_moe.qk_norm_attention(x, p, cfg)
            want = jnp.stack([ref.attention(x[j], pr, _ref_cfg(cfg), 16)
                              for j in range(2)])
        else:
            got = lfm2_moe.short_conv(x, p)
            want = jnp.stack([ref.short_conv(x[j], pr) for j in range(2)])
    assert float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_short_convolution_multiplies_in_float32():
    """The two gates and the taps' sum are float32 whatever the model's
    dtype; only the matmuls' operands and the result are bfloat16."""
    cfg = _cfg(dtype="bfloat16")
    blk = Lfm2MoeForCausalLM(cfg, seed=0).model.layers[2]
    p = {n: getattr(blk, n)._value for n in blk.names}
    x = jnp.zeros((1, 16, 64), jnp.bfloat16)
    assert lfm2_moe.short_conv(x, p).dtype == jnp.bfloat16
    jaxpr = str(jax.make_jaxpr(lambda v: lfm2_moe.short_conv(v, p))(x))
    muls = [ln for ln in jaxpr.splitlines() if " mul " in ln]
    assert muls and all("f32" in ln and "bf16" not in ln for ln in muls)


# ----------------------------------------------- grouped heads in the kernel
def test_query_head_h_reads_key_value_head_h_over_4():
    """The flash call in interpret mode, the einsum fallback the model
    takes off the TPU, and the einsum on k, v repeated per query head all
    agree, forward and backward, at the published group of 4."""
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(1, 64, 8, 64), jnp.float32)
    k = jnp.asarray(r.randn(1, 64, 2, 64), jnp.float32)
    v = jnp.asarray(r.randn(1, 64, 2, 64), jnp.float32)
    w = jnp.asarray(r.randn(1, 64, 8, 64), jnp.float32)

    def flash(q, k, v):
        return jnp.sum(flash_attention_val(q, k, v, block_q=16, block_k=32)
                       * w)

    def fallback(q, k, v):       # what the model calls: no TPU here
        return jnp.sum(_local_attention_val(q, k, v, True) * w)

    def repeated(q, k, v):       # head h reads copy h of k, v: h // 4
        return jnp.sum(_local_attention_val(
            q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2), False) * w)

    with jax.default_matmul_precision("highest"):
        got, want, plain = [jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
                            for f in (flash, fallback, repeated)]
    for other in (want, plain):
        assert float(got[0]) == pytest.approx(float(other[0]), abs=1e-3)
        for a, b in zip(got[1], other[1]):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=6e-5)
    assert got[1][1].shape == (1, 64, 2, 64)          # dk at the kv heads
    # a key/value head moved changes its own four query heads only
    moved = _local_attention_val(q, k.at[:, :, 1].add(1.0), v, True)
    same = np.asarray(jnp.max(jnp.abs(
        moved - _local_attention_val(q, k, v, True)), axis=(0, 1, 3)))
    assert np.all(same[:4] == 0.0) and np.all(same[4:] > 0.0)


def test_the_flash_call_takes_the_cells_shape():
    shape = (2, 8192, 32, 64)
    assert flash_attention_sharded_ok(shape, 8)
    assert _default_block(64, jnp.bfloat16) == 1024
    assert flash_block_choice(shape, "bfloat16") == {
        "block_q": 1024, "block_k": 1024, "source": "default"}


# ------------------------------------------ the share, tied to the model
def test_the_four_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: a conv expert layer held
    in four shares of 8 experts (mixer, norms and router alike on all
    four); the four routed parts, with what every chip computes alike (the
    residual stream after the mixer) counted once, are the uncut
    reference's whole layer."""
    whole_cfg = _cfg()
    whole = Lfm2MoeDecoderLayer(whole_cfg, "conv", False,
                                np.random.default_rng(2))
    r = np.random.RandomState(1)
    whole.moe.select_bias.set_value(r.uniform(-0.05, 0.05, 32)
                                    .astype(np.float32))
    x = jnp.asarray(r.randn(1, 48, 64), jnp.float32)
    p = {n: getattr(whole, n)._value for n in whole.names}
    full = {ref_name: jnp.asarray(
        getattr(whole.moe, n[4:])._value if n.startswith("moe.")
        else getattr(whole, n)._value)
        for n, ref_name in adapter.reference_names(whole).items()}
    full["router_bias"] = whole.moe.select_bias._value
    with jax.default_matmul_precision("highest"):
        alike = x + lfm2_moe.short_conv(
            rms_norm(x, p["op_norm_w"], whole_cfg.norm_eps), p)
        parts = []
        for i in range(4):
            held = (8 * i, 8 * i + 8)
            cfg = _cfg(num_experts=8, router_outputs=32, experts_held=held)
            share = Lfm2MoeDecoderLayer(cfg, "conv", False,
                                        np.random.default_rng(9 + i))
            for n in whole.names:
                getattr(share, n).set_value(np.asarray(
                    getattr(whole, n)._value))
            share.moe.router_w.set_value(np.asarray(
                whole.moe.router_w._value))
            share.moe.select_bias.set_value(np.asarray(
                whole.moe.select_bias._value))
            for n in ("w_gate", "w_up", "w_down"):
                getattr(share.moe, n).set_value(np.asarray(
                    getattr(whole.moe, n)._value)[held[0]:held[1]])
            share.eval()
            parts.append(share(paddle.Tensor(x, _internal=True))._value
                         - alike)
        want, _ = ref.block(x, full, _ref_cfg(whole_cfg), "conv", False)
    np.testing.assert_allclose(sum(parts) + alike, want, atol=1e-4)
    for part in parts:                       # every share's experts count
        assert float(jnp.std(part)) > 0.01
    assert float(jnp.std(want - alike)) > 0.05


def test_the_bias_selects_and_never_weighs():
    r = np.random.RandomState(0)
    x2 = jnp.asarray(r.randn(40, 32), jnp.float32)
    w = jnp.asarray(r.randn(32, 32) * 0.3, jnp.float32)
    bias = jnp.asarray(r.uniform(-0.3, 0.3, 32), jnp.float32)
    chosen, weights = moe.sigmoid_topk_route(x2, w, bias, 4, 1.0)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x2, np.float64)
                              @ np.asarray(w, np.float64))))
    want = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    # the bias changed the choice of some token, and no weight holds it
    plain, _ = moe.sigmoid_topk_route(x2, w, jnp.zeros(32), 4, 1.0)
    assert np.any(np.sort(plain, -1) != np.sort(chosen, -1))
    scores, own, margin = ref.route(
        x2, {"router": w, "router_bias": bias}, {"num_experts_per_tok": 4})
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(chosen, -1))
    assert float(jnp.min(margin)) >= 0.0


# ------------------------------------------------------ the planted faults
def _reversed_taps(z, w):
    return _TAPS(z, w[:, ::-1])


def _no_c_gate(x, p):
    h = x.shape[-1]
    bcu = (x @ p["in_w"]).astype(jnp.float32)
    c = _TAPS(bcu[..., :h] * bcu[..., 2 * h:], p["conv_w"])
    return c.astype(x.dtype) @ p["out_w"]


def _no_qk_norm(x, w, eps):
    return x if x.ndim == 4 else _RMS(x, w, eps)


def _bias_in_the_weights(x2, router_w, bias, top_k, scale):
    s = jax.nn.sigmoid(x2.astype(jnp.float32) @ router_w.astype(
        jnp.float32)) + bias
    picked, chosen = jax.lax.top_k(s, top_k)
    return chosen.astype(jnp.int32), picked / picked.sum(-1, keepdims=True)


def _sigmoid_in_bf16(x2, router_w, bias, top_k, scale):
    s = jax.nn.sigmoid(x2.astype(jnp.bfloat16) @ router_w.astype(
        jnp.bfloat16)).astype(jnp.float32)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), picked / picked.sum(-1, keepdims=True)


_TAPS, _RMS = lfm2_moe.causal_taps, lfm2_moe.rms_norm
# fault -> (module, attribute, the stand-in, the compared number that must
# pass its limit). The router's sigmoid in bfloat16 takes 64 outputs, as
# in test_benchmark_gdn_moe.py: among 32 scores few 4th and 5th tie. The
# bias in the weights takes a bias of +-0.3 (what ~300 steps at the speed
# of 0.001 can reach; +-0.05 moves a logit by 0.08 sigma)
FAULTS = {
    "taps_reversed": (lfm2_moe, "causal_taps", _reversed_taps,
                      "grad_rel_err_worst"),
    "c_gate_left_out": (lfm2_moe, "short_conv", _no_c_gate,
                        "logit_max_abs_err"),
    "qk_norm_left_out": (lfm2_moe, "rms_norm", _no_qk_norm,
                         "logit_max_abs_err"),
    "bias_added_to_the_weights": (moe, "sigmoid_topk_route",
                                  _bias_in_the_weights, "logit_max_abs_err"),
    "router_sigmoid_in_bf16": (moe, "sigmoid_topk_route", _sigmoid_in_bf16,
                               "router_same_input_flip_share"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_caught(fault, monkeypatch):
    module, name, stand_in, number = FAULTS[fault]
    cfg = _cfg(num_experts=64, num_experts_per_tok=6) \
        if fault == "router_sigmoid_in_bf16" else _cfg()
    model = _model(cfg, bias=0.3 if fault == "bias_added_to_the_weights"
                   else 0.05)
    x, y = _ids(cfg, b=1, s=160)
    out, lines = _compare(model, cfg, x, y)
    assert out["ok"], lines                      # as it is: accepted
    monkeypatch.setattr(module, name, stand_in)
    out, lines = _compare(model, cfg, x, y)
    assert not out["ok"], lines
    got, limit = out["compared"][number]
    assert got > limit, (fault, out["compared"])
