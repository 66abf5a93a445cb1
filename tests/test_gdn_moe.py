"""The qwen3_next shaped block (models/gdn_moe.py), the chunked gated delta
rule (ops/gated_delta_rule.py), the expert layer's softmax router and gated
shared expert (distributed/moe.py DroplessMoELayer) and the flash kernels'
grouped heads, at a small size on the CPU in float32, against the plain
reference benchmark/reference/qwen3_next_ref.py and plain einsum code."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kept_kernels
import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from benchmark import program_gdn_moe as adapter
from benchmark.reference import qwen3_next_ref as ref
from paddle_tpu.distributed import moe
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import FunctionalModule
from paddle_tpu.models import (GdnMoeConfig, GdnMoeForCausalLM,
                               GPTPretrainingCriterion, gdn_moe)
from paddle_tpu.models.gpt import _local_attention_val
from paddle_tpu.ops import gated_delta_rule as rule
from paddle_tpu.ops.flash_attention import (_default_block,
                                            flash_attention_supported,
                                            flash_attention_val)

# the reference comparisons' programs, alike from one case to the next,
# compile once a run (tests/conftest.py)
pytestmark = pytest.mark.shared_compile_cache

# The tests' size. initializer_range 0.1: sqrt(h) * std ~ 0.8 as at the
# published widths (sqrt(2048) * 0.02 = 0.9), so that the blocks move the
# residual stream as they do there and a wrong block shows in the logits
TEST = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4,
            full_attention_interval=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=8, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, initializer_range=0.1,
            num_experts=16, num_experts_per_tok=4)


def _cfg(**kw):
    return GdnMoeConfig(**{**TEST, **kw})


def _ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in (
        "num_hidden_layers", "full_attention_interval",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim", "rms_norm_eps",
        "num_experts_per_tok", "experts_held")}


def _model(cfg, seed=3):
    """The model with its vectors moved off their initial zeros and ones
    (a norm weight of zero hides a wrong `1 + w`), and decays from near 1
    to near 0."""
    model = GdnMoeForCausalLM(cfg, seed=seed)
    r = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            p.set_value(np.log(np.geomspace(0.02, 9.0, p.shape[0])
                               .astype(np.float32)))
        elif p.ndim == 1:
            p.set_value(np.asarray(p._value) + r.uniform(
                -0.3, 0.3, p.shape).astype(np.float32))
    return model


def _ids(cfg, b=2, s=48, seed=0):
    t = np.random.RandomState(seed).randint(0, cfg.vocab_size, (b, s + 1))
    return t[:, :-1], t[:, 1:]


def _compare(model, cfg, x, y):
    lines = []
    out = adapter.compare_with_reference(model, _ref_cfg(cfg), x, y,
                                         lines.append)
    return out, lines


# ------------------------------------------------- the chunked delta rule
def _rule_inputs(s, decay, hk=2, hv=4, dk=16, dv=8, seed=0):
    """q, k normalised (some keys repeated, the case the Neumann series
    loses), v, g = -uniform(0, 1) * decay, beta in (0.1, 1)."""
    r = np.random.RandomState(seed)
    q, k = r.randn(1, s, hk, dk), r.randn(1, s, hk, dk)
    k[:, 5:9] = k[:, 4:5]
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v, beta = r.randn(1, s, hv, dv), r.uniform(0.1, 1.0, (1, s, hv))
    g = -r.uniform(0.0, 1.0, (1, s, hv)) * decay
    cot = r.randn(1, s, hv, dv)
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta, cot)]


def _recurrence(q, k, v, g, beta, cot):
    group = v.shape[2] // q.shape[2]
    o = ref.delta_rule(jnp.repeat(q[0], group, 1), jnp.repeat(k[0], group, 1),
                       v[0], g[0], beta[0], block=8)
    return jnp.sum(o * cot[0]), o


# (tokens, decay scale, chunk): several chunks; decays near 1 (g ~ -0.001)
# and near 0 (g ~ -30 a token); sequences that are no multiple of the chunk
RULE_CASES = {"several_chunks": (64, 1.0, 16),
              "decays_near_one": (128, 0.001, 64),
              "decays_near_zero": (96, 30.0, 32),
              "no_multiple_of_the_chunk": (50, 1.0, 16),
              "shorter_than_a_chunk": (20, 0.5, 64),
              "the_default_chunk": (130, 0.2, rule.GDN_CHUNK)}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_chunked_rule_equals_the_recurrence_forward_and_gradients(case):
    s, decay, chunk = RULE_CASES[case]
    *args, cot = _rule_inputs(s, decay)

    def chunked(*a):
        o = rule.gated_delta_rule_chunked(*a, chunk=chunk)
        return jnp.sum(o * cot), o

    with jax.default_matmul_precision("highest"):
        (_, got_o), got = jax.jit(jax.value_and_grad(
            chunked, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        (_, want_o), want = jax.jit(jax.value_and_grad(
            lambda *a: _recurrence(*a, cot), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert got_o.shape == (1, s, 4, 8)
    np.testing.assert_allclose(got_o[0], want_o, atol=2e-6)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 1e-6, name           # every input has a gradient
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * scale, (case, name)


def test_the_rule_says_which_shapes_it_takes():
    q, k, v, g, beta, _ = _rule_inputs(16, 1.0)
    with pytest.raises(ValueError, match="Hk dividing Hv"):
        rule.gated_delta_rule_chunked(q, k, v[:, :, :3], g[..., :3],
                                      beta[..., :3])
    with pytest.raises(ValueError, match="gated delta rule"):
        rule.gated_delta_rule_chunked(q, k[:, :8], v, g, beta)


def test_the_rule_keeps_the_values_dtype_and_a_float32_state():
    q, k, v, g, beta, _ = _rule_inputs(32, 1.0)
    o = rule.gated_delta_rule_chunked(q, k, v.astype(jnp.bfloat16), g, beta,
                                      chunk=16)
    assert o.dtype == jnp.bfloat16
    assert rule.GDN_CHUNK == 64
    jaxpr = str(jax.make_jaxpr(lambda *a: rule.gated_delta_rule_chunked(
        *a, chunk=16))(q, k, v, g, beta))
    assert "scan" in jaxpr and "bf16" not in jaxpr


def test_unit_lower_inverse_and_its_gradient():
    """Forward substitution on blocks against numpy's inverse, at a chunk of
    64 whose keys repeat (a Neumann series' terms would reach 1e17 here)."""
    c = 64
    a = jnp.asarray(-np.tril(np.ones((2, c, c)), -1) * 0.999, jnp.float32)
    with jax.default_matmul_precision("highest"):
        t = rule.unit_lower_inverse(a)
        want = np.linalg.inv(np.eye(c) - np.asarray(a, np.float64))
        np.testing.assert_allclose(t, want, atol=1e-5)
        w = jnp.asarray(np.random.RandomState(0).randn(2, c, c), jnp.float32)
        got = jax.jit(jax.grad(lambda m: jnp.sum(
            rule.unit_lower_inverse(m) * w)))(a)
        plain = jax.jit(jax.grad(lambda m: jnp.sum(jnp.linalg.inv(
            jnp.eye(c) - m) * w)))(a)
    np.testing.assert_allclose(got, plain, atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("held", [None, (4, 12)])
def test_logits_loss_and_two_layers_agree_with_the_reference(held):
    cfg = _cfg() if held is None else _cfg(
        router_outputs=16, num_experts=8, experts_held=held)
    model = _model(cfg)
    x, y = _ids(cfg)
    out, lines = _compare(model, cfg, x, y)
    assert out["ok"], lines
    assert out["router_flip_share"] == 0.0
    assert out["router_same_input_flip_share"] == 0.0
    assert out["abs_err"] < 1e-5 and out["max_abs_logit_err"] < 1e-4
    # the last DeltaNet layer (2) and the last attention layer (3), each
    # with its expert block: 17 + 16 parameters and the two inputs
    assert len(out["grad_rel_err"]) == 35
    assert {k.split(".")[0] for k in out["grad_rel_err"]} == {"2", "3"}
    assert out["max_grad_rel_err"] < 2e-4
    assert max(out["delta_rule_rel_err"].values()) < 1e-5
    assert out["sigma"] > 0.5      # the logits are no near-constant


def test_gradients_agree_with_jax_grad_of_the_reference_loss():
    cfg = _cfg()
    model = _model(cfg, seed=4)
    x, y = _ids(cfg, b=1, s=40)
    fm = FunctionalModule(model)
    crit = GPTPretrainingCriterion()

    def program_loss(pvals):
        out, _ = fm.call(pvals, fm.buffer_values(), jax.random.PRNGKey(0),
                         (jnp.asarray(x),), training=True)
        return crit(paddle.Tensor(out, _internal=True),
                    paddle.to_tensor(y, dtype="int64"))._value

    got = dict(zip(fm.param_names, jax.jit(jax.grad(program_loss))(
        fm.param_values())))
    top, get_layer = adapter.reference_weights(model)
    layers = [get_layer(i) for i in range(cfg.num_hidden_layers)]

    def ref_loss(w):
        out = ref.forward(x, w[0], lambda i: w[1][i], _ref_cfg(cfg))
        return ref.next_token_loss(out["logits"], y)

    g_top, g_layers = jax.jit(jax.grad(ref_loss))((top, layers))
    want = {"model.embed_tokens": g_top["embed_tokens"],
            "model.final_norm_w": g_top["norm"],
            "model.lm_head_w": g_top["lm_head"]}
    for i, g in enumerate(g_layers):
        for n, r in adapter.reference_names(model.model.layers[i]).items():
            want[f"model.layers.{i}.{n}"] = g[r]
    assert set(want) == set(got)
    for name in got:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        err = float(jnp.max(jnp.abs(got[name] - want[name])))
        assert err <= 5e-4 * scale + 1e-7, (name, err, scale)
        assert scale > 1e-8, name          # every parameter has a gradient


def test_train_step_on_the_tiny_model():
    cfg = _cfg(recompute="layer", router_outputs=16, num_experts=8,
               experts_held=(0, 8), initializer_range=0.02)
    model = GdnMoeForCausalLM(cfg, seed=1)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    x, y = _ids(cfg, b=2, s=40)
    losses = []
    for _ in range(8):
        losses.append(float(step(
            inputs=(paddle.to_tensor(x, dtype="int64"),),
            labels=(paddle.to_tensor(y, dtype="int64"),))._value))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.5, losses
    for m in model.model.moe_layers():
        # 8 steps x 80 tokens x 4 assignments in every layer's counters;
        # softmax routing moves no selection bias
        assert int(np.asarray(m.assign_count._value).sum()) == 8 * 80 * 4
        assert float(jnp.max(jnp.abs(m.select_bias._value))) == 0.0
        assert int(m.touched_count._value) > 0


def test_the_layer_pattern_and_the_parameters_of_each_kind():
    cfg = _cfg(num_hidden_layers=8)
    model = GdnMoeForCausalLM(cfg, seed=0)
    kinds = [blk.full_attention for blk in model.model.layers]
    assert kinds == [False, False, False, True] * 2
    gdn, attn = model.model.layers[0], model.model.layers[3]
    assert gdn.names == ("in_norm_w", "qkvz_w", "ba_w", "conv_w", "A_log",
                         "dt_bias", "out_norm_w", "o_w", "ffn_norm_w")
    assert attn.names == ("in_norm_w", "q_w", "k_w", "v_w", "q_norm_w",
                          "k_norm_w", "o_w", "ffn_norm_w")
    assert gdn.qkvz_w.shape == [64, 2 * 32 + 2 * 32]
    assert gdn.ba_w.shape == [64, 8] and gdn.conv_w.shape == [96, 4]
    assert attn.q_w.shape == [64, 4 * 32] and attn.k_w.shape == [64, 32]
    # the released initialisation: zero-centred norms zeros, the DeltaNet
    # output norm and dt_bias ones, A_log the log of a uniform(0, 16) draw
    for name in ("in_norm_w", "ffn_norm_w"):
        assert float(jnp.max(jnp.abs(getattr(gdn, name)._value))) == 0.0
    assert float(jnp.max(jnp.abs(attn.q_norm_w._value))) == 0.0
    assert np.all(np.asarray(gdn.out_norm_w._value) == 1.0)
    assert np.all(np.asarray(gdn.dt_bias._value) == 1.0)
    a = np.exp(np.asarray(gdn.A_log._value))
    assert np.all((a > 0) & (a < 16)) and len(set(a.tolist())) == 4
    assert float(jnp.max(jnp.abs(model.model.final_norm_w._value))) == 0.0
    assert gdn.moe.router == "softmax" and "shared_gate_w" in gdn.moe.names
    with pytest.raises(ValueError, match="whole groups"):
        _cfg(num_attention_heads=3)
    with pytest.raises(ValueError, match="experts_held"):
        _cfg(experts_held=(0, 4))


# ------------------------------------------------------------- the mixers
def test_rotary_turns_half_split_pairs_of_the_first_quarter():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 6, 2, 16), jnp.float32)
    got = gdn_moe.rotary_half_split(x, 100.0, 0.25)      # r = 4: pairs
    np.testing.assert_allclose(got[..., 4:], x[..., 4:])  # (0, 2), (1, 3)
    for t in range(6):
        for i, f in enumerate((1.0, 100.0 ** -0.5)):
            c, s = np.cos(t * f), np.sin(t * f)
            a, b = x[0, t, :, i], x[0, t, :, i + 2]
            np.testing.assert_allclose(got[0, t, :, i], a * c - b * s,
                                       atol=1e-5)
            np.testing.assert_allclose(got[0, t, :, i + 2], b * c + a * s,
                                       atol=1e-5)
    np.testing.assert_allclose(got[0], ref.rotary_half(x[0], 100.0, 0.25),
                               atol=1e-6)


def test_the_convolution_is_causal_and_tap_j_reaches_back():
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(1, 7, 3), jnp.float32)
    w = jnp.asarray(r.randn(3, 4), jnp.float32)
    got = gdn_moe.causal_conv_silu(x, w)
    for t in range(7):
        acc = sum(np.asarray(w[:, j]) * (np.asarray(x[0, t - 3 + j])
                                         if t - 3 + j >= 0 else 0.0)
                  for j in range(4))
        np.testing.assert_allclose(got[0, t], acc / (1 + np.exp(-acc)),
                                   atol=1e-6)
    np.testing.assert_allclose(got[0], ref.silu(ref.causal_conv(x[0], w)),
                               atol=1e-6)


@pytest.mark.parametrize("kind", ["gated_delta_net", "gated_attention"])
def test_each_mixer_against_the_reference(kind):
    cfg = _cfg()
    model = _model(cfg, seed=6)
    i = 3 if kind == "gated_attention" else 1
    blk = model.model.layers[i]
    p = {n: getattr(blk, n)._value for n in blk.names}
    _, get_layer = adapter.reference_weights(model)
    pr = {k: jnp.asarray(v) for k, v in get_layer(i).items()}
    x = jnp.asarray(np.random.RandomState(2).randn(2, 80, 64), jnp.float32)

    def program(x, p):
        if kind == "gated_attention":
            return gdn_moe.gated_attention(x, p, cfg)
        return gdn_moe.gated_delta_net(x, p, cfg)

    def reference(x, pr):
        if kind == "gated_attention":
            return jnp.stack([ref.attention(x[j], pr, _ref_cfg(cfg), 16)
                              for j in range(2)])
        normed = ref.rms_norm(x, pr["input_layernorm"], cfg.rms_norm_eps)
        return jnp.stack([ref.gated_delta_net(normed[j], pr, _ref_cfg(cfg))
                          for j in range(2)])

    with jax.default_matmul_precision("highest"):
        got = jax.jit(program)(x, p)
        want = jax.jit(reference)(x, pr)
    assert float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5)


# ----------------------------------------------- grouped heads in the kernel
def _attention_pair(n, n_kv, blocks=(16, 32), s=64, d=32, d_v=16):
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, s, n, d), jnp.float32)
    k = jnp.asarray(r.randn(2, s, n_kv, d), jnp.float32)
    v = jnp.asarray(r.randn(2, s, n_kv, d_v), jnp.float32)
    w = jnp.asarray(r.randn(2, s, n, d_v), jnp.float32)

    def flash(q, k, v):
        o = flash_attention_val(q, k, v, block_q=blocks[0], block_k=blocks[1])
        return jnp.sum(o * w), o

    def einsum(q, k, v):
        o = _local_attention_val(q, k, v, False)
        return jnp.sum(o * w), o

    def repeated(q, k, v):       # the copy of k, v at n heads, in the test
        o = _local_attention_val(q, jnp.repeat(k, n // n_kv, 2),
                                 jnp.repeat(v, n // n_kv, 2), False)
        return jnp.sum(o * w), o

    with jax.default_matmul_precision("highest"):
        return [jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                           has_aux=True))(q, k, v)
                for f in (flash, einsum, repeated)]


@pytest.mark.parametrize("heads", [(4, 4), (8, 2), (8, 1), (6, 3)])
def test_grouped_head_flash_against_the_einsum_path(heads):
    """Interpret mode: query head h reads key/value head h // group through
    the index maps, forward and backward, at equal and unequal counts."""
    flash, einsum, repeated = _attention_pair(*heads)
    for (_, o), grads in (einsum, repeated):
        np.testing.assert_allclose(flash[0][1], o, atol=2e-5)
        for got, want in zip(flash[1], grads):
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, atol=6e-5)
    n, n_kv = heads
    assert flash[1][1].shape == (2, 64, n_kv, 32)     # dk at the kv heads


def test_grouped_head_flash_refuses_what_is_no_whole_grouping():
    q = jnp.zeros((1, 16, 4, 8))
    with pytest.raises(ValueError, match="whole groups"):
        flash_attention_val(q, jnp.zeros((1, 16, 3, 8)),
                            jnp.zeros((1, 16, 3, 8)))
    with pytest.raises(ValueError, match="must agree"):
        flash_attention_val(q, jnp.zeros((1, 16, 2, 8)),
                            jnp.zeros((1, 16, 4, 8)))
    from paddle_tpu.ops.flash_attention import flash_attention_sharded_ok
    assert flash_attention_sharded_ok((1, 1024, 16, 256), 2)
    assert not flash_attention_sharded_ok((1, 1024, 16, 256), 3)
    assert flash_attention_supported((1, 8192, 16, 256))


def test_the_block_at_256_wide_heads_in_bf16():
    """A 512-byte row sits on `_default_block`'s edge and takes 1024."""
    assert _default_block(256, jnp.bfloat16) == 1024
    assert _default_block(256, jnp.float32) == 512


# ------------------------------------------------- the second router, gated
def test_the_softmax_router_chooses_and_weighs():
    r = np.random.RandomState(0)
    x2 = jnp.asarray(r.randn(40, 32), jnp.float32)
    w = jnp.asarray(r.randn(32, 24) * 0.3, jnp.float32)
    chosen, weights = moe.softmax_topk_route(x2, w, 5)
    p = np.asarray(jax.nn.softmax(np.asarray(x2, np.float64)
                                  @ np.asarray(w, np.float64), axis=-1))
    want = np.argsort(-p, axis=-1)[:, :5]
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(p, np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    probs, own, margin = ref.route(x2, {"router": w},
                                   {"num_experts_per_tok": 5})
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(chosen, -1))
    assert float(jnp.min(margin)) >= 0.0


def _layer_and_reference(held, seed=2):
    layer = moe.DroplessMoELayer(
        32, 16, 32, 4, experts_held=held, shared_width=24, init_std=0.3,
        seed=seed, router="softmax", shared_gated=True)
    # every share draws the router and the shared expert alike; a whole
    # layer drawn from the same seed gives the experts the shares must hold
    whole = moe.DroplessMoELayer(
        32, 16, 32, 4, shared_width=24, init_std=0.3, seed=seed,
        router="softmax", shared_gated=True)
    lo, hi = held
    for n in ("w_gate", "w_up", "w_down"):
        getattr(layer, n).set_value(np.asarray(getattr(whole, n)._value)[
            lo:hi])
    for n in ("router_w",) + moe.DroplessMoELayer.SHARED + (
            "shared_gate_w",):
        getattr(layer, n).set_value(np.asarray(getattr(whole, n)._value))
    full = {adapter._MOE_NAMES[n]: jnp.asarray(getattr(whole, n)._value)
            for n in whole.names}
    return layer, full


def _run(layer, x2):
    y, _, _ = layer.apply_val(
        x2, [getattr(layer, n)._value for n in layer.names],
        layer.select_bias._value)
    return y


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: the sixteen shares'
    routed parts, plus the gated shared expert counted once, are the uncut
    reference's whole layer."""
    x2 = jnp.asarray(np.random.RandomState(0).randn(48, 32), jnp.float32)
    rcfg = {"num_experts_per_tok": 4}
    parts, shared, full = [], None, None
    with jax.default_matmul_precision("highest"):
        for i in range(16):
            held = (2 * i, 2 * i + 2)
            layer, full = _layer_and_reference(held)
            y = _run(layer, x2)
            shared = ref.shared_part(x2, full)
            parts.append(y - shared)     # what every chip computes alike
            probs, own, _ = ref.route(x2, full, rcfg)
            mine = {n: full[n][held[0]:held[1]] for n in (
                "experts_gate", "experts_up", "experts_down")}
            np.testing.assert_allclose(
                parts[-1], ref.routed_part(
                    x2, mine, dict(rcfg, experts_held=held), probs, own),
                atol=3e-5)
        probs, own, _ = ref.route(x2, full, rcfg)
        whole = ref.routed_part(x2, full, dict(rcfg, experts_held=(0, 32)),
                                probs, own) + shared
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-4)
    assert float(jnp.std(whole - shared)) > 0.05   # the routed part counts
    assert float(jnp.std(shared)) > 0.05           # and the gated shared


def test_the_two_head_slices_add_up_to_the_uncut_attention_layer():
    """The benchmark's configuration holds one key/value head's slice of
    the attention layer (its query heads, its k and v columns, W_o's rows
    for them): the program on each slice, summed over the two, is the
    uncut reference's whole layer, the norms' weights held by both."""
    cfg = _cfg()                                   # 4 query heads over 2
    blk = _model(cfg, seed=6).model.layers[3]
    p = {n: getattr(blk, n)._value for n in blk.names}
    n, n_kv, d = 4, 2, cfg.head_dim
    group = n // n_kv
    x = jnp.asarray(np.random.RandomState(4).randn(1, 48, 64), jnp.float32)
    full = {"q_proj": p["q_w"], "k_proj": p["k_w"], "v_proj": p["v_w"],
            "o_proj": p["o_w"], "q_norm": p["q_norm_w"],
            "k_norm": p["k_norm_w"]}
    one_slice = _cfg(num_attention_heads=group, num_key_value_heads=1)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda x, full: ref.attention(
            x[0], full, _ref_cfg(cfg), 16))(x, full)
        program = jax.jit(lambda x, mine: gdn_moe.gated_attention(
            x, mine, one_slice)[0])
        parts = []
        for j in range(n_kv):
            q_cols = slice(j * group * 2 * d, (j + 1) * group * 2 * d)
            kv_cols = slice(j * d, (j + 1) * d)
            mine = dict(p, q_w=p["q_w"][:, q_cols], k_w=p["k_w"][:, kv_cols],
                        v_w=p["v_w"][:, kv_cols],
                        o_w=p["o_w"][j * group * d:(j + 1) * group * d])
            parts.append(program(x, mine))
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    for part in parts:                              # each slice counts
        assert float(jnp.std(part)) > 0.3 * float(jnp.std(whole))


def test_the_shared_experts_gate_is_one_number_a_token():
    layer, full = _layer_and_reference((0, 32))
    x2 = jnp.asarray(np.random.RandomState(1).randn(20, 32), jnp.float32)
    p = {n: getattr(layer, n)._value for n in layer.names}
    ungated = {k: v for k, v in p.items() if k != "shared_gate_w"}
    kw = dict(top_k=4, scale=1.0, lo=0, router="softmax")
    y, chosen, counts = moe.dropless_moe_val(x2, p, None, **kw)
    y0, chosen0, _ = moe.dropless_moe_val(x2, ungated, None, **kw)
    np.testing.assert_array_equal(chosen, chosen0)
    gate = jax.nn.sigmoid(x2 @ p["shared_gate_w"])            # [T, 1]
    shared = moe.swiglu(x2, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    np.testing.assert_allclose(y0 - y, (1.0 - gate) * shared, atol=1e-5)
    assert int(counts.sum()) == 20 * 4


def test_softmax_routing_takes_no_scale_and_moves_no_bias():
    with pytest.raises(ValueError, match="no scale"):
        moe.DroplessMoELayer(8, 4, 8, 2, router="softmax",
                             routed_scaling=2.0)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        moe.DroplessMoELayer(8, 4, 8, 2, router="top2")
    with pytest.raises(ValueError, match="no shared expert"):
        moe.DroplessMoELayer(8, 4, 8, 2, shared_gated=True)
    layer = moe.DroplessMoELayer(8, 4, 8, 2, router="softmax")
    layer.advance(jnp.zeros((6, 2), jnp.int32),
                  jnp.asarray([12, 0, 0, 0, 0, 0, 0, 0], jnp.int32))
    assert float(jnp.max(jnp.abs(layer.select_bias._value))) == 0.0
    assert int(layer.assign_count._value[0]) == 12
    # ... and the sigmoid layer is the one it was
    old = moe.DroplessMoELayer(8, 4, 8, 2)
    assert old.router == "sigmoid" and old.names == old.PARAMS
    old.advance(jnp.zeros((6, 2), jnp.int32),
                jnp.asarray([12, 0, 0, 0, 0, 0, 0, 0], jnp.int32))
    assert float(old.select_bias._value[0]) == pytest.approx(-0.001)


# ------------------------------------------ whole-layer recomputation
# 128-wide DeltaNet heads, the narrowest the kernels take; two chunks
KERNEL_WIDTHS = dict(linear_num_key_heads=1, linear_num_value_heads=2,
                     linear_key_head_dim=128, linear_value_head_dim=128,
                     recompute="layer")
MIXERS = {"gated_delta_net": ("gdn_chunk_bwd", "gdn_chunk_fwd"),
          "gated_attention": ("flash_bwd_dkv", "flash_fwd")}


def _kernels(monkeypatch):
    """The layer's delta rule through the two Pallas kernels and its
    attention through the flash kernels (interpreted on the CPU), as on a
    TPU: only there do the kernels name their outputs."""
    monkeypatch.setattr(gdn_moe, "gated_delta_rule_chunked",
                        lambda q, k, v, g, beta: rule._chunked(
                            q, k, v, g, beta, rule.GDN_CHUNK,
                            rule.kernels_over_chunks))
    monkeypatch.setattr(gdn_moe, "_local_attention_val",
                        lambda q, k, v, use_flash: flash_attention_val(
                            q, k, v))


def _recomputed_layer(mixer, seed=0):
    return gdn_moe.GdnMoeDecoderLayer(
        _cfg(**KERNEL_WIDTHS), mixer == "gated_attention",
        np.random.default_rng(seed))


def _hidden(s=128, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (1, s, TEST["hidden_size"]), jnp.float32)


@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_a_recomputed_layer_runs_each_forward_kernel_once(mixer,
                                                          monkeypatch):
    """The backward of a recomputed layer reuses the forward kernel's
    outputs: one `gdn_chunk_fwd` (one `flash_fwd`) where a bare
    jax.checkpoint runs two, and the same gradients to one ulp."""
    _kernels(monkeypatch)
    layer, x = _recomputed_layer(mixer), _hidden()
    bwd, fwd = MIXERS[mixer]
    grad, args = kept_kernels.layer_grads(layer, x)
    assert kept_kernels.pallas_calls(grad, *args) == [bwd, fwd]
    got = grad(*args)
    monkeypatch.setattr(gdn_moe, "_policy_step",
                        kept_kernels.plain_checkpoint)
    grad, args = kept_kernels.layer_grads(layer, x)
    assert kept_kernels.pallas_calls(grad, *args) == [bwd, fwd, fwd]
    kept_kernels.assert_within_one_ulp(got, grad(*args))


def test_a_recomputed_deltanet_layer_keeps_o_the_states_and_t(monkeypatch):
    """What the layer saves beside its inputs is the rule's output o
    [b, s, Hv, dv], the state at each chunk's start [b, Hv, n, dk, dv] and
    T [n, b, Hv, C, C]; tracing it under a span books their bytes to
    `recompute_kept_bytes_total{kernel=gdn_chunk}` once."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.profiler import RecordEvent

    _kernels(monkeypatch)
    layer, x = _recomputed_layer("gated_delta_net"), _hidden()
    made = kept_kernels.made_residuals(layer, x)
    shapes = {"f32[1,128,2,128]", "f32[1,2,2,128,128]", "f32[2,1,2,64,64]"}
    assert len(made) == 3 and {line.split()[0] for line in made} == shapes
    assert all("gated_delta_rule.py" in line for line in made), made
    grad, args = kept_kernels.layer_grads(layer, x)
    with RecordEvent("t38_deltanet_layer"):
        jax.make_jaxpr(grad)(*args)
    got = {labels["kernel"]: child.value for labels, child in get_registry()
           .get("recompute_kept_bytes_total").items()
           if labels["span"] == "t38_deltanet_layer"}
    assert got == {"gdn_chunk": 4 * (128 * 2 * 128 + 2 * 2 * 128 * 128
                                     + 2 * 2 * 64 * 64)}


def test_where_no_kernel_runs_a_recomputed_layer_keeps_nothing():
    """On the CPU the rule is the scan and attention the einsum: nothing is
    named, and the layer is recomputed whole from its inputs, as before."""
    for mixer in sorted(MIXERS):
        layer = _recomputed_layer(mixer)
        assert kept_kernels.made_residuals(layer, _hidden()) == []
