"""The deepseek_v3 shaped block (models/mla_moe.py), its expert layer
(distributed/moe.py DroplessMoELayer) and the flash kernels' two widths,
at a small size on the CPU in float32, against the plain reference
benchmark/reference/deepseek_v3_ref.py and against plain einsum code."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from benchmark import program_mla_moe as adapter
from benchmark.reference import deepseek_v3_ref as ref
from paddle_tpu.distributed import moe
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import FunctionalModule
from paddle_tpu.models import (GPTPretrainingCriterion, MlaMoeConfig,
                               MlaMoeForCausalLM, mla_moe)
from paddle_tpu.ops.flash_attention import (_default_block,
                                            flash_attention_val)

# The tests' size. initializer_range 0.1: sqrt(h) * std ~ 0.8 as at the
# published widths (sqrt(2048) * 0.02 = 0.9), so that the blocks move the
# residual stream as they do there and a wrong block shows in the logits
TEST = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
            moe_intermediate_size=32, n_shared_experts=2,
            initializer_range=0.1, n_routed_experts=16,
            num_experts_per_tok=4)


def _cfg(**kw):
    return MlaMoeConfig(**{**TEST, **kw})


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_hidden_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "experts_held": cfg.experts_held}


def _ids(cfg, b=2, s=48, seed=0):
    t = np.random.RandomState(seed).randint(0, cfg.vocab_size, (b, s + 1))
    return t[:, :-1], t[:, 1:]


def _set_bias(model, seed=5, scale=0.05):
    r = np.random.RandomState(seed)
    for m in model.model.moe_layers():
        m.select_bias._value = jnp.asarray(
            r.uniform(-scale, scale, m.select_bias.shape), jnp.float32)


def _compare(model, cfg, x, y):
    lines = []
    out = adapter.compare_with_reference(model, _ref_cfg(cfg), x, y,
                                         lines.append)
    return out, lines


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("held", [None, (4, 12)])
def test_logits_and_loss_agree_with_the_reference(held):
    cfg = _cfg() if held is None else _cfg(
        router_outputs=16, n_routed_experts=8, experts_held=held)
    model = MlaMoeForCausalLM(cfg, seed=3)
    _set_bias(model)
    x, y = _ids(cfg)
    out, lines = _compare(model, cfg, x, y)
    assert out["ok"], lines
    assert out["router_flip_share"] == 0.0
    assert out["router_same_input_flip_share"] == 0.0
    assert out["abs_err"] < 1e-5 and out["max_abs_logit_err"] < 1e-4
    assert len(out["grad_rel_err"]) == 15 and out["max_grad_rel_err"] < 1e-4
    assert out["sigma"] > 0.5      # the logits are no near-constant


def test_gradients_agree_with_jax_grad_of_the_reference_loss():
    cfg = _cfg()
    model = MlaMoeForCausalLM(cfg, seed=4)
    _set_bias(model)
    x, y = _ids(cfg, b=1, s=40)
    fm = FunctionalModule(model)
    crit = GPTPretrainingCriterion()

    def program_loss(pvals):
        out, _ = fm.call(pvals, fm.buffer_values(), jax.random.PRNGKey(0),
                         (jnp.asarray(x),), training=True)
        return crit(paddle.Tensor(out, _internal=True),
                    paddle.to_tensor(y, dtype="int64"))._value

    got = dict(zip(fm.param_names, jax.grad(program_loss)(
        fm.param_values())))
    top, get_layer = adapter.reference_weights(model)
    layers = [get_layer(i) for i in range(cfg.num_hidden_layers)]

    def ref_loss(w):
        out = ref.forward(x, w[0], lambda i: w[1][i], _ref_cfg(cfg))
        return ref.next_token_loss(out["logits"], y)

    g_top, g_layers = jax.grad(ref_loss)((top, layers))
    want = {"model.embed_tokens": g_top["embed_tokens"],
            "model.final_norm_w": g_top["norm"],
            "model.lm_head_w": g_top["lm_head"]}
    for i, g in enumerate(g_layers):
        blk = model.model.layers[i]
        for n in blk.names:
            want[f"model.layers.{i}.{n}"] = g[adapter._LAYER_NAMES[n]]
        if blk.moe is not None:
            for n in blk.moe.names:
                want[f"model.layers.{i}.moe.{n}"] = g[adapter._MOE_NAMES[n]]
            assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    assert set(want) == set(got)
    for name in got:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        err = float(jnp.max(jnp.abs(got[name] - want[name])))
        assert err <= 2e-4 * scale + 1e-7, (name, err, scale)
        assert scale > 1e-8, name          # every parameter has a gradient


def test_train_step_on_the_tiny_model():
    cfg = _cfg(recompute="layer", router_outputs=16, n_routed_experts=8,
               experts_held=(0, 8), initializer_range=0.02)
    model = MlaMoeForCausalLM(cfg, seed=1)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    x, y = _ids(cfg, b=2, s=32)
    losses = []
    for _ in range(8):
        losses.append(float(step(
            inputs=(paddle.to_tensor(x, dtype="int64"),),
            labels=(paddle.to_tensor(y, dtype="int64"),))))
    assert losses[-1] < losses[0] - 0.5, losses
    for m in model.model.moe_layers():
        counts = np.asarray(m.assign_count.numpy())
        assert counts.sum() == 8 * 2 * 32 * cfg.num_experts_per_tok
        bias = np.asarray(m.select_bias.numpy())
        assert np.any(bias != 0) and np.max(np.abs(bias)) <= 8 * 0.001 + 1e-9
        assert m.chosen.shape == (2 * 32, cfg.num_experts_per_tok)
        # fewer rows than a block: a step covers all of them or none
        touched = int(m.touched_count.numpy())
        assert 0 < touched <= 8 * 256 and touched % 256 == 0


def test_eval_forward_leaves_the_buffers():
    cfg = _cfg()
    model = MlaMoeForCausalLM(cfg, seed=1)
    model.eval()
    with paddle.no_grad():
        model(paddle.to_tensor(_ids(cfg)[0], dtype="int64"))
    m = model.model.moe_layers()[0]
    assert np.all(m.assign_count.numpy() == 0)
    assert np.all(m.select_bias.numpy() == 0)
    assert m.touched_count.numpy() == 0 and int(m.rows_touched) == 2 * 48 * 4


# ------------------------------------------------------ the expert layer
def _layer_and_reference(held, bias=None, seed=2, R=16, k=4, h=32, f=16):
    """A DroplessMoELayer holding `held` of R experts, whose weights are
    slices of one full set, and that set in the reference's layout."""
    r = np.random.RandomState(seed)
    full = {"router": r.randn(h, R) * 0.3,
            "experts_gate": r.randn(R, h, f) * 0.2,
            "experts_up": r.randn(R, h, f) * 0.2,
            "experts_down": r.randn(R, f, h) * 0.2,
            "shared_gate": r.randn(h, 2 * f) * 0.2,
            "shared_up": r.randn(h, 2 * f) * 0.2,
            "shared_down": r.randn(2 * f, h) * 0.2,
            "router_bias": np.zeros(R) if bias is None else bias}
    full = {n: jnp.asarray(v, jnp.float32) for n, v in full.items()}
    lo, hi = held
    layer = moe.DroplessMoELayer(h, f, R, k, experts_held=held,
                                 shared_width=2 * f, routed_scaling=2.448)
    layer.router_w._value = full["router"]
    for n, m in (("w_gate", "experts_gate"), ("w_up", "experts_up"),
                 ("w_down", "experts_down")):
        getattr(layer, n)._value = full[m][lo:hi]
    for n in moe.DroplessMoELayer.SHARED:
        getattr(layer, n)._value = full[n]
    layer.select_bias._value = full["router_bias"]
    rcfg = {"num_experts_per_tok": k, "routed_scaling_factor": 2.448}
    return layer, full, rcfg


def _run(layer, x2):
    """The layer as a decoder layer runs it: apply_val, then advance."""
    y, chosen, counts = layer.apply_val(
        x2, [getattr(layer, n)._value for n in layer.names],
        layer.select_bias._value)
    layer.advance(chosen, counts)
    return y


def _ref_layer(x2, full, rcfg, held):
    p = dict(full)
    lo, hi = held
    for n in ("experts_gate", "experts_up", "experts_down"):
        p[n] = full[n][lo:hi]
    scores, own, _ = ref.route(x2, p, rcfg)
    return ref.expert_ffn(x2, p, dict(rcfg, experts_held=held), scores, own)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    x2 = jnp.asarray(np.random.RandomState(0).randn(48, 32), jnp.float32)
    parts, shared = [], None
    for i in range(8):
        held = (2 * i, 2 * i + 2)
        layer, full, rcfg = _layer_and_reference(held)
        layer.eval()
        y = _run(layer, x2)
        shared = ref.swiglu(x2, full["shared_gate"], full["shared_up"],
                            full["shared_down"])
        parts.append(y - shared)         # what every chip computes alike
        np.testing.assert_allclose(y, _ref_layer(x2, full, rcfg, held),
                                   atol=2e-5)
    whole = _ref_layer(x2, full, rcfg, (0, 16))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=5e-5)
    assert float(jnp.std(whole - shared)) > 0.05   # the routed part counts


def test_a_router_forced_onto_one_expert_loses_no_token():
    bias = np.zeros(16)
    bias[5] = 10.0                        # every token chooses expert 5
    layer, full, rcfg = _layer_and_reference((4, 8), bias=bias)
    layer.train()
    x2 = jnp.asarray(np.random.RandomState(1).randn(120, 32), jnp.float32)
    y = _run(layer, x2)
    assert int(layer.assign_count.numpy()[5]) == 120       # all of them
    assert np.all(np.any(np.asarray(layer.chosen) == 5, axis=-1))
    np.testing.assert_allclose(y, _ref_layer(x2, full, rcfg, (4, 8)),
                               atol=2e-5)


def test_the_bias_selects_and_does_not_weigh_and_takes_no_gradient():
    x2 = jnp.asarray(np.random.RandomState(3).randn(64, 32), jnp.float32)
    w = jnp.asarray(np.random.RandomState(4).randn(32, 16) * 0.3,
                    jnp.float32)
    zero = jnp.zeros(16)
    bias = zero.at[2].set(5.0)
    c0, w0 = moe.sigmoid_topk_route(x2, w, zero, 4, 2.448)
    c1, w1 = moe.sigmoid_topk_route(x2, w, bias, 4, 2.448)
    assert np.all(np.any(np.asarray(c1) == 2, -1))
    assert not np.all(np.any(np.asarray(c0) == 2, -1))
    # the weights are the unbiased scores of what was chosen, normalised
    s = jax.nn.sigmoid(x2 @ w)
    picked = jnp.take_along_axis(s, c1, -1)
    np.testing.assert_allclose(
        w1, picked / picked.sum(-1, keepdims=True) * 2.448, rtol=1e-5)
    np.testing.assert_allclose(w1.sum(-1), 2.448, rtol=1e-5)
    g = jax.grad(lambda b: jnp.sum(
        moe.sigmoid_topk_route(x2, w, b, 4, 2.448)[1] ** 2))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_the_bias_moves_toward_balance():
    layer, _, _ = _layer_and_reference((0, 16))
    layer.train()
    _run(layer, jnp.asarray(np.random.RandomState(6).randn(256, 32),
                            jnp.float32))
    counts = np.asarray(layer.assign_count.numpy())
    bias = np.asarray(layer.select_bias.numpy())
    mean = counts.mean()
    assert np.all(bias[counts > mean] == pytest.approx(-0.001))
    assert np.all(bias[counts < mean] == pytest.approx(0.001))


# ------------------------------------- the sorted buffer's live rows and tail
def _plain_held_experts_ffn(x2, chosen, weights, w_gate, w_up, w_down, lo):
    """The formulation this layer had at PR 27, kept as the reference: a
    gather of all T*k rows each way, a pass of its own that zeroes the
    tail, and jax's own transposes."""
    T, k = chosen.shape
    E = w_gate.shape[0]
    flat = chosen.reshape(-1)
    held = (flat >= lo) & (flat < lo + E)
    local = jnp.where(held, flat - lo, E)
    order = jnp.argsort(local, stable=True)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    group_sizes = jnp.sum(local[:, None] == jnp.arange(E)[None, :], axis=0,
                          dtype=jnp.int32)
    xs = jnp.take(x2, order // k, axis=0)
    a = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, group_sizes)) \
        * jax.lax.ragged_dot(xs, w_up, group_sizes)
    out = jax.lax.ragged_dot(a, w_down, group_sizes)
    rows = jnp.arange(out.shape[0])[:, None]
    out = jnp.where(rows < jnp.sum(group_sizes), out, 0.0)     # _keep_rows
    sel = jnp.take(out, inv, axis=0).reshape(T, k, -1)
    return jnp.sum(sel * weights[..., None], axis=1)


# 1,250 tokens x 4 = 5,000 assignment rows: two whole blocks and a part
N_TOKENS, N_K, N_HELD_LO, N_HELD = 1250, 4, 4, 4
BLOCK = moe.ROW_BLOCK
# rows of held experts -> rows the sorted-side passes cover
LIVE_CASES = {0: 0, 1: BLOCK, BLOCK - 1: BLOCK, BLOCK: BLOCK,
              BLOCK + 1: 2 * BLOCK, 2 * BLOCK: 2 * BLOCK,
              2 * BLOCK + 1: N_TOKENS * N_K, N_TOKENS * N_K: N_TOKENS * N_K}


def _assignments(live, seed=0, h=32, f=16):
    """Inputs of held_experts_ffn (experts 4-7 of 16 held) in which exactly
    `live` of the 5,000 assignments go to held experts."""
    r = np.random.RandomState(seed)
    n = N_TOKENS * N_K
    absent = np.r_[0:N_HELD_LO, N_HELD_LO + N_HELD:16]
    flat = absent[r.randint(0, len(absent), n)]
    at = r.permutation(n)[:live]
    flat[at] = N_HELD_LO + r.randint(0, N_HELD, live)
    vals = [r.randn(N_TOKENS, h), r.uniform(0.1, 1.0, (N_TOKENS, N_K)),
            r.randn(N_HELD, h, f) * 0.2, r.randn(N_HELD, h, f) * 0.2,
            r.randn(N_HELD, f, h) * 0.2]
    x2, weights, *w = (jnp.asarray(v, jnp.float32) for v in vals)
    chosen = jnp.asarray(flat.reshape(N_TOKENS, N_K), jnp.int32)
    cot = jnp.asarray(r.randn(N_TOKENS, h), jnp.float32)
    return chosen, (x2, weights, *w), cot


FFN_OUTPUTS = ("y", "d x2", "d weights", "d w_gate", "d w_up", "d w_down")


def _value_and_grads(ffn, chosen, args, cot):
    """FFN_OUTPUTS of `ffn` on _assignments' inputs, under the weight `cot`
    on every output."""
    def value(*a):
        x2, weights, *w = a
        y = ffn(x2, chosen, weights, *w, N_HELD_LO)
        return jnp.sum(y * cot), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        value, argnums=tuple(range(5)), has_aux=True))(*args)
    return (y,) + grads


@pytest.mark.parametrize("live", sorted(LIVE_CASES))
def test_held_experts_equal_the_plain_formulation_at_every_load(live):
    chosen, args, cot = _assignments(live)
    got = _value_and_grads(moe.held_experts_ffn, chosen, args, cot)
    want = _value_and_grads(_plain_held_experts_ffn, chosen, args, cot)
    # the layer sorts its assignments k-major (flat index j*T + t), the
    # plain formulation token-major, so the rows of one expert's group stand
    # in another order and a float32 sum over them rounds otherwise: hence
    # the rtol, and an atol of a few float32 ulps of the output's LARGEST
    # entry, since a sum that cancels keeps the error of its partial sums
    # (at every row live d w_down, entries up to 114: the two differ by
    # 8.4e-5 and are 3.1e-5 and 7.6e-5 off the same sum in float64)
    for name, g, w in zip(FFN_OUTPUTS, got, want):
        atol = max(2e-5, 1e-6 * float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(g, w, atol=atol, rtol=1e-5, err_msg=name)
    # none of the held chosen: nothing comes back, nothing flows
    assert (float(jnp.max(jnp.abs(want[0]))) > 0.01) == (live > 0)


def _ragged_dot_that_leaves_the_tail_unwritten():
    """jax.lax.ragged_dot as the v5e runs it (PERF.md finding 14): the
    kernel stops at the last group, forward and backward. It reads no row
    past it (whatever stands there is zeroed before the CPU's product sees
    it) and writes none (they come back NaN)."""
    real = jax.lax.ragged_dot

    def tail(v, group_sizes, fill):
        rows = jnp.arange(v.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(group_sizes), v, fill)

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        out = real(tail(lhs, group_sizes, 0.0), rhs, group_sizes)
        return tail(out, group_sizes, jnp.nan)

    def fwd(lhs, rhs, group_sizes):
        return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes),
                         tail(lhs, group_sizes, 0.0), rhs)
        d_lhs, d_rhs = vjp(tail(g, group_sizes, 0.0))
        return (tail(d_lhs, group_sizes, jnp.nan), d_rhs,
                np.zeros(group_sizes.shape, jax.dtypes.float0))

    poisoned.defvjp(fwd, bwd)
    return poisoned


@pytest.mark.parametrize("live", sorted(LIVE_CASES))
def test_a_poisoned_tail_reaches_no_output_and_no_gradient(live, monkeypatch):
    chosen, args, cot = _assignments(live, seed=1)
    want = _value_and_grads(moe.held_experts_ffn, chosen, args, cot)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _ragged_dot_that_leaves_the_tail_unwritten())
    got = _value_and_grads(moe.held_experts_ffn, chosen, args, cot)
    for name, g, w in zip(FFN_OUTPUTS, got, want):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, atol=1e-6, err_msg=name)


def _multiplied_away(rows, at, live):
    """`moe._live_rows` as it must not be: 0 * NaN is NaN."""
    keep = (at < live).reshape(at.shape + (1,) * (rows.ndim - 1))
    return rows * keep.astype(rows.dtype)


# a name of `moe` -> a stand-in that drops the tail by a multiply
TAIL_MUTANTS = {
    "_live_rows_by_token": lambda rows, inv, live: _multiplied_away(
        jnp.take(rows, inv, axis=0), inv, live),
    "_live_rows": _multiplied_away}


@pytest.mark.parametrize("name", sorted(TAIL_MUTANTS))
def test_the_poison_is_felt_where_the_tail_is_multiplied_away(name,
                                                              monkeypatch):
    """The guard guards: a combine that drops the tail by a multiply (0 *
    NaN) is caught by the poisoned product, and the whole layer, router and
    shared expert and all, is not."""
    layer, _, _ = _layer_and_reference((4, 8))
    x2 = jnp.asarray(np.random.RandomState(2).randn(700, 32), jnp.float32)
    vals = [getattr(layer, n)._value for n in layer.names]

    def loss(x, *p):
        return jnp.sum(layer.apply_val(x, p, layer.select_bias._value)[0]
                       ** 2)

    argnums = tuple(range(1 + len(vals)))
    want = jax.grad(loss, argnums)(x2, *vals)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _ragged_dot_that_leaves_the_tail_unwritten())
    got = jax.grad(loss, argnums)(x2, *vals)
    for name_, g, w in zip(("x2",) + layer.names, got, want):
        assert np.all(np.isfinite(g)), name_
        np.testing.assert_allclose(g, w, atol=1e-5, err_msg=name_)
    monkeypatch.setattr(moe, name, TAIL_MUTANTS[name])
    assert not np.all(np.isfinite(loss(x2, *vals)))


# ------------------------------------- the combine alone, on the sorted side
COMBINE_CASES = [(live, k) for k in (4, 6, 10) for live in sorted(LIVE_CASES)]


def _combine_inputs(live, k, seed=0, h=32):
    """Arguments of moe._weighted_rows_by_token (out, w, order, inv, live)
    and a cotangent: k assignments a token, k-major, over the fewest tokens
    that give LIVE_CASES' 5,000 rows (5,004 at k = 6, where the last case
    is every row), exactly `live` of them to held experts."""
    T = -(-N_TOKENS * N_K // k)
    n = T * k
    live = n if live == N_TOKENS * N_K else live
    r = np.random.RandomState(seed)
    local = np.full(n, N_HELD)
    local[r.permutation(n)[:live]] = r.randint(0, N_HELD, live)
    order = np.argsort(local, kind="stable").astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(n, dtype=np.int32)
    out, w, cot = (jnp.asarray(v, jnp.float32) for v in (
        r.randn(n, h), r.uniform(0.1, 1.0, (k, T)), r.randn(T, h)))
    return (out, w, jnp.asarray(order), jnp.asarray(inv),
            jnp.int32(live)), cot


def _plain_combine(out, w, order, inv, live):
    """The combine in plain jnp, for jax's own transposes: the tail zeroed
    in a pass of its own, every row gathered, a sum over k."""
    rows = jnp.arange(out.shape[0])[:, None]
    sel = jnp.take(jnp.where(rows < live, out, 0.0), inv, axis=0)
    return jnp.sum(sel.reshape(w.shape + (-1,)) * w[..., None], axis=0)


def _combine_and_grads(combine, args, cot):
    """(y, d out, d w) of `combine` under the cotangent `cot`."""
    out, w, *perm = args
    y, vjp = jax.vjp(lambda o, w_: combine(o, w_, *perm), out, w)
    return (y,) + vjp(cot)


@pytest.mark.parametrize("live,k", COMBINE_CASES)
def test_the_combine_equals_its_plain_formulation(live, k):
    args, cot = _combine_inputs(live, k)
    live = int(args[-1])
    y, d_out, d_w = _combine_and_grads(moe._weighted_rows_by_token, args,
                                       cot)
    want_y, want_d_out, want_d_w = _combine_and_grads(_plain_combine, args,
                                                      cot)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-5)
    # the cotangent's rows past `live` have no value: the grouped products
    # stop at the last group
    np.testing.assert_allclose(d_out[:live], want_d_out[:live], atol=2e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(d_w, want_d_w, atol=2e-5, rtol=1e-5)
    assert (float(jnp.max(jnp.abs(want_d_w))) > 0.01) == (live > 0)


@pytest.mark.parametrize("live,k", COMBINE_CASES)
def test_an_assignment_to_an_absent_expert_has_a_zero_weight_gradient(live,
                                                                      k):
    args, cot = _combine_inputs(live, k, seed=1)
    _, _, d_w = _combine_and_grads(moe._weighted_rows_by_token, args, cot)
    absent = np.asarray(args[3] >= args[4]).reshape(d_w.shape)
    assert absent.sum() == d_w.size - int(args[4])
    assert np.all(np.asarray(d_w)[absent] == 0.0)
    assert np.all(np.asarray(d_w)[~absent] != 0.0)


@pytest.mark.parametrize("live,k", COMBINE_CASES)
def test_a_poisoned_tail_reaches_neither_gradient_of_the_combine(
        live, k, monkeypatch):
    """`out`'s rows at or past `live` as the TPU's grouped product leaves
    them: they reach neither the output nor d w (the sorted-side dot drops
    them by index), and d out's covered rows never read them."""
    (out, *rest), cot = _combine_inputs(live, k, seed=2)
    live = int(rest[-1])
    want = _combine_and_grads(moe._weighted_rows_by_token, (out, *rest), cot)
    poisoned = out.at[live:].set(jnp.nan)
    got = _combine_and_grads(moe._weighted_rows_by_token, (poisoned, *rest),
                             cot)
    covered = int(moe.rows_covered(live, out.shape[0]))
    for name, g, w in zip(("y", "d out", "d w"), got, want):
        if name == "d out":
            g, w = g[:covered], w[:covered]
        assert np.all(np.isfinite(g)), name
        np.testing.assert_array_equal(g, w, err_msg=name)
    # the guard guards: the dot with the tail multiplied away is caught
    monkeypatch.setattr(moe, "_live_rows", _multiplied_away)
    d_w = _combine_and_grads(moe._weighted_rows_by_token, (poisoned, *rest),
                             cot)[2]
    # (where the loop covers a row of the tail at all)
    assert np.all(np.isfinite(d_w)) == (covered == live)


@pytest.mark.parametrize("live", sorted(LIVE_CASES))
def test_rows_touched_is_live_rounded_up_to_a_block(live):
    covered = LIVE_CASES[live]
    chosen, (x2, *_), _ = _assignments(live, seed=2)
    # what the loop wrote: every row it covered holds its token, the rest
    # were never visited
    src = jnp.abs(x2) + 1.0
    idx = jnp.asarray(np.random.RandomState(3).randint(
        0, N_TOKENS, N_TOKENS * N_K), jnp.int32)
    got = np.asarray(moe._sorted_rows(src, idx, jnp.int32(live)))
    np.testing.assert_array_equal(got[:covered], np.asarray(src)[
        np.asarray(idx)[:covered]])
    assert np.all(got[covered:] == 0)
    assert int(moe.rows_covered(jnp.int32(live), N_TOKENS * N_K)) == covered
    # what the layer says it wrote, and keeps count of in training alone
    layer = moe.DroplessMoELayer(32, 16, 16, N_K, experts_held=(
        N_HELD_LO, N_HELD_LO + N_HELD))
    counts = jnp.bincount(chosen.reshape(-1), length=16).astype(jnp.int32)
    layer.train()
    layer.advance(chosen, counts)
    layer.advance(chosen, counts)
    assert int(layer.rows_touched) == covered
    assert int(layer.touched_count.numpy()) == 2 * covered
    layer.eval()
    layer.advance(chosen, counts)
    assert int(layer.rows_touched) == covered
    assert int(layer.touched_count.numpy()) == 2 * covered


# ------------------------------------------------- rotary, attention, kernel
def test_rotary_turns_adjacent_pairs():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 9, 3, 8), jnp.float32)
    got = np.asarray(mla_moe.rotary_interleaved(x, 1e4))
    for pos in (0, 4, 8):
        for i in range(4):
            ang = pos * 1e4 ** (-2 * i / 8)
            a, b = np.asarray(x[1, pos, 2, 2 * i]), np.asarray(
                x[1, pos, 2, 2 * i + 1])
            assert got[1, pos, 2, 2 * i] == pytest.approx(
                a * np.cos(ang) - b * np.sin(ang), abs=1e-5)
            assert got[1, pos, 2, 2 * i + 1] == pytest.approx(
                b * np.cos(ang) + a * np.sin(ang), abs=1e-5)
    # q . k depends on the distance alone
    q = jnp.ones((1, 9, 1, 8)) * jnp.arange(1., 9.)
    r = np.asarray(mla_moe.rotary_interleaved(q, 1e4))[0, :, 0]
    assert r[2] @ r[5] == pytest.approx(r[4] @ r[7], rel=1e-5)


def test_latent_attention_against_plain_einsum_code():
    cfg = _cfg()
    r = np.random.RandomState(1)
    p = {n: jnp.asarray(r.randn(*s) * (1.0 if len(s) == 1 else 0.1),
                        jnp.float32)
         for n, s in mla_moe._attn_shapes(cfg).items()}
    x = jnp.asarray(r.randn(2, 12, cfg.hidden_size), jnp.float32)
    got = mla_moe.latent_attention(x, p, cfg)
    rp = {adapter._LAYER_NAMES[n]: v for n, v in p.items()}
    want = jnp.stack([ref.attention(x[j], rp, _ref_cfg(cfg), 4)
                      for j in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-5)


def _plain_attention(q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((q.shape[1],) * 2, bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("blocks", [(16, 32), (64, 64), (32, 16)])
def test_flash_kernel_with_two_widths_forward_and_three_gradients(blocks):
    r = np.random.RandomState(0)
    q, k = (jnp.asarray(r.randn(2, 64, 2, 24), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
    w = jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
    out, vjp = jax.vjp(lambda *a: flash_attention_val(
        *a, block_q=blocks[0], block_k=blocks[1]), q, k, v)
    want, vjp_w = jax.vjp(_plain_attention, q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, want, atol=2e-5)
    for g, gw, like in zip(vjp(w), vjp_w(w), (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, gw, atol=5e-5)


def test_flash_kernel_refuses_q_and_k_of_different_widths():
    q = jnp.zeros((1, 16, 1, 24))
    with pytest.raises(ValueError, match="must agree"):
        flash_attention_val(q, jnp.zeros((1, 16, 1, 16)), q)


# The traced FORWARD of an equal-width call (kernel body, grid, block
# shapes, scratch) at the GPT cells' shapes, hashed from the code of PR 31
# (8858e93), whose forward is PR 26's from before the kernels learnt the
# second width: the same program gives bit-identical outputs, on the CPU
# and on the chip. The backward became one kernel in PR 32 and is held to
# the reference in tests/test_flash_attention.py. (Traced under matmul
# precision "highest", which the printed program names, as
# tests/conftest.py sets.)
PARENT_PROGRAMS = {"gpt_s1024": ((12, 1024, 12, 64), "bfloat16",
                                 "2257bdbb4f3d4861"),
                   "gpt_s2048": ((6, 2048, 12, 64), "bfloat16",
                                 "6affac3a855f8bc8"),
                   "f32_d128": ((1, 256, 2, 128), "float32",
                                "d29f2b75b03724cf")}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_equal_width_calls_are_the_program_they_were(name):
    shape, dtype, digest = PARENT_PROGRAMS[name]
    a = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(flash_attention_val)(a, a, a))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_the_block_ladder_for_64_wide_heads_did_not_move():
    assert _default_block(64, jnp.bfloat16) == 1024
    assert _default_block(128, jnp.bfloat16) == 1024
    assert _default_block(64, jnp.float32) == 1024
    assert _default_block(256, jnp.float32) == 512
    assert _default_block(192, jnp.bfloat16, 128) == \
        _default_block(192, jnp.bfloat16)


# ---------------------------------------------------------------- mutants
def _route_mutant(scores="sigmoid", bias_in_weights=False, scale_on=True,
                  dtype=jnp.float32):
    def route(x2, router_w, bias, top_k, scale):
        logits = (x2.astype(dtype) @ router_w.astype(dtype))
        s = (jax.nn.softmax(logits, -1) if scores == "softmax"
             else jax.nn.sigmoid(logits)).astype(jnp.float32)
        _, chosen = jax.lax.top_k(s + bias, top_k)
        picked = jnp.take_along_axis(s + bias if bias_in_weights else s,
                                     chosen, -1)
        w = picked / picked.sum(-1, keepdims=True)
        return chosen.astype(jnp.int32), w * (scale if scale_on else 1.0)
    return route


def _rotary_split_halves(x, theta):
    s, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (r // 2,))
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _shared_expert_with_a_wrong_backward(x, w_gate, w_up, w_down):
    """moe.swiglu forward; backward with half the gradient of w_up."""
    @jax.custom_vjp
    def f(*a):
        return _SWIGLU(*a)

    def fwd(*a):
        return jax.vjp(_SWIGLU, *a)

    def bwd(vjp, ct):
        dx, dg, du, dd = vjp(ct)
        return dx, dg, 0.5 * du, dd

    f.defvjp(fwd, bwd)
    return f(x, w_gate, w_up, w_down)


_SWIGLU = moe.swiglu

MUTANTS = {
    "softmax_for_sigmoid": (moe, "sigmoid_topk_route",
                            _route_mutant(scores="softmax")),
    "bias_added_to_the_weights": (moe, "sigmoid_topk_route",
                                  _route_mutant(bias_in_weights=True)),
    "scaling_2.448_left_out": (moe, "sigmoid_topk_route",
                               _route_mutant(scale_on=False)),
    "rotary_on_split_halves": (mla_moe, "rotary_interleaved",
                               _rotary_split_halves),
    "shared_expert_left_out": (moe, "swiglu",
                               lambda x, *w: jnp.zeros_like(x)),
    "router_in_bf16": (moe, "sigmoid_topk_route",
                       _route_mutant(dtype=jnp.bfloat16)),
    "wrong_backward_of_the_shared_expert": (
        moe, "swiglu", _shared_expert_with_a_wrong_backward),
}


def test_the_unmutated_router_copy_passes():
    """The mutants' own copy of the router, unmutated, is accepted: what
    refuses a mutant is its one change."""
    cfg = _cfg(router_outputs=64, n_routed_experts=64,
               num_experts_per_tok=6)
    model = MlaMoeForCausalLM(cfg, seed=3)
    _set_bias(model, scale=0.2)      # a bias large enough to show misuse
    x, y = _ids(cfg, b=2, s=96)
    orig = moe.sigmoid_topk_route
    moe.sigmoid_topk_route = _route_mutant()
    try:
        out, lines = _compare(model, cfg, x, y)
    finally:
        moe.sigmoid_topk_route = orig
    assert out["ok"], lines


@pytest.mark.parametrize("name", sorted(MUTANTS))
def test_the_comparison_refuses_a_mutant(name, monkeypatch):
    cfg = _cfg(router_outputs=64, n_routed_experts=64,
               num_experts_per_tok=6)
    model = MlaMoeForCausalLM(cfg, seed=3)
    _set_bias(model, scale=0.2)      # a bias large enough to show misuse
    x, y = _ids(cfg, b=2, s=96)
    module, attr, mutant = MUTANTS[name]
    monkeypatch.setattr(module, attr, mutant)
    out, lines = _compare(model, cfg, x, y)
    assert not out["ok"], lines
    if name == "wrong_backward_of_the_shared_expert":    # by (c) alone
        assert out["max_grad_rel_err"] > 0.2 and out["abs_err"] < 1e-5
