"""The adapter between the benchmark and the program under test, for the
`qwen3_next` family (Gated DeltaNet layers beside gated grouped-head
attention, softmax-routed experts with a gated shared expert): builds the
model and the train step through the entry points a user calls, hands the
program's weights to the plain reference in the reference's layout, and
makes the comparison that decides `correct`. What the two expert families
share (the embedding's redraw, the step-program check, the counters, the
token-specific input) is program_mla_moe's, imported.

THE COMPARISON'S LIMITS, each with its reason and its two readings (TPU
v5e, the cell's size, PR 33: the program as it is over ten runs on ten
seeds, and the control that the limit has to refuse, patched into a run of
the same command; tests/benchmark_yardstick/test_benchmark_gdn_moe.py
plants the same four faults at a small size on the CPU).
The program computes in bfloat16 where the configuration says so; norms,
rotary, softmax statistics, the router, beta, g and everything inside the
delta rule in float32. Two kinds of pass are compared, as in
deepseek_v3_ref.py: the training-mode forward on the cell's seeded ids, and
single layers (the LAST DeltaNet layer and the LAST attention layer),
forward and backward through the program's own block, each on a seeded
N(0, 1) hidden state of the timed length.

(a1) ROUTER_SAME_INPUT_FLIP_TOL = 0.002: the program's router and the
     reference's, both on what the REFERENCE's router saw (cast to the
     activations' dtype): the share of (token, layer) pairs whose chosen set
     differs. One input, so only the router's own arithmetic differs. As it
     is: 0 of 49,152 pairs in every run. The router's softmax in bfloat16:
     0.0648.
(a2) ROUTER_MARGIN_TOL = 6e-4: where the program's choice differs from the
     reference's on ITS hidden state (bf16 rounding of the hidden state
     moves near-ties: 4.2-4.5 % of the pairs differ, so the share has no
     limit), the reference's margin (the 10th largest softmax probability
     less the 11th; a chosen probability is ~0.005). As it is: 1.31e-4 to
     1.69e-4 in every run. A wrong block upstream (beta fixed at 1; the
     shared expert's gate left out): 2.35e-3, 2.51e-3. (The bfloat16
     router reads 2.6e-4 here and fails (a1): one limit refuses it.)
(b)  LOSS_ATOL = 0.02, LOGIT_TOL_SIGMAS = 0.2: the reference computed ON
     the program's choices, in loss and in logits (gpt2_ref.py's limits and
     reasons). As it is: |dloss| <= 1e-4, 0.034-0.035 sigma. Beta fixed at
     1: 3.2 sigma; the gate left out: |dloss| 0.054, 8.0 sigma.
(c)  GRAD_REL_TOL = 0.2 (deepseek_v3_ref.py's): |program - reference| /
     |reference|, Frobenius, for the gradient by every parameter of the two
     layers and by their inputs. As it is: 0.003 the inputs, 0.006-0.007
     every mixer's and the shared expert's parameters, 0.016-0.033 the
     attention layer's routed experts and router (bf16 products of few
     rows each). Beta fixed at 1: 1.04 (dt_bias); the gate left out: 1.01.
(d)  DELTA_RULE_REL_TOL = 4e-5: the delta rule alone, the program's chunked
     function on the chip against the reference's recurrence on the host's
     CPU (delta_rule_alone says why there), both in float32 on the SAME q,
     k, v, g, beta (the reference's own, of the last DeltaNet layer on the
     token-specific input): the output and the five gradients, each as in
     (c). Two derivations that differ by float32 rounding only. As it is,
     on the seed whose slowest head drew A = 0.022: 1.2e-6 the output and
     four gradients, 2.3e-6 the gradient by g. The state S carried in
     bfloat16: 2.0e-4 to 3.2e-4 (three seeds), 7.8e-4 on that seed, which
     no other limit sees ((c) reads 0.014 then): the released
     initialisation makes most heads forget within a few tokens (A =
     exp(A_log) is uniform(0, 16) a head), so a rounded state is rounded
     away again and the error stays small; the limit lies 17x over the
     largest reading and 5x under the smallest control.
The readings are in PERF.md, section 6 (PR 33).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic_gen
from benchmark.program_gpt import _seed32
from benchmark.program_mla_moe import (  # noqa: F401
    assign_counts, check_step_program, redraw_embedding,
    token_specific_input)
from benchmark.reference import qwen3_next_ref as ref_mod

LOGIT_TOL_SIGMAS = 0.2
LOSS_ATOL = 0.02
ROUTER_SAME_INPUT_FLIP_TOL = 0.002
ROUTER_MARGIN_TOL = 6e-4
GRAD_REL_TOL = 0.2
DELTA_RULE_REL_TOL = 4e-5

# config.json keys the program's GdnMoeConfig takes under the same name
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "rope_theta",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "rms_norm_eps", "dtype")
# what this block has no code for: a file that asks for it is refused
_MUST_BE = {"decoder_sparse_step": 1, "mlp_only_layers": [],
            "norm_topk_prob": True, "hidden_act": "silu",
            "rope_scaling": None, "tie_word_embeddings": False,
            "use_sliding_window": False}


def model_config(cell, **overrides):
    """The program's GdnMoeConfig from the cell's configuration FILE."""
    from paddle_tpu.models import GdnMoeConfig

    c = cell.config
    for key, want in _MUST_BE.items():
        if c.get(key, want) != want:
            raise ValueError(f"{key} = {c[key]!r}: the program's block "
                             f"computes {want!r} only")
    kw = {k: c[k] for k in _CONFIG_KEYS}
    kw.update(router_outputs=c["router_outputs"],
              experts_held=tuple(c["experts_held"]),
              recompute=c.get("recompute", "none"),
              initializer_range=c["initializer_range"])
    kw.update(overrides)
    return GdnMoeConfig(**kw)


def build_train(cell, seed: int) -> dict:
    """model -> AdamW -> TrainStep on one chip, as program_mla_moe builds
    its step. A mesh is refused: the expert layer's exchange across chips
    does not exist yet."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GdnMoeForCausalLM, GPTPretrainingCriterion

    tr = cell.traffic
    if tr.get("mesh") or cell.chips != 1:
        raise ValueError("the qwen3_next block trains on one chip only")
    mesh_mod.set_mesh(None)
    cfg = model_config(cell)
    o = tr["optimizer"]
    if o["name"] != "AdamW":
        raise ValueError(f"optimizer {o['name']!r}: only AdamW is wired")
    crit = GPTPretrainingCriterion()
    model = GdnMoeForCausalLM(cfg, seed=_seed32(seed))
    redraw_embedding(model, cell.config["embedding_initializer_range"], seed)
    optim = opt.AdamW(learning_rate=o["learning_rate"],
                      parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    return {"step": step, "model": model, "cfg": cfg}


# ---------------------------------------------------------------- reference
_LAYER_NAMES = {
    "in_norm_w": "input_layernorm", "ffn_norm_w": "post_attention_layernorm",
    # DeltaNet
    "qkvz_w": "in_proj_qkvz", "ba_w": "in_proj_ba", "conv_w": "conv1d",
    "A_log": "A_log", "dt_bias": "dt_bias", "out_norm_w": "norm",
    # attention
    "q_w": "q_proj", "k_w": "k_proj", "v_w": "v_proj", "q_norm_w": "q_norm",
    "k_norm_w": "k_norm"}
_O_NAME = {False: "out_proj", True: "o_proj"}       # by full_attention
_MOE_NAMES = {"router_w": "router", "w_gate": "experts_gate",
              "w_up": "experts_up", "w_down": "experts_down",
              "shared_gate": "shared_gate", "shared_up": "shared_up",
              "shared_down": "shared_down",
              "shared_gate_w": "shared_expert_gate"}


def reference_names(layer) -> dict:
    """{the program's parameter name in `layer`: the reference's}."""
    names = {n: _LAYER_NAMES.get(n, _O_NAME[layer.full_attention])
             for n in layer.names}
    names.update({"moe." + n: _MOE_NAMES[n] for n in layer.moe.names})
    return names


def reference_weights(model):
    """(top, get_layer) in the reference's layout, from the live model.
    Arrays are fetched one layer at a time."""
    m = model.model

    def f32(p):
        return np.asarray(p._value, np.float32)

    top = {"embed_tokens": f32(m.embed_tokens), "norm": f32(m.final_norm_w),
           "lm_head": f32(m.lm_head_w)}

    def get_layer(i: int) -> dict:
        blk = m.layers[i]
        return {ref: f32(getattr(blk.moe, n[4:]) if n.startswith("moe.")
                         else getattr(blk, n))
                for n, ref in reference_names(blk).items()}

    return top, get_layer


def reference_config(cell_config: dict) -> dict:
    """The keys the reference reads, from the configuration file's dict."""
    keys = ("num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "rms_norm_eps", "num_experts_per_tok", "experts_held")
    return {k: cell_config[k] for k in keys}


def _rel(got, want) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm((got.astype(jnp.float32) - want).ravel())
                 / (jnp.linalg.norm(want.ravel()) + 1e-30))


def forward_fn(model):
    """(fn, args): fn(x, y, *args) is the program's forward in training mode
    on ids x [b, s] (labels y), made the way jit.TrainStep makes its step
    (FunctionalModule.call, the criterion), and gives (loss, logits, each
    layer's chosen experts). The ids are ARGUMENTS: closed over they are
    constants, and every seed's program is another one to compile."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import FunctionalModule
    from paddle_tpu.models import GPTPretrainingCriterion

    fm = FunctionalModule(model)
    moes = model.model.moe_layers()
    crit = GPTPretrainingCriterion()

    def fn(x, y, pvals, bvals):
        out, _ = fm.call(pvals, bvals, jax.random.PRNGKey(0), (x,),
                         training=True)
        loss = crit(paddle.Tensor(out, _internal=True),
                    paddle.Tensor(y, _internal=True))._value
        return loss.astype(jnp.float32), out, [m.chosen for m in moes]

    return fn, (fm.param_values(), fm.buffer_values())


def layer_pass_fn(layer):
    """(fn, args, names): fn(x_in, cot, *args) is the decoder layer `layer`
    in training mode on a hidden state x_in [b, s, h], forward and
    backward: ((sum(out * cot) / tokens, the chosen experts), (the gradient
    of that number by x_in, by the layer's parameters in the order of
    `names`)). The layer's own forward runs, recomputation and all. `cot`
    is an ARGUMENT (as it is of every jitted function here): closed over,
    its 67 MB become a constant of the program, which then compiles for
    minutes and passes the compile cache's entry limit."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.functional import FunctionalModule

    fm = FunctionalModule(layer)

    def value_of(x_in, pvals, cot, bvals):
        out, _ = fm.call(pvals, bvals, jax.random.PRNGKey(0), (x_in,),
                         training=True)
        return jnp.sum(out.astype(jnp.float32) * cot) \
            / (cot.shape[0] * cot.shape[1]), layer.moe.chosen

    grad = jax.value_and_grad(value_of, argnums=(0, 1), has_aux=True)
    return (lambda x_in, cot, pvals, bvals: grad(x_in, pvals, cot, bvals),
            (fm.param_values(), fm.buffer_values()), list(fm.param_names))


def delta_rule_alone(q, k, v, g, beta, cot) -> dict:
    """Part (d): the program's chunked rule against the reference's
    recurrence on one sequence's float32 inputs (q, k [T, H, dk], v
    [T, H, dv], g, beta [T, H]; `cot` [T, H, dv] weighs the outputs): the
    relative error of the output and of the five gradients."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_rule as rule

    def program(*a):
        o = rule.gated_delta_rule_chunked(*(t[None] for t in a[:5]))[0]
        return jnp.sum(o * a[5]), o

    def reference(*a):
        o = ref_mod.delta_rule(*a[:5])
        return jnp.sum(o * a[5]), o

    args = (q, k, v, g, beta, cot)
    (_, got_o), got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    # The recurrence runs on the host's CPU. The TPU's float32 exp is low
    # by 8e-7 on average (7 ulp; the CPU's is unbiased), and a decay
    # applied token by token compounds that over a slow head's memory: on
    # the seed that drew A = 0.022 the TPU's recurrence is 1.4e-5 (dg
    # 4.1e-5) off the CPU's, the chunked form, which takes one exp for a
    # pair of tokens, 1.2e-6 (2.3e-6) (PERF.md, section 6, PR 33).
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        (_, want_o), want = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                *(jax.device_put(np.asarray(t), cpu) for t in args))
    want_o, want = np.asarray(want_o), [np.asarray(t) for t in want]
    err = {"o": _rel(got_o, want_o)}
    err.update({"d" + n: _rel(a, b)
                for n, a, b in zip(("q", "k", "v", "g", "beta"), got, want)})
    return err


def compare_with_reference(model, ref_cfg: dict, x, y, log,
                           seed: int = 0) -> dict:
    """The program against the float32 reference, in the parts the module
    docstring gives the limits of. On ids x [b, s] (labels y), the
    training-mode forward: (b) logits and loss with the reference computed
    on the program's choices. On a token-specific hidden state made from
    `seed`, the last DeltaNet layer and the last attention layer alone,
    forward and backward: (c) the gradients by their parameters and their
    inputs against jax.grad of the reference's block; (d) the delta rule
    alone on the reference's own q, k, v, g, beta of that DeltaNet layer.
    On all passes: (a1) the program's router and the reference's on one
    input, the one the REFERENCE's router saw; (a2) where the reference's
    choice on its own hidden state differs from the program's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed import moe as moe_mod

    took, t_mark = {}, [time.monotonic()]

    def lap(name):
        """Seconds since the last lap (each part ends by reading its
        results on the host), for the comparison's own log line."""
        now = time.monotonic()
        took[name] = round(now - t_mark[0], 1)
        t_mark[0] = now

    fn, args = forward_fn(model)
    got, got_lg, chosen = jax.jit(fn)(jnp.asarray(x), jnp.asarray(y), *args)
    moes = model.model.moe_layers()
    for m, c in zip(moes, chosen):
        m.chosen = c                   # the trace's own values, not tracers
    got, got_lg = float(got), np.asarray(got_lg.astype(jnp.float32))
    top, get_layer = reference_weights(model)
    layers = model.model.layers
    kinds = [blk.full_attention for blk in layers]
    # the last layer of each kind, alone, on a hidden state that differs
    # from token to token (deepseek_v3_ref.py says why)
    alone = sorted({len(kinds) - 1 - kinds[::-1].index(kind)
                    for kind in set(kinds)})
    x_in, cot = token_specific_input(
        seed, x.shape + (top["norm"].shape[0],),
        layers[0].in_norm_w._value.dtype)
    passes = {}
    for i in alone:
        fn, args, names = layer_pass_fn(layers[i])
        (_, picked), (got_gx, got_gp) = jax.jit(fn)(x_in, cot, *args)
        passes[i] = (picked, got_gx, got_gp, names)
        layers[i].moe.chosen = chosen[i]
    jax.block_until_ready(passes)
    lap("program")

    def routers_on(m, routed):
        """Part (a1) for one layer: both routers on routed["input"] in the
        dtype the program's activations have."""
        seen = routed["input"].astype(m.router_w._value.dtype)
        pick, _ = moe_mod.softmax_topk_route(seen, m.router_w._value,
                                             m.top_k)
        p = {"router": jnp.asarray(m.router_w._value, jnp.float32)}
        _, ref_pick, margin = ref_mod.route(seen.astype(jnp.float32), p,
                                            ref_cfg)
        return ref_mod.router_agreement(ref_pick, margin, pick)

    grad_err, rule_err = {}, {}
    with jax.default_device(jax.devices()[0]), \
            jax.default_matmul_precision("highest"):
        ref = ref_mod.forward(x, top, get_layer, ref_cfg, choices=chosen,
                              q_block=256)
        want = float(ref_mod.next_token_loss(ref["logits"], y))
        want_lg = np.asarray(ref["logits"])
        own = [ref_mod.router_agreement(r["chosen"], r["margin"], c)
               for r, c in zip(ref["router"], chosen)]
        same = [routers_on(m, r) for m, r in zip(moes, ref["router"])]
        del ref
        lap("reference_forward")

        for i in alone:
            picked, got_gx, got_gp, names = passes.pop(i)
            p_i = {k: jnp.asarray(v, jnp.float32)
                   for k, v in get_layer(i).items()}

            def ref_value(p, x32, weigh, picked, kind=kinds[i]):
                out, routed = ref_mod.block(x32, p, ref_cfg, kind, picked,
                                            512)
                return jnp.sum(out * weigh) / (weigh.shape[0]
                                               * weigh.shape[1]), routed

            (_, routed), (want_gp, want_gx) = jax.jit(jax.value_and_grad(
                ref_value, argnums=(0, 1), has_aux=True))(
                    p_i, x_in.astype(jnp.float32), cot, picked)
            own.append(ref_mod.router_agreement(
                routed["chosen"], routed["margin"], picked))
            same.append(routers_on(layers[i].moe, routed))
            to_ref = reference_names(layers[i])
            grad_err[f"{i}.input"] = _rel(got_gx, want_gx)
            for n, g in zip(names, got_gp):
                grad_err[f"{i}.{n}"] = _rel(g, want_gp[to_ref[n]])
            lap(f"reference_layer_{i}")
            if not kinds[i]:
                # (d): the rule alone, on the reference's own inputs of
                # this layer's first sequence
                normed = ref_mod.rms_norm(
                    x_in[0].astype(jnp.float32), p_i["input_layernorm"],
                    ref_cfg["rms_norm_eps"])
                t = jax.jit(lambda xn, p: ref_mod.delta_rule_inputs(
                    xn, p, ref_cfg))(normed, p_i)
                rs = np.random.default_rng(_seed32(seed) + 2)
                rule_err = delta_rule_alone(
                    t["q"], t["k"], t["v"], t["g"], t["beta"],
                    jnp.asarray(rs.standard_normal(t["v"].shape,
                                                   dtype=np.float32)))
                del t, normed
                lap("delta_rule_alone")
            del got_gp, got_gx, want_gp, want_gx, p_i, routed

    def share(rows):
        return sum(a["differ"] for a in rows) / max(
            sum(a["tokens"] for a in rows), 1)

    same_share, own_share = share(same), share(own)
    max_margin = max([a["max_margin"] for a in own], default=0.0)
    err = abs(got - want)
    sigma = float(np.std(want_lg))
    lerr = float(np.max(np.abs(got_lg - want_lg)))
    tol = LOGIT_TOL_SIGMAS * sigma
    worst = max(grad_err, key=grad_err.get)
    worst_rule = max(rule_err, key=rule_err.get) if rule_err else None
    rule_worst = rule_err[worst_rule] if rule_err else 0.0
    ok_b = bool(np.isfinite(got) and err <= LOSS_ATOL
                and np.all(np.isfinite(got_lg)) and lerr <= tol)
    ok_a = bool(same_share <= ROUTER_SAME_INPUT_FLIP_TOL
                and max_margin <= ROUTER_MARGIN_TOL)
    ok_c = bool(grad_err[worst] <= GRAD_REL_TOL)            # nan fails
    ok_d = bool(rule_worst <= DELTA_RULE_REL_TOL)
    log(f"[reference] seconds, compiling included: {took}")
    log(f"[reference] (a1) the program's router on the reference's router "
        f"input, {len(moes)} layers on the ids and layers {alone} on the "
        f"token-specific input, {same[0]['tokens'] if same else 0} tokens "
        f"each: choice differs on {[a['differ'] for a in same]} tokens, "
        f"share {same_share:.5f} (tolerance {ROUTER_SAME_INPUT_FLIP_TOL})")
    log(f"[reference] (a2) the program's choice against the reference's on "
        f"its own hidden state, the same passes: differs on "
        f"{[a['differ'] for a in own]} tokens, share {own_share:.5f} (no "
        f"limit); largest reference margin among them {max_margin:.7f} "
        f"(tolerance {ROUTER_MARGIN_TOL})")
    log(f"[reference] (b) forward on {x.shape[0]} x {x.shape[1]} tokens, "
        f"reference on the program's choices: loss program {got:.5f}, "
        f"float32 reference {want:.5f}, |d| {err:.5f} (tolerance "
        f"{LOSS_ATOL}); max |dlogit| {lerr:.4f}, logit sigma {sigma:.4f}, "
        f"tolerance {tol:.4f} ({LOGIT_TOL_SIGMAS} sigma): "
        f"{lerr / max(sigma, 1e-30):.4f} sigma")
    log(f"[reference] (c) gradients of layers {alone} (the last DeltaNet "
        f"and the last attention layer), {len(grad_err) - len(alone)} "
        f"parameters and the {len(alone)} inputs, on the token-specific "
        f"input, |program - reference| / |reference|: "
        f"{ {k: float(f'{v:.3g}') for k, v in grad_err.items()} }; worst "
        f"{worst} {grad_err[worst]:.5f} (tolerance {GRAD_REL_TOL})")
    log(f"[reference] (d) the delta rule alone, chunked against the "
        f"recurrence in float32 on the reference's q, k, v, g, beta: "
        f"{ {k: float(f'{v:.3g}') for k, v in rule_err.items()} }; worst "
        f"{worst_rule} {rule_worst:.3g} (tolerance {DELTA_RULE_REL_TOL})")
    return {"ok": ok_a and ok_b and ok_c and ok_d, "program": got,
            "reference": want, "abs_err": err, "max_abs_logit_err": lerr,
            "sigma": sigma, "router_same_input_flip_share": same_share,
            "router_flip_share": own_share,
            "router_max_margin": max_margin,
            "grad_rel_err": grad_err, "max_grad_rel_err": grad_err[worst],
            "delta_rule_rel_err": rule_err,
            "compared": {
                "router_same_input_flip_share":
                    (same_share, ROUTER_SAME_INPUT_FLIP_TOL),
                "router_max_margin": (max_margin, ROUTER_MARGIN_TOL),
                "loss_abs_err": (err, LOSS_ATOL),
                "logit_max_abs_err": (lerr, tol),
                "grad_rel_err_worst": (grad_err[worst], GRAD_REL_TOL),
                "delta_rule_rel_err_worst": (rule_worst,
                                             DELTA_RULE_REL_TOL)},
            "why": f"the program differs from the reference: on the "
                   f"reference's router input the program's router chooses "
                   f"otherwise on a share {same_share:.5f} of (token, "
                   f"layer) pairs (tolerance {ROUTER_SAME_INPUT_FLIP_TOL}); "
                   f"the program's choices differ from the reference's at "
                   f"margins up to {max_margin:.7f} (tolerance "
                   f"{ROUTER_MARGIN_TOL}); loss {got:.5f} vs {want:.5f} "
                   f"(tolerance {LOSS_ATOL}), max |dlogit| {lerr:.4f} "
                   f"(tolerance {tol:.4f}); gradient of {worst} off by "
                   f"{grad_err[worst]:.5f} of its norm (tolerance "
                   f"{GRAD_REL_TOL}); the delta rule's {worst_rule} off by "
                   f"{rule_worst:.3g} (tolerance {DELTA_RULE_REL_TOL})"}


def check_against_reference(cell, model, seed: int, log) -> dict:
    """compare_with_reference on the cell's seeded sample, at the weights
    the window left."""
    s = cell.traffic["reference_sample"]
    x, y = traffic_gen.sample_batch(
        seed, cell.config["vocab_size"], s["sequences"], s["tokens"],
        cell.traffic["tokens"]["exponent"])
    return compare_with_reference(model, reference_config(cell.config), x,
                                  y, log, seed)
