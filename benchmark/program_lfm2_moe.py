"""The adapter between the benchmark and the program under test, for the
`lfm2_moe` family (double-gated short-convolution layers beside
grouped-head attention with a norm on q and k, bias-selected
sigmoid-routed experts, no shared expert, a tied head): builds the model
and the train step through the entry points a user calls, hands the
program's weights to the plain reference in the reference's layout, and
makes the comparison that decides `correct`. What the expert families
share (the step-program check, the counters, the token-specific input, the
forward made as jit.TrainStep makes it) is program_mla_moe's and
program_gdn_moe's, imported. The interface kinds/train_moe_family.py asks
for: `build_train`, `assign_counts`, `check_step_program`,
`check_against_reference`.

THE COMPARISON'S LIMITS, each with its reason and its two readings (TPU
v5e, the cell's size, PR 35: the program as it is over 34 runs on 20
seeds, 19 of them at a bias speed of 0.001 (six with 30 or 60 warm-up
steps, one at twice the batch), three at 0.003 and twelve at the
configuration's 0.01, and the control that the limit has to refuse (each
at 0.001), patched into a run of the same
command; tests/benchmark_yardstick/test_benchmark_lfm2_moe.py plants the
same faults at a small size on the CPU). The program computes in bfloat16
where the configuration says so; norms, rotary, softmax statistics, the
convolution's gates and sum and the router in float32. Two kinds of pass
are compared, as in deepseek_v3_ref.py: the training-mode forward on the
cell's seeded ids, and single layers (the last of each kind the model has:
the dense conv layer, the LAST conv expert layer, the attention layer),
forward and backward through the program's own block, each on a seeded
N(0, 1) hidden state of the timed length.

(a1) ROUTER_SAME_INPUT_FLIP_TOL = 0.002: the program's router and the
     reference's, both on what the REFERENCE's router saw (cast to the
     activations' dtype): the share of (token, layer) pairs whose chosen set
     differs. One input, so only the router's own arithmetic differs.
     As it is: 0 of 49,152 pairs in every run (two float32 sums in different
     orders meet only in an exact tie). The router's sigmoid in bfloat16:
     0.0262, and every other part inside its limit ((a2) 0.0060, (b) 0.047
     of 0.181, (c) 0.030): one limit refuses it.
(a2) ROUTER_MARGIN_TOL = 0.012: where the program's choice differs from the
     reference's on ITS hidden state (bf16 rounding of the hidden state
     moves near-ties, so the share has no limit), the reference's margin
     (the 4th largest biased score less the 5th).
     As it is: 1.9-2.1 % of the pairs differ, largest margin 0.0046-0.0075
     (a chosen score is ~0.6, the gap between the 4th and 5th of 32 scores
     ~0.03). A wrong block upstream: the q and k norms left out 0.0288
     (4.3 % differ), the taps reversed 0.221 and the C gate left out 0.223
     (67-69 % differ). The limit lies 1.6x over the largest reading and
     2.4x under the smallest control.
(b)  LOSS_ATOL = 0.02, LOGIT_TOL_SIGMAS = 0.2: the reference computed ON
     the program's choices, in loss and in logits (gpt2_ref.py's limits and
     reasons).
     As it is: |dloss| <= 2.2e-4, 0.045-0.064 sigma. The q and k norms left
     out: 0.32 sigma (|dloss| 0.006 passes: the logits' limit is the one
     that sees it); the taps reversed: |dloss| 0.29, 6.3 sigma; the C gate
     left out: 0.58, 9.1 sigma.
(c)  GRAD_REL_TOL = 0.2 (deepseek_v3_ref.py's): |program - reference| /
     |reference|, Frobenius, for the gradient by every parameter of the
     singled-out layers and by their inputs.
     As it is: 0.003-0.005 the inputs, 0.004-0.008 every mixer's parameter
     and the dense SwiGLU's, 0.006-0.069 the routed experts', the routers'
     and the norm's before them (bf16 products of ~2,000 rows each). The q
     and k norms left out: 1.00 (q_norm_w: it has no gradient left); the
     taps reversed: 1.17 (conv_w of the dense layer); the C gate left out:
     1.51 (conv_w of the last conv layer).
WHAT NO LIMIT SEES, measured the same way (PERF.md section 6, PR 35): the
taps' sum over products rounded to bfloat16, and the mixer's two gates and
its sum all in bfloat16, both `correct` with every reading where it was
((c) 0.0064 for W_in against 0.0057): three roundings of 2^-9 between two
bfloat16 matmuls whose own rounding is what (c)'s 0.005 already is. The
float32 there is the configuration's statement, kept by
tests/test_lfm2_moe.py (the jaxpr's multiplications are float32), not by a
limit of this comparison.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic_gen
from benchmark.program_gdn_moe import _rel, forward_fn
from benchmark.program_gpt import _seed32
from benchmark.program_mla_moe import (  # noqa: F401
    assign_counts, check_step_program, token_specific_input)
from benchmark.reference import lfm2_moe_ref as ref_mod

LOGIT_TOL_SIGMAS = 0.2
LOSS_ATOL = 0.02
ROUTER_SAME_INPUT_FLIP_TOL = 0.002
ROUTER_MARGIN_TOL = 0.012
GRAD_REL_TOL = 0.2

# config.json keys the program's Lfm2MoeConfig takes under the same name
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "num_dense_layers", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "conv_L_cache", "num_experts", "num_experts_per_tok",
    "routed_scaling_factor", "norm_eps", "rope_theta", "dtype")
# what this block has no code for: a file that asks for it is refused
_MUST_BE = {"conv_bias": False, "norm_topk_prob": True,
            "use_expert_bias": True, "tie_word_embeddings": True}


def model_config(cell, **overrides):
    """The program's Lfm2MoeConfig from the cell's configuration FILE."""
    from paddle_tpu.models import Lfm2MoeConfig

    c = cell.config
    for key, want in _MUST_BE.items():
        if c.get(key, want) != want:
            raise ValueError(f"{key} = {c[key]!r}: the program's block "
                             f"computes {want!r} only")
    kw = {k: c[k] for k in _CONFIG_KEYS}
    kw.update(router_outputs=c["router_outputs"],
              experts_held=tuple(c["experts_held"]),
              recompute=c.get("recompute", "none"),
              initializer_range=c["initializer_range"],
              bias_update_speed=c["bias_update_speed"])
    kw.update(overrides)
    return Lfm2MoeConfig(**kw)


def build_train(cell, seed: int) -> dict:
    """model -> AdamW -> TrainStep on one chip, as program_mla_moe builds
    its step. A mesh is refused: the expert layer's exchange across chips
    does not exist yet."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion, Lfm2MoeForCausalLM

    tr = cell.traffic
    if tr.get("mesh") or cell.chips != 1:
        raise ValueError("the lfm2_moe block trains on one chip only")
    mesh_mod.set_mesh(None)
    cfg = model_config(cell)
    o = tr["optimizer"]
    if o["name"] != "AdamW":
        raise ValueError(f"optimizer {o['name']!r}: only AdamW is wired")
    crit = GPTPretrainingCriterion()
    model = Lfm2MoeForCausalLM(cfg, seed=_seed32(seed))
    optim = opt.AdamW(learning_rate=o["learning_rate"],
                      parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    return {"step": step, "model": model, "cfg": cfg}


# ---------------------------------------------------------------- reference
_LAYER_NAMES = {
    "op_norm_w": "operator_norm", "ffn_norm_w": "ffn_norm",
    # short convolution
    "in_w": "in_proj", "conv_w": "conv", "out_w": "out_proj",
    # attention
    "q_w": "q_proj", "k_w": "k_proj", "v_w": "v_proj",
    "q_norm_w": "q_layernorm", "k_norm_w": "k_layernorm", "o_w": "out_proj",
    # the dense feed-forward
    "gate_w": "gate_proj", "up_w": "up_proj", "down_w": "down_proj"}
_MOE_NAMES = {"router_w": "router", "w_gate": "experts_gate",
              "w_up": "experts_up", "w_down": "experts_down"}


def reference_names(layer) -> dict:
    """{the program's parameter name in `layer`: the reference's}."""
    names = {n: _LAYER_NAMES[n] for n in layer.names}
    if layer.moe is not None:
        names.update({"moe." + n: _MOE_NAMES[n] for n in layer.moe.names})
    return names


def reference_weights(model):
    """(top, get_layer) in the reference's layout, from the live model.
    Arrays are fetched one layer at a time."""
    m = model.model

    def f32(p):
        return np.asarray(p._value, np.float32)

    top = {"embed_tokens": f32(m.embed_tokens),
           "embedding_norm": f32(m.final_norm_w)}

    def get_layer(i: int) -> dict:
        blk = m.layers[i]
        p = {ref: f32(getattr(blk.moe, n[4:]) if n.startswith("moe.")
                      else getattr(blk, n))
             for n, ref in reference_names(blk).items()}
        if blk.moe is not None:
            p["router_bias"] = f32(blk.moe.select_bias)
        return p

    return top, get_layer


def reference_config(cell_config: dict) -> dict:
    """The keys the reference reads, from the configuration file's dict."""
    keys = ("layer_types", "num_dense_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "norm_eps", "rope_theta",
            "num_experts_per_tok", "routed_scaling_factor", "experts_held")
    return {k: cell_config[k] for k in keys}


def layer_pass_fn(layer):
    """(fn, args, names): fn(x_in, cot, *args) is the decoder layer `layer`
    in training mode on a hidden state x_in [b, s, h], forward and
    backward: ((sum(out * cot) / tokens, the chosen experts or None), (the
    gradient of that number by x_in, by the layer's parameters in the order
    of `names`)). The layer's own forward runs, recomputation and all.
    `cot` is an ARGUMENT, for program_gdn_moe.layer_pass_fn's reason."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.functional import FunctionalModule

    fm = FunctionalModule(layer)

    def value_of(x_in, pvals, cot, bvals):
        out, _ = fm.call(pvals, bvals, jax.random.PRNGKey(0), (x_in,),
                         training=True)
        chosen = None if layer.moe is None else layer.moe.chosen
        return jnp.sum(out.astype(jnp.float32) * cot) \
            / (cot.shape[0] * cot.shape[1]), chosen

    grad = jax.value_and_grad(value_of, argnums=(0, 1), has_aux=True)
    return (lambda x_in, cot, pvals, bvals: grad(x_in, pvals, cot, bvals),
            (fm.param_values(), fm.buffer_values()), list(fm.param_names))


def layers_alone(layers) -> list:
    """The last layer of each kind the model has, a kind being (the mixer,
    dense or expert feed-forward): in the cell the dense conv layer, the
    attention layer and the last conv expert layer."""
    last = {}
    for i, blk in enumerate(layers):
        last[(blk.layer_type, blk.moe is None)] = i
    return sorted(last.values())


def compare_with_reference(model, ref_cfg: dict, x, y, log,
                           seed: int = 0) -> dict:
    """The program against the float32 reference, in the parts the module
    docstring gives the limits of. On ids x [b, s] (labels y), the
    training-mode forward: (b) logits and loss with the reference computed
    on the program's choices. On a token-specific hidden state made from
    `seed`, each of `layers_alone`, forward and backward: (c) the gradients
    by its parameters and its input against jax.grad of the reference's
    block. On all passes with a router: (a1) the program's router and the
    reference's on one input, the one the REFERENCE's router saw; (a2)
    where the reference's choice on its own hidden state differs from the
    program's."""
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
    got, got_lg = float(got), np.asarray(got_lg.astype(jnp.float32))
    top, get_layer = reference_weights(model)
    layers = model.model.layers
    alone = layers_alone(layers)
    # each alone, on a hidden state that differs from token to token
    # (deepseek_v3_ref.py says why)
    x_in, cot = token_specific_input(
        seed, x.shape + (top["embedding_norm"].shape[0],),
        layers[0].op_norm_w._value.dtype)
    passes = {}
    for i in alone:
        fn, args, names = layer_pass_fn(layers[i])
        (_, picked), (got_gx, got_gp) = jax.jit(fn)(x_in, cot, *args)
        passes[i] = (picked, got_gx, got_gp, names)
    for m, c in zip(moes, chosen):
        m.chosen = c                   # the trace's own values, not tracers
    jax.block_until_ready(passes)
    lap("program")

    def routers_on(m, routed):
        """Part (a1) for one expert layer: both routers on routed["input"]
        in the dtype the program's activations have."""
        seen = routed["input"].astype(m.router_w._value.dtype)
        pick, _ = moe_mod.sigmoid_topk_route(
            seen, m.router_w._value, m.select_bias._value, m.top_k,
            m.routed_scaling)
        p = {"router": jnp.asarray(m.router_w._value, jnp.float32),
             "router_bias": jnp.asarray(m.select_bias._value, jnp.float32)}
        _, ref_pick, margin = ref_mod.route(seen.astype(jnp.float32), p,
                                            ref_cfg)
        return ref_mod.router_agreement(ref_pick, margin, pick)

    grad_err = {}
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
            blk = layers[i]
            p_i = {k: jnp.asarray(v, jnp.float32)
                   for k, v in get_layer(i).items()}

            def ref_value(p, x32, weigh, picked, blk=blk):
                out, routed = ref_mod.block(
                    x32, p, ref_cfg, blk.layer_type, blk.moe is None,
                    picked, 512)
                return jnp.sum(out * weigh) / (weigh.shape[0]
                                               * weigh.shape[1]), routed

            (_, routed), (want_gp, want_gx) = jax.jit(jax.value_and_grad(
                ref_value, argnums=(0, 1), has_aux=True))(
                    p_i, x_in.astype(jnp.float32), cot, picked)
            if routed is not None:
                own.append(ref_mod.router_agreement(
                    routed["chosen"], routed["margin"], picked))
                same.append(routers_on(blk.moe, routed))
            to_ref = reference_names(blk)
            grad_err[f"{i}.input"] = _rel(got_gx, want_gx)
            for n, g in zip(names, got_gp):
                grad_err[f"{i}.{n}"] = _rel(g, want_gp[to_ref[n]])
            lap(f"reference_layer_{i}")
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
    ok_b = bool(np.isfinite(got) and err <= LOSS_ATOL
                and np.all(np.isfinite(got_lg)) and lerr <= tol)
    ok_a = bool(same_share <= ROUTER_SAME_INPUT_FLIP_TOL
                and max_margin <= ROUTER_MARGIN_TOL)
    ok_c = bool(grad_err[worst] <= GRAD_REL_TOL)            # nan fails
    log(f"[reference] seconds, compiling included: {took}")
    log(f"[reference] (a1) the program's router on the reference's router "
        f"input, {len(moes)} expert layers on the ids and those of layers "
        f"{alone} on the token-specific input, "
        f"{same[0]['tokens'] if same else 0} tokens each: choice differs "
        f"on {[a['differ'] for a in same]} tokens, share {same_share:.5f} "
        f"(tolerance {ROUTER_SAME_INPUT_FLIP_TOL})")
    log(f"[reference] (a2) the program's choice against the reference's on "
        f"its own hidden state, the same passes: differs on "
        f"{[a['differ'] for a in own]} tokens, share {own_share:.5f} (no "
        f"limit); largest reference margin among them {max_margin:.6f} "
        f"(tolerance {ROUTER_MARGIN_TOL})")
    log(f"[reference] (b) forward on {x.shape[0]} x {x.shape[1]} tokens, "
        f"reference on the program's choices: loss program {got:.5f}, "
        f"float32 reference {want:.5f}, |d| {err:.5f} (tolerance "
        f"{LOSS_ATOL}); max |dlogit| {lerr:.4f}, logit sigma {sigma:.4f}, "
        f"tolerance {tol:.4f} ({LOGIT_TOL_SIGMAS} sigma): "
        f"{lerr / max(sigma, 1e-30):.4f} sigma")
    log(f"[reference] (c) gradients of layers {alone} (the last of each "
        f"kind), {len(grad_err) - len(alone)} parameters and the "
        f"{len(alone)} inputs, on the token-specific input, |program - "
        f"reference| / |reference|: "
        f"{ {k: float(f'{v:.3g}') for k, v in grad_err.items()} }; worst "
        f"{worst} {grad_err[worst]:.5f} (tolerance {GRAD_REL_TOL})")
    return {"ok": ok_a and ok_b and ok_c, "program": got,
            "reference": want, "abs_err": err, "max_abs_logit_err": lerr,
            "sigma": sigma, "router_same_input_flip_share": same_share,
            "router_flip_share": own_share,
            "router_max_margin": max_margin,
            "grad_rel_err": grad_err, "max_grad_rel_err": grad_err[worst],
            "compared": {
                "router_same_input_flip_share":
                    (same_share, ROUTER_SAME_INPUT_FLIP_TOL),
                "router_max_margin": (max_margin, ROUTER_MARGIN_TOL),
                "loss_abs_err": (err, LOSS_ATOL),
                "logit_max_abs_err": (lerr, tol),
                "grad_rel_err_worst": (grad_err[worst], GRAD_REL_TOL)},
            "why": f"the program differs from the reference: on the "
                   f"reference's router input the program's router chooses "
                   f"otherwise on a share {same_share:.5f} of (token, "
                   f"layer) pairs (tolerance {ROUTER_SAME_INPUT_FLIP_TOL}); "
                   f"the program's choices differ from the reference's at "
                   f"margins up to {max_margin:.6f} (tolerance "
                   f"{ROUTER_MARGIN_TOL}); loss {got:.5f} vs {want:.5f} "
                   f"(tolerance {LOSS_ATOL}), max |dlogit| {lerr:.4f} "
                   f"(tolerance {tol:.4f}); gradient of {worst} off by "
                   f"{grad_err[worst]:.5f} of its norm (tolerance "
                   f"{GRAD_REL_TOL})"}


def check_against_reference(cell, model, seed: int, log) -> dict:
    """compare_with_reference on the cell's seeded sample, at the weights
    the window left."""
    s = cell.traffic["reference_sample"]
    x, y = traffic_gen.sample_batch(
        seed, cell.config["vocab_size"], s["sequences"], s["tokens"],
        cell.traffic["tokens"]["exponent"])
    return compare_with_reference(model, reference_config(cell.config), x,
                                  y, log, seed)
