"""The adapter between the benchmark and the program under test, for the
`deepseek_v3` family (latent attention, sigmoid-routed experts): builds the
model and the train step through the entry points a user calls, hands the
program's weights to the plain reference in the reference's layout, and
makes the comparison that decides `correct`.
"""
from __future__ import annotations

import types

import numpy as np

from benchmark import program_gpt, traffic_gen
from benchmark.program_gpt import _seed32
from benchmark.reference import deepseek_v3_ref as ref_mod

# config.json keys the program's MlaMoeConfig takes under the same name
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "intermediate_size", "moe_intermediate_size", "first_k_dense_replace",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "routed_scaling_factor", "rms_norm_eps", "rope_theta", "dtype")
# what this block has no code for: a file that asks for it is refused
_MUST_BE = {"attention_bias": False, "q_lora_rank": None, "n_group": 1,
            "topk_group": 1, "rope_scaling": None, "rope_interleave": True,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "norm_topk_prob": True, "hidden_act": "silu",
            "tie_word_embeddings": False, "moe_layer_freq": 1}


def model_config(cell, **overrides):
    """The program's MlaMoeConfig from the cell's configuration FILE."""
    from paddle_tpu.models import MlaMoeConfig

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
    cfg = MlaMoeConfig(**kw)
    if cfg.qk_head_dim != c["qk_head_dim"]:
        raise ValueError(f"qk_head_dim {c['qk_head_dim']} is not nope + rope")
    return cfg


def build_train(cell, seed: int) -> dict:
    """model -> AdamW -> TrainStep on one chip, as chip_smoke.train_phase
    builds the GPT step. A mesh is refused: the expert layer's exchange
    across chips does not exist yet."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion, MlaMoeForCausalLM

    tr = cell.traffic
    if tr.get("mesh") or cell.chips != 1:
        raise ValueError("the deepseek_v3 block trains on one chip only")
    mesh_mod.set_mesh(None)
    cfg = model_config(cell)
    o = tr["optimizer"]
    if o["name"] != "AdamW":
        raise ValueError(f"optimizer {o['name']!r}: only AdamW is wired")
    crit = GPTPretrainingCriterion()
    model = MlaMoeForCausalLM(cfg, seed=_seed32(seed))
    redraw_embedding(model, cell.config["embedding_initializer_range"], seed)
    optim = opt.AdamW(learning_rate=o["learning_rate"],
                      parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    return {"step": step, "model": model, "cfg": cfg}


def redraw_embedding(model, std: float, seed: int) -> None:
    """The token embedding drawn N(0, std) from `seed`, in a generator
    stream of its own (1 and 2 are the ids'), so that every other weight
    stays what the model's own stream drew: the configuration file's
    `embedding_initializer_range` (under `assumed`: the benchmark's
    statement about its weights; the model has no such option). With the
    embedding as small as every other matrix all tokens of a layer route
    alike (PERF.md finding 13)."""
    emb = model.model.embed_tokens
    draws = traffic_gen._rng(seed, 3).standard_normal(tuple(emb.shape),
                                                      dtype=np.float32)
    emb.set_value(draws * np.float32(std))


def check_step_program(built: dict, log) -> None:
    """program_gpt's check that the compiled step holds Mosaic calls. It
    asks the configuration whether the flash kernel was wanted; this block
    has no such option and always wants it."""
    program_gpt.check_step_program(
        {"step": built["step"],
         "cfg": types.SimpleNamespace(use_flash_attention=True)}, log)


def assign_counts(model) -> np.ndarray:
    """[expert layers, router outputs]: the cumulative assignment counters,
    fetched from the device (call it outside the window)."""
    return np.stack([np.asarray(m.assign_count._value)
                     for m in model.model.moe_layers()]).astype(np.int64)


# ---------------------------------------------------------------- reference
_LAYER_NAMES = {"attn_norm_w": "input_layernorm", "q_w": "q_proj",
                "kva_w": "kv_a_proj", "kv_norm_w": "kv_a_layernorm",
                "kvb_w": "kv_b_proj", "o_w": "o_proj",
                "ffn_norm_w": "post_attention_layernorm",
                "gate_w": "gate_proj", "up_w": "up_proj",
                "down_w": "down_proj"}
_MOE_NAMES = {"router_w": "router", "w_gate": "experts_gate",
              "w_up": "experts_up", "w_down": "experts_down",
              "shared_gate": "shared_gate", "shared_up": "shared_up",
              "shared_down": "shared_down"}


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
        p = {_LAYER_NAMES[n]: f32(getattr(blk, n)) for n in blk.names}
        if blk.moe is not None:
            p.update({_MOE_NAMES[n]: f32(getattr(blk.moe, n))
                      for n in blk.moe.names})
            p["router_bias"] = f32(blk.moe.select_bias)
        return p

    return top, get_layer


def reference_config(cell_config: dict) -> dict:
    """The keys the reference reads, from the configuration file's dict."""
    keys = ("num_hidden_layers", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
            "rope_theta", "first_k_dense_replace", "num_experts_per_tok",
            "routed_scaling_factor", "experts_held")
    return {k: cell_config[k] for k in keys}


def forward_fn(model, x, y):
    """(fn, args): fn(*args) is the program's forward in training mode on
    ids x [b, s] (labels y), made the way jit.TrainStep makes its step
    (FunctionalModule.call, the criterion), and gives (loss, logits, each
    expert layer's chosen experts)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import FunctionalModule
    from paddle_tpu.models import GPTPretrainingCriterion

    fm = FunctionalModule(model)
    moes = model.model.moe_layers()
    crit = GPTPretrainingCriterion()
    labels = paddle.to_tensor(y, dtype="int64")

    def fn(pvals, bvals):
        out, _ = fm.call(pvals, bvals, jax.random.PRNGKey(0),
                         (jnp.asarray(x),), training=True)
        loss = crit(paddle.Tensor(out, _internal=True), labels)._value
        return loss.astype(jnp.float32), out, [m.chosen for m in moes]

    return fn, (fm.param_values(), fm.buffer_values())


def layer_pass_fn(layer, cot):
    """(fn, args, names): fn(x_in, *args) is the decoder layer `layer` in
    training mode on a hidden state x_in [b, s, h], forward and backward:
    ((sum(out * cot) / tokens, the chosen experts or None), (the gradient
    of that number by x_in, by the layer's parameters in the order of
    `names`)). The layer's own forward runs, recomputation and all."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.functional import FunctionalModule

    fm = FunctionalModule(layer)

    def value_of(x_in, pvals, bvals):
        out, _ = fm.call(pvals, bvals, jax.random.PRNGKey(0), (x_in,),
                         training=True)
        chosen = None if layer.moe is None else layer.moe.chosen
        return jnp.sum(out.astype(jnp.float32) * cot) \
            / (cot.shape[0] * cot.shape[1]), chosen

    return (jax.value_and_grad(value_of, argnums=(0, 1), has_aux=True),
            (fm.param_values(), fm.buffer_values()), list(fm.param_names))


def token_specific_input(seed: int, shape, dtype):
    """(x_in in `dtype`, cot float32), both N(0, 1) from the seed: a hidden
    state that differs from token to token, and a weight on every output."""
    import jax.numpy as jnp

    rs = np.random.default_rng(_seed32(seed) + 1)
    x_in = jnp.asarray(rs.standard_normal(shape, dtype=np.float32), dtype)
    return x_in, jnp.asarray(rs.standard_normal(shape, dtype=np.float32))


def compare_with_reference(model, ref_cfg: dict, x, y, log,
                           seed: int = 0) -> dict:
    """The program against the float32 reference, in the parts
    deepseek_v3_ref.py's docstring gives the tolerances of. On ids x [b, s]
    (labels y), the training-mode forward: (b) logits and loss with the
    reference computed on the program's choices. On a token-specific hidden
    state made from `seed`, the last layer alone, forward and backward: (c)
    the gradients by its parameters and its input against jax.grad of the
    reference's block. On both: (a1) the program's router and the
    reference's on one input, the one the REFERENCE's router saw; (a2) where
    the reference's choice on its own hidden state differs from the
    program's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed import moe as moe_mod

    fn, args = forward_fn(model, x, y)
    got, got_lg, chosen = jax.jit(fn)(*args)
    moes = model.model.moe_layers()
    for m, c in zip(moes, chosen):
        m.chosen = c                   # the trace's own values, not tracers
    got, got_lg = float(got), np.asarray(got_lg.astype(jnp.float32))
    top, get_layer = reference_weights(model)
    n_layers = ref_cfg["num_hidden_layers"]
    last = model.model.layers[n_layers - 1]
    # the last layer alone on a hidden state that differs from token to
    # token: after the window's steps the model's own is one vector common
    # to all tokens, on which q's gradient cancels to rounding and this
    # chip's experts may get no token at all (PERF.md)
    x_in, cot = token_specific_input(
        seed, x.shape + (top["norm"].shape[0],),
        last.attn_norm_w._value.dtype)
    fn, args, names = layer_pass_fn(last, cot)
    (_, picked), (got_gx, got_gp) = jax.jit(fn)(x_in, *args)
    if last.moe is not None:
        last.moe.chosen = chosen[-1]

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

        # the reference's last block on the token-specific input, with
        # the program's choices
        p_last = {k: jnp.asarray(v, jnp.float32)
                  for k, v in get_layer(n_layers - 1).items()}

        def ref_value(p, x32):
            out, routed = ref_mod.block(x32, p, ref_cfg, last.moe is None,
                                        picked, 512)
            return jnp.sum(out * cot) / (cot.shape[0] * cot.shape[1]), routed

        (_, routed), (want_gp, want_gx) = jax.jit(jax.value_and_grad(
            ref_value, argnums=(0, 1), has_aux=True))(
                p_last, x_in.astype(jnp.float32))
        if routed is not None:
            own.append(ref_mod.router_agreement(
                routed["chosen"], routed["margin"], picked))
            same.append(routers_on(last.moe, routed))
        to_ref = {**_LAYER_NAMES,
                  **{"moe." + k: v for k, v in _MOE_NAMES.items()}}
        pairs = [("input", got_gx, want_gx)] + [
            (n, g, want_gp[to_ref[n]]) for n, g in zip(names, got_gp)]
        grad_err = {
            n: float(jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
                     / (jnp.linalg.norm(w.ravel()) + 1e-30))
            for n, g, w in pairs}
        del pairs, got_gp, got_gx, want_gp, want_gx, p_last, routed

    def share(rows):
        return sum(a["differ"] for a in rows) / max(
            sum(a["tokens"] for a in rows), 1)

    same_share, own_share = share(same), share(own)
    max_margin = max([a["max_margin"] for a in own], default=0.0)
    err = abs(got - want)
    sigma = float(np.std(want_lg))
    lerr = float(np.max(np.abs(got_lg - want_lg)))
    tol = ref_mod.LOGIT_TOL_SIGMAS * sigma
    worst = max(grad_err, key=grad_err.get)
    ok_b = bool(np.isfinite(got) and err <= ref_mod.LOSS_ATOL
                and np.all(np.isfinite(got_lg)) and lerr <= tol)
    ok_a = bool(same_share <= ref_mod.ROUTER_SAME_INPUT_FLIP_TOL
                and max_margin <= ref_mod.ROUTER_MARGIN_TOL)
    ok_c = bool(grad_err[worst] <= ref_mod.GRAD_REL_TOL)    # nan fails
    log(f"[reference] (a1) the program's router on the reference's router "
        f"input, {len(moes)} expert layers on the ids and the last on the "
        f"token-specific input, {same[0]['tokens'] if same else 0} tokens "
        f"each: choice differs on {[a['differ'] for a in same]} tokens, "
        f"share {same_share:.5f} (tolerance "
        f"{ref_mod.ROUTER_SAME_INPUT_FLIP_TOL})")
    log(f"[reference] (a2) the program's choice against the reference's on "
        f"its own hidden state, the same passes: differs on "
        f"{[a['differ'] for a in own]} tokens, share {own_share:.5f} (no "
        f"limit); largest reference margin among them {max_margin:.6f} "
        f"(tolerance {ref_mod.ROUTER_MARGIN_TOL})")
    log(f"[reference] (b) forward on {x.shape[0]} x {x.shape[1]} tokens, "
        f"reference on the program's choices: loss program {got:.5f}, "
        f"float32 reference {want:.5f}, |d| {err:.5f} (tolerance "
        f"{ref_mod.LOSS_ATOL}); max |dlogit| {lerr:.4f}, logit sigma "
        f"{sigma:.4f}, tolerance {tol:.4f} ({ref_mod.LOGIT_TOL_SIGMAS} "
        f"sigma): {lerr / max(sigma, 1e-30):.4f} sigma")
    log(f"[reference] (c) gradients of layer {n_layers - 1}'s "
        f"{len(names)} parameters and of its input, on the token-specific "
        f"input, |program - reference| / |reference|: "
        f"{ {k: float(f'{v:.3g}') for k, v in grad_err.items()} }; worst "
        f"{worst} {grad_err[worst]:.5f} (tolerance {ref_mod.GRAD_REL_TOL})")
    return {"ok": ok_a and ok_b and ok_c, "program": got, "reference": want,
            "abs_err": err, "max_abs_logit_err": lerr, "sigma": sigma,
            "router_same_input_flip_share": same_share,
            "router_flip_share": own_share,
            "router_max_margin": max_margin,
            "grad_rel_err": grad_err, "max_grad_rel_err": grad_err[worst],
            "compared": {
                "router_same_input_flip_share":
                    (same_share, ref_mod.ROUTER_SAME_INPUT_FLIP_TOL),
                "router_max_margin": (max_margin, ref_mod.ROUTER_MARGIN_TOL),
                "loss_abs_err": (err, ref_mod.LOSS_ATOL),
                "logit_max_abs_err": (lerr, tol),
                "grad_rel_err_worst": (grad_err[worst],
                                       ref_mod.GRAD_REL_TOL)},
            "why": f"the program differs from the reference: on the "
                   f"reference's router input the program's router chooses "
                   f"otherwise on a share {same_share:.5f} of (token, "
                   f"layer) pairs (tolerance "
                   f"{ref_mod.ROUTER_SAME_INPUT_FLIP_TOL}); the program's "
                   f"choices differ from the reference's at margins up to "
                   f"{max_margin:.6f} (tolerance "
                   f"{ref_mod.ROUTER_MARGIN_TOL}); loss {got:.5f} vs "
                   f"{want:.5f} (tolerance {ref_mod.LOSS_ATOL}), max "
                   f"|dlogit| {lerr:.4f} (tolerance {tol:.4f}); gradient "
                   f"of {worst} off by {grad_err[worst]:.5f} of its norm "
                   f"(tolerance {ref_mod.GRAD_REL_TOL})"}


def check_against_reference(cell, model, seed: int, log) -> dict:
    """compare_with_reference on the cell's seeded sample, at the weights
    the window left."""
    s = cell.traffic["reference_sample"]
    x, y = traffic_gen.sample_batch(
        seed, cell.config["vocab_size"], s["sequences"], s["tokens"],
        cell.traffic["tokens"]["exponent"])
    return compare_with_reference(model, reference_config(cell.config), x,
                                  y, log, seed)
