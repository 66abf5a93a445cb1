"""The adapter between the benchmark and the program under test, for the
GPT family: builds the model and the train step through the entry points a
user calls (chip_smoke.py shows them), hands the program's weights to the
plain reference in the reference's layout, and makes the comparison that
decides `correct`.
"""
from __future__ import annotations

import numpy as np

from benchmark import traffic_gen
from benchmark.reference import gpt2_ref


def gpt_config(cell, **overrides):
    """The program's GPTConfig from the cell's configuration FILE (not from
    the program's preset table; the preset, where named, must agree)."""
    from paddle_tpu.models import gpt_presets
    from paddle_tpu.models.gpt import GPTConfig

    c = cell.config
    kw = dict(vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
              num_layers=c["num_hidden_layers"],
              num_heads=c["num_attention_heads"],
              max_position_embeddings=c["max_position_embeddings"],
              layer_norm_epsilon=c["layer_norm_epsilon"], dtype=c["dtype"])
    kw.update(overrides)
    cfg = GPTConfig(**kw)
    if cfg.ffn != c["intermediate_size"] or cfg.head_dim != c["head_dim"]:
        raise ValueError(f"the program derives ffn {cfg.ffn} / head_dim "
                         f"{cfg.head_dim}; the file says "
                         f"{c['intermediate_size']} / {c['head_dim']}")
    preset = (c.get("program") or {}).get("preset")
    if preset:
        p = gpt_presets(preset)
        for key in ("vocab_size", "hidden_size", "num_layers", "num_heads"):
            if getattr(p, key) != getattr(cfg, key):
                raise ValueError(f"preset {preset!r} has {key} = "
                                 f"{getattr(p, key)}, the configuration "
                                 f"file {getattr(cfg, key)}")
    return cfg


def _seed32(seed: int) -> int:
    return int(seed) % (2 ** 32)      # numpy RandomState's range


def build_train(cell, seed: int) -> dict:
    """mesh -> model -> AdamW -> [ZeRO] -> TrainStep, in the order of
    chip_smoke.train_phase: the order a user's script has."""
    import jax
    from jax.sharding import PartitionSpec as P

    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    tr = cell.traffic
    topo = tr.get("mesh")
    n_dev = int(np.prod(list(topo.values()))) if topo else 1
    if n_dev != cell.chips:
        raise ValueError(f"mesh {topo} needs {n_dev} devices, the cell "
                         f"asks for {cell.chips} chips")
    mesh_mod.set_mesh(
        mesh_mod.build_mesh(topo, devices=jax.devices()[:n_dev])
        if topo else None)
    cfg = gpt_config(cell, max_position_embeddings=tr["seq"])
    crit = GPTPretrainingCriterion()
    o = tr["optimizer"]
    if o["name"] != "AdamW":
        raise ValueError(f"optimizer {o['name']!r}: only AdamW is wired")
    model = GPTForCausalLM(cfg, seed=_seed32(seed))
    optim = opt.AdamW(learning_rate=o["learning_rate"],
                      parameters=model.parameters())
    if topo:
        model, optim, _ = group_sharded_parallel(model, optim,
                                                 tr["zero_level"])
        step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                         batch_spec=P(("data", "sharding")))
    else:
        step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    return {"step": step, "model": model, "cfg": cfg}


def check_step_program(built: dict, log) -> None:
    """On a TPU the compiled step must hold the Mosaic flash kernel: the
    model falls back to the O(s^2) einsum silently when the kernel's shape
    gate says no, and the cell would measure another program."""
    import jax

    step, cfg = built["step"], built["cfg"]
    if jax.devices()[0].platform != "tpu" or not cfg.use_flash_attention:
        return
    program = step._cache[step._last_ckey].lower(
        *step._last_abstract).as_text()
    n = program.count("tpu_custom_call")
    log(f"[train] {n} Mosaic custom calls in the step's program")
    if n == 0:
        raise SystemExit("benchmark: no Mosaic custom call in the compiled "
                         "train step: attention fell back to the einsum")


# ---------------------------------------------------------------- reference
# the program's names for one block's parameters (models/gpt.py)
_BLOCK_PARAMS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
                 "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def reference_weights(model):
    """(top, get_block) in the released checkpoint's layout, from the live
    model's parameters. Arrays are fetched one block at a time."""
    emb = model.gpt.embeddings
    top = {"wte": np.asarray(emb.word_embeddings._value, np.float32),
           "wpe": np.asarray(emb.position_embeddings._value, np.float32),
           "ln_f_g": np.asarray(model.gpt.final_norm.weight._value,
                                np.float32),
           "ln_f_b": np.asarray(model.gpt.final_norm.bias._value,
                                np.float32)}
    dec = model.gpt.decoder
    stacked = hasattr(dec, "cfg")      # scan mode keeps layers stacked

    def get_block(i: int) -> dict:
        def val(name):
            v = getattr(dec, name)._value[i] if stacked \
                else getattr(dec[i], name)._value
            return np.asarray(v, np.float32)

        p = {n: val(n) for n in _BLOCK_PARAMS}
        h = p["qkv_w"].shape[0]
        return {"ln_1_g": p["ln1_w"], "ln_1_b": p["ln1_b"],
                # the program packs QKV as [h, 3, h]: columns q | k | v
                "c_attn_w": p["qkv_w"].reshape(h, 3 * h),
                "c_attn_b": p["qkv_b"].reshape(3 * h),
                "c_proj_w": p["out_w"], "c_proj_b": p["out_b"],
                "ln_2_g": p["ln2_w"], "ln_2_b": p["ln2_b"],
                "c_fc_w": p["fc1_w"], "c_fc_b": p["fc1_b"],
                "mlp_proj_w": p["fc2_w"], "mlp_proj_b": p["fc2_b"]}

    return top, get_block


def _reference_logits(model, cfg, ids):
    import jax

    top, get_block = reference_weights(model)
    # on one device, whatever mesh the program runs under
    with jax.default_device(jax.devices()[0]):
        lg = gpt2_ref.logits(ids, top, get_block, cfg.num_layers,
                             cfg.num_heads, cfg.layer_norm_epsilon)
        lg.block_until_ready()
    return lg


def check_forward_loss(cell, model, cfg, seed: int, log) -> dict:
    """The program's forward on a seeded sample, under the cell's mesh,
    against the float32 reference: the loss, and the logits behind it (at
    random initial weights every model's loss sits near ln V, so the loss
    alone would pass a wrong model; the logits do not)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTPretrainingCriterion

    s = cell.traffic["reference_sample"]
    x, y = traffic_gen.sample_batch(seed, cfg.vocab_size, s["sequences"],
                                    s["tokens"])
    model.eval()
    with paddle.no_grad():
        out = model(paddle.to_tensor(x, dtype="int64"))
        got = float(GPTPretrainingCriterion()(
            out, paddle.to_tensor(y, dtype="int64")))
        got_lg = np.asarray(out.astype("float32").numpy())
        del out
    model.train()
    lg = _reference_logits(model, cfg, x)
    want = float(gpt2_ref.next_token_loss(lg, y))
    want_lg = np.asarray(lg)
    del lg
    err = abs(got - want)
    sigma = float(np.std(want_lg))
    lerr = float(np.max(np.abs(got_lg - want_lg)))
    tol = gpt2_ref.LOGIT_TOL_SIGMAS * sigma
    ok = bool(np.isfinite(got) and err <= gpt2_ref.LOSS_ATOL
              and np.all(np.isfinite(got_lg)) and lerr <= tol)
    log(f"[reference] forward on {s['sequences']} x {s['tokens']} tokens: "
        f"loss program {got:.5f}, float32 reference {want:.5f}, |d| "
        f"{err:.5f} (tolerance {gpt2_ref.LOSS_ATOL}); max |dlogit| "
        f"{lerr:.4f}, logit sigma {sigma:.4f}, tolerance {tol:.4f} "
        f"({gpt2_ref.LOGIT_TOL_SIGMAS} sigma)")
    return {"ok": ok, "program": got, "reference": want, "abs_err": err,
            "max_abs_logit_err": lerr, "sigma": sigma,
            "compared": {"loss_abs_err": (err, gpt2_ref.LOSS_ATOL),
                         "logit_max_abs_err": (lerr, tol)},
            "why": f"forward differs from the reference: loss {got:.5f} vs "
                   f"{want:.5f} (tolerance {gpt2_ref.LOSS_ATOL}), max "
                   f"|dlogit| {lerr:.4f} (tolerance {tol:.4f})"}
