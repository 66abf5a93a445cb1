"""The `lfm2_moe` cell: its files load from the manifest, its kind (which
takes the adapter and the operation counts from the configuration's
`family`) runs an untraced and a traced line of a two-layer cell at a tiny
size on the CPU with the device check lifted, its operation counts agree
with hand counts, its configuration is the catalog row's but for what this
chip holds (depth, leading dense layers, the pattern's entries, experts,
rows of the table), its shares are read by `named_ops`, and each control of
ISSUE 35, planted on the CPU, turns `correct` false. Presence and content
only: no entry's position in a list is asserted, nor that a metric other
cells could read is this cell's alone."""
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import (flops_gdn_moe, flops_lfm2_moe, flops_mla_moe, harness,
                       manifest as mf, program_lfm2_moe)
from benchmark.readers import named_ops, window_counters
from paddle_tpu.distributed import moe
from paddle_tpu.models import lfm2_moe

from conftest import PRETEND_TPU, build_root
from test_benchmark_manifest import WIDTH

CONFIG = "lfm2-8b-a1b-ep4"
CELL = "lfm2-8b-a1b-ep4.train-lfm2-b2-s8192"
TINY = "lfm2-moe-test.train"
# architectures.jsonl line 34 (LFM2-8B-A1B), `config`
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
NEW_METRICS = {"scope_time_share.short_conv", "short_conv_roofline",
               "flash_gqa_roofline.lfm2_moe", "unnamed_time_share.lfm2_moe",
               "moe_held_share.lfm2_moe"}


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    """conftest's temporary benchmark root plus a two-layer cell of this
    family at the CPU tests' widths, from data files alone."""
    root = build_root(str(tmp_path_factory.mktemp("lfm2_root")))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name="lfm2-moe-test", vocab_size=512, hidden_size=64,
               num_hidden_layers=2, layer_types=["conv", "full_attention"],
               num_dense_layers=1, num_attention_heads=8,
               num_key_value_heads=2, head_dim=8, intermediate_size=128,
               moe_intermediate_size=32, num_experts=8, router_outputs=32,
               experts_held=[8, 16], num_experts_per_tok=4, dtype="float32")
    with open(os.path.join(bdir, "configs", "lfm2-moe-test.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic",
                           "train-lfm2-b2-s8192.json")) as f:
        tr = json.load(f)
    # the cell's 1e-5 moves the loss by less than one batch differs from
    # the next, so a learning rate at which it falls
    tr.update(global_batch=2, seq=80, drop_chunks=1, min_kept_chunks=2,
              trace_chunks=2, optimizer={"name": "AdamW",
                                         "learning_rate": 2e-4},
              reference_sample={"sequences": 1, "tokens": 80})
    with open(os.path.join(bdir, "traffic", "t-train-lfm2.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "lfm2-moe-test", "source": "tests/test_lfm2_moe.py TEST",
        "file": "benchmark/configs/lfm2-moe-test.json",
        "reduced": cfg["reduced"], "why": "CPU tests"})
    bench["workloads"].append({
        "name": TINY, "config": "lfm2-moe-test", "traffic": "t-train-lfm2",
        "chips": 1, "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture
def cell(lfm2_root):
    """The cell, loaded anew (a test may change its copy)."""
    return mf.load_cell(CELL, lfm2_root)


def _run(root, trace, seconds):
    lines = []
    result = harness.run_cell(
        TINY, 2 ** 31 + 11, seconds, trace, time.monotonic(), root=root,
        device=dict(PRETEND_TPU, count=1),
        log=lambda *a: lines.append(" ".join(map(str, a))))
    return result, "\n".join(lines)


# ------------------------------------------------------------- the manifest
def test_the_cell_loads_through_the_manifest(cell):
    assert cell.kind == "train_moe_family" and cell.chips == 1
    assert cell.config["family"] == "lfm2_moe"
    assert cell.traffic["global_batch"] == 2 and cell.traffic["seq"] == 8192
    assert [e["name"] for e in cell.end_to_end] == ["train_tok_s_chip",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS | {
        "mfu", "step_ms_p50", "stall_share", "mosaic_time_share",
        "device_idle_share.train", "scope_time_share.attn",
        "scope_time_share.mlp", "scope_time_share.embed",
        "scope_time_share.lm_head_loss", "scope_time_share.optimizer",
        "moe_experts_roofline", "moe_experts_time_share",
        "moe_route_time_share", "moe_load_max_over_mean",
        "hbm_window_peak_gb.train", "flash_fwd_ms_step", "flash_bwd_ms_step",
        "train_step_host_ms", "idle_ms_step.host_python", "compile_s",
        "setup_import_s", "setup_model_init_s"} <= names
    for m in cell.per_layer:              # every reader file is there
        mf.load_reader(cell, m["reader"])
    mf.load_kind(cell)


def test_the_entries_are_in_the_manifest(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                               "blob/main/config.json")
    work = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "train-lfm2-b2-s8192", 1)
    for name in NEW_METRICS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and m["unit"] == "%"
        assert m["moves"] == "train_tok_s_chip"
    rate = next(e for e in manifest["end_to_end"]
                if e["name"] == "train_tok_s_chip")
    assert rate["workloads"].count(CELL) == 1


def test_the_configuration_keeps_the_catalog_rows_widths(cell):
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    for key in cfg["reduced"]:            # counts of what is held, no width
        assert not WIDTH.search(key), key
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert cfg["router_outputs"] == CATALOG["num_experts"]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] >= 8                # the floors
    assert cfg["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    # published layers 1-5: one of the two leading dense layers, then a
    # whole period of the pattern that follows them, in the published ratio
    assert cfg["layer_types"] == CATALOG["layer_types"][1:6]
    assert cfg["num_dense_layers"] == 1
    after_dense = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert len(after_dense) >= 4
    assert sorted(after_dense) == sorted(CATALOG["layer_types"][2:6])
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert "4 chips share each layer" in cfg["deployment"]
    assert {"head_dim", "tie_word_embeddings", "in_proj_column_order",
            "initializer_range", "bias_update_speed", "no_auxiliary_loss",
            "learning_rate_schedule"} <= set(cfg["assumed"])
    assert cfg["initializer_range"] == 0.02
    assert cfg["bias_update_speed"] == 0.01       # assumed: says why
    assert "balance" in cfg["assumed"]["bias_update_speed"]
    assert cfg["tie_word_embeddings"] is True
    assert "embedding_initializer_range" not in cfg    # the released range
    optimizer = cell.traffic["optimizer"]
    assert optimizer["name"] == "AdamW" and "why" in optimizer
    assert optimizer["learning_rate"] == 1e-5


def test_the_program_takes_the_files_keys(cell):
    cfg = program_lfm2_moe.model_config(cell)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 8, 64)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv") and cfg.num_dense_layers == 1
    assert cfg.experts_held == (0, 8) and cfg.router_outputs == 32
    assert cfg.recompute == "layer" and cfg.dtype == "bfloat16"
    assert cfg.conv_L_cache == 3 and cfg.routed_scaling_factor == 1
    cell.config["conv_bias"] = True
    with pytest.raises(ValueError, match="conv_bias"):
        program_lfm2_moe.model_config(cell)


# -------------------------------------------------------------- the shares
def _row(dur, path=(), group=None, mosaic=False, name="fusion.1"):
    return {"chip": 0, "dur_ns": dur, "path": tuple(path), "group": group,
            "mosaic": mosaic, "tf_op": "/".join(path), "source": "",
            "op": {"name": name, "opcode": "fusion", "type": "bf16[8]"}}


ROWS = [
    _row(100, ["attn"], "scope_time_share.attn"),
    _row(60, ["attn"], "scope_time_share.attn", True, "flash_fwd.2"),
    _row(50, ["mlp"], "scope_time_share.mlp"),
    _row(200, ["checkpoint", "short_conv"]),
    _row(90, ["rematted_computation", "short_conv"]),
    _row(40, ["moe_experts"]), _row(70, [], None, True, "ragged-dot-none.3"),
    _row(30, ["moe_router"]), _row(20, ["moe_dispatch"]),
    _row(10, ["moe_combine"]), _row(25, ["loss_scale"]),
]


def _read(cell, name, rows=ROWS, **more):
    spec = next(m for m in cell.per_layer if m["name"] == name)
    red = {"window_s": 1000e-9, "chips": 1, "window_ns": (0, 1000)}
    obs = dict({"trace": red, "program_scopes": rows, "traced_steps": 2,
                "device": {"kind": "TPU v5 lite"}}, **more)
    return named_ops.read(spec, obs)


def test_the_shares_of_the_cell_add_up_to_the_busy_share(cell):
    conv = _read(cell, "scope_time_share.short_conv")
    assert conv == pytest.approx(29.0)
    experts = _read(cell, "moe_experts_time_share")
    route = _read(cell, "moe_route_time_share")
    unnamed = _read(cell, "unnamed_time_share.lfm2_moe")
    assert unnamed == pytest.approx(2.5)               # loss_scale alone
    family = 100.0 * (100 + 60 + 50) / 1000
    busy = 100.0 * sum(r["dur_ns"] for r in ROWS) / 1000
    assert family + conv + experts + route + unnamed == pytest.approx(busy)


def test_the_rooflines_read_the_costs_the_kind_gives(cell):
    cost = {"flops": 197e12 * 10e-9, "bytes": 1.0}     # least 10 ns a step
    assert _read(cell, "short_conv_roofline", short_conv_cost=cost) == \
        pytest.approx(100.0 * 10 / (290 / 2))
    assert _read(cell, "flash_gqa_roofline.lfm2_moe",
                 flash_gqa_cost=cost) == pytest.approx(100.0 * 10 / (60 / 2))
    assert _read(cell, "short_conv_roofline") is None   # no cost, no share
    # a program from before the scope existed: nothing to read, no error
    old = [r for r in ROWS if "short_conv" not in r["path"]]
    for name in ("scope_time_share.short_conv", "short_conv_roofline"):
        assert _read(cell, name, rows=old, short_conv_cost=cost) is None
    # the costs come under the keys the metrics' files name
    costs = flops_lfm2_moe.kernel_costs(cell.config, 2, 8192)
    for name in ("short_conv_roofline", "flash_gqa_roofline.lfm2_moe"):
        spec = next(m for m in cell.per_layer if m["name"] == name)
        assert spec["field"]["roofline"] in costs


def test_the_held_share_has_a_reading_of_its_own(cell):
    spec = next(m for m in cell.per_layer
                if m["name"] == "moe_held_share.lfm2_moe")
    assert spec["reader"] == "window_counters"
    assert window_counters.read(spec, {"moe_held_share": 25.0}) == 25.0
    assert window_counters.read(spec, {}) is None


# ------------------------------------------------------- operation counts
def test_parameter_counts_against_the_issues_hand_counts(cell):
    c = flops_lfm2_moe.param_counts(cell.config)
    assert c["conv_matrices"] == 2048 * 6144 + 2048 * 2048
    assert c["conv_layer"] == c["conv_matrices"] + 2048 * 3
    assert c["attention_matrices"] == 2048 * 2048 + 2 * 2048 * 512 \
        + 2048 * 2048
    assert c["conv_matrices"] / 1e6 == pytest.approx(16.78, abs=0.005)
    assert c["attention_matrices"] / 1e6 == pytest.approx(10.49, abs=0.005)
    assert c["dense"] / 1e6 == pytest.approx(44.04, abs=0.005)
    assert c["expert"] == 11010048
    assert c["experts_held"] / 1e6 == pytest.approx(88.08, abs=0.005)
    assert c["router"] == 65536 and c["table"] == 16384 * 2048
    assert (c["conv_layers"], c["attention_layers"], c["dense_layers"],
            c["expert_layers"]) == (4, 1, 1, 4)
    # dense layer 60.8 M, three conv expert layers at 104.9 M, the
    # attention expert layer at 98.6 M, the table 33.6 M
    assert c["held"] / 1e6 == pytest.approx(507.8, abs=0.05)
    assert c["held"] * 12 / 1e9 == pytest.approx(6.09, abs=0.01)
    # whole, the published model with one table: 8.34 B
    whole = dict(cell.config, **cell.config["published"], experts_held=[0, 32])
    assert flops_lfm2_moe.param_counts(whole)["held"] / 1e9 == \
        pytest.approx(8.339, abs=0.002)


def test_flops_per_token_against_hand_counts(cell):
    cfg = cell.config
    per = flops_lfm2_moe.expected_held_assignments(cfg)
    assert per == 1.0                           # 4 x 8 / 32
    assert 2 * 8192 * 4 == 65536                # assignment rows a layer
    fwd = flops_lfm2_moe.forward_flops_per_token(cfg, 8192, per)
    touched = 4 * 16777216 + 10485760 + 44040192 + 4 * (65536 + 11010048) \
        + 33554432
    attention = 2 * 4096 * 2 * 64 * 32          # the one attention layer
    assert attention == 33554432
    assert fwd == 2 * touched + attention
    assert flops_lfm2_moe.train_flops_per_token(cfg, 8192, per) == 3 * fwd
    # a step of 16,384 tokens under uniform routing: ~21.3 TFLOP needed
    assert 3 * fwd * 16384 / 1e12 == pytest.approx(21.3, abs=0.1)
    # more counted assignments, more needed work
    assert flops_lfm2_moe.forward_flops_per_token(cfg, 8192, 2.0) - fwd == \
        2 * 4 * 11010048


def test_kernel_costs(cell):
    cfg = cell.config
    conv = flops_lfm2_moe.short_conv_cost(2, 8192, cfg, 4)
    assert conv["flops"] == 4 * 3 * 2 * 16384 * (6144 * 2048 + 2048 * 2048)
    acts = 2 * 16384 * 6 * 2048 * 2       # x, [B | C | u], y, out; + grads
    weights = 2 * (4 * 2048 * 2048 + 2048 * 3) * 2
    assert conv["bytes"] == 4 * (acts + weights)
    costs = flops_lfm2_moe.kernel_costs(cfg, 2, 8192)
    assert costs["short_conv_cost"] == conv
    # imported, not copied
    assert flops_lfm2_moe.flash_gqa_train_cost is \
        flops_gdn_moe.flash_gqa_train_cost
    assert flops_lfm2_moe.experts_train_cost is \
        flops_mla_moe.experts_train_cost
    flash = flops_gdn_moe.flash_gqa_train_cost(2, 8192, 32, 8, 64, 1)
    assert costs["flash_gqa_cost"] == flash
    assert flash["flops"] == 3 * 2 * 32 * 8192 * 8192 * 128
    assert flash["flops"] / 1e12 == pytest.approx(1.65, abs=0.005)
    ex = flops_lfm2_moe.experts_train_cost(16384, 8, 2048, 1792)
    assert ex["flops"] == 9 * 2 * 16384 * 2048 * 1792


# ----------------------------------------------------------------- the kind
def test_the_family_kind_end_to_end_line(lfm2_root, capsys):
    result, text = _run(lfm2_root, trace=False, seconds=3.0)
    assert result["correct"] is True and result["failed"] == 0, text
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert set(compared) == {
        "router_same_input_flip_share", "router_max_margin", "loss_abs_err",
        "logit_max_abs_err", "grad_rel_err_worst",
        "assignments_off_expected", "non_finite_losses",
        "loss_last3_over_first3", "compiles_in_window"}
    for name, limit in (
            ("router_same_input_flip_share",
             program_lfm2_moe.ROUTER_SAME_INPUT_FLIP_TOL),
            ("router_max_margin", program_lfm2_moe.ROUTER_MARGIN_TOL),
            ("loss_abs_err", program_lfm2_moe.LOSS_ATOL),
            ("grad_rel_err_worst", program_lfm2_moe.GRAD_REL_TOL)):
        assert compared[name][1] == limit
    assert compared["assignments_off_expected"] == [0.0, 0.0]
    last = capsys.readouterr().err.strip().splitlines()[-len(compared):]
    assert [ln.split()[:2] for ln in last] == [["[compared]", k]
                                               for k in compared]
    assert result["attempted"] > 0
    assert "[reference] (a1) the program's router" in text
    assert "[reference] (a2)" in text and "[reference] (b)" in text
    assert "[reference] (c) gradients of layers [0, 1]" in text
    assert "1 expert layers" in text
    assert "steps x tokens x 4 x layers expected" in text
    assert "'compiles_in_window': 0" in text


def test_the_family_kind_traced_line(lfm2_root):
    result, text = _run(lfm2_root, trace=True, seconds=4.0)
    assert result["correct"] is True, text
    cell = mf.load_cell(TINY, lfm2_root)
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert {"stall_share", "step_ms_p50", "mfu", "compile_s",
            "moe_load_max_over_mean", "moe_held_share.lfm2_moe"} <= set(
                result["metrics"])
    assert 0.0 < result["metrics"]["moe_held_share.lfm2_moe"]["value"] < 100.0
    # what only a device trace gives is left out, never made up
    for name in ("short_conv_roofline", "flash_gqa_roofline.lfm2_moe",
                 "scope_time_share.short_conv", "unnamed_time_share.lfm2_moe",
                 "device_idle_share.train"):
        assert name not in result["metrics"]


def test_the_kind_finds_adapter_and_counts_by_the_family(lfm2_root):
    """The next family adds program_<family>.py and flops_<family>.py and no
    kind: a family with neither is refused by name, before anything runs."""
    cell = mf.load_cell(TINY, lfm2_root)
    cell.config["family"] = "no_such_family"
    opts = harness.Opts(seed=1, seconds=1.0, trace=False, log=print)
    with pytest.raises(ModuleNotFoundError, match="program_no_such_family"):
        mf.load_kind(cell).run(cell, opts)


# --------------------------------------------- the controls of ISSUE 35
_TAPS, _RMS = lfm2_moe.causal_taps, lfm2_moe.rms_norm


def _taps_reversed(z, w):
    return _TAPS(z, w[:, ::-1])


def _taps_summed_in_bf16(z, w):
    zp = jnp.pad(z.astype(jnp.bfloat16), ((0, 0), (w.shape[1] - 1, 0),
                                          (0, 0)))
    wb = w.astype(jnp.bfloat16)
    return sum(zp[:, j:j + z.shape[1]] * wb[:, j]
               for j in range(w.shape[1])).astype(jnp.float32)


def _c_gate_left_out(x, p):
    h = x.shape[-1]
    bcu = (x @ p["in_w"]).astype(jnp.float32)
    c = _TAPS(bcu[..., :h] * bcu[..., 2 * h:], p["conv_w"])
    return c.astype(x.dtype) @ p["out_w"]


def _qk_norm_left_out(x, w, eps):
    return x if x.ndim == 4 else _RMS(x, w, eps)


def _router_sigmoid_in_bf16(x2, router_w, bias, top_k, scale):
    s = jax.nn.sigmoid(x2.astype(jnp.bfloat16) @ router_w.astype(
        jnp.bfloat16)).astype(jnp.float32)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), picked / picked.sum(-1, keepdims=True)


# control -> (where it is patched in, the stand-in, the compared number
# that refuses it here). At these widths and a two-layer model a wrong
# mixer shows in its layer's gradients, (c), before it shows in the logits
CONTROLS = {
    "c_gate_left_out": ("short_conv", _c_gate_left_out,
                        "grad_rel_err_worst"),
    "taps_reversed": ("causal_taps", _taps_reversed, "grad_rel_err_worst"),
    "qk_norm_left_out": ("rms_norm", _qk_norm_left_out,
                         "grad_rel_err_worst"),
    "router_sigmoid_in_bf16": ("sigmoid_topk_route", _router_sigmoid_in_bf16,
                               "router_same_input_flip_share"),
}


def _compare_tiny(lfm2_root, **config):
    """program_lfm2_moe.compare_with_reference on the tiny cell's freshly
    built model (no window: the comparison alone), its norm weights moved
    off their ones (a norm left out would else hide behind them)."""
    import numpy as np

    from benchmark import traffic_gen

    cell = mf.load_cell(TINY, lfm2_root)
    cell.config.update(config)
    model = program_lfm2_moe.build_train(cell, 5)["model"]
    r = np.random.RandomState(5)
    for _, p in model.named_parameters():
        if p.ndim == 1:
            p.set_value((1.0 + r.uniform(-0.3, 0.3, p.shape))
                        .astype(np.float32))
    x, y = traffic_gen.sample_batch(5, cell.config["vocab_size"], 1, 160)
    lines = []
    out = program_lfm2_moe.compare_with_reference(
        model, program_lfm2_moe.reference_config(cell.config), x, y,
        lines.append, 5)
    return out, lines


# the router's control takes 64 outputs and 6 a token, as the qwen3_next
# cell's test does: among 32 scores few 4th and 5th tie within bf16's grid
WIDE_ROUTER = dict(router_outputs=64, num_experts=64, experts_held=[0, 64],
                   num_experts_per_tok=6)


def test_the_comparison_accepts_the_program_as_it_is(lfm2_root):
    out, lines = _compare_tiny(lfm2_root, **WIDE_ROUTER)
    assert out["ok"], lines
    for number, limit in out["compared"].values():
        assert number <= limit


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_planted_control_turns_correct_false(lfm2_root, control,
                                               monkeypatch):
    name, stand_in, number = CONTROLS[control]
    monkeypatch.setattr(moe if name == "sigmoid_topk_route" else lfm2_moe,
                        name, stand_in)
    out, lines = _compare_tiny(lfm2_root, **WIDE_ROUTER)
    assert not out["ok"], lines
    got, limit = out["compared"][number]
    assert got > limit, (control, out["compared"])


def test_the_taps_summed_in_bfloat16_pass_every_limit(lfm2_root,
                                                      monkeypatch):
    """The fifth control of ISSUE 35 is one no limit can see, and this
    says so where a later change of the limits would notice: the sum over
    three taps of products rounded to bfloat16 is off by ~2^-9 of itself, a
    tenth of what the bfloat16 matmuls on either side of it already
    are."""
    monkeypatch.setattr(lfm2_moe, "causal_taps", _taps_summed_in_bf16)
    out, lines = _compare_tiny(lfm2_root, **WIDE_ROUTER)
    assert out["ok"], lines
    assert 0.0 < out["max_grad_rel_err"] < 0.05
