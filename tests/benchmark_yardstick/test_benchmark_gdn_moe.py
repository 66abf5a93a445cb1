"""The `qwen3_next` cell: its files load from the manifest, its kind runs an
untraced and a traced line at a tiny size on the CPU with the device check
lifted, its operation counts agree with hand counts (at the head slice held
and at the published layer), its configuration is the catalog row's but for
what this chip holds (depth, experts, vocabulary rows, one key/value head's
slice of the attention heads), its shares are read by `named_ops` beside a
scope family that did not grow, and each fault of ISSUE 33's section 5,
planted on the CPU, turns `correct` false."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_gdn_moe, harness, manifest as mf, program_gdn_moe
from benchmark.readers import named_ops, program_scopes, window_counters

from conftest import PRETEND_TPU, build_root
from test_benchmark_manifest import WIDTH

CONFIG = "qwen3-next-80b-a3b-ep16"
CELL = "qwen3-next-80b-a3b-ep16.train-gdn-b1-s8192"
TINY = "gdn-moe-test.train"
# architectures.jsonl line 63 (Qwen3-Next-80B-A3B-Instruct), `config`
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
PUBLISHED_HEADS = {"num_attention_heads": 16, "num_key_value_heads": 2}


@pytest.fixture(scope="module")
def gdn_root(tmp_path_factory):
    """conftest's temporary benchmark root plus one cell of this family at
    the CPU tests' widths, from data files alone."""
    root = build_root(str(tmp_path_factory.mktemp("gdn_root")))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs",
                           "qwen3-next-80b-a3b-ep16.json")) as f:
        cfg = json.load(f)
    cfg.update(name="gdn-moe-test", vocab_size=512, hidden_size=64,
               num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=8, intermediate_size=128,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=8, router_outputs=16, experts_held=[8, 16],
               num_experts_per_tok=4, dtype="float32")
    with open(os.path.join(bdir, "configs", "gdn-moe-test.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic",
                           "train-gdn-b1-s8192.json")) as f:
        tr = json.load(f)
    # 160 tokens a step and no multiple of the rule's chunk of 64: the
    # cell's 1e-5 moves the loss by less than one batch differs from the
    # next, so a learning rate at which it falls
    tr.update(global_batch=2, seq=80, drop_chunks=1, min_kept_chunks=2,
              trace_chunks=2, optimizer={"name": "AdamW",
                                         "learning_rate": 2e-4},
              reference_sample={"sequences": 1, "tokens": 80})
    with open(os.path.join(bdir, "traffic", "t-train-gdn.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "gdn-moe-test", "source": "tests/test_gdn_moe.py TEST",
        "file": "benchmark/configs/gdn-moe-test.json",
        "reduced": cfg["reduced"], "why": "CPU tests"})
    bench["workloads"].append({
        "name": TINY, "config": "gdn-moe-test", "traffic": "t-train-gdn",
        "chips": 1, "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture
def cell(gdn_root):
    """The cell, loaded anew (a test may change its copy)."""
    return mf.load_cell(CELL, gdn_root)


def _run(root, trace, seconds):
    lines = []
    result = harness.run_cell(
        TINY, 2 ** 31 + 11, seconds, trace, time.monotonic(), root=root,
        device=dict(PRETEND_TPU, count=1),
        log=lambda *a: lines.append(" ".join(map(str, a))))
    return result, "\n".join(lines)


# ------------------------------------------------------------- the manifest
def test_the_cell_loads_through_the_manifest(cell):
    assert cell.kind == "train_gdn_moe" and cell.chips == 1
    assert cell.config["family"] == "qwen3_next"
    assert [e["name"] for e in cell.end_to_end] == ["train_tok_s_chip",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mfu", "step_ms_p50", "stall_share", "mosaic_time_share",
            "device_idle_share.train", "scope_time_share.attn",
            "scope_time_share.mlp", "scope_time_share.embed",
            "scope_time_share.lm_head_loss", "scope_time_share.optimizer",
            "scope_time_share.gdn_chunk", "scope_time_share.gdn_proj",
            "gdn_chunk_roofline", "flash_gqa_roofline",
            "moe_experts_roofline", "moe_experts_time_share",
            "moe_route_time_share", "unnamed_time_share.gdn_moe",
            "moe_load_max_over_mean", "moe_held_share.gdn_moe",
            "hbm_window_peak_gb.train", "flash_fwd_ms_step",
            "flash_bwd_ms_step", "train_step_host_ms",
            "idle_ms_step.host_python"} <= names
    # other cells' readings of the same quantities: one head size, one
    # width pair, lists of scopes that leave the gdn ones unnamed, a metric
    # a test pins to the kanana cell
    assert not {"flash_roofline", "flash_mla_roofline", "hbm_peak_gb.train",
                "unnamed_time_share", "moe_held_share",
                "scope_time_share.unscoped"} & names
    for m in cell.per_layer:              # every reader file is there
        mf.load_reader(cell, m["reader"])
    mf.load_kind(cell)


def test_the_entries_stand_at_the_ends_of_their_lists(manifest):
    """The new metrics are this cell's alone and last in `per_layer`, the
    configuration and the cell last in theirs, and an accepted metric that
    the cell reports has it as the last name of its list."""
    new = ["scope_time_share.gdn_chunk", "scope_time_share.gdn_proj",
           "unnamed_time_share.gdn_moe", "gdn_chunk_roofline",
           "flash_gqa_roofline", "moe_held_share.gdn_moe"]
    assert [m["name"] for m in manifest["per_layer"]][-len(new):] == new
    for m in manifest["per_layer"][-len(new):]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tok_s_chip"
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    assert manifest["configs"][-1]["name"] == CONFIG
    for m in manifest["end_to_end"] + manifest["per_layer"][:-len(new)]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL and \
                m["workloads"].count(CELL) == 1


def test_the_configuration_keeps_the_catalog_rows_widths(cell):
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "num_attention_heads",
                              "num_key_value_heads"]
    for key in cfg["reduced"]:            # counts of what is held, no width
        assert not WIDTH.search(key), key
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] < value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert cfg["router_outputs"] == CATALOG["num_experts"]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] >= 8                # the floors
    assert cfg["num_hidden_layers"] >= 4
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["vocab_size"] * 8 >= CATALOG["vocab_size"]
    # the heads held are whole key/value heads with all their query heads
    assert cfg["num_attention_heads"] * CATALOG["num_key_value_heads"] == \
        cfg["num_key_value_heads"] * CATALOG["num_attention_heads"]
    assert "16 chips share each layer" in cfg["deployment"]
    assert "two slices by key/value head" in cfg["deployment"]
    assert "attention_heads_share" in cfg["assumed"]
    assert {"initializer_range", "embedding_initializer_range",
            "A_log_and_dt_bias", "norm_weights", "no_auxiliary_loss",
            "no_multi_token_prediction", "column_layout"} <= set(cfg["assumed"])
    assert cfg["initializer_range"] == 0.02
    assert cfg["embedding_initializer_range"] == 1.0
    optimizer = cell.traffic["optimizer"]
    assert optimizer["name"] == "AdamW" and "why" in optimizer
    assert optimizer["learning_rate"] == 1e-5


def test_the_program_takes_the_files_keys(cell):
    cfg = program_gdn_moe.model_config(cell)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (8, 1)
    assert (cfg.key_width, cfg.value_width) == (2048, 4096)
    assert [cfg.is_full_attention(i) for i in range(4)] == [
        False, False, False, True]
    assert cfg.experts_held == (0, 32) and cfg.router_outputs == 512
    assert cfg.recompute == "layer" and cfg.dtype == "bfloat16"
    cell.config["norm_topk_prob"] = False
    with pytest.raises(ValueError, match="norm_topk_prob"):
        program_gdn_moe.model_config(cell)


# ------------------------------------------------------------ the families
def test_the_new_shares_keep_the_scope_familys_orders_distinct(cell):
    """The two gdn shares are read by `named_ops`, as the moe_* shares are:
    the `program_scopes` family is the five groups it was, its orders
    distinct, and no file of the new cell joins it."""
    groups = program_scopes.family()
    assert [name for name, _ in groups] == [
        "scope_time_share.attn", "scope_time_share.mlp",
        "scope_time_share.embed", "scope_time_share.lm_head_loss",
        "scope_time_share.optimizer"]
    for name in ("scope_time_share.gdn_chunk", "scope_time_share.gdn_proj"):
        spec = next(m for m in cell.per_layer if m["name"] == name)
        assert spec["reader"] == "named_ops" and "order" not in spec["field"]
    # an op under a gdn scope is booked to no family group
    assert program_scopes.group_of(("checkpoint", "gdn_chunk"), groups) \
        is None
    assert program_scopes.group_of(("gdn_proj",), groups) is None


def _row(dur, path=(), group=None, mosaic=False, name="fusion.1"):
    return {"chip": 0, "dur_ns": dur, "path": tuple(path), "group": group,
            "mosaic": mosaic, "tf_op": "/".join(path), "source": "",
            "op": {"name": name, "opcode": "fusion", "type": "bf16[8]"}}


ROWS = [
    _row(100, ["attn"], "scope_time_share.attn"),
    _row(60, ["attn"], "scope_time_share.attn", True, "flash_fwd.2"),
    _row(50, ["mlp"], "scope_time_share.mlp"),
    _row(200, ["checkpoint", "gdn_chunk"]),
    _row(90, ["rematted_computation", "gdn_proj"]),
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


def test_the_shares_of_the_new_cell_add_up_to_the_busy_share(cell):
    chunk = _read(cell, "scope_time_share.gdn_chunk")
    proj = _read(cell, "scope_time_share.gdn_proj")
    assert chunk == pytest.approx(20.0) and proj == pytest.approx(9.0)
    experts = _read(cell, "moe_experts_time_share")
    route = _read(cell, "moe_route_time_share")
    unnamed = _read(cell, "unnamed_time_share.gdn_moe")
    assert unnamed == pytest.approx(2.5)               # loss_scale alone
    family = 100.0 * (100 + 60 + 50) / 1000
    busy = 100.0 * sum(r["dur_ns"] for r in ROWS) / 1000
    assert family + chunk + proj + experts + route + unnamed == \
        pytest.approx(busy)


def test_the_rooflines_read_the_costs_the_kind_gives(cell):
    cost = {"flops": 197e12 * 10e-9, "bytes": 1.0}     # least 10 ns a step
    assert _read(cell, "gdn_chunk_roofline", gdn_chunk_cost=cost) == \
        pytest.approx(100.0 * 10 / (200 / 2))
    assert _read(cell, "flash_gqa_roofline", flash_gqa_cost=cost) == \
        pytest.approx(100.0 * 10 / (60 / 2))
    assert _read(cell, "gdn_chunk_roofline") is None    # no cost, no share
    # a program from before the scopes existed: nothing to read, no error
    old = [r for r in ROWS if not {"gdn_chunk", "gdn_proj"} & set(r["path"])]
    for name in ("scope_time_share.gdn_chunk", "scope_time_share.gdn_proj",
                 "gdn_chunk_roofline"):
        assert _read(cell, name, rows=old, gdn_chunk_cost=cost) is None


def test_the_held_share_has_a_reading_of_its_own(cell):
    spec = next(m for m in cell.per_layer
                if m["name"] == "moe_held_share.gdn_moe")
    assert spec["reader"] == "window_counters"
    assert window_counters.read(spec, {"moe_held_share": 6.25}) == 6.25
    assert window_counters.read(spec, {}) is None


# ------------------------------------------------------- operation counts
def test_parameter_counts_against_the_issues_hand_counts(cell):
    held = flops_gdn_moe.param_counts(cell.config)
    assert held["attention_matrices"] == 2048 * 4096 + 2 * 2048 * 256 \
        + 2048 * 2048                        # one key/value head's slice
    assert held["attention_layer"] / 1e6 == pytest.approx(13.63, abs=0.005)
    assert held["held"] / 1e6 == pytest.approx(612.0, abs=0.05)
    assert held["held"] * 12 / 1e9 == pytest.approx(7.34, abs=0.005)
    # the layer whole, as ISSUE 33 counts it
    c = flops_gdn_moe.param_counts(dict(cell.config, **PUBLISHED_HEADS))
    assert c["gdn_matrices"] == 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert c["gdn_layer"] / 1e6 == pytest.approx(33.72, abs=0.005)
    assert c["attention_matrices"] == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048
    assert c["attention_layer"] / 1e6 == pytest.approx(27.27, abs=0.01)
    assert c["held"] - held["held"] == c["attention_layer"] \
        - held["attention_layer"]
    assert c["expert"] == 3145728 and c["experts_held"] == 32 * 3145728
    assert c["outside_mixer_and_routed"] / 1e6 == pytest.approx(4.20,
                                                                abs=0.005)
    assert (c["gdn_layers"], c["attention_layers"]) == (3, 1)
    assert c["embedding"] + c["head"] == 2 * 18992 * 2048
    assert c["held"] / 1e6 == pytest.approx(625.7, abs=0.05)
    assert c["held"] * 12 / 1e9 == pytest.approx(7.51, abs=0.005)


def test_flops_per_token_against_the_issues_hand_count(cell):
    per = flops_gdn_moe.expected_held_assignments(cell.config)
    assert per == 0.625
    assert flops_gdn_moe.forward_flops_per_token(
        cell.config, 8192, per) / 1e9 == pytest.approx(0.40, abs=0.005)
    cfg = dict(cell.config, **PUBLISHED_HEADS)     # ISSUE 33 counts it whole
    fwd = flops_gdn_moe.forward_flops_per_token(cfg, 8192, per)
    assert fwd / 1e9 == pytest.approx(0.47, abs=0.01)
    assert 3 * 2 * 33.686e6 == pytest.approx(3 * 67.4e6, rel=0.01)
    attention = 2 * 4096 * 512 * 16
    assert attention == 67108864                       # ISSUE: 67 M
    delta = flops_gdn_moe.delta_rule_flops_per_token_layer(cfg)
    assert delta == 6 * 128 * 128 * 32                 # ISSUE: ~6 M x 0.5
    assert flops_gdn_moe.train_flops_per_token(cfg, 8192, per) == 3 * fwd
    # more counted assignments, more needed work
    assert flops_gdn_moe.forward_flops_per_token(cfg, 8192, 1.25) - fwd == \
        pytest.approx(2 * 4 * 0.625 * 3 * 2048 * 512)


def test_kernel_costs(cell):
    cfg = cell.config
    rule = flops_gdn_moe.gdn_chunk_cost(1, 8192, cfg, 3)
    assert rule["flops"] == 3 * 3 * 8192 * 6 * 128 * 128 * 32
    wide = 2 * (2 * 2048 + 2 * 4096) * 2          # q, k, v, o and gradients
    assert rule["bytes"] == 3 * 8192 * (wide + 2 * 2 * 32 * 4)
    # the chunk size is no argument: a kernel is read against the same work
    flash = flops_gdn_moe.flash_gqa_train_cost(1, 8192, 16, 2, 256, 1)
    assert flash["flops"] == 3 * 16 * 8192 * 8192 * 512
    assert flash["bytes"] == 6 * 8192 * 256 * 2 * (16 + 2)
    ex = flops_gdn_moe.experts_train_cost(5120, 32, 2048, 512)
    assert ex["flops"] == 9 * 2 * 5120 * 2048 * 512


# ----------------------------------------------------------------- the kind
def test_train_gdn_moe_kind_end_to_end_line(gdn_root, capsys):
    result, text = _run(gdn_root, trace=False, seconds=3.0)
    assert result["correct"] is True and result["failed"] == 0, text
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert set(compared) == {
        "router_same_input_flip_share", "router_max_margin", "loss_abs_err",
        "logit_max_abs_err", "grad_rel_err_worst",
        "delta_rule_rel_err_worst", "assignments_off_expected",
        "non_finite_losses", "loss_last3_over_first3", "compiles_in_window"}
    for name, limit in (
            ("router_same_input_flip_share",
             program_gdn_moe.ROUTER_SAME_INPUT_FLIP_TOL),
            ("router_max_margin", program_gdn_moe.ROUTER_MARGIN_TOL),
            ("grad_rel_err_worst", program_gdn_moe.GRAD_REL_TOL),
            ("delta_rule_rel_err_worst",
             program_gdn_moe.DELTA_RULE_REL_TOL)):
        assert compared[name][1] == limit
    assert compared["assignments_off_expected"] == [0.0, 0.0]
    last = capsys.readouterr().err.strip().splitlines()[-len(compared):]
    assert [ln.split()[:2] for ln in last] == [["[compared]", k]
                                               for k in compared]
    assert result["attempted"] > 0
    assert "[reference] (a1) the program's router" in text
    assert "[reference] (a2)" in text and "[reference] (b)" in text
    assert "[reference] (c) gradients of layers [2, 3]" in text
    assert "[reference] (d) the delta rule alone" in text
    assert "steps x tokens x 4 x layers expected" in text
    assert "'compiles_in_window': 0" in text


def test_train_gdn_moe_kind_traced_line(gdn_root):
    result, text = _run(gdn_root, trace=True, seconds=4.0)
    assert result["correct"] is True, text
    cell = mf.load_cell(TINY, gdn_root)
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert {"stall_share", "step_ms_p50", "mfu", "compile_s",
            "moe_load_max_over_mean", "moe_held_share.gdn_moe"} <= set(
                result["metrics"])
    assert 0.0 < result["metrics"]["moe_held_share.gdn_moe"]["value"] < 100.0
    # what only a device trace gives is left out, never made up
    for name in ("gdn_chunk_roofline", "flash_gqa_roofline",
                 "scope_time_share.gdn_chunk", "scope_time_share.gdn_proj",
                 "unnamed_time_share.gdn_moe", "device_idle_share.train"):
        assert name not in result["metrics"]


# ------------------------------------------- the planted faults of section 5
def _softmax_router_in_bf16(x2, router_w, top_k):
    p = jax.nn.softmax(x2.astype(jnp.bfloat16) @ router_w.astype(
        jnp.bfloat16), axis=-1).astype(jnp.float32)
    picked, chosen = jax.lax.top_k(p, top_k)
    return chosen.astype(jnp.int32), picked / picked.sum(-1, keepdims=True)


def _plant(monkeypatch, fault):
    from paddle_tpu.distributed import moe
    from paddle_tpu.models import gdn_moe
    from paddle_tpu.ops import gated_delta_rule as rule

    if fault == "router_softmax_in_bf16":
        monkeypatch.setattr(moe, "softmax_topk_route",
                            _softmax_router_in_bf16)
    elif fault == "beta_fixed_at_1":
        inputs = gdn_moe.delta_rule_inputs

        def beta_one(x, p, cfg):
            q, k, v, g, beta, z = inputs(x, p, cfg)
            return q, k, v, g, jnp.ones_like(beta), z

        monkeypatch.setattr(gdn_moe, "delta_rule_inputs", beta_one)
    elif fault == "shared_gate_left_out":
        val = moe.dropless_moe_val

        def ungated(x2, p, bias, **kw):
            return val(x2, {k: v for k, v in p.items()
                            if k != "shared_gate_w"}, bias, **kw)

        monkeypatch.setattr(moe, "dropless_moe_val", ungated)
    elif fault == "state_in_bf16":
        step = rule._chunk_step

        def through_bf16(state, xs, **kw):
            state, o = step(state, xs, **kw)
            return state.astype(jnp.bfloat16).astype(jnp.float32), o

        monkeypatch.setattr(rule, "_chunk_step", through_bf16)
    else:
        raise KeyError(fault)


# the limit that refuses each (at these widths and 0.02 weights a block
# barely moves the residual stream, so the two wrong blocks show in their
# layers' gradients, (c), and not in the logits)
FAULTS = {"router_softmax_in_bf16": "router_same_input_flip_share",
          "beta_fixed_at_1": "grad_rel_err_worst",
          "shared_gate_left_out": "grad_rel_err_worst",
          "state_in_bf16": "delta_rule_rel_err_worst"}


def _compare_tiny(gdn_root, **config):
    """program_gdn_moe.compare_with_reference on the tiny cell's freshly
    built model (no window: the comparison alone)."""
    from benchmark import traffic_gen

    cell = mf.load_cell(TINY, gdn_root)
    cell.config.update(config)
    model = program_gdn_moe.build_train(cell, 5)["model"]
    # four heads drawn from uniform(0, 16) may all forget within a token
    # (this seed's: A = 11 ... 14.5), and a gradient by g that vanishes has
    # no relative error to speak of: decays from near 1 to near 0 instead
    for blk in model.model.layers:
        if not blk.full_attention:
            blk.A_log.set_value(np.log(np.asarray([0.02, 0.3, 2.0, 9.0],
                                                  np.float32)))
    x, y = traffic_gen.sample_batch(5, cell.config["vocab_size"], 1, 160)
    lines = []
    out = program_gdn_moe.compare_with_reference(
        model, program_gdn_moe.reference_config(cell.config), x, y,
        lines.append, 5)
    return out, lines


def test_the_comparison_accepts_the_program_as_it_is(gdn_root):
    out, lines = _compare_tiny(gdn_root, router_outputs=64, num_experts=64,
                               experts_held=[0, 64], num_experts_per_tok=6)
    assert out["ok"], lines
    for number, limit in out["compared"].values():
        assert number <= limit


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_turns_correct_false(gdn_root, fault, monkeypatch):
    _plant(monkeypatch, fault)
    out, lines = _compare_tiny(gdn_root, router_outputs=64, num_experts=64,
                               experts_held=[0, 64], num_experts_per_tok=6)
    assert not out["ok"], lines
    number, limit = out["compared"][FAULTS[fault]]
    assert number > limit, (fault, out["compared"])
