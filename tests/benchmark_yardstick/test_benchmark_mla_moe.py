"""The `deepseek_v3` cell of the benchmark: its files load through the
manifest, its kind runs an untraced and a traced line at a tiny size on the
CPU with the device check lifted, its operation counts agree with hand
counts, its configuration keeps the catalog row's widths, and the reader
`named_ops` books hand-made events as its docstring says."""
import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import (flops_mla_moe, harness, manifest as mf,
                       program_mla_moe, traffic_gen)
from benchmark.readers import named_ops, program_scopes, window_counters

from conftest import PRETEND_TPU, REPO, build_root

CELL = "kanana-2-30b-a3b-ep8.train-b1-s8192"
TINY = "mla-moe-test.train"
# architectures.jsonl line 30 (kanana-2-30b-a3b-instruct-2601), `config`
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """conftest's temporary benchmark root plus one cell of this family at
    the CPU tests' widths, from data files alone."""
    root = build_root(str(tmp_path_factory.mktemp("moe_root")))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs",
                           "kanana-2-30b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    cfg.update(name="mla-moe-test", vocab_size=512, hidden_size=64,
               num_hidden_layers=3, num_attention_heads=4, head_dim=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
               v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
               moe_intermediate_size=32, n_routed_experts=8,
               router_outputs=16, experts_held=[8, 16],
               num_experts_per_tok=4, dtype="float32")
    with open(os.path.join(bdir, "configs", "mla-moe-test.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "train-b1-s8192.json")) as f:
        tr = json.load(f)
    # 128 tokens a step: the cell's 1e-5 moves the loss by less than one
    # batch differs from the next, so a learning rate at which it falls
    tr.update(global_batch=2, seq=64, drop_chunks=1, min_kept_chunks=2,
              trace_chunks=2, optimizer={"name": "AdamW",
                                         "learning_rate": 2e-4},
              reference_sample={"sequences": 1, "tokens": 64})
    with open(os.path.join(bdir, "traffic", "t-train-moe.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "mla-moe-test", "source": "tests/test_mla_moe.py TEST",
        "file": "benchmark/configs/mla-moe-test.json",
        "reduced": cfg["reduced"], "why": "CPU tests"})
    bench["workloads"].append({
        "name": TINY, "config": "mla-moe-test", "traffic": "t-train-moe",
        "chips": 1, "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _run(root, trace, seconds):
    lines = []
    result = harness.run_cell(
        TINY, 2 ** 31 + 11, seconds, trace, time.monotonic(), root=root,
        device=dict(PRETEND_TPU, count=1),
        log=lambda *a: lines.append(" ".join(map(str, a))))
    return result, "\n".join(lines)


# ------------------------------------------------------------- the manifest
def test_the_cell_loads_through_the_manifest():
    cell = mf.load_cell(CELL)
    assert cell.kind == "train_moe" and cell.chips == 1
    assert cell.config["family"] == "deepseek_v3"
    assert [e["name"] for e in cell.end_to_end] == ["train_tok_s_chip",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mfu", "flash_mla_roofline", "moe_experts_roofline",
            "moe_experts_time_share", "moe_route_time_share",
            "unnamed_time_share", "moe_load_max_over_mean",
            "moe_held_share", "hbm_window_peak_gb.train", "flash_fwd_ms_step",
            "scope_time_share.attn"} <= names
    # its reader counts one head size; its reader reads the process's peak
    # after the reference ran
    assert not {"flash_roofline", "hbm_peak_gb.train"} & names
    assert "scope_time_share.unscoped" not in names
    for m in cell.per_layer:              # every reader file is there
        mf.load_reader(cell, m["reader"])
    mf.load_kind(cell)


def test_the_configuration_keeps_the_catalog_rows_widths():
    cfg = mf.load_cell(CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] < value, key
        else:
            assert key in cfg and cfg[key] == value, key
    assert cfg["router_outputs"] == CATALOG["n_routed_experts"]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] >= 8           # the floors
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert {"bias_update_speed", "initializer_range",
            "embedding_initializer_range"} <= set(cfg["assumed"])
    # the benchmark's statement about its weights (finding 13), and the
    # rate of the published warm-up at the steps a window reaches
    assert cfg["initializer_range"] == 0.02
    assert cfg["embedding_initializer_range"] == 1.0
    optimizer = mf.load_cell(CELL).traffic["optimizer"]
    assert optimizer["name"] == "AdamW" and "why" in optimizer
    assert optimizer["learning_rate"] == 1e-5


def test_the_live_scope_family_is_still_the_five_groups():
    assert [name for name, _ in program_scopes.family()] == [
        "scope_time_share.attn", "scope_time_share.mlp",
        "scope_time_share.embed", "scope_time_share.lm_head_loss",
        "scope_time_share.optimizer"]


# ------------------------------------------------------- operation counts
def test_parameter_counts_against_the_issues_hand_counts():
    c = flops_mla_moe.param_counts(mf.load_cell(CELL).config)
    assert c["attention"] == 2048 * 6144 + 2048 * 576 + 512 * 8192 \
        + 4096 * 2048
    assert round(c["attention"] / 1e6, 2) == 26.35
    assert c["outside_routed"] / 1e6 == pytest.approx(36.05, abs=0.01)
    assert round(c["expert"] / 1e6, 2) == 4.72
    assert c["dense_layer"] / 1e6 == pytest.approx(64.10, abs=0.01)
    assert c["expert_layer_held"] / 1e6 == pytest.approx(111.55, abs=0.01)
    assert round(c["held"] / 1e6) == 576
    assert (c["dense_layers"], c["expert_layers"]) == (1, 4)


def test_flops_per_token_against_the_issues_hand_count():
    cfg = mf.load_cell(CELL).config
    per = flops_mla_moe.expected_held_assignments(cfg)
    assert per == 0.75
    fwd = flops_mla_moe.forward_flops_per_token(cfg, 8192, per)
    assert round(fwd / 1e6) == pytest.approx(930, abs=1)
    attention = 5 * 2 * 4096 * (192 + 128) * 32
    assert round(attention / 1e6) == 419
    assert flops_mla_moe.train_flops_per_token(cfg, 8192, per) == 3 * fwd
    # more counted assignments, more needed work
    assert flops_mla_moe.forward_flops_per_token(cfg, 8192, 1.5) - fwd == \
        pytest.approx(2 * 4 * 0.75 * 3 * 2048 * 768)


def test_kernel_costs():
    flash = flops_mla_moe.flash_mla_train_cost(1, 8192, 32, 192, 128, 5)
    assert flash["flops"] == 3 * 419430400 * 8192
    assert flash["bytes"] == 5 * 6 * 8192 * 32 * 2 * (192 + 128)
    ex = flops_mla_moe.experts_train_cost(6144, 16, 2048, 768)
    assert ex["flops"] == 9 * 2 * 6144 * 2048 * 768
    assert ex["bytes"] == 9 * 2 * (16 * 2048 * 768 + 6144 * (2048 + 768))


# ------------------------------------------------------------ named_ops
def _row(dur, path=(), group=None, mosaic=False, name="fusion.1"):
    return {"chip": 0, "dur_ns": dur, "path": tuple(path), "group": group,
            "mosaic": mosaic, "tf_op": "/".join(path), "source": "",
            "op": {"name": name, "opcode": "fusion", "type": "bf16[8]"}}


ROWS = [
    _row(100, ["attn"], "scope_time_share.attn"),
    _row(60, ["attn"], "scope_time_share.attn", True, "flash_fwd.2"),
    _row(50, ["mlp"], "scope_time_share.mlp"),
    _row(40, ["checkpoint", "moe_experts"]),
    _row(70, [], None, True, "ragged-dot-none.3"),
    _row(30, ["moe_router"]), _row(20, ["moe_dispatch"]),
    _row(10, ["rematted_computation", "moe_combine"]),
    _row(25, ["loss_scale"]), _row(15, [], None, True, "other_kernel"),
]
MOE4 = ["moe_router", "moe_dispatch", "moe_experts", "moe_combine"]
GMM = ["ragged-dot-none"]


def _obs(rows=ROWS, **more):
    red = {"window_s": 1000e-9, "chips": 1, "window_ns": (0, 1000)}
    return dict({"trace": red, "program_scopes": rows, "traced_steps": 2,
                 "device": {"kind": "TPU v5 lite"}}, **more)


def _read(field, **more):
    return named_ops.read({"name": "m", "field": field}, _obs(**more))


def test_named_ops_books_scopes_kernels_and_the_rest():
    experts = _read({"scopes": ["moe_experts"], "kernels": GMM})
    route = _read({"scopes": ["moe_router", "moe_dispatch", "moe_combine"]})
    unnamed = _read({"unnamed": True, "scopes": MOE4, "kernels": GMM})
    assert experts == pytest.approx(11.0)      # 40 + 70 of 1000 ns
    assert route == pytest.approx(6.0)
    assert unnamed == pytest.approx(4.0)       # 25 + 15
    family = 100.0 * (100 + 60 + 50) / 1000
    busy = 100.0 * sum(r["dur_ns"] for r in ROWS) / 1000
    assert family + experts + route + unnamed == pytest.approx(busy)


def test_named_ops_matches_whole_kernel_names():
    assert _read({"kernels": ["ragged-dot"]}) is None
    assert _read({"kernels": ["other_kernel"]}) == pytest.approx(1.5)
    # a family group's op is never booked to a second share ...
    assert _read({"kernels": ["flash_fwd"]}) is None
    # ... but a roofline counts a kernel's time whoever books its share
    cost = {"flops": 197e12 * 6e-9, "bytes": 1.0}
    assert _read({"roofline": "cost", "kernels": ["flash_fwd"]},
                 cost=cost) == pytest.approx(100.0 * 6 / (60 / 2))


def test_named_ops_roofline():
    cost = {"flops": 197e12 * 10e-9, "bytes": 1.0}   # least 10 ns a step
    got = _read({"roofline": "cost", "scopes": ["moe_experts"],
                 "kernels": GMM}, cost=cost)
    assert got == pytest.approx(100.0 * 10 / (110 / 2))
    assert _read({"roofline": "absent", "scopes": ["moe_experts"]}) is None


def test_named_ops_reads_nothing_from_a_program_without_the_names():
    gpt_rows = [r for r in ROWS if r["group"]] + [_row(5, ["loss_scale"])]
    for field in ({"scopes": ["moe_experts"], "kernels": GMM},
                  {"unnamed": True, "scopes": MOE4, "kernels": GMM},
                  {"roofline": "cost", "scopes": ["moe_experts"]}):
        assert _read(field, rows=gpt_rows, cost={"flops": 1, "bytes": 1}) \
            is None
    assert named_ops.read({"name": "m", "field": {"scopes": ["x"]}},
                          {"trace": None}) is None


def test_window_counters_reads_what_the_kind_gave():
    m = {"name": "moe_load_max_over_mean", "field": "moe_load_max_over_mean"}
    assert window_counters.read(m, {"moe_load_max_over_mean": 1.25}) == 1.25
    assert window_counters.read(m, {}) is None
    peak = next(m for m in mf.load_cell(CELL).per_layer
                if m["name"] == "hbm_window_peak_gb.train")
    assert window_counters.read(peak, {"hbm_window_peak_gb": 6.5}) == 6.5
    assert window_counters.read(peak, {"hbm_window_peak_gb": None}) is None


def test_moe_held_share_is_a_metric_of_the_8k_cell_alone(manifest):
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "moe_held_share")
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert entry["moves"] == "train_tok_s_chip"
    spec = next(m for m in mf.load_cell(CELL).per_layer
                if m["name"] == "moe_held_share")
    assert spec["reader"] == "window_counters"
    assert spec["layer"] == "Model step, device"
    assert window_counters.read(spec, {"moe_held_share": 12.5}) == 12.5
    assert window_counters.read(spec, {}) is None
    for w in manifest["workloads"]:
        names = {m["name"] for m in mf.load_cell(w["name"]).per_layer}
        assert ("moe_held_share" in names) == (w["name"] == CELL)


# ----------------------------------------------- the weights the cell trains
def _tiny_cell(moe_root, **config):
    cell = mf.load_cell(TINY, moe_root)
    cell.config.update(config)
    return cell


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_adapter_draws_the_embedding_at_its_own_range(moe_root, seed):
    """`embedding_initializer_range` reaches the token embedding and no
    other weight: each of the others is what the model's own stream drew."""
    from paddle_tpu.models import MlaMoeForCausalLM

    cell = _tiny_cell(moe_root)
    assert cell.config["embedding_initializer_range"] == 1.0
    built = program_mla_moe.build_train(cell, seed)
    plain = MlaMoeForCausalLM(built["cfg"], seed=program_mla_moe._seed32(seed))
    got = dict(built["model"].named_parameters())
    want = dict(plain.named_parameters())
    assert set(got) == set(want) and "model.embed_tokens" in got
    for name, p in got.items():
        w = np.asarray(p._value, np.float32)
        if name == "model.embed_tokens":
            assert w.shape == (512, 64)
            assert np.std(w) == pytest.approx(1.0, rel=0.03), name
            assert abs(np.mean(w)) < 0.03
        else:
            assert np.array_equal(w, np.asarray(want[name]._value,
                                                np.float32)), name
            if w.ndim > 1:
                assert np.std(w) == pytest.approx(0.02, rel=0.1), name
            else:
                assert np.all(w == 1.0), name
    # another seed, another table; the same seed, the same table
    again = program_mla_moe.build_train(cell, seed)["model"]
    other = program_mla_moe.build_train(cell, seed + 1)["model"]
    table = np.asarray(got["model.embed_tokens"]._value)
    assert np.array_equal(table, np.asarray(again.model.embed_tokens._value))
    assert not np.array_equal(table,
                              np.asarray(other.model.embed_tokens._value))


@pytest.mark.parametrize("std,token_by_token", [(1.0, True), (0.02, False)])
def test_routing_is_token_by_token_only_with_the_embeddings_range(
        moe_root, std, token_by_token):
    """PERF.md finding 13, pinned at step 0. The attention's widths are
    raised so that its averaged output outweighs a 0.02 embedding as it does
    at the published widths (0.02 x sqrt(heads x v x rank) = 14 here, 29
    there). With the embedding at 0.02 the stream at the routers is one
    vector common to all tokens: nearly every token of a layer picks the
    same experts. At 1.0 the tokens choose for themselves."""
    import jax

    cell = _tiny_cell(moe_root, embedding_initializer_range=std,
                      num_attention_heads=8, head_dim=8, v_head_dim=128,
                      kv_lora_rank=512)
    model = program_mla_moe.build_train(cell, 5)["model"]
    x, y = traffic_gen.ZipfTokens(5, 512, 1.1).batch(0, 2, 64)
    fn, args = program_mla_moe.forward_fn(model, x, y)
    _, _, chosen = jax.jit(fn)(*args)
    assert len(chosen) == 2
    for picked in np.asarray(chosen):                 # [tokens, k] a layer
        tokens, k = picked.shape
        sets = {tuple(sorted(row)) for row in picked.tolist()}
        fullest = np.bincount(picked.ravel(), minlength=16).max() / tokens
        if token_by_token:
            assert len(sets) > tokens // 3 and fullest < 0.75, (len(sets),
                                                                fullest)
        else:
            assert len(sets) < tokens // 4 and fullest > 0.8, (len(sets),
                                                               fullest)


def test_adamw_gets_the_traffic_files_learning_rate(moe_root):
    cell = _tiny_cell(moe_root)
    cell.traffic["optimizer"] = mf.load_cell(CELL).traffic["optimizer"]
    built = program_mla_moe.build_train(cell, 1)
    assert built["step"].optimizer.get_lr() == 1e-5


# ----------------------------------------------------------------- the kind
def test_the_step_program_check_asks_the_block_for_no_option(monkeypatch):
    """On a TPU program_gpt's check reads `cfg.use_flash_attention`; the
    deepseek_v3 block has no such option (the CPU runs below return before
    that line)."""
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [types.SimpleNamespace(platform="tpu")])
    lowered = types.SimpleNamespace(
        as_text=lambda: "a tpu_custom_call b tpu_custom_call")
    step = types.SimpleNamespace(
        _cache={"k": types.SimpleNamespace(lower=lambda *a: lowered)},
        _last_ckey="k", _last_abstract=())
    lines = []
    program_mla_moe.check_step_program(
        {"step": step, "model": None, "cfg": object()}, lines.append)
    assert "2 Mosaic custom calls" in lines[0]


def test_train_moe_kind_end_to_end_line(moe_root, capsys):
    result, text = _run(moe_root, trace=False, seconds=3.0)
    assert result["correct"] is True and result["failed"] == 0, text
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert set(compared) == {
        "router_same_input_flip_share", "router_max_margin", "loss_abs_err",
        "logit_max_abs_err", "grad_rel_err_worst", "assignments_off_expected",
        "non_finite_losses", "loss_last3_over_first3", "compiles_in_window"}
    assert compared["router_max_margin"][1] == 0.012
    assert compared["grad_rel_err_worst"][1] == 0.2
    assert compared["assignments_off_expected"] == [0.0, 0.0]
    # ... and the same numbers are the run's last lines on stderr
    last = capsys.readouterr().err.strip().splitlines()[-len(compared):]
    assert [ln.split()[:2] for ln in last] == [["[compared]", k]
                                               for k in compared]
    assert last[-1] == "[compared] compiles_in_window 0 limit 0"
    assert result["attempted"] > 0
    assert "[reference] (a1) the program's router" in text
    assert "[reference] (a2)" in text and "[reference] (b)" in text
    assert ("[reference] (c) gradients of layer 2's 14 parameters and of its "
            "input") in text
    assert "steps x tokens x 4 x layers expected" in text
    assert "'compiles_in_window': 0" in text


def test_train_moe_kind_traced_line(moe_root):
    result, text = _run(moe_root, trace=True, seconds=4.0)
    assert result["correct"] is True, text
    cell = mf.load_cell(TINY, moe_root)
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert {"stall_share", "step_ms_p50", "mfu", "compile_s",
            "moe_load_max_over_mean", "moe_held_share"} <= set(
                result["metrics"])
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert result["metrics"]["moe_held_share"]["unit"] == "%"
    assert 0.0 < result["metrics"]["moe_held_share"]["value"] < 100.0
    # what only a device trace gives is left out, never made up
    for name in ("flash_mla_roofline", "moe_experts_roofline",
                 "moe_experts_time_share", "unnamed_time_share",
                 "device_idle_share.train"):
        assert name not in result["metrics"]


def test_the_gpt_cells_do_not_read_the_new_counters(moe_root):
    """conftest appends its GPT test cells to every metric that lists
    cells, the new ones too: their readers find nothing there."""
    cell = mf.load_cell("gpt-test.train", moe_root)
    obs = {"chunk_seconds": [0.1], "trace": None}
    for m in cell.per_layer:
        if m["reader"] in ("named_ops", "window_counters"):
            assert mf.load_reader(cell, m["reader"]).read(m, obs) is None
