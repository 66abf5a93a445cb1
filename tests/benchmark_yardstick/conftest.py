"""Shared by the yardstick's tests: a temporary benchmark root that holds a
copy of benchmark/ plus cells at gpt-test widths, built from data files
alone (which is also the proof that a cell needs nothing but files)."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the device the kinds are told they run on: the TPU check is lifted by the
# test, never by an option of the benchmark (no CPU number leaves a test)
PRETEND_TPU = {"platform": "tpu", "kind": "TPU v5 lite"}

TEST_CELLS = {"train": ("t-train", 1), "train4": ("t-train4", 4)}


def build_root(root: str) -> str:
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bdir = os.path.join(root, "benchmark")

    def put(rel, obj):
        with open(os.path.join(bdir, rel), "w") as f:
            json.dump(obj, f)

    def get(rel):
        with open(os.path.join(bdir, rel)) as f:
            return json.load(f)

    put("configs/gpt-test.json", {
        "name": "gpt-test", "family": "gpt2", "vocab_size": 8192,
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "head_dim": 16, "intermediate_size": 256,
        "max_position_embeddings": 128, "layer_norm_epsilon": 1e-5,
        "dtype": "float32", "reduced": []})
    t = get("traffic/train-b12-s1024.json")
    # one-step chunks and a floor of two kept: under the suite's six loaded
    # workers a step here can take a fifth of a second
    t.update(global_batch=4, seq=64, chunk_steps=1, drop_chunks=1,
             min_kept_chunks=2, trace_chunks=2,
             reference_sample={"sequences": 2, "tokens": 32})
    put("traffic/t-train.json", t)
    put("traffic/t-train4.json",
        dict(t, mesh={"sharding": 2, "model": 2}, zero_level="os_g"))
    bench["configs"].append({
        "name": "gpt-test", "source": "paddle_tpu gpt_presets",
        "file": "benchmark/configs/gpt-test.json", "reduced": [],
        "why": "CPU tests"})
    for key, (traffic, chips) in TEST_CELLS.items():
        bench["workloads"].append({
            "name": "gpt-test." + key, "config": "gpt-test",
            "traffic": traffic, "chips": chips, "why": "CPU tests"})
    train_cells = ["gpt-test." + k for k in TEST_CELLS]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + train_cells
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    return build_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)
