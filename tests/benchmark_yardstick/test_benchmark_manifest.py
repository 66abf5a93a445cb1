"""BENCHMARK.json against the contract's rules that a test can check, and
against the benchmark's own data files."""
import json
import os
import re

import pytest

from benchmark import manifest as mf

REPO = mf.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")


def _load(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert len(json.dumps(manifest)) < 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    # the command names a file under `paths` and nothing else of the repo
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")


def test_every_file_under_paths_is_named_from_a_names_characters(manifest):
    for p in manifest["paths"]:
        for d, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        cfg = _load(c["file"])
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        # the widths the reference, the FLOP count and the program need
        for key in ("vocab_size", "hidden_size", "num_hidden_layers",
                    "num_attention_heads", "head_dim", "intermediate_size",
                    "max_position_embeddings", "dtype", "family"):
            assert key in cfg, (c["file"], key)
        assert cfg["hidden_size"] == \
            cfg["num_attention_heads"] * cfg["head_dim"]
        assert os.path.exists(os.path.join(
            REPO, manifest["paths"][0], "reference",
            cfg["family"] + "_ref.py"))


def test_workloads(manifest):
    ws = manifest["workloads"]
    assert 1 <= len(ws) <= 24
    assert len({w["name"] for w in ws}) == len(ws)
    assert len({(w["config"], w["traffic"]) for w in ws}) == len(ws)
    configs = {c["name"] for c in manifest["configs"]}
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] \
            and "\t" not in w["why"], len(w["why"])
        cell = mf.load_cell(w["name"])          # files exist and parse
        assert os.path.exists(os.path.join(
            cell.bench_dir, "kinds", cell.kind + ".py"))
    four = sum(w["chips"] == 4 for w in ws)
    assert four <= max(1, len(ws) // 4)


def test_end_to_end_metrics(manifest):
    e2e = manifest["end_to_end"]
    assert 1 <= len(e2e) <= 16
    assert "setup_s" in {e["name"] for e in e2e}
    for e in e2e:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    setup = next(e for e in e2e if e["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def _cells_of(metric, manifest):
    return set(metric.get("workloads")
               or [w["name"] for w in manifest["workloads"]])


def test_per_layer_metrics_and_what_they_move(manifest):
    pls = manifest["per_layer"]
    assert 1 <= len(pls) <= 128
    names = [m["name"] for m in manifest["end_to_end"] + pls]
    assert len(set(names)) == len(names)
    e2e = {e["name"]: e for e in manifest["end_to_end"]}
    all_cells = {w["name"] for w in manifest["workloads"]}
    for m in pls:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert _cells_of(m, manifest) <= all_cells
        # the metric it moves is reported in every cell this one is in
        assert m["moves"] in e2e, m
        assert _cells_of(m, manifest) <= _cells_of(e2e[m["moves"]],
                                                   manifest), m["name"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        spec = _load(os.path.join(manifest["paths"][0], "layer_metrics",
                                  m["name"] + ".json"))
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            REPO, manifest["paths"][0], "readers", spec["reader"] + ".py"))


@pytest.mark.parametrize("which", ["end_to_end", "per_layer"])
def test_every_cell_reports_enough(manifest, which):
    for w in manifest["workloads"]:
        got = [m["name"] for m in manifest[which]
               if w["name"] in _cells_of(m, manifest)]
        if which == "end_to_end":
            assert "setup_s" in got and len(got) >= 2, w["name"]
        else:
            assert got, w["name"]


def test_one_layer_name_per_layer(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    folded = {re.sub(r"\W+", "", x).lower() for x in layers}
    assert len(folded) == len(layers)    # no two spellings of one layer
