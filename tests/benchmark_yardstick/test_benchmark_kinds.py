"""Each kind's driver called as a function at gpt-test widths for two
seconds, with the TPU check lifted by the test; the command itself on the
CPU; and a dummy configuration, traffic mix, kind and metric added from
files alone."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, manifest as mf

from conftest import PRETEND_TPU, REPO, TEST_CELLS, build_root

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(bench_root, key, trace, seconds=2.0, seed=2 ** 31 + 7):
    import time

    chips = TEST_CELLS[key][1]
    lines = []
    result = harness.run_cell(
        "gpt-test." + key, seed, seconds, trace, time.monotonic(),
        root=bench_root, device=dict(PRETEND_TPU, count=chips),
        log=lambda *a: lines.append(" ".join(map(str, a))))
    return result, lines


def _check_line(result, cell_metrics):
    json.loads(json.dumps(result))                  # one JSON object
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert DEVICE_KEYS <= set(result["device"])
    # every number that decided `correct` beside its limit, as the last key
    assert list(result)[-1] == "compared"
    assert {"loss_abs_err", "logit_max_abs_err", "non_finite_losses",
            "compiles_in_window"} <= set(result["compared"])
    for value, limit in result["compared"].values():
        assert isinstance(value, float) and isinstance(limit, float)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) <= set(cell_metrics)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)


@pytest.mark.parametrize("key", ["train", "train4"])
def test_train_kind_end_to_end_line(bench_root, fresh_mesh, key):
    result, lines = _run(bench_root, key, trace=False)
    cell = mf.load_cell("gpt-test." + key, bench_root)
    _check_line(result, [e["name"] for e in cell.end_to_end])
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert result["metrics"]["train_tok_s_chip"]["unit"] == "tokens/s/chip"
    assert result["device"]["count"] == TEST_CELLS[key][1]
    text = "\n".join(lines)
    assert "float32 reference" in text and "[train] losses" in text
    assert "'compiles_in_window': 0" in text


def test_train_kind_traced_line(bench_root, fresh_mesh):
    result, lines = _run(bench_root, "train", trace=True, seconds=3.0)
    cell = mf.load_cell("gpt-test.train", bench_root)
    _check_line(result, [m["name"] for m in cell.per_layer])
    # host-clock and counter metrics are there; what only a device trace
    # gives is left out on a machine without the device, never made up
    assert {"stall_share", "step_ms_p50", "mfu", "compile_s",
            "compiles_in_window"} <= set(result["metrics"])
    for name in ("mosaic_time_share", "flash_roofline",
                 "device_idle_share.train", "hbm_peak_gb.train"):
        assert name not in result["metrics"]
    assert "busy_s" not in result["device"]
    assert any("chunks traced" in ln for ln in lines)


def test_train_run_is_seeded(bench_root, fresh_mesh):
    def losses(seed):
        _, lines = _run(bench_root, "train", trace=False, seconds=2.0,
                        seed=seed)
        ln = next(x for x in lines if "[train] losses" in x)
        return json.loads(ln[ln.index("["):].split("]", 1)[1].split(":", 1)[1]
                          .strip())[:3]

    a, b, c = losses(5), losses(5), losses(6)
    assert a == pytest.approx(b, abs=1e-4) and a != pytest.approx(c, abs=1e-4)


def test_too_short_a_window_fails_instead_of_reporting(bench_root,
                                                       fresh_mesh):
    with pytest.raises(SystemExit, match="kept chunks"):
        _run(bench_root, "train", trace=False, seconds=0.01)


def test_the_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt-125m.train-b12-s1024", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    for line in p.stdout.splitlines():          # no result line
        assert not line.lstrip().startswith("{"), line


def test_the_command_needs_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files there is nothing to measure: non-zero exit, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt-125m.train-b12-s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "paddle_tpu" in p.stderr
    assert "{" not in p.stdout


DUMMY_KIND = '''
import time
from benchmark.harness import Record


def run(cell, opts):
    t = time.monotonic()
    return Record(attempted=3, failed=0,
                  end_to_end={"dummy_rate": cell.traffic["rate"]
                              * cell.config["hidden_size"]},
                  t_window_start=t, t_window_end=time.monotonic(),
                  obs={"ticks": [1, 2, 3]})
'''
DUMMY_READER = '''
def read(metric, obs):
    ticks = obs.get("ticks")
    return float(sum(ticks)) if ticks and metric["field"] == "sum" else None
'''


def test_a_new_config_traffic_kind_and_metric_need_only_new_files(tmp_path):
    """What a later PR does: add files and entries, edit nothing."""
    import time

    root = build_root(str(tmp_path / "root"))
    bdir = os.path.join(root, "benchmark")
    before = {}
    for d, _, files in os.walk(bdir):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    def put(rel, text):
        with open(os.path.join(bdir, rel), "w") as f:
            f.write(text)

    put("configs/dummy-model.json", json.dumps(
        {"name": "dummy-model", "hidden_size": 7, "reduced": []}))
    put("traffic/dummy-mix.json", json.dumps({"kind": "dummy", "rate": 6}))
    put("kinds/dummy.py", DUMMY_KIND)
    put("readers/dummy_ticks.py", DUMMY_READER)
    put("layer_metrics/dummy_ticks.json", json.dumps(
        {"name": "dummy_ticks", "layer": "Dummy layer", "unit": "count",
         "moves": "dummy_rate", "reader": "dummy_ticks",
         "field": "sum"}))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "dummy-model", "source": "none",
                             "file": "benchmark/configs/dummy-model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-model.dummy-mix",
                               "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({
        "name": "dummy_rate", "unit": "x/s", "better": "higher",
        "bound": 0.01, "source": "host_clock",
        "workloads": ["dummy-model.dummy-mix"]})
    bench["per_layer"].append({
        "name": "dummy_ticks", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Dummy layer",
        "moves": "dummy_rate", "workloads": ["dummy-model.dummy-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    dev = dict(PRETEND_TPU, count=1)
    r0 = harness.run_cell("dummy-model.dummy-mix", 1, 1.0, False,
                          time.monotonic(), root=root, device=dev,
                          log=lambda *a: None)
    assert r0["metrics"]["dummy_rate"] == {"value": 42.0, "unit": "x/s"}
    assert set(r0["metrics"]) == {"dummy_rate", "setup_s"}
    r1 = harness.run_cell("dummy-model.dummy-mix", 1, 1.0, True,
                          time.monotonic(), root=root, device=dev,
                          log=lambda *a: None)
    # its own metric, and those that have no `workloads` key (compile)
    assert r1["metrics"]["dummy_ticks"] == {"value": 6.0, "unit": "count"}
    assert "compiles_in_window" in r1["metrics"]
    assert "step_ms_p50" not in r1["metrics"]
    # nothing that was there was edited
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path


def test_an_unknown_workload_names_the_known_ones():
    with pytest.raises(KeyError, match="gpt-125m.train-b12-s1024"):
        mf.load_cell("no-such-cell")
