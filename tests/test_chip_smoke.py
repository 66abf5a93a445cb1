"""chip_smoke.py's phase functions, driven here on the CPU at gpt-test size
(the script itself refuses to run without a TPU — that refusal is tested
too), and the compile-cache placement rule the entry points share."""
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from paddle_tpu.jit import artifact_cache  # noqa: E402

SIZE = dict(preset="gpt-test", batch=8, seq=64, dtype="float32")


@pytest.fixture(scope="module")
def one_chip():
    return chip_smoke.train_phase("cpu", steps=6, **SIZE)


def test_train_phase_at_test_size(one_chip):
    out = one_chip
    assert len(out["losses"]) == 6 and out["losses"][-1] < out["losses"][0]
    assert out["state_arrays_on_device"] > 0
    # interpret mode on the CPU: no Mosaic call, and no complaint about it
    assert out["mosaic_custom_calls"] == 0
    # operands are committed before the first call, so only it compiles
    assert out["call_s"][1] < 0.5 * out["call_s"][0]


def test_placement_is_read_off_the_arrays():
    with pytest.raises(AssertionError, match="expected platform 'tpu'"):
        chip_smoke._on_platform([jax.numpy.ones(3)], "tpu")


def test_four_chip_phase_on_the_virtual_mesh(one_chip):
    out = chip_smoke.four_chip_phase("cpu", one_chip, steps=2, **SIZE)
    assert out["devices_per_param"] == 4
    assert out["params_partitioned"] and out["slots_partitioned"]
    assert out["losses"][0] == pytest.approx(one_chip["losses"][0],
                                             rel=1e-5)


def test_serve_phase_at_test_size():
    out = chip_smoke.serve_phase(
        "cpu", preset="gpt-test", seq=128, dtype="float32",
        prompt_lens=(8, 20, 40, 64), new_tokens=(4, 6, 8, 5), n_blocks=64)
    assert out["requests"] == 8
    assert out["tokens_generated"] == 2 * (4 + 6 + 8 + 5)
    assert out["kv_blocks_in_use_after"] == 0
    assert out["max_abs_logit_diff_vs_train_forward"] < 1e-4   # fp32 here


def test_main_refuses_any_platform_but_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""       # no result line


@pytest.fixture()
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_last_stdout_line_is_the_verdict_and_nothing_else(
        monkeypatch, capsys, config_updates):
    """The driver parses the last line strictly: the keys "ok" and "device",
    the device's "platform", "kind" and "count". Details go on the line
    before. main() on a pretended one-chip TPU, with the phases stubbed."""
    import json

    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "find_device", lambda: dict(tpu))
    monkeypatch.setattr(chip_smoke, "train_phase",
                        lambda platform: {"losses": [2.0, 1.0]})
    monkeypatch.setattr(chip_smoke, "serve_phase",
                        lambda platform: {"tokens_generated": 184})
    chip_smoke.main()
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": tpu}
    assert lines[-2].startswith("[result] ")
    detail = json.loads(lines[-2][len("[result] "):])
    assert detail["phases"]["train"]["losses"] == [2.0, 1.0]
    assert detail["phases"]["four_chips"] == "skipped: 1 chips"
    assert "[four_chips] skipped: 1 chips" in lines
    # compile seconds by phase, read off the program's own counters
    assert set(detail["phases"]["train"]["compile_s"]) == {
        "trace", "lower", "backend", "cache_load"}


def test_compile_seconds_are_the_programs_own_counters():
    """chip_smoke keeps no jax.monitoring listener of its own: what it
    reports between two marks is `jit_compile_seconds_total`'s growth."""
    mark = chip_smoke.compile_seconds()
    jax.jit(lambda x: x * 59 + 1)(jax.numpy.ones(59))
    now = chip_smoke.compile_seconds()
    assert now["trace"] > mark["trace"] and now["backend"] > mark["backend"]
    assert now["cache_load"] == mark["cache_load"]


def test_compile_cache_placed_from_outside(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert artifact_cache.use_compile_cache() == "/some/dir"
    assert config_updates == []     # jax honours the variable by itself


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert artifact_cache.use_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]
