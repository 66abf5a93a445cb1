"""The plain float32 reference against paddle_tpu's own forward at gpt-test
widths, and the comparison that decides `correct`."""
import numpy as np
import pytest

from benchmark import program_gpt
from benchmark.reference import gpt2_ref


class _Cell:
    """A cell as the adapter needs it, at gpt-test widths."""
    config = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 4, "head_dim": 16,
              "intermediate_size": 256, "max_position_embeddings": 128,
              "layer_norm_epsilon": 1e-5, "dtype": "float32",
              "program": {"preset": "gpt-test"}}
    traffic = {"reference_sample": {"sequences": 2, "tokens": 48}}


@pytest.mark.parametrize("mode", ["loop", "scan"])
def test_reference_logits_are_the_programs(fresh_mesh, mode):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    cfg = program_gpt.gpt_config(_Cell, mode=mode)
    model = GPTForCausalLM(cfg, seed=3)
    model.eval()
    ids = np.random.RandomState(0).randint(0, 256, (2, 40))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids, dtype="int64")).numpy())
    top, get_block = program_gpt.reference_weights(model)
    want = np.asarray(gpt2_ref.logits(ids, top, get_block, 2, 4, 1e-5))
    assert want.shape == (2, 40, 256) and want.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_forward_loss_check_passes_and_catches_a_changed_model(fresh_mesh):
    from paddle_tpu.models import GPTForCausalLM

    cfg = program_gpt.gpt_config(_Cell)
    model = GPTForCausalLM(cfg, seed=5)
    log = []
    ok = program_gpt.check_forward_loss(_Cell, model, cfg, 2 ** 31 + 1,
                                        log.append)
    assert ok["ok"] and ok["abs_err"] < 1e-5 \
        and ok["max_abs_logit_err"] < 1e-4
    assert "float32 reference" in log[0]
    # the reference is independent of the program: weights the program
    # does not see (the reference gets another model's) fail the check
    other = GPTForCausalLM(cfg, seed=6)
    real = program_gpt.reference_weights
    try:
        program_gpt.reference_weights = lambda m: real(other)
        bad = program_gpt.check_forward_loss(_Cell, model, cfg,
                                             2 ** 31 + 1, log.append)
    finally:
        program_gpt.reference_weights = real
    assert not bad["ok"]
    assert bad["max_abs_logit_err"] > gpt2_ref.LOGIT_TOL_SIGMAS * bad["sigma"]


def test_config_file_must_agree_with_the_named_preset():
    class Wrong(_Cell):
        config = dict(_Cell.config, num_hidden_layers=3)

    with pytest.raises(ValueError, match="preset"):
        program_gpt.gpt_config(Wrong)
