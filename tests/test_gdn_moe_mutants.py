"""Mutants of the qwen3_next shaped block: each changes one thing the
reference does otherwise, and the benchmark's comparison
(benchmark/program_gdn_moe.py compare_with_reference) has to refuse it. A
file of its own beside tests/test_gdn_moe.py, whose helpers it uses: each
case builds the model and compiles the comparison anew."""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.distributed import moe
from paddle_tpu.models import gdn_moe
from paddle_tpu.ops import gated_delta_rule as rule

from test_gdn_moe import _cfg, _compare, _ids, _model

# the reference comparisons' programs, alike from one case to the next,
# compile once a run (tests/conftest.py)
pytestmark = pytest.mark.shared_compile_cache


def _softmax_mutant(dtype=jnp.float32, renormalise=True, sigmoid=False):
    def route(x2, router_w, top_k):
        logits = x2.astype(dtype) @ router_w.astype(dtype)
        p = (jax.nn.sigmoid(logits) if sigmoid
             else jax.nn.softmax(logits, -1)).astype(jnp.float32)
        picked, chosen = jax.lax.top_k(p, top_k)
        w = picked / picked.sum(-1, keepdims=True) if renormalise else picked
        return chosen.astype(jnp.int32), w
    return route


def _rotary_adjacent_pairs(x, theta, factor):
    from paddle_tpu.models.mla_moe import rotary_interleaved

    r = int(x.shape[-1] * factor)
    return jnp.concatenate([rotary_interleaved(x[..., :r], theta),
                            x[..., r:]], -1)


def _inputs_mutant(**change):
    inputs = gdn_moe.delta_rule_inputs

    def mutant(x, p, cfg):
        t = dict(zip("q k v g beta z".split(), inputs(x, p, cfg)))
        for name, fn in change.items():
            t[name] = fn(t[name])
        return tuple(t.values())
    return mutant


_ZERO_CENTRED = gdn_moe.rms_norm_zero_centred
_CHUNK_STEP = rule._chunk_step


def _state_through_bf16(state, xs, **kw):
    """The scan's step with the state it hands on rounded to bfloat16."""
    state, o = _CHUNK_STEP(state, xs, **kw)
    return state.astype(jnp.bfloat16).astype(jnp.float32), o


MUTANTS = {
    "weights_not_renormalised": (moe, "softmax_topk_route",
                                 _softmax_mutant(renormalise=False)),
    "sigmoid_for_softmax": (moe, "softmax_topk_route",
                            _softmax_mutant(sigmoid=True)),
    "router_in_bf16": (moe, "softmax_topk_route",
                       _softmax_mutant(dtype=jnp.bfloat16)),
    "rotary_on_adjacent_pairs": (gdn_moe, "rotary_half_split",
                                 _rotary_adjacent_pairs),
    "norm_not_zero_centred": (
        gdn_moe, "rms_norm_zero_centred",
        lambda x, w, eps: _ZERO_CENTRED(x, w - 1.0, eps)),
    "beta_fixed_at_1": (gdn_moe, "delta_rule_inputs",
                        _inputs_mutant(beta=jnp.ones_like)),
    "no_decay": (gdn_moe, "delta_rule_inputs",
                 _inputs_mutant(g=jnp.zeros_like)),
    "q_not_scaled": (gdn_moe, "delta_rule_inputs",
                     _inputs_mutant(q=lambda q: q * 4.0)),
    "convolution_left_out": (gdn_moe, "causal_conv_silu",
                             lambda x, w: jax.nn.silu(x)),
    "state_in_bf16": (rule, "_chunk_step", _state_through_bf16),
}


def _mutant_case():
    cfg = _cfg(router_outputs=64, num_experts=64, num_experts_per_tok=6)
    x, y = _ids(cfg, b=1, s=96)
    return cfg, _model(cfg), x, y


def test_the_unmutated_router_copy_passes():
    """The mutants' own copy of the router, unmutated, is accepted: what
    refuses a mutant is its one change."""
    cfg, model, x, y = _mutant_case()
    orig = moe.softmax_topk_route
    moe.softmax_topk_route = _softmax_mutant()
    try:
        out, lines = _compare(model, cfg, x, y)
    finally:
        moe.softmax_topk_route = orig
    assert out["ok"], lines


@pytest.mark.parametrize("name", sorted(MUTANTS))
def test_the_comparison_refuses_a_mutant(name, monkeypatch):
    cfg, model, x, y = _mutant_case()
    module, attr, mutant = MUTANTS[name]
    monkeypatch.setattr(module, attr, mutant)
    out, lines = _compare(model, cfg, x, y)
    assert not out["ok"], lines
    if name == "state_in_bf16":                   # (d) is what names it
        assert max(out["delta_rule_rel_err"].values()) > 5e-4
