"""The gated delta rule's two Pallas kernels (ops/gated_delta_rule.py
`kernels_over_chunks`: gdn_chunk_fwd, gdn_chunk_bwd) on the CPU, in
interpret mode, against the scan over chunks they take the place of on a
TPU: output and every gradient; which of the two a call runs; and a state
rounded to bfloat16 on the KERNELS' path, which a rule-alone comparison in
float32 has to see (the benchmark's control plants it there on the chip)."""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import gated_delta_rule as rule

from test_gdn_moe import _recurrence, _rule_inputs

CHUNK = rule.GDN_CHUNK


def _inputs(s, decay, hk, hv, values=jnp.float32, d=128):
    """tests/test_gdn_moe.py's inputs of the rule and a cotangent of o, at
    heads `d` wide, v in `values`."""
    q, k, v, g, beta, cot = _rule_inputs(s, decay, hk=hk, hv=hv, dk=d, dv=d)
    return q, k, v.astype(values), g, beta, cot


def _value_and_grads(over_chunks, args, cot):
    def weighed(*a):
        o = rule._chunked(*a, CHUNK, over_chunks)
        return jnp.sum(o.astype(jnp.float32) * cot), o

    with jax.default_matmul_precision("highest"):
        (_, o), grads = jax.value_and_grad(
            weighed, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return o.astype(jnp.float32), [t.astype(jnp.float32) for t in grads]


# (tokens, decay scale, key heads, value heads, v's dtype, heads a grid
# step at most): RULE_CASES' scales of tests/test_gdn_moe.py at 128-wide
# heads, one and two value heads a key head, and four heads over two grid
# steps
KERNEL_CASES = {
    "several_chunks_two_value_heads_a_key_head":
        (192, 1.0, 1, 2, jnp.float32, 8),
    "one_value_head_a_key_head_bf16_values":
        (128, 1.0, 2, 2, jnp.bfloat16, 8),
    "two_value_heads_a_key_head_bf16_values":
        (130, 0.2, 1, 2, jnp.bfloat16, 8),
    "no_multiple_of_the_chunk": (150, 1.0, 2, 2, jnp.float32, 8),
    "shorter_than_a_chunk": (20, 0.5, 1, 1, jnp.float32, 8),
    "decays_near_one": (128, 0.001, 1, 2, jnp.float32, 8),
    "decays_near_zero": (128, 30.0, 1, 2, jnp.float32, 8),
    "four_heads_over_two_grid_steps": (128, 1.0, 2, 4, jnp.float32, 2),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernels_equal_the_scan_forward_and_gradients(case, monkeypatch):
    s, decay, hk, hv, values, heads = KERNEL_CASES[case]
    monkeypatch.setattr(rule, "_HEADS_A_STEP", heads)
    *args, cot = _inputs(s, decay, hk, hv, values)
    got_o, got = _value_and_grads(rule.kernels_over_chunks, args, cot)
    want_o, want = _value_and_grads(rule.scan_over_chunks, args, cot)
    assert got_o.shape == (1, s, hv, 128)
    # what tests/test_gdn_moe.py holds the scan to against the recurrence;
    # where o and dv are bfloat16, one step of theirs
    ulp = 2.0 ** -8 if values == jnp.bfloat16 else 0.0
    o_scale = float(jnp.max(jnp.abs(want_o)))
    assert float(jnp.max(jnp.abs(got_o - want_o))) <= 2e-6 + ulp * o_scale
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 1e-6, name           # every input has a gradient
        limit = (ulp if name == "v" else 0.0) + 2e-5
        assert float(jnp.max(jnp.abs(a - b))) <= limit * scale, (case, name)


def _dispatched(source):
    from paddle_tpu.observability import get_registry

    return get_registry().snapshot().get("kernel_dispatch_total", {}).get(
        f"kernel=gated_delta_rule,source={source}", 0)


def test_which_path_runs_follows_the_platform_and_the_shapes():
    assert rule.gdn_kernel_supported((1, 8192, 16, 128), (1, 8192, 32, 128),
                                     CHUNK)
    assert rule.gdn_kernel_supported((1, 64, 2, 256), (1, 64, 2, 128), CHUNK)
    # 8-wide heads (every other test of the rule), another chunk, a state
    # past the VMEM plan, a key head with more value heads than a grid
    # step has, no [b, s, H, d]
    assert not rule.gdn_kernel_supported((1, 64, 2, 8), (1, 64, 4, 8), CHUNK)
    assert not rule.gdn_kernel_supported((1, 64, 2, 128), (1, 64, 4, 8),
                                         CHUNK)
    assert not rule.gdn_kernel_supported((1, 64, 2, 128), (1, 64, 2, 128), 16)
    assert not rule.gdn_kernel_supported((1, 64, 2, 512), (1, 64, 2, 512),
                                         CHUNK)
    assert not rule.gdn_kernel_supported((1, 64, 1, 128), (1, 64, 16, 128),
                                         CHUNK)
    assert not rule.gdn_kernel_supported((64, 2, 128), (64, 2, 128), CHUNK)

    def jaxpr(*args):
        # a function of its own each time: a trace is cached by function
        # and shapes, whatever platform it was made for
        return str(jax.make_jaxpr(
            lambda *a: rule.gated_delta_rule_chunked(*a))(*args))

    # on the CPU the scan runs whatever the shapes, and the counter says so
    for width in (8, 128):
        *args, _ = _inputs(64, 1.0, 1, 2, d=width)
        scans, kernels = _dispatched("scan"), _dispatched("kernel")
        text = jaxpr(*args)
        assert "scan" in text and "pallas_call" not in text
        assert (_dispatched("scan"), _dispatched("kernel")) == (
            scans + 1, kernels)
    # compiled for a TPU, the shapes decide
    from paddle_tpu.framework.target import force_target

    with force_target("tpu"):
        kernels = _dispatched("kernel")
        text = jaxpr(*args)
        assert "gdn_chunk_fwd" in text and "scan" not in text
        assert _dispatched("kernel") == kernels + 1
        *narrow, _ = _inputs(64, 1.0, 1, 2, d=8)
        scans = _dispatched("scan")
        assert "pallas_call" not in jaxpr(*narrow)
        assert _dispatched("scan") == scans + 1


def _rel(got, want):
    """benchmark/program_gdn_moe.py's measure of comparison (d)."""
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


@pytest.mark.parametrize("state", ["float32", "through_bfloat16"])
def test_a_bf16_state_on_the_kernels_path_is_seen_by_the_rule_alone(
        state, monkeypatch):
    """Comparison (d) at a small length: the kernels against the
    reference's token-by-token recurrence in float32, under (d)'s limit as
    they stand and past it with the state they hand on rounded to
    bfloat16 (slow decays: a head that remembers)."""
    if state == "through_bfloat16":
        next_state = rule._next_state
        monkeypatch.setattr(
            rule, "_next_state", lambda *a: next_state(*a).astype(
                jnp.bfloat16).astype(jnp.float32))
    *args, cot = _inputs(192, 0.05, 1, 2)
    got_o, got = _value_and_grads(rule.kernels_over_chunks, args, cot)

    with jax.default_matmul_precision("highest"):
        (_, want_o), want = jax.value_and_grad(
            lambda *a: _recurrence(*a, cot), argnums=(0, 1, 2, 3, 4),
            has_aux=True)(*args)
    worst = max([_rel(got_o[0], want_o)]
                + [_rel(a, b) for a, b in zip(got, want)])
    limit = 4e-5                # benchmark/program_gdn_moe.py, part (d)
    assert (worst < limit / 4) if state == "float32" else (worst > 4 * limit)
