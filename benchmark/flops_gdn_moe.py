"""Operations and bytes the `qwen3_next` block needs, computed from the
configuration file's shapes and from COUNTED expert assignments. Nothing
here times anything: these are the numerators of `mfu`,
`gdn_chunk_roofline`, `flash_gqa_roofline` and `moe_experts_roofline` in
the cells of that family. As in flops.py, recomputed work is not counted.
"""
from __future__ import annotations

from benchmark.flops_mla_moe import experts_train_cost  # noqa: F401


def _full_attention_layers(cfg: dict) -> int:
    every = cfg["full_attention_interval"]
    return sum((i + 1) % every == 0 for i in range(cfg["num_hidden_layers"]))


def param_counts(cfg: dict) -> dict:
    """Parameters by part, from the file's keys. `*_matrices` are what a
    matmul touches and a FLOP count uses; `gdn_layer` and `attention_layer`
    are a mixer whole (its matrices and its own vectors: the convolution's
    taps, A_log, dt_bias, the output norm; the q and k norms);
    `outside_mixer_and_routed` is the rest of a layer but its routed
    experts (router, shared expert, its gate, the layer's two norms);
    `held` is every parameter this chip holds."""
    h = cfg["hidden_size"]
    n, n_kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    kk = hk * cfg["linear_key_head_dim"]
    vv = hv * cfg["linear_value_head_dim"]
    gdn_matrices = h * (2 * kk + 2 * vv) + h * 2 * hv + vv * h
    gdn_layer = gdn_matrices + (2 * kk + vv) * cfg["linear_conv_kernel_dim"] \
        + 2 * hv + cfg["linear_value_head_dim"]
    attn_matrices = h * n * 2 * d + 2 * h * n_kv * d + n * d * h
    attn_layer = attn_matrices + 2 * d
    expert = 3 * h * cfg["moe_intermediate_size"]
    shared = 3 * h * cfg["shared_expert_intermediate_size"]
    router = h * cfg["router_outputs"]
    outside = router + shared + h + 2 * h
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    n_attn = _full_attention_layers(cfg)
    n_gdn = cfg["num_hidden_layers"] - n_attn
    head = h * cfg["vocab_size"]
    return {
        "gdn_layer": gdn_layer, "gdn_matrices": gdn_matrices,
        "attention_layer": attn_layer, "attention_matrices": attn_matrices,
        "expert": expert, "shared": shared, "router": router,
        "outside_mixer_and_routed": outside,
        "experts_held": held * expert,
        "gdn_layers": n_gdn, "attention_layers": n_attn,
        "embedding": head, "head": head,
        "held": n_gdn * gdn_layer + n_attn * attn_layer
        + cfg["num_hidden_layers"] * (outside + held * expert)
        + 2 * head + h}


def delta_rule_flops_per_token_layer(cfg: dict) -> float:
    """The RECURRENCE's count, forward: three products of dk x dv a token a
    value head (S^T k, k d^T, S^T q), 2 FLOPs a multiply-add. The chunked
    form makes more; this is the least, whatever implements the rule."""
    return 3 * 2.0 * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * cfg["linear_num_value_heads"]


def forward_flops_per_token(cfg: dict, seq: int,
                            held_assignments_per_token_layer: float) -> float:
    """2 x the matrix parameters a token touches here (the routed experts
    by how many of its assignments per layer went to experts HELD here; the
    shared expert's gate's h; the embedding is a lookup) + causal
    attention, 2 x (s/2) x 2d x heads an attention layer + the delta rule
    by the recurrence count a DeltaNet layer."""
    c = param_counts(cfg)
    h = cfg["hidden_size"]
    touched = c["gdn_layers"] * c["gdn_matrices"] \
        + c["attention_layers"] * c["attention_matrices"] \
        + cfg["num_hidden_layers"] * (
            c["router"] + c["shared"] + h
            + held_assignments_per_token_layer * c["expert"]) + c["head"]
    attention = c["attention_layers"] * 2.0 * (seq / 2.0) \
        * 2 * cfg["head_dim"] * cfg["num_attention_heads"]
    delta = c["gdn_layers"] * delta_rule_flops_per_token_layer(cfg)
    return 2.0 * touched + attention + delta


def train_flops_per_token(cfg: dict, seq: int,
                          held_assignments_per_token_layer: float) -> float:
    """Forward + backward: three times the forward."""
    return 3.0 * forward_flops_per_token(cfg, seq,
                                         held_assignments_per_token_layer)


def expected_held_assignments(cfg: dict) -> float:
    """Per token per layer under uniform routing: k x held / R."""
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    return cfg["num_experts_per_tok"] * held / cfg["router_outputs"]


def gdn_chunk_cost(batch: int, seq: int, cfg: dict, layers: int,
                   dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes the gated delta rule needs forward + backward,
    for `layers` DeltaNet layers. FLOPs: the recurrence's three products a
    token a value head, three times for forward and backward; it does not
    depend on the chunk size nor on what implements the rule, and being the
    least count it cannot read over 100 %. Bytes: q, k (at the key heads'
    count), v, o and their four gradients in the activations' dtype, g and
    beta and their gradients in float32, each moved once."""
    tokens = batch * seq
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    wide = 2 * (2 * hk * dk + 2 * hv * dv) * dtype_bytes
    narrow = 2 * 2 * hv * 4
    return {"flops": layers * 3.0 * tokens
            * delta_rule_flops_per_token_layer(cfg),
            "bytes": float(layers * tokens * (wide + narrow))}


def flash_gqa_train_cost(batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, layers: int,
                         dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes causal attention forward + backward needs at q
    [batch, seq, heads, d] over k, v [batch, seq, kv_heads, d], for
    `layers` layers. FLOPs as flops_mla_moe.flash_mla_train_cost at d / d.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o,
    dO and writes dq, dk, dv: six tensors at `heads` (q, o, q, o, dO, dq)
    and six at `kv_heads` (k, v, k, v, dk, dv), each moved once."""
    fwd = batch * heads * float(seq) * seq * 2 * head_dim
    row = batch * seq * head_dim * dtype_bytes
    return {"flops": layers * 3.0 * fwd,
            "bytes": float(layers * 6 * row * (heads + kv_heads))}
