"""Operations and bytes the `lfm2_moe` block needs, computed from the
configuration file's shapes and from COUNTED expert assignments. Nothing
here times anything: these are the numerators of `mfu`,
`short_conv_roofline`, `flash_gqa_roofline.lfm2_moe` and
`moe_experts_roofline` in the cells of that family. As in flops.py,
recomputed work is not counted.

The interface kinds/train_moe_family.py asks of a family's module:
`train_flops_per_token`, `experts_train_cost`, `kernel_costs`.
"""
from __future__ import annotations

from benchmark.flops_gdn_moe import flash_gqa_train_cost  # noqa: F401
from benchmark.flops_mla_moe import experts_train_cost  # noqa: F401


def param_counts(cfg: dict) -> dict:
    """Parameters by part, from the file's keys. `*_matrices` are what a
    matmul touches and a FLOP count uses; `conv_layer` and
    `attention_layer` are a mixer whole (its matrices and its own vectors:
    the taps; the q and k norms); `held` is every parameter this chip
    holds, the tied table once."""
    h = cfg["hidden_size"]
    n, n_kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    conv_matrices = h * 3 * h + h * h
    conv_layer = conv_matrices + h * cfg["conv_L_cache"]
    attn_matrices = h * n * d + 2 * h * n_kv * d + n * d * h
    attn_layer = attn_matrices + 2 * d
    dense = 3 * h * cfg["intermediate_size"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    router = h * cfg["router_outputs"]
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    kinds = cfg["layer_types"]
    n_attn = sum(k == "full_attention" for k in kinds)
    n_conv = len(kinds) - n_attn
    n_dense = cfg["num_dense_layers"]
    n_moe = len(kinds) - n_dense
    table = h * cfg["vocab_size"]
    return {
        "conv_layer": conv_layer, "conv_matrices": conv_matrices,
        "attention_layer": attn_layer, "attention_matrices": attn_matrices,
        "dense": dense, "expert": expert, "router": router,
        "experts_held": held * expert,
        "conv_layers": n_conv, "attention_layers": n_attn,
        "dense_layers": n_dense, "expert_layers": n_moe, "table": table,
        "held": n_conv * conv_layer + n_attn * attn_layer
        + len(kinds) * 2 * h + n_dense * dense
        + n_moe * (router + held * expert) + table + h}


def forward_flops_per_token(cfg: dict, seq: int,
                            held_assignments_per_token_layer: float) -> float:
    """2 x the matrix parameters a token touches here (the routed experts
    by how many of its assignments per expert layer went to experts HELD
    here; the table once, as the head: the embedding is a lookup) + causal
    attention, 2 x (s/2) x 2d x heads an attention layer."""
    c = param_counts(cfg)
    touched = c["conv_layers"] * c["conv_matrices"] \
        + c["attention_layers"] * c["attention_matrices"] \
        + c["dense_layers"] * c["dense"] + c["expert_layers"] * (
            c["router"] + held_assignments_per_token_layer * c["expert"]) \
        + c["table"]
    attention = c["attention_layers"] * 2.0 * (seq / 2.0) \
        * 2 * cfg["head_dim"] * cfg["num_attention_heads"]
    return 2.0 * touched + attention


def train_flops_per_token(cfg: dict, seq: int,
                          held_assignments_per_token_layer: float) -> float:
    """Forward + backward: three times the forward."""
    return 3.0 * forward_flops_per_token(cfg, seq,
                                         held_assignments_per_token_layer)


def expected_held_assignments(cfg: dict) -> float:
    """Per token per expert layer under uniform routing: k x held / R."""
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    return cfg["num_experts_per_tok"] * held / cfg["router_outputs"]


def short_conv_cost(batch: int, seq: int, cfg: dict, layers: int,
                    dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes the short-convolution mixer needs forward +
    backward, WHOLE (its two matmuls included, so that no fusion across
    the gates can make a share of it read over 100 %), for `layers` conv
    layers. FLOPs: W_in [h, 3h] and W_out [h, h], 2 a multiply-add, three
    times for forward and backward (the gates and the taps, 2L + 2 a
    channel a token, are a thousandth of that and left out). Bytes: x,
    [B | C | u], y = C * c and the result, with their gradients, each
    moved once; the weights (the taps too) and their gradients once.
    Independent of what implements the taps."""
    h, tokens = cfg["hidden_size"], batch * seq
    acts = 2 * tokens * (h + 3 * h + h + h) * dtype_bytes
    weights = 2 * (4 * h * h + h * cfg["conv_L_cache"]) * dtype_bytes
    return {"flops": layers * 3 * 2.0 * tokens * 4 * h * h,
            "bytes": float(layers * (acts + weights))}


def kernel_costs(cfg: dict, batch: int, seq: int) -> dict:
    """Per step, under the `obs` keys the cell's roofline metrics name
    (readers/named_ops.py)."""
    c = param_counts(cfg)
    return {
        "flash_gqa_cost": flash_gqa_train_cost(
            batch, seq, cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            c["attention_layers"]),
        "short_conv_cost": short_conv_cost(batch, seq, cfg,
                                           c["conv_layers"])}
