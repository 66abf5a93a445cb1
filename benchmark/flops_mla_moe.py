"""Operations and bytes the `deepseek_v3` block needs, computed from the
configuration file's shapes and from COUNTED expert assignments. Nothing
here times anything: these are the numerators of `mfu`,
`flash_mla_roofline` and `moe_experts_roofline` in the cells of that
family. As in flops.py, recomputed work is not counted (whole-layer
recomputation's second forward is time, not needed work).
"""
from __future__ import annotations


def param_counts(cfg: dict) -> dict:
    """Matrix parameters by part, from the file's keys (norm vectors, a
    few thousand each, are left out: no matmul touches them)."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    attention = h * n * qk + h * (rank + cfg["qk_rope_head_dim"]) \
        + rank * n * (cfg["qk_nope_head_dim"] + v) + n * v * h
    expert = 3 * h * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    router = h * cfg["router_outputs"]
    dense_layer = attention + 3 * h * cfg["intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    head = h * cfg["vocab_size"]
    return {
        "attention": attention, "expert": expert, "shared": shared,
        "router": router, "outside_routed": attention + shared + router,
        "dense_layer": dense_layer, "expert_layer_held": attention + shared
        + router + held * expert, "embedding": head, "head": head,
        "dense_layers": n_dense, "expert_layers": n_moe,
        "held": n_dense * dense_layer + n_moe * (
            attention + shared + router + held * expert) + 2 * head}


def forward_flops_per_token(cfg: dict, seq: int,
                            held_assignments_per_token_layer: float) -> float:
    """2 x the matrix parameters a token touches here (the routed experts
    by how many of its assignments per expert layer went to experts HELD
    here; the embedding is a lookup) + causal attention, 2 x (s/2) x
    (qk + v) x heads a layer."""
    c = param_counts(cfg)
    touched = c["dense_layers"] * c["dense_layer"] + c["expert_layers"] * (
        c["outside_routed"] + held_assignments_per_token_layer * c["expert"]
    ) + c["head"]
    widths = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    attention = cfg["num_hidden_layers"] * 2.0 * (seq / 2.0) * widths \
        * cfg["num_attention_heads"]
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


def flash_mla_train_cost(batch: int, seq: int, heads: int, d_qk: int,
                         d_v: int, layers: int, dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes causal attention forward + backward needs at
    q, k [batch, seq, heads, d_qk] and v [.., d_v], for `layers` layers.
    FLOPs: forward QK^T 2 s^2 d_qk and PV 2 s^2 d_v per head, halved by the
    mask; backward dV, dP (d_v) and dQ, dK (d_qk): twice the forward. The
    backward kernels' recomputation of QK^T is not counted. Bytes: forward
    reads q, k, v and writes o; backward reads q, k, v, o, dO and writes
    dq, dk, dv: six tensors d_qk wide and six d_v wide, each moved once."""
    fwd = batch * heads * float(seq) * seq * (d_qk + d_v)
    rows = batch * seq * heads * dtype_bytes
    return {"flops": layers * 3.0 * fwd,
            "bytes": float(layers * 6 * rows * (d_qk + d_v))}


def experts_train_cost(rows: float, experts: int, hidden: int, width: int,
                       dtype_bytes: int = 2) -> dict:
    """The held experts' nine products of ONE expert layer, three forward
    (gate, up, down) and six backward (a data and a weight gradient each),
    over `rows` assignment rows in all: FLOPs 9 x 2 x rows x hidden x
    width; bytes: each product reads its row operand, the layer's [experts,
    hidden, width] weights (or writes their gradient) and writes its
    result, once."""
    weights = experts * hidden * width * dtype_bytes
    acts = rows * (hidden + width) * dtype_bytes
    return {"flops": 9 * 2.0 * rows * hidden * width,
            "bytes": float(9 * (weights + acts))}
