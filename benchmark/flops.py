"""Operations and bytes the algorithms need, computed from shapes.

Nothing here times anything: these are the numerators of `mfu` and
`flash_roofline_share`. Recomputed work (remat, the flash backward's second
pass over QK^T) is NOT counted: a utilization counts what the mathematics
requires, not what an implementation chose to repeat.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip. An unknown kind is an error, never a
    default: a utilization against a guessed peak is worse than none."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {_PEAKS}")
    return table[device_kind]


def gpt_param_count(cfg: dict) -> int:
    """Every parameter of a GPT-2 shaped model once (the LM head is tied to
    the token embedding): embeddings, per block 4 LayerNorm vectors, QKV,
    attention output, two MLP matrices with biases, the final LayerNorm."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    f = cfg.get("intermediate_size") or 4 * h
    block = (2 * h) + (3 * h * h + 3 * h) + (h * h + h) + (2 * h) \
        + (h * f + f) + (f * h + h)
    return v * h + cfg["max_position_embeddings"] * h \
        + cfg["num_hidden_layers"] * block + 2 * h


def gpt_train_flops_per_token(cfg: dict, seq: int) -> float:
    """6 N for the matrix multiplications (forward 2N, backward 4N) plus
    6 L s h for causal attention (QK^T and PV: 4 s h per token per layer
    forward when every key is attended, half of it under the causal mask,
    times three for forward + backward). The formula of bench.py
    measure_gpt, copied."""
    n = gpt_param_count(cfg)
    return 6.0 * n + 6.0 * cfg["num_hidden_layers"] * seq * cfg["hidden_size"]


def flash_causal_train_cost(batch: int, seq: int, heads: int, head_dim: int,
                            layers: int, dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes that causal attention forward + backward needs
    for `layers` layers at [batch, seq, heads, head_dim].

    FLOPs: forward QK^T and PV are 2 * 2*s*s*d per head, halved by the
    causal mask; backward has dV, dP, dQ, dK: twice the forward. The
    recomputation of QK^T inside the backward kernels is not counted.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv: 12 tensors of b*s*n*d elements, each moved
    once (the log-sum-exp rows are 1/d of a tensor and left out).
    """
    per_head_fwd = 2 * (2.0 * seq * seq * head_dim) / 2.0
    flops = layers * batch * heads * per_head_fwd * 3.0
    tensor = batch * seq * heads * head_dim * dtype_bytes
    return {"flops": flops, "bytes": float(layers * 12 * tensor)}


def roofline_seconds(cost: dict, peak: dict) -> tuple:
    """(least seconds the chip could take, which bound applies)."""
    t_c = cost["flops"] / peak["bf16_flops_per_s"]
    t_m = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
