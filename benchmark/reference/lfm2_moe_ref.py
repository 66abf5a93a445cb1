"""Plain float32 reference of the LFM2 decoder as LFM2-8B-A1B's config.json
states it (`model_type: lfm2_moe`): double-gated short-convolution layers
beside grouped-head attention with a norm on q and k, bias-selected
sigmoid-routed experts with no shared expert, a tied head. Straight
`jax.numpy`, float32, `default_matmul_precision("highest")`, no kernel, no
sort, no grouped product, no cache. It imports nothing of paddle_tpu.

    x += Mixer_i(N_op(x)); x += FFN_i(N_ffn(x)); logits = N_emb(x) E^T
    N(x) = w * x / sqrt(mean(x^2) + norm_eps)        (the plain RMS norm)
    layer i is what `layer_types[i]` says: "conv" or "full_attention";
    E is the token table: the embedding and the head are one matrix.

Short convolution (h channels, L = `conv_L_cache` taps): [B | C | u] =
x W_in (columns: all of B, then C, then u); z = B * u; the causal
depthwise convolution c_t = sum_j w[:, j] * z_{t - (L - 1) + j}, zeros
left of the start, no bias, no activation; y = (C * c) W_out. HERE AS A
SUM OVER TAPS OF SHIFTED COPIES of z (tap j multiplies z moved down by
L - 1 - j tokens, built by concatenation), not as the program's padded
slices: two derivations are compared.

Attention (n query heads, n_kv key/value heads, d wide): q = x W_q, k =
x W_k, v = x W_v; q and k pass the plain RMS norm over d (one weight
vector for all heads each); rotary over all of d, half-split: pair i =
(x[i], x[i + d/2]) turns by t * theta^(-2i/d); causal softmax(q k^T /
sqrt(d)) v, query head h reading key/value head h // (n / n_kv); ctx W_o.

Feed-forward of the first `num_dense_layers` layers: SwiGLU of
`intermediate_size`. Of the others: s = sigmoid(x W_r) over all R router
outputs; chosen = top-k of s + b (the bias selects, never weighs); w =
s[chosen] / (sum s[chosen] + 1e-20) * `routed_scaling_factor`; y = sum
over chosen experts HELD HERE of w_e E_e(x), each expert a SwiGLU. No
shared expert.

Departures from the published description, noted:
  * Linear weights are stored [in, out] and applied as x @ W.
  * W_in's columns are [B | C | u] as whole blocks in that order; the
    released checkpoints' order is a permutation of columns: with weights
    drawn from a seed, the same model.
  * The head is tied (the catalog row has no `tie_word_embeddings`; the
    published 8.3 B total only adds up with one table) and `head_dim` is
    hidden_size / num_attention_heads.
  * The share: only the experts in `experts_held` = [lo, hi) exist here
    and what the absent ones would add is left out; the table is a slice
    of the vocabulary's rows.
  * Attention is computed over blocks of queries, the held experts in
    groups under jax.checkpoint: each is the unblocked result.
  * `choices`, where given, replaces the reference's own top-k in the
    COMPUTATION of each expert layer (its own choice is still returned),
    for the reason deepseek_v3_ref.py gives.
  * No auxiliary loss (the catalog's `config` has no key for one).

Weights: `top` = embed_tokens [V, h], embedding_norm [h]; `get_layer(i)`
gives one layer's dict:
  every layer: operator_norm [h], ffn_norm [h], out_proj;
  conv: in_proj [h, 3h], conv [h, L], out_proj [h, h];
  attention: q_proj [h, n d], k_proj, v_proj [h, n_kv d], q_layernorm [d],
  k_layernorm [d], out_proj [n d, h];
  dense: gate_proj, up_proj [h, f], down_proj [f, h];
  expert: router [h, R], router_bias [R], experts_gate, experts_up
  [E, h, fe], experts_down [E, fe, h].
`cfg` is the configuration file's dict.

TOLERANCES, with their reasons: benchmark/program_lfm2_moe.py keeps them,
beside the comparison that uses them.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def swiglu(x, gate, up, down):
    return (silu(x @ gate) * (x @ up)) @ down


# ------------------------------------------------------ short convolution
def causal_conv(z, w):
    """z [T, ch], w [ch, L]: c_t = sum_j w[:, j] * z_{t - (L - 1) + j},
    zeros left of the start: tap j times z moved down by L - 1 - j."""
    t_len, taps = z.shape[0], w.shape[1]
    out = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        moved = z if back == 0 else jnp.concatenate(
            [jnp.zeros((back, z.shape[1]), z.dtype), z[:t_len - back]])
        out = out + moved * w[:, j]
    return out


def short_conv(x, p: dict):
    """The short-convolution mixer for one sequence x [T, h], x already
    normalised."""
    h = x.shape[-1]
    bcu = x @ p["in_proj"]
    gate_b, gate_c, u = bcu[:, :h], bcu[:, h:2 * h], bcu[:, 2 * h:]
    return (gate_c * causal_conv(gate_b * u, p["conv"])) @ p["out_proj"]


# -------------------------------------------------------------- attention
def rotary_half(x, theta):
    """x [T, n, d]: rotary over all of d, pair i = (x[i], x[i + d/2])
    turning by t * theta^(-2i/d)."""
    t_len, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(t_len, dtype=jnp.float32)[:, None]
           * freq[None, :])[:, None, :]                      # [T, 1, d/2]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(x, p: dict, cfg: dict, q_block: int):
    """Grouped-head attention for one sequence x [T, h], x already
    normalised."""
    t_len = x.shape[0]
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = (x @ p["q_proj"]).reshape(t_len, n, d)
    k = (x @ p["k_proj"]).reshape(t_len, n_kv, d)
    v = (x @ p["v_proj"]).reshape(t_len, n_kv, d)
    q = rotary_half(rms_norm(q, p["q_layernorm"], eps), theta)
    k = rotary_half(rms_norm(k, p["k_layernorm"], eps), theta)
    q = q.reshape(t_len, n_kv, n // n_kv, d)     # head h = kv * group + g
    pos = jnp.arange(t_len)
    q_block = min(q_block, t_len)
    if t_len % q_block:
        raise ValueError(f"{t_len} tokens are no whole blocks of {q_block}")

    @jax.checkpoint          # a gradient keeps no block's scores
    def rows(blk):           # every block of queries sees all keys
        q_rows, pos_rows = blk
        scores = jnp.einsum("qhgd,khd->hgqk", q_rows, k) \
            / jnp.sqrt(jnp.float32(d))
        mask = pos_rows[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v)

    out = jax.lax.map(rows, (q.reshape(-1, q_block, n_kv, n // n_kv, d),
                             pos.reshape(-1, q_block)))
    return out.reshape(t_len, n * d) @ p["out_proj"]


# ------------------------------------------------------------ the experts
def route(x, p: dict, cfg: dict):
    """(scores [T, R], own choice [T, k], margin [T]: the k-th largest
    biased score less the (k + 1)-th) of the router."""
    k = cfg["num_experts_per_tok"]
    scores = 1.0 / (1.0 + jnp.exp(-(x @ p["router"])))
    top, chosen = jax.lax.top_k(scores + p["router_bias"], k + 1)
    return scores, chosen[:, :k], top[:, k - 1] - top[:, k]


def routed_part(x, p: dict, cfg: dict, scores, chosen, group: int = 8):
    """The held experts' part for `chosen` [T, k]: every held expert over
    ALL tokens, weighted by 0 where it was not chosen; `group` experts at
    a time, a gradient recomputing each group."""
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    lo, hi = cfg["experts_held"]
    held = jnp.arange(lo, hi)
    w_te = jnp.sum(jnp.where(chosen[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)                                        # [T, E]
    n_held = hi - lo
    group = group if n_held % group == 0 else n_held

    @jax.checkpoint
    def some(args):
        gate, up, down, w_e = args                # [g, h, f] ..., [g, T]
        act = silu(jnp.einsum("th,ehf->etf", x, gate)) \
            * jnp.einsum("th,ehf->etf", x, up)
        return jnp.einsum("et,etf,efh->th", w_e, act, down)

    def grouped(t):
        return t.reshape((n_held // group, group) + t.shape[1:])

    parts = jax.lax.map(some, (
        grouped(p["experts_gate"]), grouped(p["experts_up"]),
        grouped(p["experts_down"]), grouped(w_te.T)))
    return jnp.sum(parts, axis=0)


def block(x, p: dict, cfg: dict, layer_type: str, dense: bool, choice=None,
          q_block: int = 512):
    """One layer on x [b, s, h]: (x after it, None for a dense layer or the
    router's dict). `choice` [b*s, k] replaces the own top-k in the
    computation."""
    b, s, _ = x.shape
    eps = cfg["norm_eps"]
    normed = rms_norm(x, p["operator_norm"], eps)
    x = x + jnp.stack([
        short_conv(normed[j], p) if layer_type == "conv"
        else attention(normed[j], p, cfg, q_block) for j in range(b)])
    hn = rms_norm(x, p["ffn_norm"], eps).reshape(b * s, -1)
    if dense:
        y, routed = swiglu(hn, p["gate_proj"], p["up_proj"],
                           p["down_proj"]), None
    else:
        scores, own, margin = route(hn, p, cfg)
        routed = {"input": hn, "chosen": own, "margin": margin}
        y = routed_part(hn, p, cfg, scores, own if choice is None
                        else jnp.asarray(choice, jnp.int32))
    return x + y.reshape(b, s, -1), routed


def forward(ids, top: dict, get_layer: Callable[[int], dict], cfg: dict,
            choices: Optional[list] = None, q_block: int = 512) -> dict:
    """ids [b, s] -> {"logits" [b, s, V] float32, "router": one dict per
    expert layer with "input" [b*s, h] (what the reference's router saw),
    "chosen" [b*s, k] (the reference's own choice) and "margin" [b*s]}.
    `choices`: per expert layer a [b*s, k] array to compute with in place
    of the own choice."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jnp.asarray(t, jnp.float32)   # noqa: E731
        ids = jnp.asarray(ids, jnp.int32)
        table = f32(top["embed_tokens"])
        x = table[ids]                                           # [b, s, h]
        router = []
        for i, layer_type in enumerate(cfg["layer_types"]):
            p = {k: f32(v) for k, v in get_layer(i).items()}
            dense = i < cfg["num_dense_layers"]
            x, routed = block(
                x, p, cfg, layer_type, dense, None if choices is None
                or dense else choices[len(router)], q_block)
            if routed is not None:
                router.append(routed)
            jax.block_until_ready(x)    # one layer in flight (no-op in a trace)
            del p
        x = rms_norm(x, f32(top["embedding_norm"]), cfg["norm_eps"])
        return {"logits": x @ table.T, "router": router}


def next_token_loss(lg, labels):
    """Mean cross-entropy of logits [b, s, V] against labels [b, s]."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(labels, jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def router_agreement(own, margin, program_chosen) -> dict:
    """For one expert layer: the share of tokens whose chosen SET differs,
    and the largest reference margin among them."""
    same = jnp.all(jnp.sort(own, -1) == jnp.sort(
        jnp.asarray(program_chosen, jnp.int32), -1), axis=-1)
    differ = ~same
    return {"tokens": int(same.shape[0]), "differ": int(jnp.sum(differ)),
            "max_margin": float(jnp.max(jnp.where(differ, margin, 0.0)))}
