"""Plain float32 reference of the DeepSeek-V3 decoder as kanana-2's
config.json states it (`model_type: deepseek_v3`; the layer equations of
arXiv:2412.19437 section 2.1 with `q_lora_rank: null`, `n_group = topk_group
= 1`, `rope_scaling: null`). Straight `jax.numpy`, float32,
`default_matmul_precision("highest")`, no kernel, no sort, no grouped
product, no cache. It imports nothing of paddle_tpu.

    x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)); logits = RMSNorm(x) W_head

Attn: q = x W_q -> [T, n, nope + rope]; x W_kva -> [T, rank + rope], c =
RMSNorm(first rank), k_pe = last rope (one per token, shared by all heads);
c W_kvb -> [T, n, nope + v]; rotary with theta on q_pe and k_pe over
adjacent pairs (2i, 2i+1); k = [k_nope | k_pe]; softmax(q k^T /
sqrt(nope + rope) + causal) v; W_o.
FFN of the first `first_k_dense_replace` layers: SwiGLU of
`intermediate_size`. Of the others: s = sigmoid(x W_g) over all R experts;
chosen = top-k of s + b; w = s[chosen] / (sum s[chosen] + 1e-20) * scaling;
y = sum over chosen experts HELD HERE of w_e E_e(x) + Shared(x).

Departures from the published description, noted:
  * Linear weights are stored [in, out] and applied as x @ W (the released
    checkpoints store [out, in]).
  * The share: only the experts in `experts_held` = [lo, hi) exist here and
    what the absent ones would add is left out; the vocabulary is a slice
    (the embedding and the head have `vocab_size` rows / columns as given).
  * Rotary turns adjacent pairs in place. The released code de-interleaves
    q_pe and k_pe first and turns split halves: the same permutation on
    both sides of q k^T, so the same scores.
  * Attention is computed over blocks of queries, so that 8192 tokens fit;
    each block sees all its keys, so the result is the unblocked one.
  * `choices`, where given, replaces the reference's own top-k in the
    COMPUTATION of each expert layer (its own choice is still returned):
    at random weights the 6th and 7th biased score of a token are often
    closer than bf16 rounding of the hidden state moves them, and a model
    that is right would otherwise be compared on different experts.

Weights: `top` = embed_tokens [V, h], norm [h], lm_head [h, V];
`get_layer(i)` gives one layer's dict, asked for one at a time:
  input_layernorm [h], q_proj [h, n*(nope+rope)], kv_a_proj [h, rank+rope],
  kv_a_layernorm [rank], kv_b_proj [rank, n*(nope+v)], o_proj [n*v, h],
  post_attention_layernorm [h], and
  dense: gate_proj, up_proj [h, f], down_proj [f, h];
  expert: router [h, R], router_bias [R], experts_gate, experts_up
  [E, h, fe], experts_down [E, fe, h], shared_gate, shared_up [h, fs],
  shared_down [fs, h].
`cfg` is the configuration file's dict (the config.json keys, and
`experts_held`, `router_outputs`).

TOLERANCES, with their reason (the program computes in bfloat16 where the
configuration says so; norms, softmax statistics and the router in float32).
Two passes are compared (benchmark/program_mla_moe.py): the forward on the
cell's seeded ids, and the LAST layer alone, forward and backward, on a
seeded N(0, 1) hidden state of the same length with a seeded N(0, 1) weight
on every output. The second is there because the model's own stream, after
a window of AdamW at 2e-4 from N(0, 0.02) weights, is one vector common to
all tokens: every token has the same scores (no near-tie for a low-precision
router to flip), the gradient by W_q is the rounding of a sum that cancels,
and this chip's experts may get no token (PERF.md findings 13, 15, 16).
Readings: TPU v5e, the cell's size, PR 27; "control" = the same run with a
mutant of tests/test_mla_moe.py patched in.

SINCE PR 30 the cell's embedding is N(0, 1) and AdamW runs at 1e-5: the
model's own stream differs from token to token too, and near-ties are spread
over every pass. No limit moved. Readings at that traffic (TPU v5e, the
cell's size, PR 30): the program as it is, 25 runs on 17 seeds; each control
at the cell's own load and window, patched in the same way:
  (a1) 0 of 40,960 pairs in every run. Router in bfloat16, three seeds:
       0.0928, 0.0974, 0.0981 (506-1,170 of each ids layer's 8,192 tokens,
       no longer 5 of 32,768). Softmax for sigmoid: 0.890.
  (a2) differs on 4.2-6.5 % of the pairs, largest margin 0.0029-0.0052.
       Softmax for sigmoid: 0.0688. (The bfloat16 router: 0.0035-0.0041,
       passes this one and fails (a1).)
  (b)  |dloss| <= 0.00031, max |dlogit| 0.047-0.053 sigma. Softmax for
       sigmoid: 0.91 sigma.
  (c)  worst parameter 0.019-0.055 (the router's or a routed expert's
       weights; attention's, the input's and the shared expert's
       0.004-0.008). Half the gradient of the shared W_up: 0.500 on that
       parameter, the others unmoved.

Part (b), the forward on the ids, the reference computed on the program's
choices: gpt2_ref.py's limits and their reasons hold unchanged:
  LOGIT_TOL_SIGMAS = 0.2, LOSS_ATOL = 0.02.
  Largest readings over sixteen runs: max |dlogit| 0.074 sigma (0.030 in
  fifteen of them), |dloss| 0.0022 (0.055 sigma and 0.0003 with the first
  hand-in's even routing).
  A wrong block (softmax scores, the bias in the weights, no 2.448, rotary
  on split halves, no shared expert: tests/test_mla_moe.py) is off by 0.3
  sigma or more.

Part (a), the router, by two limits, over the four expert layers of the
ids pass and the last layer of the token-specific pass.
(a1) The PROGRAM's router and the reference's (`route`, float32), both on
what the REFERENCE's router saw in that pass (cast to the program's
activation dtype). One input, so only the router's own arithmetic differs:
  ROUTER_SAME_INPUT_FLIP_TOL = 0.002   share of (token, layer) pairs whose
      chosen set differs. As it is: 0 of 40,960 pairs in each of
      eight runs (two float32 sums in different orders meet only in an
      exact tie). Control, the router in
      bfloat16 (a grid of 2^-9 to 2^-8 under scores of 0.3-0.9, so the 6th
      and 7th often tie): 0.0142, of it 578 of the 8,192 tokens of the token-specific
      pass and 5 of the 32,768 pairs of the ids pass, which alone no
      limit could refuse.
(a2) The program's choice against the reference's own on ITS hidden state
(which followed the program's choices up to that layer). Here bf16 rounding
of the hidden state moves the scores (a score by ~0.002) and flips
near-ties: 0-10 % of an ids layer's tokens and 1.4-1.7 % of the
token-specific pass's differ (5.4-7.3 % with the first hand-in's even
routing), so the share has no limit; what
has one is WHERE they differ:
  ROUTER_MARGIN_TOL = 0.012   every differing pair has a reference margin
      (weakest chosen minus strongest unchosen biased score) under this.
      As it is: largest 0.0019 over sixteen runs (0.0046 with the first hand-in's even
      routing). Control, softmax for sigmoid: 0.059 (and (a1) 0.527). The
      bfloat16 router passes this one and fails (a1): one limit refuses
      it, not each.

Part (c), the last layer's backward on the token-specific pass: the
gradients of sum(out * weight) / tokens by the layer's input and by each of
its parameters, program (its own block: recomputation, flash kernels,
ragged_dot) against jax.grad of `block` on the program's choices:
  GRAD_REL_TOL = 0.2   |program - reference| / |reference|, Frobenius, for
      every one of them. As it is, nine runs: 0.004-0.009 for the input,
      attention's and the shared expert's parameters, 0.020-0.032 for
      the routed experts' (bf16 products of few rows each), up to 0.058
      for the router's weights, whose gradient is a difference between
      experts' outputs. Control, the shared
      expert's backward with half the gradient of W_up: 0.5 on that
      parameter and the others unmoved.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

LOGIT_TOL_SIGMAS = 0.2
LOSS_ATOL = 0.02
ROUTER_SAME_INPUT_FLIP_TOL = 0.002
ROUTER_MARGIN_TOL = 0.012
GRAD_REL_TOL = 0.2


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def swiglu(x, gate, up, down):
    return (silu(x @ gate) * (x @ up)) @ down


def rotary_pairs(x, theta):
    """x [T, ..., r]: pair i = (x[2i], x[2i+1]) turns by t * theta^(-2i/r)."""
    t_len, r = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t_len, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((t_len,) + (1,) * (x.ndim - 2) + (r // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out_even = even * jnp.cos(ang) - odd * jnp.sin(ang)
    out_odd = odd * jnp.cos(ang) + even * jnp.sin(ang)
    return jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)


def attention(x, p: dict, cfg: dict, q_block: int):
    """Attn(x) for one sequence x [T, h], x already normalised."""
    t_len = x.shape[0]
    n = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = (x @ p["q_proj"]).reshape(t_len, n, nope + rope)
    kva = x @ p["kv_a_proj"]
    latent = rms_norm(kva[:, :rank], p["kv_a_layernorm"],
                      cfg["rms_norm_eps"])
    k_pe = rotary_pairs(kva[:, rank:], cfg["rope_theta"])         # [T, rope]
    kv = (latent @ p["kv_b_proj"]).reshape(t_len, n, nope + v_dim)
    q_pe = rotary_pairs(q[..., nope:], cfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :],
                                          (t_len, n, rope))], axis=-1)
    v = kv[..., nope:]
    pos = jnp.arange(t_len)
    q_block = min(q_block, t_len)
    if t_len % q_block:
        raise ValueError(f"{t_len} tokens are no whole blocks of {q_block}")

    @jax.checkpoint          # a gradient keeps no block's scores
    def rows(blk):           # every block of queries sees all keys
        q_rows, pos_rows = blk
        scores = jnp.einsum("qnd,knd->nqk", q_rows, k) \
            / jnp.sqrt(jnp.float32(nope + rope))
        mask = pos_rows[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("nqk,knd->qnd", probs, v)

    out = jax.lax.map(rows, (q.reshape(-1, q_block, n, nope + rope),
                             pos.reshape(-1, q_block)))     # one at a time
    ctx = out.reshape(t_len, n * v_dim)
    return ctx @ p["o_proj"]


def route(x, p: dict, cfg: dict):
    """(scores [T, R], own choice [T, k], margin [T]) of the router."""
    k = cfg["num_experts_per_tok"]
    scores = 1.0 / (1.0 + jnp.exp(-(x @ p["router"])))
    biased = scores + p["router_bias"]
    top, chosen = jax.lax.top_k(biased, k + 1)
    return scores, chosen[:, :k], top[:, k - 1] - top[:, k]


def expert_ffn(x, p: dict, cfg: dict, scores, chosen):
    """The held experts' part for `chosen` [T, k], plus the shared expert:
    every held expert over ALL tokens, weighted by 0 where it was not
    chosen."""
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    lo, hi = cfg["experts_held"]
    held = jnp.arange(lo, hi)
    w_te = jnp.sum(jnp.where(chosen[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)                                        # [T, E]
    act = silu(jnp.einsum("th,ehf->etf", x, p["experts_gate"])) \
        * jnp.einsum("th,ehf->etf", x, p["experts_up"])
    y_e = jnp.einsum("etf,efh->eth", act, p["experts_down"])
    return swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"]) \
        + jnp.einsum("te,eth->th", w_te, y_e)


def block(x, p: dict, cfg: dict, dense: bool, choice=None,
          q_block: int = 512):
    """One layer on x [b, s, h]: (x after it, None for a dense layer or the
    router's dict). `choice` [b*s, k] replaces the own top-k in the
    computation."""
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    x = x + jnp.stack([
        attention(rms_norm(x[j], p["input_layernorm"], eps), p, cfg, q_block)
        for j in range(b)])
    hn = rms_norm(x, p["post_attention_layernorm"], eps).reshape(b * s, -1)
    if dense:
        y, routed = swiglu(hn, p["gate_proj"], p["up_proj"],
                           p["down_proj"]), None
    else:
        scores, own, margin = route(hn, p, cfg)
        routed = {"input": hn, "chosen": own, "margin": margin}
        y = expert_ffn(hn, p, cfg, scores, own if choice is None
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
        x = f32(top["embed_tokens"])[ids]                        # [b, s, h]
        router = []
        for i in range(cfg["num_hidden_layers"]):
            p = {k: f32(v) for k, v in get_layer(i).items()}
            dense = i < cfg["first_k_dense_replace"]
            x, routed = block(
                x, p, cfg, dense, None if choices is None or dense
                else choices[len(router)], q_block)
            if routed is not None:
                router.append(routed)
            jax.block_until_ready(x)    # one layer in flight (no-op in a trace)
            del p
        x = rms_norm(x, f32(top["norm"]), cfg["rms_norm_eps"])
        return {"logits": x @ f32(top["lm_head"]), "router": router}


def next_token_loss(lg, labels):
    """Mean cross-entropy of logits [b, s, V] against labels [b, s]."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(labels, jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def router_agreement(own, margin, program_chosen) -> dict:
    """Part (a) for one expert layer: the share of tokens whose chosen SET
    differs, and the largest reference margin among them."""
    same = jnp.all(jnp.sort(own, -1) == jnp.sort(
        jnp.asarray(program_chosen, jnp.int32), -1), axis=-1)
    differ = ~same
    return {"tokens": int(same.shape[0]), "differ": int(jnp.sum(differ)),
            "max_margin": float(jnp.max(jnp.where(differ, margin, 0.0)))}
