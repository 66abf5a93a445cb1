"""Plain float32 reference of the Qwen3-Next decoder as its config.json
states it (`model_type: qwen3_next`): Gated DeltaNet layers beside gated
grouped-head attention, softmax-routed experts with a gated shared expert.
Straight `jax.numpy`, float32, `default_matmul_precision("highest")`, no
kernel, no chunked form, no sort, no grouped product, no cache. It imports
nothing of paddle_tpu.

    x += Mixer_i(N(x)); x += MoE(N(x)); logits = N(x) W_head
    N(x) = x / rms(x) * (1 + w)          (the zero-centred RMS norm)
    layer i is full attention where (i + 1) % full_attention_interval == 0,
    Gated DeltaNet otherwise.

Gated DeltaNet (Hk key heads dk wide, Hv value heads dv wide; K = Hk dk,
V = Hv dv): [q | k | v | z] = x W_qkvz (columns: all of q head-major, then
k, then v, then z), [b | a] = x W_ba (Hv of each). [q | k | v] <-
silu(conv([q | k | v])): causal depthwise convolution over the sequence,
tap j of the `linear_conv_kernel_dim` taps multiplying the token
(taps - 1 - j) back, zeros left of the start, no bias. beta = sigmoid(b);
g = -exp(A_log) * softplus(a + dt_bias); q <- q / |q| / sqrt(dk),
k <- k / |k| (l2 over the head, x / sqrt(sum x^2 + 1e-6)). Key head j
serves the value heads j * (Hv / Hk) ... Per value head, S_0 = 0:
    S_t = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S_t^T k_t);
    S_t += k_t d_t^T;        o_t = S_t^T q_t
HERE AS THAT RECURRENCE, token by token under `lax.scan` (the program runs
the chunked form: two derivations are compared). y = (w_n * o / rms(o)) *
silu(z) per head over its dv, then y W_o.

Gated attention (n query heads, n_kv key/value heads, d wide): [q | gate]
= x W_q per head (d + d), k = x W_k, v = x W_v; q and k pass the
zero-centred RMS norm over d (one weight vector for all heads); rotary on
the first d * partial_rotary_factor of the d, half-split: pair i =
(x[i], x[i + r/2]) turns by t * theta^(-2i/r); causal softmax(q k^T /
sqrt(d)) v, query head h reading key/value head h // (n / n_kv);
(ctx * sigmoid(gate)) W_o.

Experts: p = softmax(x W_r) over all R; the k largest are chosen; weights
p[chosen] / sum(p[chosen]); y = sum over chosen experts HELD HERE of w_e
E_e(x) + sigmoid(x w_sg) * Shared(x), each expert a SwiGLU.

Departures from the published description, noted:
  * Linear weights are stored [in, out] and applied as x @ W.
  * The released checkpoints lay W_qkvz's columns out per key head
    ([q | k | v v | z z] of head 0, then head 1, ...) and W_ba's likewise;
    here q, k, v, z (and b, a) are whole column blocks, head-major inside.
    A permutation of columns: with weights drawn from a seed, the same
    model.
  * The share: only the experts in `experts_held` = [lo, hi) exist here
    and what the absent ones would add is left out; the vocabulary is a
    slice.
  * Attention is computed over blocks of queries, the recurrence in blocks
    of tokens under jax.checkpoint (a gradient keeps one state a block,
    not one a token: 8192 x 32 x 128 x 128 x 4 B would be 17 GB), the held
    experts in groups: each is the unblocked result.
  * `choices`, where given, replaces the reference's own top-k in the
    COMPUTATION of each expert layer (its own choice is still returned),
    for the reason deepseek_v3_ref.py gives.
  * No multi-token-prediction head and no auxiliary loss (the catalog's
    `config` has a key for neither).

Weights: `top` = embed_tokens [V, h], norm [h], lm_head [h, V];
`get_layer(i)` gives one layer's dict:
  every layer: input_layernorm [h], post_attention_layernorm [h], router
  [h, R], experts_gate, experts_up [E, h, f], experts_down [E, f, h],
  shared_gate, shared_up [h, fs], shared_down [fs, h], shared_expert_gate
  [h, 1];
  DeltaNet: in_proj_qkvz [h, 2K + 2V], in_proj_ba [h, 2 Hv], conv1d
  [2K + V, taps], A_log [Hv], dt_bias [Hv], norm [dv], out_proj [V, h];
  attention: q_proj [h, n 2d], k_proj, v_proj [h, n_kv d], q_norm [d],
  k_norm [d], o_proj [n d, h].
`cfg` is the configuration file's dict.

TOLERANCES, with their reasons: benchmark/program_gdn_moe.py keeps them,
beside the comparison that uses them.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    """The zero-centred RMS norm: x / rms(x) * (1 + w)."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def swiglu(x, gate, up, down):
    return (silu(x @ gate) * (x @ up)) @ down


def is_full_attention(i: int, cfg: dict) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


# --------------------------------------------------------- gated attention
def rotary_half(x, theta, factor):
    """x [T, n, d]: rotary on the first r = d * factor of d, pair i =
    (x[i], x[i + r/2]) turning by t * theta^(-2i/r)."""
    t_len, d = x.shape[0], x.shape[-1]
    r = int(d * factor)
    freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = (jnp.arange(t_len, dtype=jnp.float32)[:, None]
           * freq[None, :])[:, None, :]                      # [T, 1, r/2]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., r:]], axis=-1)


def attention(x, p: dict, cfg: dict, q_block: int):
    """Gated attention for one sequence x [T, h], x already normalised."""
    t_len = x.shape[0]
    n, n_kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    factor = cfg["partial_rotary_factor"]
    qg = (x @ p["q_proj"]).reshape(t_len, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]).reshape(t_len, n_kv, d)
    v = (x @ p["v_proj"]).reshape(t_len, n_kv, d)
    q = rotary_half(rms_norm(q, p["q_norm"], eps), theta, factor)
    k = rotary_half(rms_norm(k, p["k_norm"], eps), theta, factor)
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
    ctx = out.reshape(t_len, n, d) * sigmoid(gate)
    return ctx.reshape(t_len, n * d) @ p["o_proj"]


# --------------------------------------------------------- gated DeltaNet
def causal_conv(x, w):
    """x [T, ch], w [ch, taps]: y_t = sum_j w[:, j] * x_{t - (taps-1) + j},
    zeros left of the start."""
    taps = w.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[j:j + x.shape[0]] * w[:, j] for j in range(taps))


def l2_norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta, block: int = 64):
    """The gated delta rule as the recurrence, one sequence: q, k
    [T, H, dk], v [T, H, dv], g, beta [T, H] -> o [T, H, dv]. Blocks of
    `block` tokens under jax.checkpoint: a gradient keeps a state a block."""
    t_len, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -t_len % block
    if pad:      # beta = 0 and g = 0 leave the state as it is
        q, k, v, g, beta = (
            jnp.concatenate([t, jnp.zeros((pad,) + t.shape[1:], t.dtype)])
            for t in (q, k, v, g, beta))

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, None, None]
        d_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(t.reshape((-1, block) + t.shape[1:])
               for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens, jnp.zeros((heads, dk, dv), jnp.float32), xs)
    return o.reshape(-1, heads, dv)[:t_len]


def delta_rule_inputs(x, p: dict, cfg: dict) -> dict:
    """What the rule is given for one normalised sequence x [T, h]: q, k
    (normalised, each key head repeated for its value heads), v, g, beta,
    and the output gate z."""
    t_len = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kk, vv = hk * dk, hv * dv
    qkvz = x @ p["in_proj_qkvz"]
    ba = x @ p["in_proj_ba"]
    mixed = silu(causal_conv(qkvz[:, :2 * kk + vv], p["conv1d"]))
    q = l2_norm(mixed[:, :kk].reshape(t_len, hk, dk)) / jnp.sqrt(
        jnp.float32(dk))
    k = l2_norm(mixed[:, kk:2 * kk].reshape(t_len, hk, dk))
    return {
        "q": jnp.repeat(q, hv // hk, axis=1),
        "k": jnp.repeat(k, hv // hk, axis=1),
        "v": mixed[:, 2 * kk:].reshape(t_len, hv, dv),
        "z": qkvz[:, 2 * kk + vv:].reshape(t_len, hv, dv),
        "beta": sigmoid(ba[:, :hv]),
        "g": -jnp.exp(p["A_log"]) * softplus(ba[:, hv:] + p["dt_bias"])}


def gated_delta_net(x, p: dict, cfg: dict):
    """The DeltaNet mixer for one sequence x [T, h], x already normalised."""
    t = delta_rule_inputs(x, p, cfg)
    o = delta_rule(t["q"], t["k"], t["v"], t["g"], t["beta"])
    eps = cfg["rms_norm_eps"]
    y = p["norm"] * o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * silu(t["z"])
    return y.reshape(x.shape[0], -1) @ p["out_proj"]


# ------------------------------------------------------------ the experts
def route(x, p: dict, cfg: dict):
    """(probabilities [T, R], own choice [T, k], margin [T]: the k-th
    largest probability less the (k + 1)-th) of the router."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, k + 1)
    return probs, chosen[:, :k], top[:, k - 1] - top[:, k]


def routed_part(x, p: dict, cfg: dict, probs, chosen, group: int = 8):
    """The held experts' part for `chosen` [T, k]: every held expert over
    ALL tokens, weighted by 0 where it was not chosen; `group` experts at
    a time, a gradient recomputing each group."""
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
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


def shared_part(x, p: dict):
    return sigmoid(x @ p["shared_expert_gate"]) * swiglu(
        x, p["shared_gate"], p["shared_up"], p["shared_down"])


def block(x, p: dict, cfg: dict, full_attention: bool, choice=None,
          q_block: int = 512):
    """One layer on x [b, s, h]: (x after it, the router's dict). `choice`
    [b*s, k] replaces the own top-k in the computation."""
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    normed = rms_norm(x, p["input_layernorm"], eps)
    x = x + jnp.stack([
        attention(normed[j], p, cfg, q_block) if full_attention
        else gated_delta_net(normed[j], p, cfg) for j in range(b)])
    hn = rms_norm(x, p["post_attention_layernorm"], eps).reshape(b * s, -1)
    probs, own, margin = route(hn, p, cfg)
    routed = {"input": hn, "chosen": own, "margin": margin}
    y = routed_part(hn, p, cfg, probs, own if choice is None
                    else jnp.asarray(choice, jnp.int32)) + shared_part(hn, p)
    return x + y.reshape(b, s, -1), routed


def forward(ids, top: dict, get_layer: Callable[[int], dict], cfg: dict,
            choices: Optional[list] = None, q_block: int = 512) -> dict:
    """ids [b, s] -> {"logits" [b, s, V] float32, "router": one dict per
    layer with "input" [b*s, h] (what the reference's router saw),
    "chosen" [b*s, k] (the reference's own choice) and "margin" [b*s]}.
    `choices`: per layer a [b*s, k] array to compute with in place of the
    own choice."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jnp.asarray(t, jnp.float32)   # noqa: E731
        ids = jnp.asarray(ids, jnp.int32)
        x = f32(top["embed_tokens"])[ids]                        # [b, s, h]
        router = []
        for i in range(cfg["num_hidden_layers"]):
            p = {k: f32(v) for k, v in get_layer(i).items()}
            x, routed = block(x, p, cfg, is_full_attention(i, cfg),
                              None if choices is None else choices[i],
                              q_block)
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
    """For one layer: the share of tokens whose chosen SET differs, and the
    largest reference margin among them."""
    same = jnp.all(jnp.sort(own, -1) == jnp.sort(
        jnp.asarray(program_chosen, jnp.int32), -1), axis=-1)
    differ = ~same
    return {"tokens": int(same.shape[0]), "differ": int(jnp.sum(differ)),
            "max_margin": float(jnp.max(jnp.where(differ, margin, 0.0)))}
