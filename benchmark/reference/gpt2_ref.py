"""Plain float32 reference of the GPT-2 / GPT-3 decoder (Radford et al.
2019; Brown et al. 2020 use the same block): learned absolute positions,
pre-LayerNorm blocks, multi-head causal softmax attention, a GELU MLP of
four times the width, a final LayerNorm and the output head tied to the
token embedding. Straight `jax.numpy`, float32, no kernel, no cache, no
batching trick, `default_matmul_precision("highest")` (on a TPU a float32
matmul otherwise runs in bf16 passes). It imports nothing of paddle_tpu.

Departure from the papers, noted: GELU is the tanh approximation
(`gelu_new`), which is what the released GPT-2 computes.

Weights come in the released checkpoint's layout, one dict per block:
  ln_1_g, ln_1_b [h]; c_attn_w [h, 3h] (columns q | k | v), c_attn_b [3h];
  c_proj_w [h, h], c_proj_b [h]; ln_2_g, ln_2_b [h];
  c_fc_w [h, f], c_fc_b [f]; mlp_proj_w [f, h], mlp_proj_b [h]
and `wte` [V, h], `wpe` [P, h], `ln_f_g`, `ln_f_b` [h]. Blocks are asked
for one at a time through `get_block(i)`, so that a model too large to hold
in float32 beside the program's own copy goes through layer by layer.

TOLERANCES, with their reason. The program computes in bfloat16 (8 bits of
mantissa, relative rounding 2^-9 = 0.2 %) where the configuration says so,
with float32 LayerNorm and softmax. Through L residual blocks the rounding
of each logit accumulates to a few percent of the logits' own standard
deviation: PR 21 measured max |dlogit| 0.037 between two bf16 paths at
gpt-125m, logit sigma 0.55, i.e. 0.07 sigma. So:
  LOGIT_TOL_SIGMAS = 0.2   max |dlogit| <= 0.2 * std(reference logits).
      Computing the matmuls in an 8-bit float (3 bits of mantissa, errors
      ~16x bf16's) lands near 1 sigma and fails; bf16 passes with 3x room.
      A wrong model (another block order, a missing bias or mask) is off by
      about a whole sigma.
  LOSS_ATOL = 0.02         the loss is a mean over >= 512 tokens, so the
      per-logit rounding averages out to ~1e-3; 0.02 nats (0.2 % of
      ln 50304 = 10.8) is 10x that, and far under the 0.1+ that dropping a
      block, a bias, the causal mask or a LayerNorm would move it.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

LOGIT_TOL_SIGMAS = 0.2
LOSS_ATOL = 0.02


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, p: dict, n_heads: int, eps: float):
    """One decoder block on x [b, s, h]."""
    b, s, h = x.shape
    d = h // n_heads
    a = _layer_norm(x, p["ln_1_g"], p["ln_1_b"], eps)
    qkv = a @ p["c_attn_w"] + p["c_attn_b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, n_heads, d).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + ctx @ p["c_proj_w"] + p["c_proj_b"]
    m = _layer_norm(x, p["ln_2_g"], p["ln_2_b"], eps)
    m = _gelu_new(m @ p["c_fc_w"] + p["c_fc_b"])
    return x + m @ p["mlp_proj_w"] + p["mlp_proj_b"]


def logits(ids, top: dict, get_block: Callable[[int], dict], n_layers: int,
           n_heads: int, eps: float = 1e-5):
    """Full-sequence logits [b, s, V] in float32 for token ids [b, s]."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jnp.asarray(t, jnp.float32)   # noqa: E731
        ids = jnp.asarray(ids, jnp.int32)
        wte = f32(top["wte"])
        x = wte[ids] + f32(top["wpe"])[: ids.shape[1]]
        for i in range(n_layers):
            p = {k: f32(v) for k, v in get_block(i).items()}
            x = block(x, p, n_heads, eps)
            x.block_until_ready()
            del p
        x = _layer_norm(x, f32(top["ln_f_g"]), f32(top["ln_f_b"]), eps)
        return x @ wte.T


def next_token_loss(lg, labels):
    """Mean cross-entropy of logits [b, s, V] against labels [b, s]."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(labels, jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
