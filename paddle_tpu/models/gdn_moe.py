"""The Qwen3-Next shaped decoder (`model_type: qwen3_next`): Gated DeltaNet
layers beside gated grouped-head attention, softmax-routed experts with a
gated shared expert, for training.

The third block beside gpt.py's and mla_moe.py's, on the same train path
(numpy weights from a seed, `paddle.optimizer.AdamW`, `jit.TrainStep`,
`GPTPretrainingCriterion`, the flash kernels of ops/flash_attention.py and
the recomputation policy `_policy_step`). What it has of its own:

  * the zero-centred RMS norm N(x) = x / rms(x) * (1 + w), w drawn zeros;
  * a mixer that changes with the layer index: layer i is gated full
    attention where (i + 1) % `full_attention_interval` == 0, a Gated
    DeltaNet layer otherwise (three and one at the published interval 4);
  * the expert block (distributed/moe.py DroplessMoELayer with
    `router="softmax"` and `shared_gated=True`) on every layer;
  * no bias anywhere, an untied head.

    x += Mixer_i(N(x)); x += MoE(N(x)); logits = N(x) W_head

**Gated DeltaNet** (Hk = `linear_num_key_heads` heads dk wide, Hv =
`linear_num_value_heads` heads dv wide; K = Hk dk, V = Hv dv). THE LAYOUT
of the two input projections' columns, stated once, here (the reference
benchmark/reference/qwen3_next_ref.py has the same one): `qkvz_w`
[h, 2K + 2V] = [q | k | v | z], each a whole column block, head-major
inside (q's head j is columns j dk ... (j + 1) dk of its block); `ba_w`
[h, 2 Hv] = [b | a], one column a value head each. (The released
checkpoints group the columns per key head; a permutation, and with
weights drawn from a seed the same model.) [q | k | v] <- silu(conv(.)): a
causal depthwise convolution over the sequence, `conv_w` [2K + V, taps],
tap j multiplying the token (taps - 1 - j) back, zeros left of the start.
beta = sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias); q <- q / |q| /
sqrt(dk), k <- k / |k|; key head j serves the value heads j (Hv / Hk) ...;
the gated delta rule in its chunked form (ops/gated_delta_rule.py), never
the token-by-token recurrence; y = (w_n * o / rms(o)) * silu(z) per head
over its dv (`out_norm_w` drawn ones), then y W_o.

**Gated attention** (n = `num_attention_heads` query heads over n_kv =
`num_key_value_heads`, d = `head_dim` wide): `q_w` [h, n 2d] gives
[q | gate] per head; q and k pass the zero-centred RMS norm over d (one
weight vector for all heads); rotary on the first d *
`partial_rotary_factor` of the d, half-split pairs (x[i], x[i + r/2]),
not mla_moe's adjacent ones; causal softmax attention through the flash
kernels, query head h reading key/value head h // (n / n_kv) by the
kernels' index maps; (ctx * sigmoid(gate)) W_o.

Float32 whatever the model's dtype: the norms (RMS, l2, the gated output
norm), rotary, the softmax statistics (inside the kernel), the
convolution's sum and its SiLU, beta, g and everything inside the delta
rule, the router and the shared expert's gate.

Device-trace scopes, siblings of one another so that no op is booked
twice: `embed`; `attn` (the attention layers' mixer only); `gdn_proj` (a
DeltaNet layer's input norm, two input projections, convolution and SiLU,
l2 norms, beta and g, and after the rule the gated output norm and the
output projection); `gdn_chunk` (the delta rule: decays, the triangular
solve, the chunk products, the scan; forward, recomputed and backward);
`mlp` (the norm before the experts and the shared expert); `moe_router`,
`moe_dispatch`, `moe_experts`, `moe_combine`; `lm_head`; `loss` and
`optimizer` come from jit.TrainStep. Host span: `model_init`
(RecordEvent). Counters: each layer's expert block carries the
`assign_count` and `touched_count` buffers of DroplessMoELayer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.moe import DroplessMoELayer
from ..framework import dtype as dtype_mod
from ..framework.autograd import call_op
from ..framework.tensor import Tensor
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops.gated_delta_rule import gated_delta_rule_chunked
from ..profiler import RecordEvent
from .gpt import _as_parameter, _local_attention_val, _policy_step


@dataclasses.dataclass
class GdnMoeConfig:
    """The keys of a `qwen3_next` config.json under their own names.
    `num_experts` is how many experts this chip HOLDS (`experts_held` says
    which), `router_outputs` how many exist; `vocab_size` is the rows of
    the vocabulary held here."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    router_outputs: Optional[int] = None      # None: num_experts
    experts_held: Optional[Tuple[int, int]] = None    # None: all of them
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    recompute: str = "none"          # "none" | "layer": whole-layer remat
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_outputs is None:
            self.router_outputs = self.num_experts
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(e) for e in self.experts_held)
        lo, hi = self.experts_held
        if hi - lo != self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not the "
                f"{self.num_experts} experts num_experts says")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads are no whole "
                f"groups over {self.num_key_value_heads} key/value heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads are no whole "
                f"groups over {self.linear_num_key_heads} key heads")
        if self.recompute not in ("none", "layer"):
            raise ValueError(f"recompute {self.recompute!r}: none or layer")

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim


# --------------------------------------------------------------------------
# pure block math
# --------------------------------------------------------------------------

def rms_norm_zero_centred(x, w, eps):
    """x / rms(x) * (1 + w) in float32, returned in x's dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rotary_half_split(x, theta: float, factor: float):
    """Rotary positions on the first r = d * factor of x [b, s, n, d], over
    half-split pairs: pair i = (x[i], x[i + r/2]) turns by
    pos * theta^(-2i/r). Float32 inside."""
    s, d = x.shape[1], x.shape[-1]
    r = int(d * factor)
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * freq[None, :])[None, :, None, :]               # [1, s, 1, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :r // 2], xf[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            xf[..., r:]], axis=-1).astype(x.dtype)


def gated_attention(x, p: dict, cfg: GdnMoeConfig):
    """The attention mixer on normalised x [b, s, h] (no residual)."""
    b, s, _ = x.shape
    n, n_kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    qg = (x @ p["q_w"]).reshape(b, s, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_w"]).reshape(b, s, n_kv, d)
    v = (x @ p["v_w"]).reshape(b, s, n_kv, d)
    q = rotary_half_split(
        rms_norm_zero_centred(q, p["q_norm_w"], cfg.rms_norm_eps),
        cfg.rope_theta, cfg.partial_rotary_factor)
    k = rotary_half_split(
        rms_norm_zero_centred(k, p["k_norm_w"], cfg.rms_norm_eps),
        cfg.rope_theta, cfg.partial_rotary_factor)
    ctx = _local_attention_val(q, k, v, True)
    ctx = (ctx.astype(jnp.float32)
           * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
    return ctx.reshape(b, s, n * d) @ p["o_w"]


def causal_taps(x, w):
    """The causal depthwise convolution of x [b, s, ch] float32 with w
    [ch, taps]: y_t = sum_j w[:, j] * x_{t - (taps - 1) + j}, zeros left of
    the start. No bias and no activation. Float32 in and out."""
    taps, s = w.shape[1], x.shape[1]
    xf = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    return sum(xf[:, j:j + s] * wf[:, j] for j in range(taps))


def causal_conv_silu(x, w):
    """silu of `causal_taps` of x [b, s, ch] with w [ch, taps]. The sum and
    the SiLU in float32, the result in x's dtype."""
    return jax.nn.silu(causal_taps(x.astype(jnp.float32), w)).astype(x.dtype)


def _l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule_inputs(x, p: dict, cfg: GdnMoeConfig):
    """What the delta rule is given for normalised x [b, s, h]: (q, k
    [b, s, Hk, dk] float32, normalised, q scaled; v [b, s, Hv, dv]; g, beta
    [b, s, Hv] float32; the output gate z [b, s, Hv, dv])."""
    b, s, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kk, vv = cfg.key_width, cfg.value_width
    qkvz = x @ p["qkvz_w"]
    ba = (x @ p["ba_w"]).astype(jnp.float32)
    mixed = causal_conv_silu(qkvz[..., :2 * kk + vv], p["conv_w"])
    q = _l2_norm(mixed[..., :kk].reshape(b, s, hk, dk).astype(jnp.float32)) \
        * (1.0 / math.sqrt(dk))
    k = _l2_norm(mixed[..., kk:2 * kk].reshape(b, s, hk, dk)
                 .astype(jnp.float32))
    v = mixed[..., 2 * kk:].reshape(b, s, hv, dv)
    z = qkvz[..., 2 * kk + vv:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(jnp.float32))
    return q, k, v, g, beta, z


def gated_delta_net(x, p: dict, cfg: GdnMoeConfig):
    """The DeltaNet mixer on x [b, s, h], its input norm included (no
    residual). Two scopes, siblings: `gdn_proj` around what comes before
    and after the rule, `gdn_chunk` around the rule."""
    b, s, _ = x.shape
    with jax.named_scope("gdn_proj"):
        xn = rms_norm_zero_centred(x, p["in_norm_w"], cfg.rms_norm_eps)
        q, k, v, g, beta, z = delta_rule_inputs(xn, p, cfg)
    with jax.named_scope("gdn_chunk"):
        o = gated_delta_rule_chunked(q, k, v, g, beta)
    with jax.named_scope("gdn_proj"):
        of = o.astype(jnp.float32)
        of = of * jax.lax.rsqrt(
            jnp.mean(of * of, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = of * p["out_norm_w"].astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        return y.astype(x.dtype).reshape(b, s, cfg.value_width) @ p["o_w"]


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _gdn_shapes(cfg: GdnMoeConfig) -> dict:
    h, kk, vv = cfg.hidden_size, cfg.key_width, cfg.value_width
    hv = cfg.linear_num_value_heads
    return {"in_norm_w": (h,), "qkvz_w": (h, 2 * kk + 2 * vv),
            "ba_w": (h, 2 * hv),
            "conv_w": (2 * kk + vv, cfg.linear_conv_kernel_dim),
            "A_log": (hv,), "dt_bias": (hv,),
            "out_norm_w": (cfg.linear_value_head_dim,), "o_w": (vv, h),
            "ffn_norm_w": (h,)}


def _attn_shapes(cfg: GdnMoeConfig) -> dict:
    h, n, n_kv, d = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    return {"in_norm_w": (h,), "q_w": (h, n * 2 * d), "k_w": (h, n_kv * d),
            "v_w": (h, n_kv * d), "q_norm_w": (d,), "k_norm_w": (d,),
            "o_w": (n * d, h), "ffn_norm_w": (h,)}


def _draw(rs, name: str, shape, cfg: GdnMoeConfig):
    """A parameter in the model's dtype from the numpy Generator `rs`, as
    the released modelling code initialises it: a matrix (the convolution's
    taps too) normal(0, initializer_range); a zero-centred norm's vector
    zeros; the DeltaNet output norm's and dt_bias ones; A_log the log of a
    uniform(0, 16) draw."""
    if name == "A_log":
        w = np.log(rs.uniform(0.0, 16.0, shape)).astype(np.float32)
    elif name in ("out_norm_w", "dt_bias"):
        w = np.ones(shape, np.float32)
    elif len(shape) == 1:
        w = np.zeros(shape, np.float32)
    else:
        w = rs.standard_normal(shape, dtype=np.float32) \
            * cfg.initializer_range
    return _as_parameter(
        Tensor(w, dtype=dtype_mod.convert_dtype(cfg.dtype)), None)


class GdnMoeDecoderLayer(Layer):
    """One block: the mixer `full_attention` picks, then the expert block
    (`self.moe`), whose buffers and chosen experts advance with every
    forward."""

    def __init__(self, cfg: GdnMoeConfig, full_attention: bool, rs):
        super().__init__()
        self.cfg = cfg
        self.full_attention = bool(full_attention)
        shapes = _attn_shapes(cfg) if full_attention else _gdn_shapes(cfg)
        for name, shape in shapes.items():
            setattr(self, name, _draw(rs, name, shape, cfg))
        self.names = tuple(shapes)
        self.moe = DroplessMoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.router_outputs,
            cfg.num_experts_per_tok, experts_held=cfg.experts_held,
            shared_width=cfg.shared_expert_intermediate_size,
            init_std=cfg.initializer_range, dtype=cfg.dtype, rs=rs,
            router="softmax", shared_gated=True)

    def forward(self, x):
        cfg, moe = self.cfg, self.moe
        own = [getattr(self, n) for n in self.names]
        n_own = len(own)

        def block(xv, vals):
            p = dict(zip(self.names, vals[:n_own]))
            if self.full_attention:
                with jax.named_scope("attn"):
                    xv = xv + gated_attention(rms_norm_zero_centred(
                        xv, p["in_norm_w"], cfg.rms_norm_eps), p, cfg)
            else:
                mixed = gated_delta_net(xv, p, cfg)
                with jax.named_scope("gdn_proj"):
                    xv = xv + mixed
            with jax.named_scope("mlp"):
                hn = rms_norm_zero_centred(xv, p["ffn_norm_w"],
                                           cfg.rms_norm_eps)
            hn = hn.reshape(-1, hn.shape[-1])
            y, chosen, counts = moe.apply_val(hn, vals[n_own + 1:],
                                              vals[n_own])
            return xv + y.reshape(xv.shape), chosen, counts

        step = _policy_step(
            block, "remat" if cfg.recompute == "layer" else "none")

        def fn(xv, *vals):
            return step(xv, vals)

        x, chosen, counts = call_op(
            fn, x, *own, moe.select_bias,
            *[getattr(moe, n) for n in moe.names], op_name="gdn_moe_block")
        moe.advance(chosen._value, counts._value)
        return x


class GdnMoeModel(Layer):
    """Embedding -> blocks -> final norm weight (applied in the head's
    scope by GdnMoeForCausalLM). Returns hidden states [b, s, h]."""

    def __init__(self, cfg: GdnMoeConfig, seed: int = 0):
        super().__init__()
        self.config = cfg
        rs = np.random.default_rng(seed)
        self.embed_tokens = _draw(rs, "embed_tokens",
                                  (cfg.vocab_size, cfg.hidden_size), cfg)
        self.layers = LayerList([
            GdnMoeDecoderLayer(cfg, cfg.is_full_attention(i), rs)
            for i in range(cfg.num_hidden_layers)])
        self.final_norm_w = _draw(rs, "final_norm_w", (cfg.hidden_size,),
                                  cfg)
        self.lm_head_w = _draw(rs, "lm_head_w",
                               (cfg.hidden_size, cfg.vocab_size), cfg)

    def moe_layers(self) -> list:
        return [blk.moe for blk in self.layers]

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = call_op(lambda w, ids: jnp.take(w, ids, axis=0),
                        self.embed_tokens, input_ids, op_name="gdn_embed")
        for blk in self.layers:
            x = blk(x)
        return x


class GdnMoeForCausalLM(Layer):
    """The model with its untied head: logits [b, s, vocab_size]."""

    def __init__(self, config: GdnMoeConfig, seed: int = 0):
        super().__init__()
        with RecordEvent("model_init"):
            self.model = GdnMoeModel(config, seed=seed)
        self.config = config

    def forward(self, input_ids):
        x = self.model(input_ids)
        eps = self.config.rms_norm_eps
        with jax.named_scope("lm_head"):
            return call_op(
                lambda h, g, w: rms_norm_zero_centred(h, g, eps) @ w, x,
                self.model.final_norm_w, self.model.lm_head_w,
                op_name="gdn_logits")
