"""The LFM2 shaped decoder (LiquidAI LFM2-8B-A1B, `model_type: lfm2_moe`):
double-gated short-convolution layers beside grouped-head attention with a
norm on q and k, bias-selected sigmoid-routed experts with no shared
expert, a tied head, for training.

The fourth block beside gpt.py's, mla_moe.py's and gdn_moe.py's, on the
same train path (numpy weights from a seed, `paddle.optimizer.AdamW`,
`jit.TrainStep`, `GPTPretrainingCriterion`, the flash kernels of
ops/flash_attention.py and the recomputation policy `_policy_step`). What
it has of its own:

  * a mixer that is neither attention nor a recurrence, the short
    convolution, and a layer pattern given as an explicit list
    (`layer_types`: "conv" | "full_attention"), not an interval;
  * `num_dense_layers` leading layers whose feed-forward is a SwiGLU of
    `intermediate_size`; the others carry the expert block
    (distributed/moe.py DroplessMoELayer, `router="sigmoid"`,
    `shared_width=0`, `routed_scaling` 1);
  * a TIED head: the token table E is the embedding and the head, so its
    gradient is the sum of two uses;
  * no bias anywhere.

With N(x) = w * x / rms(x) (the plain RMS norm, w drawn ones):

    x += Mixer_i(N_op(x)); x += FFN_i(N_ffn(x)); logits = N_emb(x) E^T

**Short convolution**: [B | C | u] = x W_in, `in_w` [h, 3h], three whole
column blocks IN THAT ORDER (the released checkpoints' order is a
permutation of columns: with weights drawn from a seed the same model);
z = B * u; c_t = sum_j w_conv[:, j] * z_{t - (L - 1) + j}
(gdn_moe.causal_taps), depthwise over the h channels, L = `conv_L_cache`
taps, zeros left of the start, no bias, NO activation; y = (C * c) W_out.
The two products and the taps' sum in float32, the result in the model's
dtype.

**Attention**: n = `num_attention_heads` query heads over n_kv =
`num_key_value_heads`, d = `head_dim` wide; q and k each pass a plain RMS
norm over d (one weight vector for all heads: `q_norm_w`, `k_norm_w`);
rotary over ALL of d, half-split pairs (gdn_moe.rotary_half_split at
factor 1), theta `rope_theta`; causal softmax attention at 1 / sqrt(d)
through the flash kernels, query head h reading key/value head
h // (n / n_kv) by the kernels' index maps; ctx W_o.

**Experts**: s = sigmoid(x W_r) over all `router_outputs` in float32; the
top `num_experts_per_tok` of s + bias are chosen (the bias selects and
never weighs; it moves by `bias_update_speed` * sign(mean load - load)
from this chip's counts); weights s[chosen] / sum; the held experts'
SwiGLU on the tokens routed to them, what the absent experts would add
left out.

Conventions this module sets where the source's config.json is silent
(the configuration file lists them under `assumed`): `head_dim` =
`hidden_size` / `num_attention_heads`; the head tied (the row has no
`tie_word_embeddings`; the published 8.3 B total only adds up with one
table); every matrix and the taps N(0, `initializer_range`), norms ones;
no auxiliary loss.

Float32 whatever the model's dtype: the norms, rotary, the softmax
statistics (inside the kernel), the convolution's gates and sum, the
router.

Device-trace scopes, siblings of one another so that no op is booked
twice: `embed`; `short_conv` (a conv layer's mixer whole: input norm,
W_in, the two gates and the taps, W_out, the residual add); `attn` (the
attention layers' mixer only); `mlp` (the norm before the feed-forward and
the dense SwiGLU); `moe_router`, `moe_dispatch`, `moe_experts`,
`moe_combine`; `lm_head`; `loss` and `optimizer` come from jit.TrainStep.
Host span: `model_init` (RecordEvent). Counters: each expert layer's
`assign_count` and `touched_count` buffers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.moe import DroplessMoELayer, swiglu
from ..framework import dtype as dtype_mod
from ..framework.autograd import call_op
from ..framework.tensor import Tensor
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..profiler import RecordEvent
from .gdn_moe import causal_taps, rotary_half_split
from .gpt import _as_parameter, _local_attention_val, _policy_step
from .mla_moe import rms_norm

LAYER_TYPES = ("conv", "full_attention")
# LFM2-8B-A1B's published pattern: 18 short convolutions, 6 attention
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass
class Lfm2MoeConfig:
    """The keys of an `lfm2_moe` config.json under their own names.
    `num_experts` is how many experts this chip HOLDS (`experts_held` says
    which), `router_outputs` how many exist; `vocab_size` is the rows of
    the table held here; `layer_types` has one entry a layer."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None            # None: hidden_size / heads
    conv_L_cache: int = 3
    num_experts: int = 32
    router_outputs: Optional[int] = None      # None: num_experts
    experts_held: Optional[Tuple[int, int]] = None    # None: all of them
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    bias_update_speed: float = 0.001
    recompute: str = "none"          # "none" | "layer": whole-layer remat
    dtype: str = "float32"

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.router_outputs is None:
            self.router_outputs = self.num_experts
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(e) for e in self.experts_held)
        self.layer_types = tuple(self.layer_types)
        lo, hi = self.experts_held
        if hi - lo != self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not the "
                f"{self.num_experts} experts num_experts says")
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - set(LAYER_TYPES):
            raise ValueError(
                f"layer_types {self.layer_types}: one of {LAYER_TYPES} for "
                f"each of the {self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads are no whole "
                f"groups over {self.num_key_value_heads} key/value heads")
        if self.recompute not in ("none", "layer"):
            raise ValueError(f"recompute {self.recompute!r}: none or layer")


# --------------------------------------------------------------------------
# pure block math
# --------------------------------------------------------------------------

def short_conv(x, p: dict):
    """The short-convolution mixer on normalised x [b, s, h] (no
    residual): (C * taps(B * u)) W_out with [B | C | u] = x W_in."""
    h = x.shape[-1]
    bcu = (x @ p["in_w"]).astype(jnp.float32)
    gate_b, gate_c, u = bcu[..., :h], bcu[..., h:2 * h], bcu[..., 2 * h:]
    c = causal_taps(gate_b * u, p["conv_w"])
    return (gate_c * c).astype(x.dtype) @ p["out_w"]


def qk_norm_attention(x, p: dict, cfg: Lfm2MoeConfig):
    """The attention mixer on normalised x [b, s, h] (no residual)."""
    b, s, _ = x.shape
    n, n_kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = (x @ p["q_w"]).reshape(b, s, n, d)
    k = (x @ p["k_w"]).reshape(b, s, n_kv, d)
    v = (x @ p["v_w"]).reshape(b, s, n_kv, d)
    q = rotary_half_split(rms_norm(q, p["q_norm_w"], cfg.norm_eps),
                          cfg.rope_theta, 1.0)
    k = rotary_half_split(rms_norm(k, p["k_norm_w"], cfg.norm_eps),
                          cfg.rope_theta, 1.0)
    ctx = _local_attention_val(q, k, v, True)
    return ctx.reshape(b, s, n * d) @ p["o_w"]


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _mixer_shapes(cfg: Lfm2MoeConfig, layer_type: str) -> dict:
    h = cfg.hidden_size
    if layer_type == "conv":
        return {"op_norm_w": (h,), "in_w": (h, 3 * h),
                "conv_w": (h, cfg.conv_L_cache), "out_w": (h, h),
                "ffn_norm_w": (h,)}
    n, n_kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    return {"op_norm_w": (h,), "q_w": (h, n * d), "k_w": (h, n_kv * d),
            "v_w": (h, n_kv * d), "q_norm_w": (d,), "k_norm_w": (d,),
            "o_w": (n * d, h), "ffn_norm_w": (h,)}


def _dense_shapes(cfg: Lfm2MoeConfig) -> dict:
    h, f = cfg.hidden_size, cfg.intermediate_size
    return {"gate_w": (h, f), "up_w": (h, f), "down_w": (f, h)}


def _draw(rs, shape, cfg: Lfm2MoeConfig):
    """A parameter in the model's dtype: ones for a norm's vector, else
    (the convolution's taps too) normal(0, initializer_range) from the
    numpy Generator `rs`."""
    w = np.ones(shape, np.float32) if len(shape) == 1 else \
        rs.standard_normal(shape, dtype=np.float32) * cfg.initializer_range
    return _as_parameter(
        Tensor(w, dtype=dtype_mod.convert_dtype(cfg.dtype)), None)


class Lfm2MoeDecoderLayer(Layer):
    """One block: the mixer `layer_type` names, then a SwiGLU of
    `intermediate_size` where `dense`, else the expert block (`self.moe`),
    whose buffers and chosen experts advance with every forward."""

    def __init__(self, cfg: Lfm2MoeConfig, layer_type: str, dense: bool, rs):
        super().__init__()
        self.cfg = cfg
        self.layer_type = layer_type
        shapes = _mixer_shapes(cfg, layer_type)
        if dense:
            shapes.update(_dense_shapes(cfg))
        for name, shape in shapes.items():
            setattr(self, name, _draw(rs, shape, cfg))
        self.names = tuple(shapes)
        self.moe = None if dense else DroplessMoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.router_outputs,
            cfg.num_experts_per_tok, experts_held=cfg.experts_held,
            shared_width=0, routed_scaling=cfg.routed_scaling_factor,
            bias_speed=cfg.bias_update_speed,
            init_std=cfg.initializer_range, dtype=cfg.dtype, rs=rs,
            router="sigmoid")

    def forward(self, x):
        cfg, moe = self.cfg, self.moe
        own = [getattr(self, n) for n in self.names]
        n_own = len(own)

        def block(xv, vals):
            p = dict(zip(self.names, vals[:n_own]))
            if self.layer_type == "conv":
                with jax.named_scope("short_conv"):
                    xv = xv + short_conv(
                        rms_norm(xv, p["op_norm_w"], cfg.norm_eps), p)
            else:
                with jax.named_scope("attn"):
                    xv = xv + qk_norm_attention(
                        rms_norm(xv, p["op_norm_w"], cfg.norm_eps), p, cfg)
            with jax.named_scope("mlp"):
                hn = rms_norm(xv, p["ffn_norm_w"], cfg.norm_eps)
                if moe is None:
                    return xv + swiglu(hn, p["gate_w"], p["up_w"],
                                       p["down_w"])
            hn = hn.reshape(-1, hn.shape[-1])
            y, chosen, counts = moe.apply_val(hn, vals[n_own + 1:],
                                              vals[n_own])
            return xv + y.reshape(xv.shape), chosen, counts

        step = _policy_step(
            block, "remat" if cfg.recompute == "layer" else "none")

        def fn(xv, *vals):
            return step(xv, vals)

        if moe is None:
            return call_op(fn, x, *own, op_name="lfm2_dense_block")
        x, chosen, counts = call_op(
            fn, x, *own, moe.select_bias,
            *[getattr(moe, n) for n in moe.names], op_name="lfm2_moe_block")
        moe.advance(chosen._value, counts._value)
        return x


class Lfm2MoeModel(Layer):
    """Token table -> blocks -> final norm weight (applied in the head's
    scope by Lfm2MoeForCausalLM). Returns hidden states [b, s, h]."""

    def __init__(self, cfg: Lfm2MoeConfig, seed: int = 0):
        super().__init__()
        self.config = cfg
        rs = np.random.default_rng(seed)
        self.embed_tokens = _draw(rs, (cfg.vocab_size, cfg.hidden_size), cfg)
        self.layers = LayerList([
            Lfm2MoeDecoderLayer(cfg, kind, i < cfg.num_dense_layers, rs)
            for i, kind in enumerate(cfg.layer_types)])
        self.final_norm_w = _draw(rs, (cfg.hidden_size,), cfg)

    def moe_layers(self) -> list:
        return [blk.moe for blk in self.layers if blk.moe is not None]

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = call_op(lambda w, ids: jnp.take(w, ids, axis=0),
                        self.embed_tokens, input_ids, op_name="lfm2_embed")
        for blk in self.layers:
            x = blk(x)
        return x


class Lfm2MoeForCausalLM(Layer):
    """The model with its tied head: logits [b, s, vocab_size] =
    N_emb(x) E^T, E the token table the embedding reads."""

    def __init__(self, config: Lfm2MoeConfig, seed: int = 0):
        super().__init__()
        with RecordEvent("model_init"):
            self.model = Lfm2MoeModel(config, seed=seed)
        self.config = config

    def forward(self, input_ids):
        x = self.model(input_ids)
        eps = self.config.norm_eps
        with jax.named_scope("lm_head"):
            return call_op(lambda h, g, w: rms_norm(h, g, eps) @ w.T, x,
                           self.model.final_norm_w, self.model.embed_tokens,
                           op_name="lfm2_logits")
