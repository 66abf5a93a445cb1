"""The DeepSeek-V3 shaped decoder (kanana-2, `model_type: deepseek_v3`):
latent attention and sigmoid-routed experts, for training.

Beside gpt.py's block, which it shares the train path with (numpy weights
from a seed, `paddle.optimizer.AdamW`, `jit.TrainStep`,
`GPTPretrainingCriterion`, the flash kernels of ops/flash_attention.py and
the recomputation policy `_policy_step`), this one has: RMS norm, no bias
anywhere, rotary positions on a `qk_rope_head_dim`-wide part of each head
over adjacent pairs (2i, 2i+1), latent attention (keys and values made from
one normalised `kv_lora_rank`-wide latent per token plus one rotary key
shared by all heads; q and k are qk_nope + qk_rope wide, v is v_head_dim
wide), SwiGLU feed-forwards, `first_k_dense_replace` leading dense layers
and then expert layers (distributed/moe.py DroplessMoELayer: this chip's
range of the experts, routed over all of them, plus the shared experts as
one SwiGLU), and an untied head.

Float32 whatever the model's dtype: the RMS norms, the rotary rotation,
the softmax statistics (inside the kernel), the router's scores, top-k and
weights, and the selection bias's update.

    x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)); logits = RMSNorm(x) W_head

Device-trace scopes, siblings of one another so that no op is booked
twice: `embed`, `attn`, `mlp` (the dense feed-forward and the shared
experts), `moe_router`, `moe_dispatch`, `moe_experts`, `moe_combine`,
`lm_head`; `loss` and `optimizer` come from jit.TrainStep.
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
from .gpt import _as_parameter, _local_attention_val, _policy_step


@dataclasses.dataclass
class MlaMoeConfig:
    """The keys of a `deepseek_v3` config.json under their own names.
    `n_routed_experts` is how many experts this chip HOLDS (`experts_held`
    says which), `router_outputs` how many exist; `vocab_size` is the rows
    of the vocabulary held here."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    router_outputs: Optional[int] = None      # None: n_routed_experts
    experts_held: Optional[Tuple[int, int]] = None    # None: all of them
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    bias_update_speed: float = 0.001
    recompute: str = "none"          # "none" | "layer": whole-layer remat
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_outputs is None:
            self.router_outputs = self.n_routed_experts
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(int(e) for e in self.experts_held)
        lo, hi = self.experts_held
        if hi - lo != self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not the "
                f"{self.n_routed_experts} experts n_routed_experts says")
        if self.recompute not in ("none", "layer"):
            raise ValueError(f"recompute {self.recompute!r}: none or layer")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# --------------------------------------------------------------------------
# pure block math
# --------------------------------------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def rotary_interleaved(x, theta: float):
    """Rotary positions on x [b, s, ..., r] over adjacent pairs: pair i =
    (x[2i], x[2i+1]) turns by pos * theta^(-2i/r). Float32 inside."""
    s, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    shape = (1, s) + (1,) * (x.ndim - 3) + (r // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def latent_attention(x, p: dict, cfg: MlaMoeConfig):
    """Attn(x) on normalised x [b, s, h] (no residual)."""
    b, s, _ = x.shape
    n, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim)
    q = (x @ p["q_w"]).reshape(b, s, n, dn + dr)
    kva = x @ p["kva_w"]
    latent = rms_norm(kva[..., :cfg.kv_lora_rank], p["kv_norm_w"],
                      cfg.rms_norm_eps)
    k_pe = rotary_interleaved(kva[..., cfg.kv_lora_rank:], cfg.rope_theta)
    kv = (latent @ p["kvb_w"]).reshape(b, s, n, dn + cfg.v_head_dim)
    q = jnp.concatenate(
        [q[..., :dn], rotary_interleaved(q[..., dn:], cfg.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None, :], (b, s, n, dr))],
        -1)
    ctx = _local_attention_val(q, k, kv[..., dn:], True)
    return ctx.reshape(b, s, n * cfg.v_head_dim) @ p["o_w"]


def _draw(rs, shape, cfg: MlaMoeConfig):
    """A parameter in the model's dtype: ones for a norm's vector, else
    normal(0, initializer_range) from the numpy Generator `rs`."""
    w = np.ones(shape, np.float32) if len(shape) == 1 else \
        rs.standard_normal(shape, dtype=np.float32) * cfg.initializer_range
    return _as_parameter(
        Tensor(w, dtype=dtype_mod.convert_dtype(cfg.dtype)), None)


ATTN_PARAMS = ("attn_norm_w", "q_w", "kva_w", "kv_norm_w", "kvb_w", "o_w",
               "ffn_norm_w")
DENSE_PARAMS = ("gate_w", "up_w", "down_w")


def _attn_shapes(cfg: MlaMoeConfig) -> dict:
    h, n = cfg.hidden_size, cfg.num_attention_heads
    return {"attn_norm_w": (h,), "q_w": (h, n * cfg.qk_head_dim),
            "kva_w": (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm_w": (cfg.kv_lora_rank,),
            "kvb_w": (cfg.kv_lora_rank,
                      n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "o_w": (n * cfg.v_head_dim, h), "ffn_norm_w": (h,)}


class MlaMoeDecoderLayer(Layer):
    """One block. `dense` layers carry a SwiGLU of `intermediate_size`,
    the others a DroplessMoELayer (`self.moe`) whose buffers and chosen
    experts advance with every forward."""

    def __init__(self, cfg: MlaMoeConfig, dense: bool, rs):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        for name, shape in _attn_shapes(cfg).items():
            setattr(self, name, _draw(rs, shape, cfg))
        self.names = ATTN_PARAMS
        self.moe = None
        if dense:
            f = cfg.intermediate_size
            for name, shape in zip(DENSE_PARAMS, ((h, f), (h, f), (f, h))):
                setattr(self, name, _draw(rs, shape, cfg))
            self.names = ATTN_PARAMS + DENSE_PARAMS
        else:
            self.moe = DroplessMoELayer(
                h, cfg.moe_intermediate_size, cfg.router_outputs,
                cfg.num_experts_per_tok, experts_held=cfg.experts_held,
                shared_width=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                routed_scaling=cfg.routed_scaling_factor,
                bias_speed=cfg.bias_update_speed,
                init_std=cfg.initializer_range,
                dtype=cfg.dtype, rs=rs)

    def forward(self, x):
        cfg, moe = self.cfg, self.moe
        own = [getattr(self, n) for n in self.names]
        n_own = len(own)

        def block(xv, vals):
            p = dict(zip(self.names, vals[:n_own]))
            with jax.named_scope("attn"):
                xv = xv + latent_attention(
                    rms_norm(xv, p["attn_norm_w"], cfg.rms_norm_eps), p, cfg)
            with jax.named_scope("mlp"):
                hn = rms_norm(xv, p["ffn_norm_w"], cfg.rms_norm_eps)
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
            return call_op(fn, x, *own, op_name="mla_dense_block")
        x, chosen, counts = call_op(
            fn, x, *own, moe.select_bias,
            *[getattr(moe, n) for n in moe.names], op_name="mla_moe_block")
        moe.advance(chosen._value, counts._value)
        return x


class MlaMoeModel(Layer):
    """Embedding -> blocks -> final RMS norm weight (applied in the head's
    scope by MlaMoeForCausalLM). Returns hidden states [b, s, h]."""

    def __init__(self, cfg: MlaMoeConfig, seed: int = 0):
        super().__init__()
        self.config = cfg
        rs = np.random.default_rng(seed)
        self.embed_tokens = _draw(rs, (cfg.vocab_size, cfg.hidden_size), cfg)
        self.layers = LayerList([
            MlaMoeDecoderLayer(cfg, i < cfg.first_k_dense_replace, rs)
            for i in range(cfg.num_hidden_layers)])
        self.final_norm_w = _draw(rs, (cfg.hidden_size,), cfg)
        self.lm_head_w = _draw(rs, (cfg.hidden_size, cfg.vocab_size), cfg)

    def moe_layers(self) -> list:
        return [blk.moe for blk in self.layers if blk.moe is not None]

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = call_op(lambda w, ids: jnp.take(w, ids, axis=0),
                        self.embed_tokens, input_ids, op_name="mla_embed")
        for blk in self.layers:
            x = blk(x)
        return x


class MlaMoeForCausalLM(Layer):
    """The model with its untied head: logits [b, s, vocab_size]."""

    def __init__(self, config: MlaMoeConfig, seed: int = 0):
        super().__init__()
        with RecordEvent("model_init"):
            self.model = MlaMoeModel(config, seed=seed)
        self.config = config

    def forward(self, input_ids):
        x = self.model(input_ids)
        eps = self.config.rms_norm_eps
        with jax.named_scope("lm_head"):
            return call_op(lambda h, g, w: rms_norm(h, g, eps) @ w, x,
                           self.model.final_norm_w, self.model.lm_head_w,
                           op_name="mla_logits")
