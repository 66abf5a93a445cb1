"""GPT — decoder-only LM, the flagship hybrid-parallel model.

Reference precedent: the GPT used by the fleet hybrid tests
(unittests/hybrid_parallel_gpt_*.py via PaddleNLP) built on
meta_parallel/parallel_layers/mp_layers.py (Vocab/Column/RowParallelLinear) and
pp_layers.py (PipelineLayer). TPU-native design:

- ONE logical model; parallelism is carried by PartitionSpecs on parameters and
  sharding constraints on activations over the hybrid mesh axes
  [data, pipe, sharding, sep, model] (distributed/mesh.py). GSPMD emits the
  Megatron collectives; the reference's explicit c_* ops dissolve.
- TP: fused qkv + fc1 are column-sharded ('model'), out-proj + fc2 row-sharded;
  vocab embedding row-sharded; logits stay vocab-sharded into the loss
  (reference: c_softmax_with_cross_entropy).
- PP: `mode="scan"` stacks the L identical blocks on a leading 'layers' dim
  sharded over 'pipe' and runs them with lax.scan — per-stage weights live on
  their pipe group only (reference SectionWorker/PipelineLayer, re-designed
  as SPMD scan instead of p2p 1F1B).
- SP: activations' sequence dim sharded over 'sep'; attention runs ring
  attention over 'sep' (net-new vs reference, SURVEY.md §5 long-context gap).
- Recompute: jax.checkpoint around each block (reference:
  fleet/utils/recompute.py).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed import collective as coll
from ..distributed import mesh as mesh_mod
from ..framework import dtype as dtype_mod
from ..framework.autograd import call_op
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.common import Dropout, Embedding
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm
from ..profiler import RecordEvent
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("data", "sharding")  # batch is sharded over dp × zero-dp
SEQ_AXIS = "sep"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    max_position_embeddings: int = 1024
    dropout: float = 0.0
    attn_dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    mode: str = "loop"  # "loop" (unrolled blocks) | "scan" (pipe-stacked)
    recompute: bool = False
    # per-layer activation policy ("none" | "remat" | "offload"), the
    # planner-chosen refinement of the boolean `recompute` (ISSUE 15):
    # length num_layers, or layers-per-stage for the pipelined path (a
    # full-length vector must then tile uniformly across stages — the
    # schedule is ONE SPMD program, stages cannot differ). None defers to
    # `recompute` (True = all-"remat"). "offload" saves the block input in
    # host memory (jax.checkpoint whose carried residual lives in the
    # offload tier; see distributed/pipeline/memory_plan.py for when that
    # buys real bytes).
    recompute_policy: Optional[tuple] = None
    sequence_parallel: bool = False
    use_ring_attention: bool = False
    # 'sep'-axis SP via all_to_all head/sequence swap instead of the ring
    # (DeepSpeed-Ulysses scheme; heads must divide by sep degree)
    use_ulysses_attention: bool = False
    use_flash_attention: bool = True  # pallas kernel on TPU when shapes allow
    pp_microbatches: int = 0  # pipeline micro-batches (0 = pipe degree)
    # >0: forward(input_ids, labels=...) computes the LM loss by chunked
    # fused linear+CE over the tied embedding — the [b*s, vocab] logits are
    # never materialized (incubate fused_linear_cross_entropy)
    fused_loss_chunk: int = 0
    dtype: str = "float32"

    def __post_init__(self):
        if self.use_ring_attention and self.use_ulysses_attention:
            raise ValueError(
                "use_ring_attention and use_ulysses_attention are mutually "
                "exclusive sequence-parallel schemes — pick one")
        if self.recompute_policy is not None:
            pol = tuple(self.recompute_policy)
            bad = [p for p in pol if p not in ("none", "remat", "offload")]
            if bad:
                raise ValueError(
                    f"recompute_policy entries must be one of "
                    f"none/remat/offload, got {bad}")
            if self.num_layers % max(1, len(pol)):
                raise ValueError(
                    f"recompute_policy length {len(pol)} does not tile "
                    f"num_layers={self.num_layers}")
            self.recompute_policy = pol

    @property
    def ffn(self):
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def gpt_presets(name: str, **overrides) -> GPTConfig:
    presets = {
        "gpt-test": dict(vocab_size=256, hidden_size=64, num_layers=2,
                         num_heads=4, max_position_embeddings=128),
        "gpt-125m": dict(vocab_size=50304, hidden_size=768, num_layers=12,
                         num_heads=12, max_position_embeddings=1024),
        "gpt-350m": dict(vocab_size=50304, hidden_size=1024, num_layers=24,
                         num_heads=16, max_position_embeddings=1024),
        "gpt-760m": dict(vocab_size=50304, hidden_size=1536, num_layers=24,
                         num_heads=16, max_position_embeddings=2048),
        "gpt-1.3b": dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                         num_heads=16, max_position_embeddings=2048),
    }
    cfg = dict(presets[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


# --------------------------------------------------------------------------
# pure block math, shared by loop and scan modes
# --------------------------------------------------------------------------

def _constrain_val(v, *spec):
    m = mesh_mod.get_mesh()
    if m is None:
        return v
    # axes the surrounding trace maps manually (a shard_map body — e.g.
    # TrainStep's explicit-SPMD quantized-grad path) cannot be constrained
    # again: the body already sees its per-device block
    manual = mesh_mod.manual_axis_names()

    def keep(a):
        return a in m.axis_names and a not in manual

    spec = tuple(
        (s if keep(s) else None) if isinstance(s, str)
        else (tuple(a for a in s if keep(a)) or None)
        if isinstance(s, tuple) else s
        for s in spec
    )
    if not any(s is not None for s in spec):
        return v
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(v, NamedSharding(m, P(*spec)))


def _flash_sharded(q, k, v):
    """Pallas flash kernel, wrapped in shard_map when a mesh is active so the
    custom call stays SPMD (GSPMD can't partition a pallas_call on its own —
    without this it would all-gather the head-sharded q/k/v). The wrapping
    lives in ops.flash_attention_val_auto, shared with the nn sdpa path."""
    from ..ops.flash_attention import flash_attention_val_auto

    return flash_attention_val_auto(q, k, v, causal=True)


def _attention_val(q, k, v, cfg: GPTConfig):
    """[b, s, n, d] causal attention at value level."""
    if cfg.use_ring_attention and mesh_mod.axis_size(SEQ_AXIS) > 1:
        from ..distributed.ring_attention import ring_attention_val

        return ring_attention_val(q, k, v, axis=SEQ_AXIS, causal=True)
    if cfg.use_ulysses_attention and mesh_mod.axis_size(SEQ_AXIS) > 1:
        from ..distributed.ulysses import ulysses_attention_val

        return ulysses_attention_val(
            q, k, v, axis=SEQ_AXIS, causal=True,
            use_flash=cfg.use_flash_attention and cfg.attn_dropout == 0.0)
    return _local_attention_val(
        q, k, v, cfg.use_flash_attention and cfg.attn_dropout == 0.0)


def _local_attention_val(q, k, v, use_flash: bool):
    """Causal attention over the whole sequence on this device: q
    [b, s, n, d_qk], k [b, s, n_kv, d_qk], v [b, s, n_kv, d_v], query head
    h reading k / v head h // (n // n_kv). The Pallas flash kernel on a TPU
    where its shape gate allows, else the O(s^2) einsum with a float32
    softmax."""
    from ..framework.target import target_platform

    n, n_kv = q.shape[2], k.shape[2]
    if use_flash and target_platform() == "tpu":
        from ..ops.flash_attention import flash_attention_sharded_ok

        if flash_attention_sharded_ok(q.shape, n_kv):
            return _flash_sharded(q, k, v)
    # the query heads as [n_kv, group], so that k and v are used as they are
    b, ql, _, d = q.shape
    qg = q.reshape(b, ql, n_kv, n // n_kv, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (1.0 / math.sqrt(d))
    kl = logits.shape[-1]
    causal = jnp.tril(jnp.ones((ql, kl), dtype=bool), k=kl - ql)
    logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(v.dtype)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return ctx.reshape(b, ql, n, v.shape[-1])


def _block_apply(pd: dict, x, cfg: GPTConfig):
    """One transformer block. pd maps name → raw array (one layer's slice)."""
    b, s, h = x.shape
    n, d = cfg.num_heads, cfg.head_dim
    eps = cfg.layer_norm_epsilon

    def ln(v, w, bi):
        mu = jnp.mean(v.astype(jnp.float32), axis=-1, keepdims=True)
        var = jnp.var(v.astype(jnp.float32), axis=-1, keepdims=True)
        out = (v.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
        return (out * w + bi).astype(v.dtype)

    # the scopes carry no layer index: a trace's reader adds the layers up
    with jax.named_scope("attn"):
        hn = ln(x, pd["ln1_w"], pd["ln1_b"])
        qkv = jnp.einsum("bsh,hcj->bscj", hn, pd["qkv_w"]) + pd["qkv_b"]
        qkv = qkv.reshape(b, s, 3, n, d)  # [b,s,3,H] col-sharded on 'model'
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = _constrain_val(q, BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None)
        k = _constrain_val(k, BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None)
        v = _constrain_val(v, BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None)
        attn = _attention_val(q, k, v, cfg)
        attn = attn.reshape(b, s, h)
        y = attn @ pd["out_w"] + pd["out_b"]  # row-sharded: GSPMD allreduces
        x = x + y
        x = _constrain_val(x, BATCH_AXES, SEQ_AXIS, None)

    with jax.named_scope("mlp"):
        hn = ln(x, pd["ln2_w"], pd["ln2_b"])
        z = hn @ pd["fc1_w"] + pd["fc1_b"]
        z = jax.nn.gelu(z, approximate=True)
        z = z @ pd["fc2_w"] + pd["fc2_b"]
        x = x + z
        return _constrain_val(x, BATCH_AXES, SEQ_AXIS, None)


def _block_apply_manual(pd: dict, x, cfg: GPTConfig, mesh):
    """One transformer block INSIDE a shard_map manual region (the pipeline
    path). Explicit Megatron TP — qkv/fc1 are column-sharded local slices,
    out/fc2 row-sharded with a psum over 'model' (the c_allreduce_sum the
    reference emits, mp_layers.py) — and ring attention over 'sep'."""
    b, s, _ = x.shape
    d = cfg.head_dim
    eps = cfg.layer_norm_epsilon
    has_model = MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1
    has_sep = SEQ_AXIS in mesh.axis_names and mesh.shape[SEQ_AXIS] > 1

    def ln(v, w, bi):
        mu = jnp.mean(v.astype(jnp.float32), axis=-1, keepdims=True)
        var = jnp.var(v.astype(jnp.float32), axis=-1, keepdims=True)
        out = (v.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
        return (out * w + bi).astype(v.dtype)

    with jax.named_scope("attn"):
        hn = ln(x, pd["ln1_w"], pd["ln1_b"])
        qkv = jnp.einsum("bsh,hcj->bscj", hn, pd["qkv_w"]) + pd["qkv_b"]
        n_loc = qkv.shape[-1] // d                    # local head count (H/mp)/d
        qkv = qkv.reshape(b, s, 3, n_loc, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if has_sep:
            if cfg.use_ulysses_attention:
                from ..distributed.ulysses import ulysses_attention_manual

                attn = ulysses_attention_manual(
                    q, k, v, SEQ_AXIS, causal=True,
                    use_flash=(cfg.use_flash_attention
                               and cfg.attn_dropout == 0.0))
            else:
                from ..distributed.ring_attention import ring_attention_manual

                attn = ring_attention_manual(q, k, v, SEQ_AXIS,
                                             mesh.shape[SEQ_AXIS], causal=True)
        else:
            attn = None
            from ..framework.target import target_platform

            if (cfg.use_flash_attention and cfg.attn_dropout == 0.0
                    and target_platform() == "tpu"):
                from ..ops.flash_attention import (
                    flash_attention_supported, flash_attention_val,
                )

                if flash_attention_supported(q.shape):
                    attn = flash_attention_val(q, k, v, causal=True)
            if attn is None:
                scale = 1.0 / math.sqrt(d)
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
                causal = jnp.tril(jnp.ones((s, s), dtype=bool))
                logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
                probs = jax.nn.softmax(logits.astype(jnp.float32),
                                       axis=-1).astype(v.dtype)
                attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        attn = attn.reshape(b, s, n_loc * d)
        y = attn @ pd["out_w"]                        # row-sharded: partial sums
        if has_model:
            y = coll.in_trace_psum(y, MODEL_AXIS)
        x = x + y + pd["out_b"]

    with jax.named_scope("mlp"):
        hn = ln(x, pd["ln2_w"], pd["ln2_b"])
        z = hn @ pd["fc1_w"] + pd["fc1_b"]
        z = jax.nn.gelu(z, approximate=True)
        z = z @ pd["fc2_w"]
        if has_model:
            z = coll.in_trace_psum(z, MODEL_AXIS)
        return x + z + pd["fc2_b"]


_BLOCK_PARAMS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
                 "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def _block_shapes(cfg: GPTConfig):
    h, f = cfg.hidden_size, cfg.ffn
    return {
        "ln1_w": ([h], None), "ln1_b": ([h], None),
        # qkv packed as [h, 3(q|k|v), h] so a 'model'-axis shard of the LAST
        # dim slices q, k and v heads consistently (a flat [h, 3h] chunk
        # would mix all of q with part of k under manual TP)
        "qkv_w": ([h, 3, h], P(None, None, MODEL_AXIS)),
        "qkv_b": ([3, h], P(None, MODEL_AXIS)),
        "out_w": ([h, h], P(MODEL_AXIS, None)), "out_b": ([h], None),
        "ln2_w": ([h], None), "ln2_b": ([h], None),
        "fc1_w": ([h, f], P(None, MODEL_AXIS)), "fc1_b": ([f], P(MODEL_AXIS)),
        "fc2_w": ([f, h], P(MODEL_AXIS, None)), "fc2_b": ([h], None),
    }


def _block_init(name, shape, cfg: GPTConfig, rs: np.random.RandomState):
    if name.startswith("ln") and name.endswith("_w"):
        return np.ones(shape, dtype="float32")
    if name.endswith("_b"):
        return np.zeros(shape, dtype="float32")
    std = cfg.initializer_range
    if name in ("out_w", "fc2_w"):
        # GPT-2 residual-projection scaling: std / sqrt(2*L)
        std = std / math.sqrt(2.0 * cfg.num_layers)
    return (rs.randn(*shape) * std).astype("float32")


def _resolve_policies(cfg: GPTConfig, n_layers: int):
    """Per-layer activation policies for a stack of `n_layers` scanned
    blocks (the whole model, or one pipeline stage's slice). A
    full-model-length vector collapses onto a stage slice only when it
    tiles uniformly — the SPMD schedule runs ONE stage program."""
    pol = cfg.recompute_policy
    if pol is None:
        return ("remat" if cfg.recompute else "none",) * n_layers
    if len(pol) == n_layers:
        return tuple(pol)
    if len(pol) % n_layers == 0:
        # full-length vector over a stage slice: must tile uniformly
        for s in range(0, len(pol), n_layers):
            if tuple(pol[s:s + n_layers]) != tuple(pol[:n_layers]):
                raise ValueError(
                    f"recompute_policy {pol} varies across pipeline "
                    f"stages of {n_layers} layers; the SPMD schedule "
                    f"runs one stage program — use a uniform per-stage "
                    f"vector")
        return tuple(pol[:n_layers])
    if n_layers % len(pol) == 0:
        return tuple(pol) * (n_layers // len(pol))
    raise ValueError(
        f"recompute_policy length {len(pol)} does not tile {n_layers} "
        f"layers")


def _policy_step(apply_full, policy: str):
    """Wrap one scanned-block step `apply_full(carry, slices) -> carry`
    with its activation policy. "remat" is the classic jax.checkpoint;
    "offload" additionally parks the saved block input in the offload
    memory space, so the residual jax keeps for the backward is the
    host-resident copy (the device copy is transient)."""
    if policy == "remat":
        return jax.checkpoint(apply_full)
    if policy == "offload":
        from ..distributed.pipeline.memory_plan import OFFLOAD_KIND
        from ..distributed.pipeline.schedule import _to_memory_kind

        def run(carry, slices):
            c_host = _to_memory_kind(carry, OFFLOAD_KIND)

            def inner(c2, sl):
                return apply_full(_to_memory_kind(c2, "device"), sl)

            return jax.checkpoint(inner)(c_host, slices)

        return run
    return apply_full


def _scan_policied(apply_full, stacked, x, policies):
    """lax.scan the stacked block params over `x`, one scan segment per
    contiguous run of equal policy — the lowering of the planner's
    per-layer vector onto scanned blocks (a single scan has one body, so
    heterogeneous policies become consecutive homogeneous scans)."""
    runs = []
    for p in policies:
        if runs and runs[-1][0] == p:
            runs[-1][1] += 1
        else:
            runs.append([p, 1])
    off = 0
    for pol, cnt in runs:
        seg = tuple(a[off:off + cnt] for a in stacked)
        step = _policy_step(apply_full, pol)
        x, _ = jax.lax.scan(lambda c, s: (step(c, s), None), x, seg)
        off += cnt
    return x


class GPTDecoderLayer(Layer):
    """Loop-mode block: individually named parameters, TP dist_specs."""

    def __init__(self, cfg: GPTConfig, rs: np.random.RandomState):
        super().__init__()
        self.cfg = cfg
        dt = dtype_mod.convert_dtype(cfg.dtype)
        for name, (shape, spec) in _block_shapes(cfg).items():
            p = Tensor(_block_init(name, shape, cfg, rs), dtype=dt)
            param = _as_parameter(p, spec)
            setattr(self, name, param)

    def forward(self, x):
        pd = {n: getattr(self, n)._value for n in _BLOCK_PARAMS}

        def fn(xv, *pvals):
            d = dict(zip(_BLOCK_PARAMS, pvals))
            body = partial(_block_apply, d, cfg=self.cfg)
            if self.cfg.recompute:
                body = jax.checkpoint(body)
            return body(xv)

        return call_op(fn, x, *[getattr(self, n) for n in _BLOCK_PARAMS],
                       op_name="gpt_block")


def _as_parameter(t: Tensor, spec):
    from ..framework.tensor import Parameter

    p = Parameter(t._value, trainable=True)
    if spec is not None:
        p.dist_spec = spec
        p.is_distributed = True
    return p


class GPTScanDecoder(Layer):
    """Scan-mode stack: each block parameter stacked on a leading 'layers'
    dim sharded over 'pipe' — pipeline-parallel weight placement, executed as
    lax.scan (reference PipelineLayer re-designed SPMD)."""

    def __init__(self, cfg: GPTConfig, rs: np.random.RandomState):
        super().__init__()
        self.cfg = cfg
        dt = dtype_mod.convert_dtype(cfg.dtype)
        L = cfg.num_layers
        shapes = _block_shapes(cfg)
        # draw layer-major so loop and scan modes share bit-identical init
        per_layer = [
            {name: _block_init(name, shape, cfg, rs)
             for name, (shape, _) in shapes.items()}
            for _ in range(L)
        ]
        for name, (shape, spec) in shapes.items():
            stacked = np.stack([per_layer[l][name] for l in range(L)])
            base = spec if spec is not None else P(*([None] * len(shape)))
            pipe_spec = P(PIPE_AXIS, *base)
            setattr(self, name, _as_parameter(Tensor(stacked, dtype=dt), pipe_spec))

    def forward(self, x):
        cfg = self.cfg
        mesh = mesh_mod.get_mesh()
        if mesh is not None and mesh_mod.axis_size(PIPE_AXIS) > 1:
            return self._forward_pipelined(x, mesh)

        def fn(xv, *stacked):
            def apply_full(carry, layer_slices):
                d = dict(zip(_BLOCK_PARAMS, layer_slices))
                return _block_apply(d, carry, cfg=cfg)

            return _scan_policied(apply_full, tuple(stacked), xv,
                                  _resolve_policies(cfg, cfg.num_layers))

        return call_op(fn, x, *[getattr(self, n) for n in _BLOCK_PARAMS],
                       op_name="gpt_scan_stack")

    def _forward_pipelined(self, x, mesh):
        """Micro-batched collective-permute pipeline over the 'pipe' axis
        (distributed/pipeline.py) — the reference's 1F1B train_batch schedule
        (pipeline_parallel.py:80-150) as one SPMD program."""
        from ..distributed.pipeline import pipeline_spmd

        cfg = self.cfg
        shapes = _block_shapes(cfg)
        specs = []
        for name in _BLOCK_PARAMS:
            shape, spec = shapes[name]
            base = spec if spec is not None else P(*([None] * len(shape)))
            specs.append(mesh_mod.sanitize_spec(P(PIPE_AXIS, *base), mesh))

        pipe_deg = int(mesh.shape[PIPE_AXIS])
        stage_policies = _resolve_policies(cfg, cfg.num_layers // pipe_deg)

        def fn(xv, *stacked):
            def stage(params_local, mb):
                def apply_full(carry, layer_slices):
                    d = dict(zip(_BLOCK_PARAMS, layer_slices))
                    return _block_apply_manual(d, carry, cfg=cfg, mesh=mesh)

                return _scan_policied(apply_full, tuple(params_local), mb,
                                      stage_policies)

            return pipeline_spmd(
                stage, stacked, xv, mesh=mesh, param_specs=specs,
                microbatches=cfg.pp_microbatches or None)

        return call_op(fn, x, *[getattr(self, n) for n in _BLOCK_PARAMS],
                       op_name="gpt_pipeline_1f1b")


class GPTEmbeddings(Layer):
    """Vocab-parallel word embedding + learned position embedding."""

    def __init__(self, cfg: GPTConfig, rs: np.random.RandomState):
        super().__init__()
        dt = dtype_mod.convert_dtype(cfg.dtype)
        std = cfg.initializer_range
        self.word_embeddings = _as_parameter(
            Tensor((rs.randn(cfg.vocab_size, cfg.hidden_size) * std
                    ).astype("float32"), dtype=dt),
            P(MODEL_AXIS, None))
        self.position_embeddings = _as_parameter(
            Tensor((rs.randn(cfg.max_position_embeddings, cfg.hidden_size) * std
                    ).astype("float32"), dtype=dt),
            None)
        self.dropout = Dropout(cfg.dropout)
        self.cfg = cfg

    def forward(self, input_ids, position_ids=None):
        def fn(w, pos, ids):
            emb = jnp.take(w, ids, axis=0)
            s = ids.shape[-1]
            pe = jax.lax.dynamic_slice_in_dim(pos, 0, s, axis=0)
            return emb + pe

        with jax.named_scope("embed"):
            if position_ids is None:
                x = call_op(fn, self.word_embeddings,
                            self.position_embeddings, input_ids,
                            op_name="gpt_embed")
            else:
                x = call_op(
                    lambda w, pos, ids, pid: (jnp.take(w, ids, 0)
                                              + jnp.take(pos, pid, 0)),
                    self.word_embeddings, self.position_embeddings,
                    input_ids, position_ids, op_name="gpt_embed")
            x = mesh_mod.constrain(x, BATCH_AXES, SEQ_AXIS, None)
            return self.dropout(x)


class GPTModel(Layer):
    """Embeddings → L blocks → final LN. Returns hidden states [b, s, H]."""

    def __init__(self, config: GPTConfig, seed: int = 0):
        super().__init__()
        self.config = config
        rs = np.random.RandomState(seed)
        self.embeddings = GPTEmbeddings(config, rs)
        if config.mode == "scan":
            self.decoder = GPTScanDecoder(config, rs)
        else:
            from ..nn.layer.container import LayerList

            self.decoder = LayerList(
                [GPTDecoderLayer(config, rs) for _ in range(config.num_layers)])
        self.final_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        if self.config.mode == "scan":
            x = self.decoder(x)
        else:
            for blk in self.decoder:
                x = blk(x)
        return self.final_norm(x)


class GPTForCausalLM(Layer):
    """LM head tied to the vocab-parallel embedding: logits stay vocab-sharded
    into the loss (reference: c_softmax_with_cross_entropy)."""

    def __init__(self, config: GPTConfig, seed: int = 0):
        super().__init__()
        # the numpy draws and their uploads: a set-up phase of its own
        with RecordEvent("model_init"):
            self.gpt = GPTModel(config, seed=seed)
        self.config = config

    def forward(self, input_ids, position_ids=None, labels=None):
        x = self.gpt(input_ids, position_ids)
        w = self.gpt.embeddings.word_embeddings
        with jax.named_scope("lm_head"):
            if labels is not None and self.config.fused_loss_chunk > 0:
                # fused chunked linear+CE: logits never hit HBM whole
                from ..incubate.nn.functional import (
                    fused_linear_cross_entropy,
                )

                h = x.reshape([-1, self.config.hidden_size])
                return fused_linear_cross_entropy(
                    h, w, labels.reshape([-1]),
                    vocab_chunk=self.config.fused_loss_chunk,
                    transposed_weight=True)
            logits = call_op(lambda h, wv: h @ wv.T, x, w,
                             op_name="gpt_logits")
            return mesh_mod.constrain(logits, BATCH_AXES, SEQ_AXIS,
                                      MODEL_AXIS)


class GPTPretrainingCriterion(Layer):
    """Masked LM loss over vocab-sharded logits (stable log-softmax in fp32)."""

    def forward(self, prediction_scores, masked_lm_labels, loss_mask=None):
        def fn(logits, labels, *mask):
            lg = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
            nll = lse - picked
            if mask:
                m = mask[0].astype(jnp.float32)
                return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
            return jnp.mean(nll)

        args = [prediction_scores, masked_lm_labels]
        if loss_mask is not None:
            args.append(loss_mask)
        return call_op(fn, *args, op_name="gpt_loss")


def gpt_1f1b_grad_fn(model: "GPTForCausalLM", *, memory_plan=None,
                     zero3_stage_params: bool = False, grad_sync=None,
                     sync_axes=(), sync_state_specs=()):
    """TrainStep grad_fn running the whole GPT train step under the
    memory-bounded 1F1B schedule (distributed/pipeline/schedule.py
    pipeline_1f1b; reference: pipeline_parallel.py:80-150
    forward_backward_pipeline).

    The embedding runs on stage 0, the decoder stack is pipe-stacked, and
    the final-norm + tied vocab-parallel LM head + CE run on the last stage
    — all inside ONE shard_map program; the tied embedding weight picks up
    both its stage-0 and last-stage grad contributions via the cross-stage
    psum. Requires cfg.mode == "scan", dropout 0 (no per-tick RNG plumbed).

    ISSUE-15 composition knobs (PipelineTrainStep drives these):

    - ``memory_plan`` (a ``distributed.pipeline.MemoryPlan``): per-layer
      remat/offload policies for the stage stack (overrides
      cfg.recompute/recompute_policy) + the stash's host-offload tier.
    - ``zero3_stage_params``: hold the pipe-stacked block weights at rest
      sharded over ('pipe', 'sharding') jointly on the layer dim — each
      rank keeps L/(P*Z) layers; the stage body all_gathers its own
      stage's slice over 'sharding' before scanning, and the gather's AD
      transpose (psum_scatter) both sums the sharding-batch-shard grad
      contributions AND re-shards the result: the ZeRO-3 x pipeline grad
      path, with fp32 grad accumulators and optimizer slots staying
      1/(P*Z)-sized (the PR-9 follow-on composition).
    - ``grad_sync`` / ``sync_axes`` / ``sync_state_specs``: the in-body
      quantized bucket-reduction hook forwarded to ``pipeline_1f1b`` —
      the grad_fn then takes and returns the residual state, one
      spec-sharded array per bucket (``handles_grad_comm`` marks the
      wider signature for TrainStep).
    """
    cfg = model.config
    if cfg.mode != "scan":
        raise ValueError("1F1B needs the scan-mode (pipe-stacked) decoder")
    if cfg.dropout or cfg.attn_dropout:
        raise ValueError(
            "the 1F1B schedule plumbs no per-tick RNG; set dropout=0 "
            "and attn_dropout=0 (the hybrid-parallel pretraining configs "
            "train without dropout)")
    mesh = mesh_mod.get_mesh()
    if mesh is None or PIPE_AXIS not in mesh.axis_names \
            or mesh.shape[PIPE_AXIS] <= 1:
        raise ValueError("1F1B needs an active mesh with pipe degree > 1")
    mp = int(mesh.shape.get(MODEL_AXIS, 1)) if MODEL_AXIS in mesh.axis_names else 1
    sep = int(mesh.shape.get(SEQ_AXIS, 1)) if SEQ_AXIS in mesh.axis_names else 1
    dt = dtype_mod.convert_dtype(cfg.dtype)
    eps = cfg.layer_norm_epsilon

    # FunctionalModule order -> short names (trainable params only)
    short = {"gpt.embeddings.word_embeddings": "wte",
             "gpt.embeddings.position_embeddings": "wpe",
             "gpt.final_norm.weight": "lnf_w",
             "gpt.final_norm.bias": "lnf_b"}
    for n in _BLOCK_PARAMS:
        short[f"gpt.decoder.{n}"] = n
    order = []
    for name, p in model.named_parameters():
        if p.stop_gradient:
            continue
        if name not in short:
            raise ValueError(f"unexpected GPT parameter {name}")
        order.append(short[name])

    shapes = _block_shapes(cfg)
    pipe_deg = int(mesh.shape[PIPE_AXIS])
    if cfg.num_layers % pipe_deg:
        raise ValueError(
            f"num_layers={cfg.num_layers} not divisible by pipe degree "
            f"{pipe_deg}")
    layers_per_stage = cfg.num_layers // pipe_deg
    shard_deg = (int(mesh.shape["sharding"])
                 if "sharding" in mesh.axis_names else 1)
    zero3 = bool(zero3_stage_params) and shard_deg > 1
    if zero3 and layers_per_stage % shard_deg:
        raise ValueError(
            f"zero3_stage_params shards the {layers_per_stage} layers of "
            f"a stage over sharding degree {shard_deg} — not divisible")
    specs = {"wte": mesh_mod.sanitize_spec(P(MODEL_AXIS, None), mesh),
             "wpe": P(), "lnf_w": P(), "lnf_b": P()}
    for n in _BLOCK_PARAMS:
        _, spec = shapes[n]
        base = spec if spec is not None else P(*([None] * len(shapes[n][0])))
        # at rest: layer dim over 'pipe' (one stage per pipe group), and
        # with zero3 additionally over 'sharding' (each rank keeps
        # L/(P*Z) layers; the stage body gathers its own stage's slice)
        lead = (PIPE_AXIS, "sharding") if zero3 else PIPE_AXIS
        specs[n] = mesh_mod.sanitize_spec(P(lead, *base), mesh)

    if memory_plan is not None:
        stage_policies = tuple(memory_plan.policies)
        if len(stage_policies) != layers_per_stage:
            raise ValueError(
                f"memory plan has {len(stage_policies)} per-layer policies "
                f"for a {layers_per_stage}-layer stage")
        stash_kind = memory_plan.stash_memory_kind
    else:
        stage_policies = _resolve_policies(cfg, layers_per_stage)
        stash_kind = None

    def embed_fn(p, ids):
        wte = p["wte"]
        if mp > 1:
            r = jax.lax.axis_index(MODEL_AXIS)
            vloc = wte.shape[0]
            off = r * vloc
            loc = jnp.clip(ids - off, 0, vloc - 1)
            emb = jnp.take(wte, loc, axis=0)
            emb = jnp.where(((ids >= off) & (ids < off + vloc))[..., None],
                            emb, 0)
            emb = coll.in_trace_psum(emb, MODEL_AXIS)   # c_embedding allreduce
        else:
            emb = jnp.take(wte, ids, axis=0)
        s_loc = ids.shape[1]
        pos0 = jax.lax.axis_index(SEQ_AXIS) * s_loc if sep > 1 else 0
        pe = jax.lax.dynamic_slice_in_dim(p["wpe"], pos0, s_loc, axis=0)
        return (emb + pe).astype(dt)

    def stage_fn(p, h):
        stacked = tuple(p[n] for n in _BLOCK_PARAMS)
        if zero3:
            # re-materialize this stage's L/P layers from the at-rest
            # 1/(P*Z) shards; AD's transpose (psum_scatter over
            # 'sharding') returns grads already summed over the sharding
            # batch shards AND sharded back to the at-rest layout
            stacked = tuple(
                coll.in_trace_all_gather(a, "sharding", gather_axis=0)
                for a in stacked)

        def apply_full(carry, slices):
            d = dict(zip(_BLOCK_PARAMS, slices))
            return _block_apply_manual(d, carry, cfg=cfg, mesh=mesh)

        return _scan_policied(apply_full, stacked, h, stage_policies)

    def loss_fn(p, y, lbl):
        x32 = y.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        ln = (x32 - mu) * jax.lax.rsqrt(var + eps) * p["lnf_w"] + p["lnf_b"]
        h2 = ln.reshape(-1, cfg.hidden_size).astype(dt)
        wte = p["wte"]
        flat = lbl.reshape(-1)
        logits = (h2 @ wte.T).astype(jnp.float32)
        if mp > 1:
            # ParallelCrossEntropy over the vocab-sharded logits
            # (c_softmax_with_cross_entropy, mp_layers.py)
            r = jax.lax.axis_index(MODEL_AXIS)
            vloc = wte.shape[0]
            off = r * vloc
            # the max-shift cancels out of d(lse)/d(logits) exactly, so it
            # can (and must — pmax has no VJP) sit behind stop_gradient
            lmax = coll.in_trace_pmax(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)), MODEL_AXIS)
            sumexp = coll.in_trace_psum(
                jnp.sum(jnp.exp(logits - lmax[:, None]), axis=-1), MODEL_AXIS)
            lse = jnp.log(sumexp) + lmax
            in_rng = (flat >= off) & (flat < off + vloc)
            loc = jnp.clip(flat - off, 0, vloc - 1)
            # local gather of each label's logit (zero off-shard), summed
            # across the vocab shards — exactly one rank contributes per
            # token (this line used to self-reference `picked` before it
            # was bound; the pre-vma TP refusal kept it unreached)
            picked_loc = jnp.take_along_axis(logits, loc[:, None],
                                             axis=-1)[:, 0]
            picked = coll.in_trace_psum(
                jnp.where(in_rng, picked_loc, 0.0), MODEL_AXIS)
        else:
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, flat[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)

    from ..distributed.pipeline.schedule import pipeline_1f1b

    inv_shard = np.float32(1.0 / shard_deg)

    def _run(train_p, in_vals, lbl_vals, state):
        if len(in_vals) != 1 or len(lbl_vals) != 1:
            raise ValueError(
                "gpt 1F1B step takes exactly (input_ids,) and (labels,): "
                "custom position_ids / loss_mask are not plumbed through "
                "the pipeline schedule")
        p = dict(zip(order, train_p))
        out = pipeline_1f1b(
            embed_fn, stage_fn, loss_fn, p, in_vals[0], lbl_vals[0],
            mesh=mesh, param_specs={k: specs[k] for k in p},
            microbatches=cfg.pp_microbatches or None,
            natural_axes=(MODEL_AXIS,),
            grad_sync=grad_sync, sync_axes=sync_axes,
            sync_state=state, sync_state_specs=tuple(sync_state_specs),
            stash_memory_kind=stash_kind)
        if grad_sync is not None:
            loss, g, new_state = out
        else:
            (loss, g), new_state = out, ()
        if zero3:
            # the all_gather transpose SUMMED the sharding ranks' batch
            # contributions (psum_scatter); the unsharded semantics are
            # the mean over batch shards — scale once, linear either side
            # of the codec reduction
            g = {k: (v * inv_shard if k in _BLOCK_PARAMS else v)
                 for k, v in g.items()}
        return loss, [g[k] for k in order], tuple(new_state)

    if grad_sync is not None:
        def grad_fn(train_p, frozen_p, bvals, gc_res, key, in_vals,
                    lbl_vals):
            loss, grads, new_state = _run(train_p, in_vals, lbl_vals,
                                          tuple(gc_res))
            return loss, grads, new_state

        grad_fn.handles_grad_comm = True
    else:
        def grad_fn(train_p, frozen_p, bvals, key, in_vals, lbl_vals):
            loss, grads, _ = _run(train_p, in_vals, lbl_vals, ())
            return loss, grads

        grad_fn.handles_grad_comm = False
    # surfaced for PipelineTrainStep: the traversal order and at-rest
    # specs it builds its (local-shape) bucket plan and shardings from
    grad_fn.order = list(order)
    grad_fn.specs = dict(specs)
    grad_fn.zero3_stage_params = zero3
    grad_fn.stage_policies = tuple(stage_policies)
    return grad_fn


def gpt_1f1b_train_step(model: "GPTForCausalLM", optimizer, batch_spec=None,
                        **kwargs):
    """TrainStep whose loss+grads run the 1F1B pipeline schedule (the
    schedule_mode="1F1B" the reference's strategy selects); optimizer
    update, clipping and shardings are the standard compiled path.
    Extra kwargs (memory_plan=, zero3_stage_params=) forward to
    gpt_1f1b_grad_fn; for the grad_comm / planner-driven composition use
    distributed.pipeline.PipelineTrainStep, which builds on this."""
    from ..jit import TrainStep

    return TrainStep(model, None, optimizer, batch_spec=batch_spec,
                     grad_fn=gpt_1f1b_grad_fn(model, **kwargs))


def gpt_hbm_estimate(cfg: GPTConfig, mesh, global_batch: int,
                     seq: Optional[int] = None):
    """Per-device HBM estimate for one GSPMD AdamW train step — the
    BASELINE config-4 feasibility check (GPT-1.3B, ZeRO stage-2 sharding +
    mp2 on a v5e-64 mesh, per-chip HBM <= 16 GB).

    Compiles ABSTRACTLY (jax.ShapeDtypeStruct — no arrays materialized):
    embeddings -> scan-stacked decoder (remat honored via cfg.recompute) ->
    tied LM head + CE -> grads -> AdamW update with fp32 moments sharded
    over the 'sharding' axis (ZeRO stage-2: optimizer state sharded, bf16
    params replicated over 'sharding'). Params/moments are donated, so
    XLA's estimate is the real steady-state residency.

    Returns a dict of byte counts from XLA's memory analysis, including
    "peak_hbm_bytes" = arguments + temps + outputs - aliased.
    """
    import jax
    from jax.sharding import NamedSharding

    SDS = jax.ShapeDtypeStruct
    h, L = cfg.hidden_size, cfg.num_layers
    seq = seq or cfg.max_position_embeddings
    dt = dtype_mod.convert_dtype(cfg.dtype)
    shard_deg = (int(mesh.shape["sharding"])
                 if "sharding" in mesh.axis_names else 1)

    shapes = _block_shapes(cfg)
    pshapes = {"wte": (cfg.vocab_size, h),
               "wpe": (cfg.max_position_embeddings, h),
               "lnf_w": (h,), "lnf_b": (h,)}
    pspecs = {"wte": P(MODEL_AXIS, None), "wpe": P(),
              "lnf_w": P(), "lnf_b": P()}
    for n, (shape, spec) in shapes.items():
        base = tuple(spec) if spec is not None else (None,) * len(shape)
        pshapes[n] = (L, *shape)
        pspecs[n] = P(None, *base)
    pspecs = {k: mesh_mod.sanitize_spec(v, mesh) for k, v in pspecs.items()}

    from ..distributed.sharding import zero_slot_spec

    def slot_spec(shape, pspec):
        # the SAME ZeRO rule TrainStep applies to its slots, so a sharding
        # regression there is caught by the feasibility test
        return zero_slot_spec(shape, pspec, "sharding", shard_deg)

    def ns(spec):
        return NamedSharding(mesh, spec)

    params = {k: SDS(pshapes[k], dt, sharding=ns(pspecs[k]))
              for k in pshapes}
    sspecs = {k: slot_spec(pshapes[k], pspecs[k]) for k in pshapes}
    m1 = {k: SDS(pshapes[k], jnp.float32, sharding=ns(sspecs[k]))
          for k in pshapes}
    m2 = dict(m1)
    bspec = mesh_mod.sanitize_spec(P(BATCH_AXES), mesh)
    ids_sds = SDS((global_batch, seq), jnp.int32, sharding=ns(bspec))
    lbl_sds = SDS((global_batch, seq), jnp.int32, sharding=ns(bspec))

    def constrain(v, *spec):
        return jax.lax.with_sharding_constraint(
            v, ns(mesh_mod.sanitize_spec(P(*spec), mesh)))

    def train_step(p, mom1, mom2, ids, labels, lr):
        def loss_of(pp):
            x = jnp.take(pp["wte"], ids, axis=0) \
                + jax.lax.dynamic_slice_in_dim(pp["wpe"], 0, seq, axis=0)
            x = constrain(x.astype(dt), BATCH_AXES, SEQ_AXIS, None)
            stacked = tuple(pp[n] for n in _BLOCK_PARAMS)

            def apply_full(carry, slices):
                # _block_apply reads the ambient mesh for its sharding
                # constraints — callers set_mesh(mesh) first
                d = dict(zip(_BLOCK_PARAMS, slices))
                return _block_apply(d, carry, cfg=cfg)

            x = _scan_policied(apply_full, stacked, x,
                               _resolve_policies(cfg, L))
            x32 = x.astype(jnp.float32)
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            x = ((x32 - mu) * jax.lax.rsqrt(var + cfg.layer_norm_epsilon)
                 * pp["lnf_w"] + pp["lnf_b"]).astype(dt)
            logits = constrain(x @ pp["wte"].T,
                               BATCH_AXES, SEQ_AXIS, MODEL_AXIS)
            lg = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            picked = jnp.take_along_axis(lg, labels[..., None],
                                         axis=-1)[..., 0]
            return jnp.mean(lse - picked)

        loss, g = jax.value_and_grad(loss_of)(p)
        b1, b2, eps, wd = 0.9, 0.95, 1e-8, 0.1
        new_p, new_m1, new_m2 = {}, {}, {}
        for k in p:
            gk = g[k].astype(jnp.float32)
            nm1 = constrain_to(b1 * mom1[k] + (1 - b1) * gk, sspecs[k])
            nm2 = constrain_to(b2 * mom2[k] + (1 - b2) * gk * gk, sspecs[k])
            upd = nm1 / (jnp.sqrt(nm2) + eps) + wd * p[k].astype(jnp.float32)
            new_p[k] = constrain_to(
                (p[k].astype(jnp.float32) - lr * upd).astype(dt), pspecs[k])
            new_m1[k], new_m2[k] = nm1, nm2
        return loss, new_p, new_m1, new_m2

    def constrain_to(v, spec):
        return jax.lax.with_sharding_constraint(v, ns(spec))

    # _block_apply's per-activation constraints read the ambient mesh —
    # pin it to the argument for the trace so callers can't get a silently
    # unconstrained (wrong) estimate
    prev_mesh = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh)
    try:
        lowered = jax.jit(train_step, donate_argnums=(0, 1, 2)).lower(
            params, m1, m2, ids_sds, lbl_sds,
            SDS((), jnp.float32))
    finally:
        mesh_mod.set_mesh(prev_mesh)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    if mem is None:
        return None
    out = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
    }
    out["peak_hbm_bytes"] = (out["argument_bytes"] + out["temp_bytes"]
                             + out["output_bytes"] - out["alias_bytes"])
    from ..jit.aot import cost_counters

    # raw compiler cost counters for the planner's ranking signal
    # (jit/aot.py estimate_step_seconds decides how to trust them:
    # optimal_seconds goes negative-sentinel on large collective
    # programs, flops/bytes stay valid)
    out.update(cost_counters(compiled))
    return out
