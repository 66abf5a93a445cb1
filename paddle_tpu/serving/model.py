"""Decode-model adapter: GPTForCausalLM -> jitted prefill/decode steps.

The training model computes full-sequence logits with no KV reuse; serving
needs the split the continuous-batching scheduler works in:

  prefill(ids, lengths)            one pass over the whole prompt ->
                                   logits at the last prompt position +
                                   the per-token KV payload to cache
  decode(ids, pos, past, past_len) one token per sequence against the
                                   cached KV -> next-token logits + the
                                   new token's KV row

Both are pure-jnp jitted functions over a parameter pytree extracted once
from the live model — replicas share the SAME arrays zero-copy (the
``Predictor.clone()`` contract: weights held once, per-replica state is
only the KV pool + scheduler). The block math mirrors ``models.gpt``'s
``_block_apply`` exactly (fp32 layernorm, approximate gelu, einsum
attention) so incremental decode is numerically the training forward;
``tests/test_serving.py`` pins teacher-forced logits parity.

Shapes are static per (batch, context) bucket: callers round batch up to
a power of two and past-context to a power-of-two bucket, so the jit
cache holds a handful of entries instead of one per sequence length.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["GPTDecodeModel", "bucket_pow2"]

_BLOCK_PARAMS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
                 "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def bucket_pow2(n: int, minimum: int = 1, maximum: int = 0) -> int:
    """Round ``n`` up to a power of two (>= minimum, capped at maximum
    when given) — the jit-cache shape bucket."""
    b = max(int(minimum), 1)
    while b < n:
        b *= 2
    if maximum:
        b = min(b, int(maximum))
    return b


def _ln(v, w, b, eps):
    mu = jnp.mean(v.astype(jnp.float32), axis=-1, keepdims=True)
    var = jnp.var(v.astype(jnp.float32), axis=-1, keepdims=True)
    out = (v.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + eps)
    return (out * w + b).astype(v.dtype)


class GPTDecodeModel:
    """Serving adapter over a loop- or scan-mode GPTForCausalLM."""

    def __init__(self, model):
        cfg = model.config
        self.config = cfg
        self.n_layers = cfg.num_layers
        self.n_heads = cfg.num_heads
        self.head_dim = cfg.head_dim
        self.hidden = cfg.hidden_size
        self.vocab_size = cfg.vocab_size
        self.max_context = cfg.max_position_embeddings
        # per-token KV payload: layers x {k, v} x heads x head_dim
        self.elems_per_token = self.n_layers * 2 * self.hidden
        self._eps = cfg.layer_norm_epsilon
        self.params = self._extract(model)
        self._jit_steps()

    def _jit_steps(self):
        self._prefill_fn = jax.jit(self._make_prefill())
        self._decode_fn = jax.jit(self._make_decode())
        self._extend_fn = jax.jit(self._make_extend())

    def truncated(self, n_layers: int) -> "GPTDecodeModel":
        """A draft model from this model's own weights: the first
        ``n_layers`` decoder blocks under the same embeddings and final
        norm (zero new parameters — the serving analog of early-exit
        self-drafting). Its KV payload is proportionally smaller
        (``elems_per_token = n_layers * 2 * hidden``); it is NOT paged —
        the engine keeps a small dense draft cache per sequence."""
        if not (0 < int(n_layers) <= self.n_layers):
            raise ValueError(
                f"truncated wants 1..{self.n_layers} layers, got {n_layers}")
        new = object.__new__(GPTDecodeModel)
        new.config = self.config
        new.n_layers = int(n_layers)
        new.n_heads = self.n_heads
        new.head_dim = self.head_dim
        new.hidden = self.hidden
        new.vocab_size = self.vocab_size
        new.max_context = self.max_context
        new.elems_per_token = new.n_layers * 2 * new.hidden
        new._eps = self._eps
        new.params = dict(self.params)
        for name in _BLOCK_PARAMS:
            new.params[name] = self.params[name][:new.n_layers]
        new._jit_steps()
        return new

    # ------------------------------------------------------------ params
    def _extract(self, model) -> dict:
        emb = model.gpt.embeddings
        p = {
            "word": emb.word_embeddings._value,
            "pos": emb.position_embeddings._value,
            "final_w": model.gpt.final_norm.weight._value,
            "final_b": model.gpt.final_norm.bias._value,
        }
        dec = model.gpt.decoder
        if hasattr(dec, "cfg"):  # scan mode: already layer-stacked
            for name in _BLOCK_PARAMS:
                p[name] = getattr(dec, name)._value
        else:  # loop mode: LayerList of GPTDecoderLayer
            for name in _BLOCK_PARAMS:
                p[name] = jnp.stack(
                    [getattr(layer, name)._value for layer in dec])
        return p

    def param_list(self) -> list:
        """Flat deterministic parameter list (ReplicaGuard digests)."""
        return [self.params[k] for k in sorted(self.params)]

    # ------------------------------------------------------- traced steps
    def _make_prefill(self):
        L, n, d = self.n_layers, self.n_heads, self.head_dim
        eps, scale = self._eps, 1.0 / math.sqrt(self.head_dim)

        def fn(params, ids, lengths):
            b, s = ids.shape
            x = jnp.take(params["word"], ids, axis=0) + params["pos"][:s]

            def body(carry, pl):
                x = carry
                hn = _ln(x, pl["ln1_w"], pl["ln1_b"], eps)
                qkv = jnp.einsum("bsh,hcj->bscj", hn, pl["qkv_w"]) \
                    + pl["qkv_b"]
                qkv = qkv.reshape(b, s, 3, n, d)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
                causal = jnp.tril(jnp.ones((s, s), dtype=bool))
                logits = jnp.where(causal, logits,
                                   jnp.finfo(logits.dtype).min)
                probs = jax.nn.softmax(logits.astype(jnp.float32),
                                       axis=-1).astype(v.dtype)
                attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
                y = attn.reshape(b, s, n * d) @ pl["out_w"] + pl["out_b"]
                x = x + y
                hn = _ln(x, pl["ln2_w"], pl["ln2_b"], eps)
                z = hn @ pl["fc1_w"] + pl["fc1_b"]
                z = jax.nn.gelu(z, approximate=True)
                z = z @ pl["fc2_w"] + pl["fc2_b"]
                return x + z, (k, v)

            stacked = {name: params[name] for name in _BLOCK_PARAMS}
            x, (ks, vs) = jax.lax.scan(body, x, stacked)
            x = _ln(x, params["final_w"], params["final_b"], eps)
            logits = x @ params["word"].T                      # [b, s, V]
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
            # [L,b,s,n,d] x2 -> [L,b,2,s,n,d] -> [b,s,L,2,n,d] -> [b,s,ept]
            kv = jnp.stack([ks, vs], axis=2)
            kv = kv.transpose(1, 3, 0, 2, 4, 5).reshape(
                b, s, self.elems_per_token)
            return last, kv, logits

        return fn

    def _make_decode(self):
        L, n, d = self.n_layers, self.n_heads, self.head_dim
        eps, scale = self._eps, 1.0 / math.sqrt(self.head_dim)

        def fn(params, ids, pos, past, past_len):
            b = ids.shape[0]
            S = past.shape[1]
            x = jnp.take(params["word"], ids, axis=0) \
                + jnp.take(params["pos"], pos, axis=0)         # [b, h]
            # the host keeps `past` as an fp32 working copy; attention runs
            # in the model's dtype (an fp32 past under bf16 weights would
            # promote the residual stream and break the scan carry)
            past_r = past.reshape(b, S, L, 2, n, d).astype(x.dtype)
            pk = past_r[:, :, :, 0].transpose(2, 0, 1, 3, 4)   # [L,b,S,n,d]
            pv = past_r[:, :, :, 1].transpose(2, 0, 1, 3, 4)
            valid = jnp.arange(S)[None, :] < past_len[:, None]  # [b, S]
            mask = jnp.concatenate(
                [valid, jnp.ones((b, 1), bool)], axis=1)[:, None, :]

            def body(carry, inp):
                x = carry
                pl, k_past, v_past = inp
                hn = _ln(x, pl["ln1_w"], pl["ln1_b"], eps)
                qkv = jnp.einsum("bh,hcj->bcj", hn, pl["qkv_w"]) \
                    + pl["qkv_b"]
                qkv = qkv.reshape(b, 3, n, d)
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
                lp = jnp.einsum("bnd,bsnd->bns", q, k_past) * scale
                ls = jnp.sum(q * k, axis=-1, keepdims=True) * scale
                al = jnp.concatenate([lp, ls], axis=-1)        # [b,n,S+1]
                al = jnp.where(mask, al, jnp.finfo(al.dtype).min)
                probs = jax.nn.softmax(al.astype(jnp.float32),
                                       axis=-1).astype(v.dtype)
                attn = jnp.einsum("bns,bsnd->bnd", probs[:, :, :S], v_past) \
                    + probs[:, :, S:] * v
                y = attn.reshape(b, n * d) @ pl["out_w"] + pl["out_b"]
                x = x + y
                hn = _ln(x, pl["ln2_w"], pl["ln2_b"], eps)
                z = hn @ pl["fc1_w"] + pl["fc1_b"]
                z = jax.nn.gelu(z, approximate=True)
                z = z @ pl["fc2_w"] + pl["fc2_b"]
                return x + z, (k, v)

            stacked = {name: params[name] for name in _BLOCK_PARAMS}
            x, (ks, vs) = jax.lax.scan(body, x, (stacked, pk, pv))
            x = _ln(x, params["final_w"], params["final_b"], eps)
            logits = x @ params["word"].T                      # [b, V]
            # [L,b,n,d] x2 -> [L,b,2,n,d] -> [b,L,2,n,d] -> [b,ept]
            kv = jnp.stack([ks, vs], axis=2)
            kv = kv.transpose(1, 0, 2, 3, 4).reshape(
                b, self.elems_per_token)
            return logits, kv

        return fn

    def _make_extend(self):
        """Multi-token incremental step: ``s`` new tokens per row attend
        to the cached past AND causally within the tail — ``decode``
        generalized from one token to a ragged tail. One program serves
        both prefix-cache tail prefill (prompt minus the cached prefix)
        and speculative verification (target scores k+1 draft positions
        in one bucketed forward)."""
        L, n, d = self.n_layers, self.n_heads, self.head_dim
        eps, scale = self._eps, 1.0 / math.sqrt(self.head_dim)

        def fn(params, ids, pos, past, past_len, tail_len):
            b, s = ids.shape
            S = past.shape[1]
            x = jnp.take(params["word"], ids, axis=0) \
                + jnp.take(params["pos"], pos, axis=0)       # [b, s, h]
            past_r = past.reshape(b, S, L, 2, n, d).astype(x.dtype)
            pk = past_r[:, :, :, 0].transpose(2, 0, 1, 3, 4)  # [L,b,S,n,d]
            pv = past_r[:, :, :, 1].transpose(2, 0, 1, 3, 4)
            valid_past = (jnp.arange(S)[None, :]
                          < past_len[:, None])[:, None, None, :]  # [b,1,1,S]
            causal = jnp.tril(jnp.ones((s, s), dtype=bool))
            tail_ok = jnp.arange(s)[None, :] < tail_len[:, None]  # [b, s]
            mask_tail = causal[None, None, :, :] \
                & tail_ok[:, None, None, :]                  # [b,1,s,s]

            def body(carry, inp):
                x = carry
                pl, k_past, v_past = inp
                hn = _ln(x, pl["ln1_w"], pl["ln1_b"], eps)
                qkv = jnp.einsum("bsh,hcj->bscj", hn, pl["qkv_w"]) \
                    + pl["qkv_b"]
                qkv = qkv.reshape(b, s, 3, n, d)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                lp = jnp.einsum("bqnd,bknd->bnqk", q, k_past) * scale
                lt = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
                neg = jnp.finfo(lp.dtype).min
                al = jnp.concatenate(
                    [jnp.where(valid_past, lp, neg),
                     jnp.where(mask_tail, lt, neg)], axis=-1)  # [b,n,s,S+s]
                probs = jax.nn.softmax(al.astype(jnp.float32),
                                       axis=-1).astype(v.dtype)
                attn = jnp.einsum("bnqk,bknd->bqnd", probs[..., :S], v_past) \
                    + jnp.einsum("bnqk,bknd->bqnd", probs[..., S:], v)
                y = attn.reshape(b, s, n * d) @ pl["out_w"] + pl["out_b"]
                x = x + y
                hn = _ln(x, pl["ln2_w"], pl["ln2_b"], eps)
                z = hn @ pl["fc1_w"] + pl["fc1_b"]
                z = jax.nn.gelu(z, approximate=True)
                z = z @ pl["fc2_w"] + pl["fc2_b"]
                return x + z, (k, v)

            stacked = {name: params[name] for name in _BLOCK_PARAMS}
            x, (ks, vs) = jax.lax.scan(body, x, (stacked, pk, pv))
            x = _ln(x, params["final_w"], params["final_b"], eps)
            logits = x @ params["word"].T                    # [b, s, V]
            # [L,b,s,n,d] x2 -> [b,s,L,2,n,d] -> [b,s,ept]
            kv = jnp.stack([ks, vs], axis=2)
            kv = kv.transpose(1, 3, 0, 2, 4, 5).reshape(
                b, s, self.elems_per_token)
            return logits, kv

        return fn

    # ------------------------------------------------------- host surface
    def prefill(self, prompts: Sequence[np.ndarray]
                ) -> Tuple[np.ndarray, List[np.ndarray]]:
        """Batch-prefill prompts (host pads to shape buckets). Returns
        (last-position logits [n, V], per-sequence KV [s_i, ept])."""
        n_seq = len(prompts)
        lengths = np.array([len(p) for p in prompts], np.int32)
        if lengths.min() < 1:
            raise ValueError("empty prompt")
        if lengths.max() > self.max_context:
            raise ValueError(
                f"prompt of {lengths.max()} tokens exceeds max_context "
                f"{self.max_context}")
        b = bucket_pow2(n_seq)
        s = bucket_pow2(int(lengths.max()), minimum=8,
                        maximum=self.max_context)
        ids = np.zeros((b, s), np.int32)
        for i, p in enumerate(prompts):
            ids[i, :len(p)] = np.asarray(p, np.int32)
        lens = np.ones((b,), np.int32)
        lens[:n_seq] = lengths
        last, kv, _ = self._prefill_fn(self.params, jnp.asarray(ids),
                                       jnp.asarray(lens))
        last = np.asarray(last)
        kv = np.asarray(kv)
        return last[:n_seq], [kv[i, :lengths[i]] for i in range(n_seq)]

    def forced_logits(self, ids: np.ndarray) -> np.ndarray:
        """Full-sequence logits [b, s, V] (parity tests / scoring)."""
        ids = np.asarray(ids, np.int32)
        lens = np.full((ids.shape[0],), ids.shape[1], np.int32)
        _, _, logits = self._prefill_fn(self.params, jnp.asarray(ids),
                                        jnp.asarray(lens))
        return np.asarray(logits)

    def decode(self, ids: np.ndarray, pos: np.ndarray, past: np.ndarray,
               past_len: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """One decode step for a (bucketed) batch. ``past`` is
        [b, S, ept] fp32 (dequantized working copy), ``past_len`` the
        per-row valid prefix. Returns (logits [b, V], new KV [b, ept])."""
        logits, kv = self._decode_fn(
            self.params, jnp.asarray(ids, np.int32),
            jnp.asarray(pos, np.int32), jnp.asarray(past, np.float32),
            jnp.asarray(past_len, np.int32))
        return np.asarray(logits), np.asarray(kv)

    def extend(self, ids: np.ndarray, pos: np.ndarray, past: np.ndarray,
               past_len: np.ndarray, tail_len: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Multi-token step for a (bucketed) batch: ``ids``/``pos`` are
        [b, s] tails, ``past`` [b, S, ept] fp32 with ``past_len`` valid
        rows, ``tail_len`` the per-row valid tail. Returns
        (logits [b, s, V], new KV [b, s, ept]); rows past ``tail_len``
        are padding garbage the caller must ignore."""
        logits, kv = self._extend_fn(
            self.params, jnp.asarray(ids, np.int32),
            jnp.asarray(pos, np.int32), jnp.asarray(past, np.float32),
            jnp.asarray(past_len, np.int32),
            jnp.asarray(tail_len, np.int32))
        return np.asarray(logits), np.asarray(kv)
