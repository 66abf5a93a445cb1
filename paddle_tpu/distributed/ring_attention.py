"""Ring attention — sequence/context parallelism over the 'sep' mesh axis.

NET-NEW vs the reference: SURVEY.md §5 records that shjNT/Paddle has NO
sequence/context parallelism (no ring attention/Ulysses; only chunked p2p
primitives partial_send/recv, operators/collective/partial_*_op.cc, that
nothing composes). This module supplies the capability TPU-natively:

- sequence dim sharded over the 'sep' ICI axis;
- each device holds q/k/v chunks; k/v rotate around the ring via ppermute
  while partial attention accumulates with the online-softmax (flash) update,
  so the full O(s^2) score matrix never materializes on one chip;
- compute of chunk i overlaps the ICI transfer of chunk i+1 (XLA schedules
  the ppermute concurrently with the einsum).

Used by models/gpt.py when config.use_ring_attention and a 'sep' axis exists.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mesh as mesh_mod

_NEG = -1e30


def _axes_in(mesh, names):
    kept = tuple(a for a in names if a in mesh.axis_names)
    return kept if kept else None


def _vary_like(inits, refs):
    """Under vma-tracked shard_map (the 1F1B pipeline), fresh-zeros scan
    carries are typed replicated while the loop makes them device-varying;
    pcast them up to the union of the reference operands' vma. In untracked
    regions (check_vma=False, e.g. ring_attention_val's own shard_map) every
    vma reads empty and this is a no-op."""
    target = set()
    for r in refs:
        target |= set(jax.typeof(r).vma)
    if not target:
        return inits

    def cast(a):
        need = tuple(ax for ax in target if ax not in set(jax.typeof(a).vma))
        return jax.lax.pcast(a, need, to="varying") if need else a

    return jax.tree.map(cast, inits)


def _plain_attention(q, k, v, causal):
    """Single-device causal attention — the shared no-SP fallback (also
    used by ulysses.py)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        keep = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(keep, logits, _NEG)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _flash_ring_ok(shape) -> bool:
    """Use the pallas kernel for the per-chunk attention when on TPU with a
    kernel-friendly chunk length (VERDICT r1 item 3: 'extend [flash] to the
    ring-attention inner block')."""
    from ..framework.target import target_platform

    if target_platform() != "tpu":
        return False
    from ..ops.flash_attention import flash_attention_supported

    return flash_attention_supported(tuple(shape), block=256)


def ring_attention_manual(ql, kl, vl, axis: str, sp: int, causal: bool = True):
    """Ring attention body for code ALREADY inside a shard_map manual region
    over `axis` (used directly by the SPMD pipeline schedule, which owns the
    enclosing shard_map). ql/kl/vl: local [b, s_loc, h, d]; `sp` is the static
    size of the ring axis.

    The per-chunk attention is the pallas flash kernel on TPU (diagonal
    chunk causal, earlier chunks unmasked, later chunks skipped) with chunk
    results merged by their log-sum-exp; elsewhere the einsum online-softmax
    path runs."""
    s_loc = ql.shape[1]
    scale = 1.0 / (ql.shape[-1] ** 0.5)
    my = jax.lax.axis_index(axis)
    q_pos = my * s_loc + jnp.arange(s_loc)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    b, s, h, d = ql.shape

    if _flash_ring_ok(ql.shape):
        return _ring_flash(ql, kl, vl, axis, sp, causal)

    def body(carry, i):
        o, m, l, kc, vc = carry
        src = (my - i) % sp  # ring position the current chunk came from
        logits = jnp.einsum("bqhd,bkhd->bhqk", ql, kc) * scale
        logits = logits.astype(jnp.float32)
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            keep = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(keep[None, None], logits, _NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
        kc, vc = jax.lax.ppermute((kc, vc), axis, perm)
        return (o_new, m_new, l_new, kc, vc), None

    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    o0, m0, l0 = _vary_like((o0, m0, l0), (ql, kl, vl))
    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, kl, vl), jnp.arange(sp))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(ql.dtype)


def _ring_flash_forward(ql, kl, vl, axis, sp, causal):
    """Ring forward with the pallas flash kernel per chunk: diagonal chunk
    causal, earlier chunks unmasked, later chunks dropped; chunk outputs
    merged by their log-sum-exp."""
    from ..ops.flash_attention import _fwd, _pick_block

    b, s_loc, h, d = ql.shape
    my = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    blk = _pick_block(s_loc, 256)
    qt = jnp.transpose(ql, (0, 2, 1, 3))                     # [b, h, s, d]

    def chunk_flash(kc, vc, diagonal):
        kt = jnp.transpose(kc, (0, 2, 1, 3))
        vt = jnp.transpose(vc, (0, 2, 1, 3))
        out, lse = _fwd(qt, kt, vt, diagonal, blk, blk)
        return out, lse[..., 0]                              # [b,h,s,d],[b,h,s]

    def body(carry, i):
        o, lse_tot, kc, vc = carry
        src = (my - i) % sp
        if causal:
            o_c, lse_c = jax.lax.cond(
                src == my,
                lambda: chunk_flash(kc, vc, True),
                lambda: chunk_flash(kc, vc, False))
            lse_c = jnp.where(src > my, _NEG, lse_c)   # later chunks dropped
        else:
            o_c, lse_c = chunk_flash(kc, vc, False)
        new_tot = jnp.logaddexp(lse_tot, lse_c)
        w_old = jnp.exp(lse_tot - new_tot)[..., None]
        w_new = jnp.exp(lse_c - new_tot)[..., None]
        o = o * w_old + o_c.astype(jnp.float32) * w_new
        kc, vc = jax.lax.ppermute((kc, vc), axis, perm)
        return (o, new_tot, kc, vc), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
    o0, lse0 = _vary_like((o0, lse0), (ql, kl, vl))
    (o, _, _, _), _ = jax.lax.scan(body, (o0, lse0, kl, vl), jnp.arange(sp))
    return jnp.transpose(o, (0, 2, 1, 3)).astype(ql.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis, sp, causal):
    return _ring_flash_forward(q, k, v, axis, sp, causal)


def _ring_flash_fwd(q, k, v, axis, sp, causal):
    return _ring_flash_forward(q, k, v, axis, sp, causal), (q, k, v)


def _ring_flash_bwd(axis, sp, causal, res, cot):
    # backward recomputes through the (mathematically identical) einsum ring
    # — the flash kernel accelerates the forward; grads stay exact
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b2, c: _ring_einsum(a, b2, c, axis, sp, causal), q, k, v)
    return vjp(cot)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_einsum(ql, kl, vl, axis, sp, causal):
    """The reference einsum online-softmax ring (used as the flash path's
    backward and as the non-TPU path)."""
    s_loc = ql.shape[1]
    scale = 1.0 / (ql.shape[-1] ** 0.5)
    my = jax.lax.axis_index(axis)
    q_pos = my * s_loc + jnp.arange(s_loc)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    b, s, h, d = ql.shape

    def body(carry, i):
        o, m, l, kc, vc = carry
        src = (my - i) % sp
        logits = jnp.einsum("bqhd,bkhd->bhqk", ql, kc) * scale
        logits = logits.astype(jnp.float32)
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            keep = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(keep[None, None], logits, _NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
        kc, vc = jax.lax.ppermute((kc, vc), axis, perm)
        return (o_new, m_new, l_new, kc, vc), None

    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    o0, m0, l0 = _vary_like((o0, m0, l0), (ql, kl, vl))
    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, kl, vl), jnp.arange(sp))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(ql.dtype)


def ring_attention_val(q, k, v, axis: str = "sep", causal: bool = True):
    """Value-level ring attention. q/k/v: [batch, seq, heads, head_dim] with
    seq sharded over `axis`. Returns same shape/sharding. Traceable under jit;
    enters a shard_map manual region over the full mesh."""
    mesh = mesh_mod.get_mesh()
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return _plain_attention(q, k, v, causal)

    sp = mesh.shape[axis]
    batch_ax = _axes_in(mesh, ("data", "sharding"))
    head_ax = _axes_in(mesh, ("model",))
    spec = P(batch_ax, axis, head_ax, None)

    @partial(mesh_mod.compat_shard_map, mesh=mesh,
             in_specs=(spec, spec, spec), out_specs=spec)
    def ring(ql, kl, vl):
        return ring_attention_manual(ql, kl, vl, axis, sp, causal=causal)

    return ring(q, k, v)


def ring_attention(q, k, v, causal: bool = True, axis: str = "sep"):
    """Tensor-level API: paddle_tpu.distributed.ring_attention."""
    from ..framework.autograd import call_op

    return call_op(lambda a, b, c: ring_attention_val(a, b, c, axis=axis,
                                                      causal=causal),
                   q, k, v, op_name="ring_attention")
