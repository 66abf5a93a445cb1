"""The gated delta rule in its chunked (WY) form, forward and backward.

Per value head, with a state S in R^{dk x dv} and S_0 = 0, the rule is the
recurrence

    S_t = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S_t^T k_t);
    S_t += k_t d_t^T;        o_t = S_t^T q_t

(Gated DeltaNet, arXiv:2412.06464): a decay, then a rank-one correction of
what the state returns for k_t toward v_t. Run so it is one dependent step
a token. Here a sequence is cut into chunks of C tokens; inside a chunk the
C corrections are solved at once, and only the chunks follow one another.
With gamma the running sum of g inside a chunk and K_beta, V_beta the rows
of K, V times beta:

    A = -strict_lower((K_beta K^T) * exp(gamma_i - gamma_j))
    T = (I - A)^{-1};  U = T V_beta;  W = T (K_beta * exp(gamma))

then chunk by chunk, S the state at the chunk's start:

    V' = U - W S
    O  = (Q * exp(gamma)) S + lower((Q K^T) * exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

which equals the recurrence in exact arithmetic (tests/test_gdn_moe.py
holds it to benchmark/reference/qwen3_next_ref.py's recurrence, forward
and every gradient). A and T are made for all chunks at once; the scan
over chunks carries S and makes U, W, the chunk's own scores and the three
products with S from the chunk's inputs, so that nothing but the inputs,
T and one state a chunk lies in memory for a whole sequence (with U, W and
the decayed copies of Q and K made for all chunks ahead of the scan the
8k step's temporaries were 7.9 GB by the TPU compiler's count, 14.3 GB
with the state: PERF.md, PR 33).

Float32 throughout, whatever the inputs' dtype: g, beta, the cumulative
decays, every operand and accumulation of every product (precision
HIGHEST: on the TPU a float32 product at default precision rounds its
operands to bfloat16), the state S. Every exponent is <= 0: the decays
are formed as exp of differences gamma_i - gamma_j with i >= j, masked
BEFORE the exp.

T is made by forward substitution on 16 x 16 diagonal blocks and the
block formula [[T11, 0], [T22 A21 T11, T22]] above them, not by the
Neumann series I + A + A^2 + ...: with repeated keys the series' terms
grow binomially and cancel, forward substitution does not. Its gradient is
written by hand (dA = T^T dT T^T); everything else is differentiated by
JAX, the scan's body under jax.checkpoint, so that what the backward keeps
of the scan is one state a chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Tokens a chunk. 64: the intra-chunk products are 64 x 64 x 128, the scan
# has s / 64 steps. A choice of this file, not a key of any configuration.
GDN_CHUNK = 64
_BASE = 16      # the diagonal blocks solved by forward substitution
_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _inverse_rows(a):
    """(I - a)^{-1} for a strictly lower triangular [..., c, c], by forward
    substitution: row i = e_i + a[i, :i] @ rows[:i]."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], a.shape[:-2] + (c,))]
    for i in range(1, c):
        done = jnp.stack(rows, axis=-2)                      # [..., i, c]
        rows.append(eye[i] + jnp.einsum(
            "...j,...jk->...k", a[..., i, :i], done, precision=_HI))
    return jnp.stack(rows, axis=-2)


def _inverse_blocks(a):
    """(I - a)^{-1} for a strictly lower triangular [..., c, c]: halves
    until the blocks are _BASE wide. With M = I - a in blocks
    [[M11, 0], [-a21, M22]], M^{-1} = [[T11, 0], [T22 a21 T11, T22]]."""
    c = a.shape[-1]
    if c <= _BASE or c % 2:
        return _inverse_rows(a)
    h = c // 2
    t11 = _inverse_blocks(a[..., :h, :h])
    t22 = _inverse_blocks(a[..., h:, h:])
    t21 = _mm(_mm(t22, a[..., h:, :h]), t11)
    top = jnp.concatenate([t11, jnp.zeros_like(t21)], axis=-1)
    return jnp.concatenate(
        [top, jnp.concatenate([t21, t22], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """T = (I - a)^{-1} for a strictly lower triangular a [..., c, c]."""
    return _inverse_blocks(a)


def _unit_lower_inverse_fwd(a):
    t = _inverse_blocks(a)
    return t, t


def _unit_lower_inverse_bwd(t, ct):
    # dT = T dA T, so the cotangent of A is T^T ct T^T (its upper part is
    # dropped by the mask that made A)
    tt = jnp.swapaxes(t, -1, -2)
    return (_mm(_mm(tt, ct), tt),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _per_value_head(t, group: int):
    """A key head's [.., Hk, C, d] tile for each value head it serves."""
    return t if group == 1 else jnp.repeat(t, group, axis=-3)


def _decay(gam, lower):
    """exp(gamma_i - gamma_j) for i >= j, 0 above the diagonal; masked
    before the exp, so that no exponent is positive."""
    return jnp.exp(jnp.where(
        lower, gam[..., :, None] - gam[..., None, :], -jnp.inf))


def _chunk_step(state, xs, *, group: int):
    """One chunk of the scan: U, W, the chunk's own scores, then the three
    products with the state S [b, Hv, dk, dv], which is carried in float32
    (rounded to bfloat16 between chunks the error compounds over a
    sequence: the benchmark's comparison (d) refuses it). Everything here
    is a chunk's size; under jax.checkpoint the backward recomputes it
    from the chunk's inputs and the state."""
    t_n, q_n, k_n, v_n, beta_n, gam = xs
    f32 = jnp.float32
    idx = jnp.arange(gam.shape[-1])
    lower = idx[:, None] >= idx[None, :]
    q_n, k_n = _per_value_head(q_n, group), _per_value_head(k_n, group)
    e_gam = jnp.exp(gam)[..., None]
    k_beta = k_n * beta_n[..., None]
    u = _mm(t_n, v_n.astype(f32) * beta_n[..., None])
    w = _mm(t_n, k_beta * e_gam)
    a_qk = jnp.where(
        lower, _mm(q_n, jnp.swapaxes(k_n, -1, -2)) * _decay(gam, lower), 0.0)
    v_new = u - _mm(w, state)
    o_n = _mm(q_n * e_gam, state) + _mm(a_qk, v_new)
    gam_end = gam[..., -1:]
    k_d = k_n * jnp.exp(gam_end - gam)[..., None]
    state = jnp.exp(gam_end)[..., None] * state \
        + _mm(jnp.swapaxes(k_d, -1, -2), v_new)
    return state, o_n


def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int = GDN_CHUNK):
    """The gated delta rule over whole sequences, S_0 = 0.

    q, k [b, s, Hk, dk] (as the rule takes them: k of unit length, q
    scaled), v [b, s, Hv, dv], g (log decay, <= 0) and beta [b, s, Hv]; Hk
    divides Hv and key head j serves the value heads j * (Hv // Hk) ...
    Returns o [b, s, Hv, dv] in v's dtype. A sequence that is no whole
    number of chunks is padded at its end with tokens of beta = 0 and
    g = 0, which leave the state as it is, and their outputs are dropped.
    """
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if k.shape != q.shape or v.shape[:2] != (b, s) or hv % hk \
            or g.shape != (b, s, hv) or beta.shape != (b, s, hv):
        raise ValueError(
            f"gated delta rule: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape} are no [b, s, Hk, dk] x 2, "
            f"[b, s, Hv, dv], [b, s, Hv] x 2 with Hk dividing Hv")
    out_dtype = v.dtype
    f32 = jnp.float32
    group = hv // hk
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunks(t):
        """[b, n*C, H, ...] -> [n, b, H, C, ...]: chunks lead (the scan's
        axis), a head's chunk is a contiguous (C, d) tile."""
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    # q, k stay at the key heads' count and v in its own dtype until a
    # chunk's step needs them: what lies in memory for the whole sequence
    # is the inputs once and T
    q, k, v = chunks(q.astype(f32)), chunks(k.astype(f32)), chunks(v)
    beta = chunks(beta.astype(f32))                      # [n, b, Hv, C]
    gamma = jnp.cumsum(chunks(g.astype(f32)), axis=-1)
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    strict = idx[:, None] > idx[None, :]

    # the solve, for all chunks at once: K K^T is a key head's, beta and
    # the decays a value head's
    kk = _per_value_head(_mm(k, jnp.swapaxes(k, -1, -2)), group)
    t = unit_lower_inverse(jnp.where(
        strict, -(beta[..., None] * kk) * _decay(gamma, lower), 0.0))

    step = jax.checkpoint(functools.partial(_chunk_step, group=group))
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), f32),
                        (t, q, k, v, beta, gamma))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)        # [b, n, C, H, dv]
    return o.reshape(b, n * chunk, hv, dv)[:, :s].astype(out_dtype)
