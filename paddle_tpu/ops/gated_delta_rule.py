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
and every gradient). A and T are made for all chunks at once, in XLA.
Then the chunks follow one another, in one of two implementations of
(T, q, k, v, beta, gamma) -> o:

  - `kernels_over_chunks`, two Pallas kernels under one jax.custom_vjp,
    where the program is compiled for a TPU and `gdn_kernel_supported`
    takes the shapes: grid (b, Hv / 8, chunks), the chunk axis sequential,
    eight value heads' states S (and in the backward dS) in VMEM for the
    heads' whole sequence, q and k read at the key head by the index map,
    v in its own dtype. The forward saves the state at each chunk's start;
    the backward walks the chunks from the last to the first and makes, by
    hand, what JAX makes of `_chunk_step`.
  - `scan_over_chunks`, a `lax.scan` of `_chunk_step` under jax.checkpoint,
    everywhere else (the CPU, narrow heads), and what the kernels are
    tested against (tests/test_gated_delta_rule_kernel.py).

Either way nothing but the inputs, T and one state a chunk lies in memory
for a whole sequence (with U, W and the decayed copies of Q and K made
for all chunks ahead of the scan the 8k step's temporaries were 7.9 GB by
the TPU compiler's count, 14.3 GB with the state: PERF.md, PR 33).

Float32 throughout, whatever the inputs' dtype: g, beta, the cumulative
decays, every operand and accumulation of every product (precision
HIGHEST: on the TPU a float32 product at default precision rounds its
operands to bfloat16; in the kernels HIGHEST's six bfloat16 passes are
written out, `_dot`), the states S and dS. Every exponent is <= 0: the
decays are formed as exp of differences gamma_i - gamma_j with i >= j,
masked BEFORE the exp.

T is made by forward substitution on 16 x 16 diagonal blocks and the
block formula [[T11, 0], [T22 A21 T11, T22]] above them, not by the
Neumann series I + A + A^2 + ...: with repeated keys the series' terms
grow binomially and cancel, forward substitution does not. Its gradient is
written by hand (dA = T^T dT T^T), as the kernels' is; everything else
(the padding, the decays, K K^T, the mask; in the fallback the scan's body
under jax.checkpoint, so that what its backward keeps is one state a
chunk) is differentiated by JAX.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _sds

# Tokens a chunk. 64: the intra-chunk products are 64 x 64 x 128, a sequence
# is s / 64 dependent steps. A choice of this file, not a key of any
# configuration. (128 on the chip, PR 34: the kernels no faster, the solve
# and T four times the size a chunk.)
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


def _chunks(t, n: int, chunk: int):
    """[b, n*C, H, ...] -> [n, b, H, C, ...]: chunks lead (the scan's
    axis), a head's chunk is a contiguous (C, d) tile."""
    t = t.reshape((t.shape[0], n, chunk) + t.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)


def scan_over_chunks(t, q, k, v, beta, gamma):
    """The chunks one after another as a `lax.scan` of `_chunk_step`, under
    jax.checkpoint: what runs wherever the kernels do not, and what they
    are tested against. t [n, b, Hv, C, C], beta and gamma [n, b, Hv, C];
    q, k [b, n*C, Hk, dk] float32 and v [b, n*C, Hv, dv] as they came.
    Returns o [b, n*C, Hv, dv] float32."""
    n, b, hv, chunk = beta.shape
    dk, dv = q.shape[3], v.shape[3]
    # q, k stay at the key heads' count and v in its own dtype until a
    # chunk's step needs them: what lies in memory for the whole sequence
    # is the inputs once and T
    q, k, v = (_chunks(x, n, chunk) for x in (q, k, v))
    step = jax.checkpoint(functools.partial(
        _chunk_step, group=hv // q.shape[2]))
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32),
                        (t, q, k, v, beta, gamma))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)        # [b, n, C, H, dv]
    return o.reshape(b, n * chunk, hv, dv)


# ---------------------------------------------------------------------------
# the same chunks as two Pallas kernels: grid (b, Hv / heads, chunks), the
# chunk axis innermost and sequential, the heads' states in VMEM for their
# whole sequence
# ---------------------------------------------------------------------------

def _interpret() -> bool:
    # this file's own, as every kernel file has: the static gate (K001)
    # follows a pallas_call's interpret= to target_platform() in its file
    from ..framework.target import target_platform

    return target_platform() != "tpu"


def gdn_kernel_supported(q_shape, v_shape, chunk: int) -> bool:
    """True where the kernels take q, k [b, s, Hk, dk] and v
    [b, s, Hv, dv] at this chunk: heads that are whole 128-lane tiles (a
    head's (C, d) tile is then a block of the [b, s, H * d] array, read by
    its index map: no copy in chunk layout), a key head's value heads all
    in one grid step (dq and dk are summed over them there), the file's
    chunk, and states small enough for the VMEM plan."""
    if len(q_shape) != 4 or len(v_shape) != 4:
        return False
    (hk, dk), (hv, dv) = q_shape[2:], v_shape[2:]
    return (dk % 128 == 0 and dv % 128 == 0 and chunk == GDN_CHUNK
            and hv // hk <= _HEADS_A_STEP and dk * dv <= _STATE_ELEMENTS)


# The VMEM plan, a grid step of _HEADS_A_STEP heads: S or dS as scratch and
# the saved states' block twice (double-buffered) are 3 x 8 tiles of
# dk x dv float32, 1.5 MB at 128 x 128; a chunk's blocks twice (T and dT as
# (64, 128) tiles, q, k, v, do and four gradients 64 rows of 8 heads) 5 MB;
# the rest is the compiler's (every product's operands as six bfloat16
# parts, eight heads at once): at 128 x 128 the backward's scoped VMEM is
# 16.1 MB by its count, over the 16 MB default. _VMEM_LIMIT is half the
# v5e's 128 MiB; a state of 256 x 128 compiles under it, the largest tried
# (tests/test_tpu_aot.py holds the cell's shape).
_STATE_ELEMENTS = 256 * 128
_VMEM_LIMIT = 64 << 20


def _parts(x):
    """float32 x as three bfloat16 that sum to it within 2^-24 of its size:
    what precision HIGHEST feeds the MXU."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    return hi, mid, (rest - mid.astype(f32)).astype(bf16)


def _dot(a, b, ca: int = 1, cb: int = 0, *, memo: dict):
    """For each head of a [heads, ., .] and b [heads, ., .], a . b over a's
    matrix dim `ca` and b's `cb` (0 rows, 1 columns), float32 operands and
    accumulation, as precision HIGHEST makes it on the MXU: the six
    products a1 b1 + (a1 b2 + a2 b1) + (a1 b3 + a2 b2 + a3 b1) of the
    operands' bfloat16 parts. Written out, and not left to Mosaic's
    contract_precision<fp32> (the same passes, each its own matmul), so
    that the six lie side by side along ONE contraction,
    [a1 a2 a3 a1 a2 a1] . [b1; b1; b1; b2; b2; b3]: two 64-deep passes then
    share one 128-deep tile of the MXU, which is what bounds these
    kernels (PERF.md, PR 34), and an operand's parts are made once however
    many products it enters (`memo`, by operand and side)."""
    def side(x, contracted, order, left):
        """x's parts in `order` along its contracted dim; a left operand
        contracts its columns (transposed first where it gave its rows)."""
        key = (id(x), contracted, left)
        if key not in memo:
            if left and contracted == 0:
                x, contracted = jnp.swapaxes(x, 1, 2), 1
            parts = _parts(x)
            # x is kept beside its parts: an id is unique while it lives
            memo[key] = x, jnp.concatenate(
                [parts[i] for i in order], axis=1 + contracted)
        return memo[key][1]

    return jax.lax.dot_general(
        side(a, ca, (0, 1, 2, 0, 1, 0), True),
        side(b, cb, (0, 0, 0, 1, 1, 2), False),
        (((2,), (1 + cb,)), ((0,), (0,))),
        # one pass a product: each is exact in float32, whatever precision
        # the caller's context asks of float32 operands
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


class _Chunk:
    """What forward and backward both make of one chunk of a grid step's
    value heads (every array [heads, ., .]): the per-token factors as
    (C, 1) columns, the chunk's own scores and V' = T (V_beta - (K_beta
    e^gamma) S) (U - W S with T taken out: one product with S and one
    with T where U, W and W S are three), from T (C, C), v (C, dv)
    float32, beta and gamma as (1, C) rows (64 values a chunk, lane-dense
    in HBM), the state S (dk, dv) at the chunk's start, and q, k (C, dk)
    at the KEY heads, each serving `heads / key heads` value heads in a
    row: q k^T is made once a key head."""

    def __init__(self, t, q, k, v, beta_row, gam_row, s):
        c = q.shape[1]
        self.dot = functools.partial(_dot, memo={})
        i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye, self.lower = i == j, i >= j
        self.last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
        self.group = group = v.shape[0] // q.shape[0]

        def per_value_head(x):
            return jnp.repeat(x, group, axis=0)

        qk = per_value_head(self.dot(q, k, 1, 1))
        self.t, self.v, self.s = t, v, s
        self.q, self.k = q, k = per_value_head(q), per_value_head(k)
        self.beta, self.gam = self.col(beta_row), self.col(gam_row)
        self.e_gam = jnp.exp(self.gam)
        self.k_beta_e = (k * self.beta) * self.e_gam
        # exp(gamma_i - gamma_j), i >= j; masked before the exp
        self.decay = jnp.exp(jnp.where(
            self.lower, self.gam - gam_row, -jnp.inf))
        self.a_qk = jnp.where(self.lower, qk * self.decay, 0.0)
        self.unsolved = v * self.beta - self.dot(self.k_beta_e, s)
        self.v_new = self.dot(t, self.unsolved)
        self.q_e = q * self.e_gam
        gam_end = jnp.sum(jnp.where(self.last, gam_row, 0.0), axis=2,
                          keepdims=True)                      # (H, 1, 1)
        self.e_end = jnp.exp(gam_end)
        self.to_end = jnp.exp(gam_end - self.gam)                # (C, 1)
        self.k_d = k * self.to_end

    def col(self, row):
        """A (1, C) row as a (C, 1) column: exact (a sum of one value and
        zeros), and no transpose of a 64-lane tile."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=2, keepdims=True)

    def row(self, col):
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=1, keepdims=True)

    def per_key_head(self, x):
        """The sum over the value heads a key head serves."""
        return jnp.stack([sum(x[h] for h in range(j, j + self.group))
                          for j in range(0, x.shape[0], self.group)])

    def forward(self):
        """(o (C, dv), the state at the chunk's end)."""
        o = self.dot(self.q_e, self.s) + self.dot(self.a_qk, self.v_new)
        return o, _next_state(self.e_end * self.s,
                              self.dot(self.k_d, self.v_new, 0, 0))

    def backward(self, do, ds_end):
        """From do (C, dv) and the cotangent of the state at the chunk's
        END: (dT, dq and dk per KEY head, dv, dbeta and dgamma as rows, the
        cotangent of the state at the chunk's START). gamma enters in the
        decays of the scores, in e^gamma (K_beta and q), in
        e^(gamma_C - gamma) (k) and in e^gamma_C (S)."""
        dot, s = self.dot, self.s

        def rowsum(x):
            return jnp.sum(x, axis=2, keepdims=True)

        dv_new = dot(self.a_qk, do, 0, 0) + dot(self.k_d, ds_end)
        da = jnp.where(self.lower, dot(do, self.v_new, 1, 1), 0.0)
        dq_e = dot(do, s, 1, 1)
        dk_d = dot(self.v_new, ds_end, 1, 1)
        dunsolved = dot(self.t, dv_new, 0, 0)
        dt = dot(dv_new, self.unsolved, 1, 1)
        dk_beta_e = -dot(dunsolved, s, 1, 1)
        dqk = da * self.decay
        through_decay = da * self.a_qk          # d decay_ij x decay_ij
        dq = dot(dqk, self.k) + dq_e * self.e_gam
        dk = dot(dqk, self.q, 0, 0) + dk_d * self.to_end \
            + dk_beta_e * (self.beta * self.e_gam)
        dv = dunsolved * self.beta
        dbeta = rowsum(dunsolved * self.v) \
            + rowsum(dk_beta_e * self.k) * self.e_gam
        from_end = rowsum(dk_d * self.k_d)                       # (C, 1)
        dgam = rowsum(dk_beta_e * self.k_beta_e) + rowsum(dq_e * self.q_e) \
            + rowsum(through_decay) - from_end
        at_end = jnp.sum(from_end, axis=1, keepdims=True) + self.e_end \
            * jnp.sum(rowsum(s * ds_end), axis=1, keepdims=True)
        dgam_row = self.row(dgam) \
            - jnp.sum(through_decay, axis=1, keepdims=True) \
            + jnp.where(self.last, at_end, 0.0)
        ds = self.e_end * ds_end + dot(self.q_e, do, 0, 0) \
            - dot(self.k_beta_e, dunsolved, 0, 0)
        return (dt, self.per_key_head(dq), self.per_key_head(dk), dv,
                self.row(dbeta), dgam_row, ds)


def _next_state(decayed, update):
    """S at a chunk's end, float32 as its two terms are (what the
    benchmark's bf16-state control wraps on the kernels' path)."""
    return decayed + update


def _load_heads(ref, d: int):
    """The (C, heads x d) tile `ref` holds as float32 [heads, C, d]."""
    return jnp.stack([ref[0, :, h * d:(h + 1) * d]
                      for h in range(ref.shape[2] // d)]).astype(jnp.float32)


def _heads_chunk(refs, dk: int, dv: int, s):
    """The `_Chunk` of a grid step's value heads from the blocks (T, q, k,
    v, beta, gamma) and the heads' states s [heads, dk, dv]."""
    t_ref, q_ref, k_ref, v_ref, beta_ref, gam_ref = refs
    return _Chunk(t_ref[0, 0], _load_heads(q_ref, dk), _load_heads(k_ref, dk),
                  _load_heads(v_ref, dv), beta_ref[0, 0], gam_ref[0, 0], s)


def _store_heads(ref, x):
    """x [heads, C, d] into the (C, heads x d) tile `ref` holds."""
    d = x.shape[2]
    for h in range(x.shape[0]):
        ref[0, :, h * d:(h + 1) * d] = x[h]


def _fwd_kernel(*refs, dk, dv):
    """The value heads of a grid step over one chunk. After the six
    inputs: o; where the backward will want them, the block of the states
    output; the state scratch."""
    o_ref, *states_ref, s_ref = refs[6:]

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    for ref in states_ref:
        ref[0, :, 0] = s
    o, s_ref[...] = _heads_chunk(refs[:6], dk, dv, s).forward()
    _store_heads(o_ref, o)


def _bwd_kernel(*refs, dk, dv):
    """The value heads of a grid step over one chunk, the chunks from the
    last to the first. After the six inputs: the states, do; dT, dq, dk,
    dv, dbeta, dgamma; ds_ref, the cotangent of the state at the chunk's
    end, 0 past the last chunk (the final state is no output)."""
    s_ref, do_ref = refs[6:8]
    dt_ref, dq_ref, dk_ref, dv_ref, dbeta_ref, dgam_ref, ds_ref = refs[8:]

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    (dt_ref[0, 0], dq, dk_, dv_, dbeta_ref[0, 0], dgam_ref[0, 0],
     ds_ref[...]) = _heads_chunk(refs[:6], dk, dv, s_ref[0, :, 0]).backward(
         _load_heads(do_ref, dv), ds_ref[...])
    for ref, x in ((dq_ref, dq), (dk_ref, dk_), (dv_ref, dv_)):
        _store_heads(ref, x)


# Value heads a grid step, at most. A chunk is a chain of dependent
# products: one head a step waits on itself (1,180 bundles a head by the
# compiler's schedule, 620 at eight heads worked stage by stage as one
# [heads, ., .] batch; 4 and 8 read the same on the chip: PERF.md, PR 34)
_HEADS_A_STEP = 8


def _heads_a_step(hv: int, group: int) -> int:
    """The most value heads a step, up to _HEADS_A_STEP, that divide Hv
    and are whole groups: a step's q, k, dq, dk blocks are then whole key
    heads."""
    return max(h for h in range(group, _HEADS_A_STEP + 1, group)
               if hv % h == 0)


def _call(kernel, name, shapes, chunk_of, ins, outs, out_shapes, like):
    """A kernel over grid (b, Hv / heads, chunks), the chunk axis
    innermost and sequential, `chunk_of` the chunk a grid step works on.
    `ins` / `outs` name each operand's block: "t" (a chunk's T of the
    step's heads), "row" (their beta or gamma), "qk" / "v" (a (C, heads x
    d) tile of a [b, s, H * d] array: the step's key heads, its value
    heads), "state"."""
    b, s, hk, hv, dk, dv, chunk = shapes
    heads = _heads_a_step(hv, hv // hk)

    def tile(width):
        return pl.BlockSpec((1, chunk, width), lambda bi, hi, ci: (
            bi, chunk_of(ci), hi))

    def per_chunk(*block):
        return pl.BlockSpec((1, 1, heads) + block, lambda bi, hi, ci: (
            chunk_of(ci), bi, hi, 0, 0))

    specs = {
        "t": per_chunk(chunk, chunk), "row": per_chunk(1, chunk),
        "qk": tile(heads * hk // hv * dk), "v": tile(heads * dv),
        "state": pl.BlockSpec((1, heads, 1, dk, dv), lambda bi, hi, ci: (
            bi, hi, chunk_of(ci), 0, 0))}
    return pl.pallas_call(
        functools.partial(kernel, dk=dk, dv=dv),
        grid=(b, hv // heads, s // chunk),
        in_specs=[specs[x] for x in ins], out_specs=[specs[x] for x in outs],
        out_shape=[_sds(shape, jnp.float32, like) for shape in out_shapes],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        # the names the benchmark's readers find the kernels by
        # (benchmark/layer_metrics/gdn_chunk_roofline.json)
        name=name)


def _shapes(q, v, beta):
    (b, s, hk, dk), (hv, dv) = q.shape, v.shape[2:]
    return b, s, hk, hv, dk, dv, beta.shape[-1]


def _flat(x):
    """[b, s, H, d] as [b, s, H * d]: a head's chunk is a (C, d) block."""
    return x.reshape(x.shape[:2] + (-1,))


def _fwd(t, q, k, v, beta, gamma, save_states: bool):
    """(o [b, s, Hv, dv] float32, the state at each chunk's start
    [b, Hv, n, dk, dv] or None)."""
    b, s, hk, hv, dk, dv, chunk = shapes = _shapes(q, v, beta)
    n = s // chunk
    rows = (n, b, hv, 1, chunk)
    outs = {"v": (b, s, hv * dv)}
    if save_states:
        outs["state"] = (b, hv, n, dk, dv)
    o, *states = _call(
        _fwd_kernel, "gdn_chunk_fwd", shapes, lambda ci: ci,
        ("t", "qk", "qk", "v", "row", "row"), tuple(outs),
        list(outs.values()), q,
    )(t, _flat(q), _flat(k), _flat(v), beta.reshape(rows),
      gamma.reshape(rows))
    return o.reshape(b, s, hv, dv), states[0] if states else None


def _bwd(t, q, k, v, beta, gamma, states, do):
    b, s, hk, hv, dk, dv, chunk = shapes = _shapes(q, v, beta)
    n = s // chunk
    rows = (n, b, hv, 1, chunk)
    dt, dq, dk_, dv_, dbeta, dgamma = _call(
        _bwd_kernel, "gdn_chunk_bwd", shapes, lambda ci: n - 1 - ci,
        ("t", "qk", "qk", "v", "row", "row", "state", "v"),
        ("t", "qk", "qk", "v", "row", "row"),
        [t.shape, (b, s, hk * dk), (b, s, hk * dk), (b, s, hv * dv), rows,
         rows], q,
    )(t, _flat(q), _flat(k), _flat(v), beta.reshape(rows),
      gamma.reshape(rows), states, _flat(do))
    return (dt, dq.reshape(q.shape), dk_.reshape(k.shape),
            dv_.reshape(v.shape).astype(v.dtype), dbeta.reshape(beta.shape),
            dgamma.reshape(gamma.shape))


@jax.custom_vjp
def kernels_over_chunks(t, q, k, v, beta, gamma):
    """`scan_over_chunks` as the two kernels `gdn_chunk_fwd` and
    `gdn_chunk_bwd`, the same arguments and result. q and k are read at
    the key heads by the index maps (dq, dk leave at them), v in its own
    dtype and widened in VMEM; between the forward and the backward lie
    the inputs and the state at each chunk's start (what the scan
    keeps)."""
    return _fwd(t, q, k, v, beta, gamma, False)[0]


def _kernels_fwd_rule(t, q, k, v, beta, gamma):
    o, states = _fwd(t, q, k, v, beta, gamma, True)
    return o, (t, q, k, v, beta, gamma, states)


def _kernels_bwd_rule(res, do):
    # the scope of the forward's caller (models/gdn_moe.py), so that the
    # XLA ops around the backward kernel are booked with it
    with jax.named_scope("gdn_chunk"):
        return _bwd(*res, do)


kernels_over_chunks.defvjp(_kernels_fwd_rule, _kernels_bwd_rule)


def _chunked(q, k, v, g, beta, chunk: int, over_chunks):
    """The rule with `over_chunks` for the chunks one after another."""
    s, hk, hv = q.shape[1], q.shape[2], v.shape[2]
    out_dtype = v.dtype
    f32 = jnp.float32
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk
    q, k = q.astype(f32), k.astype(f32)
    beta = _chunks(beta.astype(f32), n, chunk)           # [n, b, Hv, C]
    gamma = jnp.cumsum(_chunks(g.astype(f32), n, chunk), axis=-1)
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    strict = idx[:, None] > idx[None, :]

    # the solve, for all chunks at once: K K^T is a key head's, beta and
    # the decays a value head's
    k_n = _chunks(k, n, chunk)
    kk = _per_value_head(_mm(k_n, jnp.swapaxes(k_n, -1, -2)), hv // hk)
    t = unit_lower_inverse(jnp.where(
        strict, -(beta[..., None] * kk) * _decay(gamma, lower), 0.0))
    o = over_chunks(t, q, k, v, beta, gamma)
    return o[:, :s].astype(out_dtype)


def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int = GDN_CHUNK):
    """The gated delta rule over whole sequences, S_0 = 0.

    q, k [b, s, Hk, dk] (as the rule takes them: k of unit length, q
    scaled), v [b, s, Hv, dv], g (log decay, <= 0) and beta [b, s, Hv]; Hk
    divides Hv and key head j serves the value heads j * (Hv // Hk) ...
    Returns o [b, s, Hv, dv] in v's dtype. A sequence that is no whole
    number of chunks is padded at its end with tokens of beta = 0 and
    g = 0, which leave the state as it is, and their outputs are dropped.

    The chunks follow one another in the two Pallas kernels where the
    program is compiled for a TPU and `gdn_kernel_supported` takes the
    shapes, else in the scan; ops.pallas.autotune's dispatch counter says
    which (`gated_delta_rule`: `kernel` or `scan`).
    """
    b, s, hk, dk = q.shape
    hv = v.shape[2]
    if k.shape != q.shape or v.shape[:2] != (b, s) or hv % hk \
            or g.shape != (b, s, hv) or beta.shape != (b, s, hv):
        raise ValueError(
            f"gated delta rule: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape} are no [b, s, Hk, dk] x 2, "
            f"[b, s, Hv, dv], [b, s, Hv] x 2 with Hk dividing Hv")
    from ..framework.target import target_platform
    from .pallas import autotune

    kernels = target_platform() == "tpu" and gdn_kernel_supported(
        q.shape, v.shape, chunk)
    autotune.count_dispatch("gated_delta_rule",
                            "kernel" if kernels else "scan")
    return _chunked(q, k, v, g, beta, chunk,
                    kernels_over_chunks if kernels else scan_over_chunks)
