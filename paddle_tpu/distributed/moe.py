"""Mixture-of-Experts — expert parallelism.

Reference: the EP building blocks global_scatter/global_gather
(operators/collective/global_scatter_op.cc, python/paddle/distributed/
utils.py:57,179) route variable token counts between n_expert*world_size
experts with NCCL alltoall; no gating library exists in the snapshot
(SURVEY.md §2.3: "building block only").

TPU-native inversion: variable-count alltoall is hostile to XLA's static
shapes, so routing uses the GShard/Switch fixed-capacity design — top-k gating
+ one-hot dispatch einsums; expert weights carry a PartitionSpec over the
'expert' mesh axis and GSPMD emits the AllToAll from the dispatch einsum's
contraction. The reference's global_scatter/global_gather API survives in
distributed/utils.py as eager permutation semantics for compatibility.

Two expert layers live here. `MoELayer` is that GShard layer: softmax top-2,
a capacity factor (tokens over capacity are dropped), one-hot [T, E, C]
dispatch. `DroplessMoELayer` is the layer of one chip of an
expert-parallel group: it is told which experts it holds, routes over all
of them (sigmoid scores and a selection bias, DeepSeek-V3 style, or a
softmax over all outputs with the chosen weights renormalised), and
computes its own experts' part for every token routed to them as grouped
matrix products over rows sorted by expert: no capacity, no dropped
token, no one-hot. Dispatch and combine are a pair of custom_vjp
primitives over k-major assignments whose residuals and backward passes
stay on the sorted side (`held_experts_ffn`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..framework.autograd import call_op
from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer
from . import mesh as mesh_mod

EXPERT_AXIS = mesh_mod.AXIS_EXPERT


def _top2_gating(logits, capacity):
    """GShard top-2 gating: returns (combine [T,E,C], dispatch [T,E,C], aux)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)
    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)

    # load-balance aux loss (Switch/GShard): E * mean(frac_tokens * frac_probs)
    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E

    # positions within each expert's capacity buffer
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - 1.0
    used1 = jnp.sum(mask1, axis=0, keepdims=True)
    pos2 = (jnp.cumsum(mask2, axis=0) * mask2 - 1.0) + used1 * mask2
    mask1 = mask1 * (pos1 < capacity)
    mask2 = mask2 * (pos2 < capacity)

    g1 = jnp.sum(probs * mask1, axis=-1)
    g2 = jnp.sum(probs * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    loc1 = jax.nn.one_hot(jnp.sum(pos1 * mask1, axis=-1).astype(jnp.int32),
                          capacity, dtype=jnp.float32)
    loc2 = jax.nn.one_hot(jnp.sum(pos2 * mask2, axis=-1).astype(jnp.int32),
                          capacity, dtype=jnp.float32)
    combine = (g1[:, None, None] * mask1[:, :, None] * loc1[:, None, :]
               + g2[:, None, None] * mask2[:, :, None] * loc2[:, None, :])
    dispatch = combine > 0.0
    return combine, dispatch, aux


class MoELayer(Layer):
    """Gated MoE FFN: top-2 routing over `num_experts` expert MLPs, experts
    sharded over the 'expert' mesh axis (build the mesh with
    {"expert": k, ...}). Input/output [batch, seq, hidden]. The load-balance
    aux loss is stored on ``self.aux_loss`` after each forward (add
    ``aux_weight * layer.aux_loss`` to the training loss)."""

    def __init__(self, hidden_size, ffn_hidden_size, num_experts,
                 capacity_factor=1.25, init_std=0.02, seed=0, dtype="float32"):
        super().__init__()
        from ..framework import dtype as dtype_mod
        from ..framework.tensor import Parameter

        self.num_experts = int(num_experts)
        self.capacity_factor = float(capacity_factor)
        rs = np.random.RandomState(seed)
        dt = dtype_mod.convert_dtype(dtype)

        def param(shape, std, spec):
            p = Parameter(Tensor((rs.randn(*shape) * std).astype("float32"),
                                 dtype=dt)._value, trainable=True)
            p.dist_spec = spec
            p.is_distributed = True
            return p

        E, H, F_ = self.num_experts, hidden_size, ffn_hidden_size
        self.gate_w = param([H, E], init_std, None)
        self.w_in = param([E, H, F_], init_std, P(EXPERT_AXIS, None, "model"))
        self.b_in = param([E, F_], 0.0, P(EXPERT_AXIS, "model"))
        self.w_out = param([E, F_, H], init_std, P(EXPERT_AXIS, "model", None))
        self.b_out = param([E, H], 0.0, P(EXPERT_AXIS, None))
        self.aux_loss = None

    def forward(self, x):
        E = self.num_experts
        cf = self.capacity_factor

        def fn(xv, gw, wi, bi, wo, bo):
            b, s, h = xv.shape
            T = b * s
            cap = max(1, int(math.ceil(T * cf / E)))
            tokens = xv.reshape(T, h)
            logits = tokens.astype(jnp.float32) @ gw.astype(jnp.float32)
            combine, dispatch, aux = _top2_gating(logits, cap)
            combine = combine.astype(xv.dtype)
            # dispatch: [T,E,C] x [T,H] -> [E,C,H]; GSPMD AllToAlls to experts
            ein = jnp.einsum("tec,th->ech", dispatch.astype(xv.dtype), tokens)
            ein = _constrain(ein, EXPERT_AXIS, None, None)
            z = jnp.einsum("ech,ehf->ecf", ein, wi) + bi[:, None, :]
            z = jax.nn.gelu(z, approximate=True)
            z = jnp.einsum("ecf,efh->ech", z, wo) + bo[:, None, :]
            z = _constrain(z, EXPERT_AXIS, None, None)
            out = jnp.einsum("tec,ech->th", combine, z)
            return out.reshape(b, s, h), aux

        out, aux = call_op(fn, x, self.gate_w, self.w_in, self.b_in,
                           self.w_out, self.b_out, op_name="moe_layer")
        self.aux_loss = aux
        return out


def _constrain(v, *spec):
    m = mesh_mod.get_mesh()
    if m is None:
        return v
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        v, NamedSharding(m, mesh_mod.sanitize_spec(P(*spec), m)))


# ---------------------------------------------------------------------------
# the dropless layer: sigmoid routing over all experts, grouped products
# over the experts held here
# ---------------------------------------------------------------------------

def _int_zero(v):
    """The cotangent custom_vjp wants for an integer input."""
    return np.zeros(v.shape, jax.dtypes.float0)


# Rows a sorted-side pass moves at a time. 2048 rows of a [T*k, h] buffer
# are a few MB: long enough that a loop's step costs little beside its
# rows, short enough that rounding `live` up to it wastes little.
ROW_BLOCK = 2048


def _row_blocks(live, n_rows: int):
    """(rows a block, blocks that hold the first `live` of `n_rows` rows)."""
    block = min(ROW_BLOCK, n_rows)
    return block, -(-live // block)


def rows_covered(live, n_rows: int):
    """The sorted rows the passes below write when `live` of `n_rows`
    belong to held experts: `live` rounded up to whole blocks."""
    block, trips = _row_blocks(live, n_rows)
    return jnp.minimum(trips * block, n_rows)


def _take_rows(v, idx):
    """v[idx] for indices this module made (a permutation, or token
    numbers): all in bounds, so no pass checks them."""
    return v.at[idx].get(mode="promise_in_bounds")


def _over_row_blocks(n_rows: int, live, step, carry):
    """carry = step(start, block, carry) for the blocks of `block` rows
    from `start` that hold the first `live` of `n_rows` sorted rows: a loop
    whose trip count the device computes from `live`, so a pass written as
    one costs what the step's held experts were given and not T*k. The last
    block of a buffer that is no whole number of blocks starts early and
    does some rows a second time, alike."""
    block, trips = _row_blocks(live, n_rows)
    return jax.lax.fori_loop(
        0, trips, lambda i, c: step(
            jnp.minimum(i * block, n_rows - block), block, c), carry)


def _sorted_rows(src, idx, live):
    """Row a of the result is src[idx[a]] for every a < rows_covered(live)
    (`_over_row_blocks`). The rows past that are zero and stand for
    NOTHING: what reads a sorted buffer stops at the last group (the
    grouped products) or drops the tail where it consumes it
    (`_live_rows`)."""
    n = idx.shape[0]
    # XLA sinks a fusible producer of a loop's operand into the loop's body
    # (seen compiling for the v5e: the whole [T*k, h] cotangent was made
    # again in every trip); behind a barrier it is made once
    src = jax.lax.optimization_barrier(src)

    def move(start, block, out):
        rows = _take_rows(src, jax.lax.dynamic_slice(idx, (start,), (block,)))
        return jax.lax.dynamic_update_slice(out, rows, (start, 0))

    return _over_row_blocks(n, live, move,
                            jnp.zeros((n,) + src.shape[1:], src.dtype))


def _live_rows(rows, at, live):
    """`rows` [n, ...] where they stand for the sorted rows `at` [n] that
    belong to held experts (at < live), zero where they stand for the
    tail's. This is where the tail is dropped, everywhere it is consumed,
    inside the pass that consumes it and not in one of its own. A `where`
    and never a multiply: XLA's grouped-matmul kernel on the TPU leaves the
    rows past the last group UNWRITTEN (5.2 was read there on the v5e, PR
    27; NaN is as likely; the CPU lowering zeroes them), and 0 * NaN is
    NaN."""
    keep = (at < live).reshape(at.shape + (1,) * (rows.ndim - 1))
    return jnp.where(keep, rows, jnp.zeros((), rows.dtype))


def _live_rows_by_token(rows, inv, live):
    """Row j*T + t of the result is the sorted row inv[j*T + t] of token
    t's j-th assignment, zero where that went to an absent expert (a sorted
    row at or past `live`: `_live_rows`). The one token-side pass over
    T*k rows each direction has: the forward of combine and the backward
    of dispatch, each summing the k slabs [T, h] it returns."""
    return _live_rows(_take_rows(rows, inv), inv, live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_by_expert(x2, order, inv, live, k: int):
    """Dispatch: row a of the result is token order[a] % T of x2 [T, h]
    (the T*k assignments are k-major: flat index j*T + t), for the `live`
    rows that belong to held experts (rounded up to a block; the tail has
    no value, see `_sorted_rows`). The transpose of this gather is a
    scatter-add; `inv` (order's inverse permutation) turns it into a gather
    of [k*T, h] by token and a sum of its k slabs [T, h], which the TPU
    does at memory speed. The cotangent's tail comes out of the grouped
    products unwritten and is dropped on the gathered rows
    (`_live_rows_by_token`)."""
    return _sorted_rows(x2, order % x2.shape[0], live)


def _rows_by_expert_fwd(x2, order, inv, live, k):
    return _rows_by_expert(x2, order, inv, live, k), (order, inv, live)


def _rows_by_expert_bwd(k, res, g):
    order, inv, live = res
    # k-major: the reshape moves nothing and the sum over k is one of slabs
    dx = jnp.sum(_live_rows_by_token(g, inv, live).reshape(
        k, -1, g.shape[-1]), axis=0, dtype=jnp.float32)
    return (dx.astype(g.dtype), _int_zero(order), _int_zero(inv),
            _int_zero(live))


_rows_by_expert.defvjp(_rows_by_expert_fwd, _rows_by_expert_bwd)


@jax.custom_vjp
def _weighted_rows_by_token(out, w, order, inv, live):
    """Combine: y[t] = sum over j of w[j, t] * out[inv[j*T + t]], in
    float32 and cast to out's dtype, `out` [k*T, h] the sorted rows the
    grouped products wrote, `w` [k, T] float32; an assignment to an absent
    expert (a sorted row at or past `live`, which nothing wrote) adds zero
    (`_live_rows_by_token`). Forward: one gather of [k*T, h] by token and a
    weighted sum of its k slabs.

    The residuals are `out`, `w` and the permutation, NOT the gathered rows:
    under a caller's jax.checkpoint the recomputed forward's gather then
    feeds nothing and XLA drops it. Backward runs on the SORTED side and
    covers rows_covered(live) rows, in one loop over blocks
    (`_over_row_blocks`): for the sorted row a of token t = order[a] % T,
    d out[a] = w_sorted[a] * dy[t] (gathered out of dy [T, h] itself) and
    dw_sorted[a] = sum over h of out[a] * dy[t] in float32, the rows of
    `out` at or past `live` dropped by index (`_live_rows`); then d w is
    dw_sorted gathered by `inv`, T*k scalars. No token-side [T*k, h] array
    is made: the one [k*T, h] buffer is d out, whose tail needs no value,
    since only the grouped products read it and they stop at the last
    group."""
    k = w.shape[0]
    sel = _live_rows_by_token(out, inv, live).reshape(k, -1, out.shape[-1])
    y = jnp.sum(sel.astype(jnp.float32) * w[..., None], axis=0)
    return y.astype(out.dtype)


def _weighted_rows_by_token_fwd(out, w, order, inv, live):
    return (_weighted_rows_by_token(out, w, order, inv, live),
            (out, w, order, inv, live))


def _weighted_rows_by_token_bwd(res, dy):
    out, w, order, inv, live = res
    n, T = order.shape[0], w.shape[1]
    w_flat = w.reshape(-1)
    # as in `_sorted_rows`: made once, not in every trip
    dy, out = jax.lax.optimization_barrier((dy, out))

    def rows(start, block, carry):
        d_out, dw = carry
        at = jax.lax.dynamic_slice(order, (start,), (block,))
        g = _take_rows(dy, at % T).astype(jnp.float32)
        d_rows = (_take_rows(w_flat, at)[:, None] * g).astype(out.dtype)
        mine = _live_rows(
            jax.lax.dynamic_slice(out, (start, 0), (block, out.shape[1])),
            start + jnp.arange(block, dtype=jnp.int32), live)
        dots = jnp.sum(mine.astype(jnp.float32) * g, axis=-1)
        return (jax.lax.dynamic_update_slice(d_out, d_rows, (start, 0)),
                jax.lax.dynamic_update_slice(dw, dots, (start,)))

    d_out, dw = _over_row_blocks(n, live, rows, (
        jnp.zeros_like(out), jnp.zeros((n,), jnp.float32)))
    dw = _live_rows(_take_rows(dw, inv), inv, live).reshape(w.shape)
    return d_out, dw, _int_zero(order), _int_zero(inv), _int_zero(live)


_weighted_rows_by_token.defvjp(_weighted_rows_by_token_fwd,
                               _weighted_rows_by_token_bwd)


def sigmoid_topk_route(x2, router_w, bias, top_k: int, scale: float):
    """DeepSeek-V3 routing (`noaux_tc`, one group) of x2 [T, h] over the
    router's R outputs, all in float32: scores s = sigmoid(x W_g); the
    top_k of s + bias are chosen (the bias selects and never weighs, and
    takes no gradient); weights s[chosen] / sum(s[chosen]) * scale.
    Returns (chosen [T, k] int32, weights [T, k] float32)."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.dot(
            x2.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * scale
        return chosen.astype(jnp.int32), w


def softmax_topk_route(x2, router_w, top_k: int):
    """Softmax routing of x2 [T, h] over the router's R outputs, all in
    float32: p = softmax(x W_r) over all R; the top_k of p are chosen;
    weights p[chosen] / sum(p[chosen]). No selection bias and no scale.
    Returns (chosen [T, k] int32, weights [T, k] float32)."""
    with jax.named_scope("moe_router"):
        p = jax.nn.softmax(jnp.dot(
            x2.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        picked, chosen = jax.lax.top_k(p, top_k)
        w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w


def held_experts_ffn(x2, chosen, weights, w_gate, w_up, w_down, lo: int):
    """The part of the routed result that experts [lo, lo + E) give, E the
    leading size of the weights [E, h, f] / [E, f, h] (SwiGLU experts):
    y[t] = sum over t's chosen experts e held here of weights * E_e(x[t]).

    The T*k assignments, laid out k-major (flat index j*T + t, so a
    [k*T, h] buffer by token is k slabs [T, h] and a sum over k a sum of
    slabs: no array carries k in its tiled minor axes), are sorted by
    expert; those to absent experts fall in a tail behind the `live` rows
    of the held ones. Two token-side passes over T*k rows of h are left, a
    gather each: the combine's forward and the dispatch's backward
    (`_live_rows_by_token`), which drop the tail on the rows they gathered,
    with a `where` since the tail may hold anything. Everything else runs on
    the sorted side for no longer than `live` asks: the dispatch's forward
    and the combine's WHOLE backward (d out and the weights' gradient,
    `_weighted_rows_by_token`) are loops that stop at rows_covered(live),
    the grouped products stop at the last group, and the combine keeps the
    sorted rows and not the gathered ones, so a caller's jax.checkpoint
    recomputes no token-side gather. Every assignment to a held expert is
    computed whatever the load, from none to all T*k: nothing is dropped
    and nothing is capped.
    """
    k = chosen.shape[1]
    E = w_gate.shape[0]
    with jax.named_scope("moe_dispatch"):
        flat = chosen.T.reshape(-1)                 # k-major: j*T + t
        held = (flat >= lo) & (flat < lo + E)
        local = jnp.where(held, flat - lo, E)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        group_sizes = jnp.sum(
            local[:, None] == jnp.arange(E, dtype=local.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        live = jnp.sum(group_sizes)
        xs = _rows_by_expert(x2, order, inv, live, k)
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, w_gate, group_sizes)
        u = jax.lax.ragged_dot(xs, w_up, group_sizes)
        a = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x2.dtype)
        out = jax.lax.ragged_dot(a, w_down, group_sizes)
    with jax.named_scope("moe_combine"):
        return _weighted_rows_by_token(out, weights.T, order, inv, live)


def swiglu(x, w_gate, w_up, w_down):
    """down(silu(gate(x)) * up(x)), the gate in float32."""
    a = jax.nn.silu((x @ w_gate).astype(jnp.float32)) \
        * (x @ w_up).astype(jnp.float32)
    return a.astype(x.dtype) @ w_down


def dropless_moe_val(x2, p: dict, bias, *, top_k: int, scale: float,
                     lo: int, router: str = "sigmoid"):
    """x2 [T, h] through the whole layer at value level. `p` holds
    router_w [h, R], w_gate / w_up [E, h, f], w_down [E, f, h] and, where
    the layer has shared experts, shared_gate / shared_up [h, fs] and
    shared_down [fs, h], and where those have a gate, shared_gate_w [h, 1]:
    the shared output is multiplied by sigmoid(x w), one number a token.
    `router`: "sigmoid" (`sigmoid_topk_route` with `bias` and `scale`) or
    "softmax" (`softmax_topk_route`, which takes neither). Returns (y
    [T, h], chosen [T, k], the counts of assignments per router output [R]
    int32)."""
    if router == "softmax":
        chosen, weights = softmax_topk_route(x2, p["router_w"], top_k)
    else:
        chosen, weights = sigmoid_topk_route(x2, p["router_w"], bias, top_k,
                                             scale)
    y = held_experts_ffn(x2, chosen, weights.astype(jnp.float32),
                         p["w_gate"], p["w_up"], p["w_down"], lo)
    with jax.named_scope("moe_router"):
        R = p["router_w"].shape[1]
        counts = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(
            R, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
    if "shared_gate" in p:
        with jax.named_scope("mlp"):
            shared = swiglu(x2, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
            if "shared_gate_w" in p:
                gate = jax.nn.sigmoid(
                    x2.astype(jnp.float32)
                    @ p["shared_gate_w"].astype(jnp.float32))
                shared = (shared.astype(jnp.float32) * gate).astype(x2.dtype)
            y = y + shared
    return y, chosen, counts


class DroplessMoELayer(Layer):
    """One chip's share of an expert layer, DeepSeek-V3 style or, with
    `router="softmax"`, Qwen3-Next style.

    `router_outputs` experts exist (R); this layer holds those in
    `experts_held` = [lo, hi). Every token is routed over all R (`router`
    "sigmoid": sigmoid scores, top `experts_per_token` of score +
    selection bias, weights normalised and scaled by `routed_scaling`;
    "softmax": softmax over all R, the top `experts_per_token`, weights
    renormalised, the bias unused and never moved, `routed_scaling` 1), and
    the layer returns the part of the result its own experts give, plus the
    shared expert's (one SwiGLU of `shared_width`, 0 for none; with
    `shared_gated` multiplied by sigmoid(x w_sg), one number a token). What
    the absent experts would add is left out; held on one chip the layer
    runs without the exchange that brings other chips' tokens.

    Two buffers ride through jit.TrainStep the way batch-norm statistics
    do: `select_bias` [R] float32, moved after each training forward by
    `bias_speed * sign(mean load - load)` from this chip's own counts (the
    auxiliary-loss-free balancing of arXiv:2412.19437, 2.1.2), and
    `assign_count` [R] int32, the cumulative assignments per router output.
    A third counts work: `touched_count` int32, the cumulative sorted rows
    the layer's passes covered (rows_covered of the held experts' rows:
    what the dispatch's forward writes and, since the combine's backward
    runs on the sorted side too, what that reads and writes), so
    touched_count / (steps * T * k) is the share of the sorted buffer that
    was moved at all (read it as a difference: int32 wraps). After a forward `self.chosen` holds the chosen
    experts [T, k], the way MoELayer keeps `aux_loss`, for a comparison
    with a reference router, and `self.rows_touched` that forward's rows.

    The layer has no `forward`: a decoder layer runs `apply_val` inside its
    own traced block (under jax.checkpoint, so that attention and the
    experts are recomputed as one) and hands `advance` what came out."""

    PARAMS = ("router_w", "w_gate", "w_up", "w_down")
    SHARED = ("shared_gate", "shared_up", "shared_down")

    def __init__(self, hidden_size, expert_width, router_outputs,
                 experts_per_token, experts_held=None, shared_width=0,
                 routed_scaling=1.0, bias_speed=0.001, init_std=0.02,
                 seed=0, dtype="float32", rs=None, router="sigmoid",
                 shared_gated=False):
        """`rs`: a numpy Generator to draw the weights from, in place of
        one made from `seed` (a model draws all its layers from one)."""
        super().__init__()
        from ..framework import dtype as dtype_mod
        from ..framework.tensor import Parameter

        lo, hi = experts_held or (0, router_outputs)
        if not 0 <= lo < hi <= router_outputs:
            raise ValueError(f"experts_held {experts_held} is no range of "
                             f"the router's {router_outputs} outputs")
        if router not in ("sigmoid", "softmax"):
            raise ValueError(f"router {router!r}: sigmoid or softmax")
        if router == "softmax" and routed_scaling != 1.0:
            raise ValueError(f"routed_scaling {routed_scaling}: softmax "
                             f"routing has no scale")
        if shared_gated and not shared_width:
            raise ValueError("shared_gated: the layer has no shared expert")
        self.router = router
        self.lo = int(lo)
        self.top_k = int(experts_per_token)
        self.routed_scaling = float(routed_scaling)
        self.bias_speed = float(bias_speed)
        rs = rs or np.random.default_rng(seed)
        dt = dtype_mod.convert_dtype(dtype)

        def param(*shape):
            w = rs.standard_normal(shape, dtype=np.float32) * init_std
            return Parameter(Tensor(w, dtype=dt)._value, trainable=True)

        E, h, f = int(hi - lo), hidden_size, expert_width
        self.router_w = param(h, router_outputs)
        self.w_gate = param(E, h, f)
        self.w_up = param(E, h, f)
        self.w_down = param(E, f, h)
        self.names = self.PARAMS
        if shared_width:
            self.shared_gate = param(h, shared_width)
            self.shared_up = param(h, shared_width)
            self.shared_down = param(shared_width, h)
            self.names = self.PARAMS + self.SHARED
        if shared_gated:
            self.shared_gate_w = param(h, 1)
            self.names = self.names + ("shared_gate_w",)
        self.register_buffer("select_bias", Tensor(
            np.zeros(router_outputs, np.float32)))
        self.register_buffer("assign_count", Tensor(
            np.zeros(router_outputs, np.int32)))
        self.register_buffer("touched_count", Tensor(np.zeros((), np.int32)))
        self.chosen = None
        self.rows_touched = None

    def apply_val(self, x2, pvals, bias):
        """The layer at value level on x2 [T, h]: `pvals` in the order of
        `self.names`. For a caller that runs the layer inside its own
        traced block (a decoder layer under jax.checkpoint)."""
        return dropless_moe_val(x2, dict(zip(self.names, pvals)), bias,
                                top_k=self.top_k, scale=self.routed_scaling,
                                lo=self.lo, router=self.router)

    def advance(self, chosen, counts):
        """Keep the router's choice; in training add the step's counts and
        move the selection bias toward balance. Values, inside or outside
        a trace."""
        self.chosen = chosen
        n_held = self.w_gate.shape[0]
        self.rows_touched = rows_covered(
            jnp.sum(counts[self.lo:self.lo + n_held]), chosen.size)
        if not self.training:
            return
        c = counts.astype(jnp.float32)
        self.assign_count._value = self.assign_count._value + counts
        self.touched_count._value = self.touched_count._value \
            + self.rows_touched
        if self.router == "softmax":       # no bias selects: none moves
            return
        self.select_bias._value = self.select_bias._value \
            + self.bias_speed * jnp.sign(jnp.mean(c) - c)
