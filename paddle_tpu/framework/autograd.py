"""Eager autograd engine over jax VJPs.

Reference behavior: paddle/fluid/imperative/{tracer.cc,basic_engine.cc,
gradient_accumulator.cc} — ``Tracer::TraceOp`` records a ``GradOpNode`` per op;
``loss.backward()`` runs a reverse-topological walk accumulating gradients.

TPU-native design: instead of per-op grad kernels, every functional kernel is a
pure jax function; at dispatch time (``call_op``) we take ``jax.vjp`` of the
function over its differentiable Tensor inputs. That computes the forward *once*
(vjp returns primal outputs + a pullback closure holding residuals on device)
and records a ``GradNode``. ``backward()`` is a Kahn walk over GradNodes calling
the pullbacks — the analog of BasicEngine::Execute's queue over GradOpNode.

The fast path (whole-step ``jax.jit``) does not use this tape at all: to_static
traces the forward functionally and differentiates with ``jax.grad``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_tls = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_tls, "grad_enabled", True)


def _set_grad_enabled(v: bool):
    _tls.grad_enabled = v


class no_grad(contextlib.ContextDecorator):
    """paddle.no_grad — usable as context manager or decorator."""

    def __enter__(self):
        self._prev = is_grad_enabled()
        _set_grad_enabled(False)
        return self

    def __exit__(self, *exc):
        _set_grad_enabled(self._prev)
        return False


class enable_grad(contextlib.ContextDecorator):
    def __enter__(self):
        self._prev = is_grad_enabled()
        _set_grad_enabled(True)
        return self

    def __exit__(self, *exc):
        _set_grad_enabled(self._prev)
        return False


@contextlib.contextmanager
def set_grad_enabled(mode: bool):
    prev = is_grad_enabled()
    _set_grad_enabled(bool(mode))
    try:
        yield
    finally:
        _set_grad_enabled(prev)


class GradNode:
    """One recorded op: pullback + which Tensors its cotangents flow to.

    ``inputs`` snapshots each input's producing node at record time — the tape
    must route cotangents through the graph as it existed when the op ran, not
    as it looks after a later in-place rebind of the same Tensor (otherwise
    ``y = x*2; x[0] = 5; y.backward()`` would send y's cotangent through the
    setitem node and corrupt gradients).
    """

    __slots__ = (
        "vjp_fn",
        "inputs",
        "out_avals",
        "multi_output",
        "pending",
        "name",
        "released",
        "replay",
        "__weakref__",
    )

    def __init__(self, vjp_fn, inputs, out_avals, multi_output, name="",
                 replay=None):
        self.vjp_fn = vjp_fn
        # list[(Tensor, producer GradNode|None, out_index)] aligned with the
        # pullback's cotangent outputs
        self.inputs = inputs
        self.out_avals = out_avals  # list[ShapeDtypeStruct]
        self.multi_output = multi_output
        self.pending: Dict[int, Any] = {}
        self.name = name
        self.released = False
        # (fn, args, kwargs, tensor_pos, diff_j) when the op can be replayed
        # differentiably for create_graph (double grad)
        self.replay = replay

    def seed(self, idx: int, cot):
        cur = self.pending.get(idx)
        self.pending[idx] = cot if cur is None else cur + cot

    def release(self):
        self.vjp_fn = None
        self.inputs = None
        self.pending = {}
        self.replay = None
        self.released = True


_FLOATING_DTYPES: Dict[Any, bool] = {}


def _is_floating(val) -> bool:
    # dtype-keyed cache: issubdtype costs ~2us and runs per tensor per op
    # on the eager hot path
    dt = getattr(val, "dtype", None)
    if dt is None:
        dt = jnp.result_type(val)
    hit = _FLOATING_DTYPES.get(dt)
    if hit is None:
        hit = _FLOATING_DTYPES[dt] = bool(
            jnp.issubdtype(dt, jnp.floating)
            or jnp.issubdtype(dt, jnp.complexfloating))
    return hit


# Static-graph recorder hook (paddle_tpu.static): while a Program is being
# built, every dispatched op is also appended to its tape. The analog of
# OpDesc emission under program_guard (reference: fluid/framework.py
# append_op); replay happens in static.Executor as one jitted function.
_op_recorder = None

# Profiler hook (paddle_tpu.profiler): when active, called as
# hook(op_name, start_ns, end_ns) after each eager dispatch — the analog of
# the RecordEvent wrap around compute (reference: operator.cc:1264).
_op_profiler = None

# Grad-ready hook (distributed/overlap.py): when set, run_backward calls
# hook(tensor) the moment a LEAF tensor's gradient is final — every
# contribution deposited, no more edges pending — which is the reference
# Reducer's "variable ready" signal (imperative/reducer.cc MarkVarReady).
# The overlap layer uses it to launch a gradient bucket's collective while
# the rest of backward is still running.
_grad_ready_hook = None


def set_grad_ready_hook(hook):
    """Install the leaf-grad-ready callback; returns the previous one so
    callers can restore it (the overlap layer installs per backward)."""
    global _grad_ready_hook
    prev = _grad_ready_hook
    _grad_ready_hook = hook
    return prev


# Value materializer (distributed/sharding/stage3.py): under ZeRO-3 a
# parameter freed after use carries a FreedParamValue placeholder instead
# of a jax array. A dispatch that still reaches it (a tied weight read
# outside its owning layer's forward) must re-materialize the value —
# jax.jit rejects foreign objects, it does not consult __array__. When a
# materializer is installed, every dispatched input value passes through
# it; unset (the default), the hot path pays one module-global None check.
_value_materializer = None


def set_value_materializer(fn):
    """Install the freed-value materializer; returns the previous one."""
    global _value_materializer
    prev = _value_materializer
    _value_materializer = fn
    return prev

# Dispatch telemetry (observability.MetricsRegistry): pre-bound Counter
# objects so the hot path pays one attribute add per event, no registry
# lookup. trace-cache hit/miss tracks _OPCACHE (a miss = a fresh jax trace
# + jit compile — the number the EQuARX-style step-time audits need).
from ..observability.metrics import get_registry as _get_registry

_m_dispatch = _get_registry().counter(
    "eager_dispatch_total", help="eager ops dispatched through call_op",
).bind()
_m_cache_hit = _get_registry().counter(
    "trace_cache_hits_total", help="eager op-cache hits (no retrace)",
).bind()
_m_cache_miss = _get_registry().counter(
    "trace_cache_misses_total",
    help="eager op-cache misses (fresh trace+jit)").bind()
_m_uncacheable = _get_registry().counter(
    "trace_cache_uncacheable_total",
    help="dispatches with no cache key (dynamic closure/static args)",
).bind()


def set_op_recorder(recorder):
    global _op_recorder
    prev = _op_recorder
    _op_recorder = recorder
    return prev


def set_op_profiler(hook):
    global _op_profiler
    prev = _op_profiler
    _op_profiler = hook
    return prev


# ---------------------------------------------------------------------------
# eager op-cache (SURVEY §7 hard part 1: "aggressive eager compilation cache")
#
# Reference precedent: the eager final-state dygraph dispatches pre-registered
# kernels per op; here each call_op would otherwise re-TRACE fn via jax.vjp on
# every dispatch. The cache holds, per (fn code+closure, static args, input
# shapes/dtypes, diff positions), a jitted forward and a jitted backward
# (which recomputes the forward inside the vjp — rematerialized residuals
# trade a little FLOP for not keeping a Python pullback per call). Keys are
# only formed from whitelisted static closure/arg values, so fresh lambdas
# over arrays (uncacheable) transparently use the direct path.
# ---------------------------------------------------------------------------

_OPCACHE: Dict[Any, Any] = {}
_OPCACHE_CAP = 2048


def _static_ok(v) -> bool:
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return True
    if isinstance(v, (tuple, frozenset)):
        return all(_static_ok(x) for x in v)
    if isinstance(v, (np.dtype, type)):
        return True
    return False


def _op_cache_key(fn, args, tensor_pos, kwargs, vals, diff_j, op_name):
    code = getattr(fn, "__code__", None)
    ident = code if code is not None else fn
    cells = ()
    closure = getattr(fn, "__closure__", None)
    if closure:
        cells = tuple(c.cell_contents for c in closure)
        if not all(_static_ok(c) for c in cells):
            return None
    defaults = getattr(fn, "__defaults__", None)
    if defaults and not all(_static_ok(d) for d in defaults):
        return None
    static_args = tuple(a for i, a in enumerate(args) if i not in tensor_pos)
    if not all(_static_ok(a) for a in static_args):
        return None
    kw = tuple(sorted(kwargs.items()))
    if not all(_static_ok(v) for _, v in kw):
        return None
    # np.dtype is hashable; str(dtype) costs ~3us/tensor on the hot path
    sig = tuple((v.shape, v.dtype) for v in vals)
    return (ident, cells, defaults, static_args, kw, sig, tuple(diff_j),
            op_name)


class _OpCacheEntry:
    __slots__ = ("fwd", "bwd")


def _make_cache_entry(fn, args, tensor_pos, kwargs, diff_j):
    snapshot = [None if i in set(tensor_pos) else a for i, a in enumerate(args)]

    def assemble(vals):
        full = list(snapshot)
        for j, i in enumerate(tensor_pos):
            full[i] = vals[j]
        return full

    def fwd(vals):
        return fn(*assemble(vals), **kwargs)

    entry = _OpCacheEntry()
    entry.fwd = jax.jit(fwd)
    if diff_j:
        def bwd(vals, cots):
            def closure(*dvals):
                merged = list(vals)
                for j, dv in zip(diff_j, dvals):
                    merged[j] = dv
                return fn(*assemble(merged), **kwargs)

            _, vjp_fn = jax.vjp(closure, *[vals[j] for j in diff_j])
            return vjp_fn(cots)

        entry.bwd = jax.jit(bwd)
    else:
        entry.bwd = None
    return entry


def _opcache_get(key, fn, args, tensor_pos, kwargs, diff_j):
    entry = _OPCACHE.get(key)
    if entry is None:
        _m_cache_miss.value += 1
        if len(_OPCACHE) >= _OPCACHE_CAP:
            _OPCACHE.pop(next(iter(_OPCACHE)))
        entry = _OPCACHE[key] = _make_cache_entry(
            fn, args, tensor_pos, kwargs, tuple(diff_j))
    else:
        _m_cache_hit.value += 1
    return entry


def clear_op_cache():
    _OPCACHE.clear()


def call_op(fn: Callable, *args, op_name: str = "", **kwargs):
    """Dispatch a functional kernel with optional tape recording.

    ``fn`` is a pure function taking raw jax values in the positions where
    Tensors appear in ``args``. Returns Tensor (or tuple of Tensors).
    The analog of Tracer::TraceOp (imperative/tracer.cc:157).
    """
    from .tensor import Tensor

    _m_dispatch.value += 1
    tensor_pos = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    vals = [args[i]._value for i in tensor_pos]
    if _value_materializer is not None:
        # ZeRO-3 freed-parameter self-heal (stage3.py): swap any freed
        # placeholder for its re-gathered device value before dispatch
        vals = [_value_materializer(v) for v in vals]

    # AMP O1/O2 input casting (reference: imperative/amp_auto_cast.cc)
    from ..amp import amp_cast_inputs, amp_state

    if amp_state() is not None:
        vals = amp_cast_inputs(op_name, vals)

    diff_j = []
    if is_grad_enabled():
        for j, i in enumerate(tensor_pos):
            t = args[i]
            if not t.stop_gradient and _is_floating(t._value):
                diff_j.append(j)

    def assemble(merged_vals):
        full = list(args)
        for j, i in enumerate(tensor_pos):
            full[i] = merged_vals[j]
        return full

    # compiled-cache lookup (None key → direct path)
    ckey = None
    if _op_recorder is None:  # static capture needs the raw fn, not a jit
        ckey = _op_cache_key(fn, args, tensor_pos, kwargs, vals, diff_j,
                             op_name)
        if ckey is None:
            _m_uncacheable.value += 1

    if not diff_j:
        if _op_profiler is not None:
            from ..profiler import now_ns   # the hook's owner: imported

            t0 = now_ns()
            if ckey is not None:
                entry = _opcache_get(ckey, fn, args, tensor_pos, kwargs, diff_j)
                out = entry.fwd(tuple(vals))
            else:
                out = fn(*assemble(vals), **kwargs)
            _op_profiler(op_name or getattr(fn, "__name__", "op"), t0,
                         now_ns())
        elif ckey is not None:
            entry = _opcache_get(ckey, fn, args, tensor_pos, kwargs, diff_j)
            out = entry.fwd(tuple(vals))
        else:
            out = fn(*assemble(vals), **kwargs)
        res = _wrap_outputs(out, node=None, op_name=op_name)
        if _op_recorder is not None:
            _op_recorder(fn, args, kwargs, res, op_name)
        return res

    def closure(*dvals):
        merged = list(vals)
        for j, dv in zip(diff_j, dvals):
            merged[j] = dv
        return fn(*assemble(merged), **kwargs)

    primals = tuple(vals[j] for j in diff_j)

    def _dispatch():
        if ckey is None:
            return jax.vjp(closure, *primals)
        entry = _opcache_get(ckey, fn, args, tensor_pos, kwargs, diff_j)
        outs = entry.fwd(tuple(vals))
        vals_t = tuple(vals)

        def cached_vjp(cot):
            leaves = jax.tree_util.tree_leaves(cot)
            if any(getattr(c, "dtype", None) == jax.dtypes.float0
                   for c in leaves):
                # integer-output cotangents (float0) don't pass through jit;
                # retrace this rare case directly
                _, vjp_fn = jax.vjp(closure, *primals)
                return vjp_fn(cot)
            return entry.bwd(vals_t, cot)

        return outs, cached_vjp

    if _op_profiler is not None:
        from ..profiler import now_ns

        t0 = now_ns()
        outs, vjp_fn = _dispatch()
        _op_profiler(op_name or getattr(fn, "__name__", "op"), t0,
                     now_ns())
    else:
        outs, vjp_fn = _dispatch()

    multi = isinstance(outs, (tuple, list))
    out_list = list(outs) if multi else [outs]
    out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_list]
    node = GradNode(
        vjp_fn,
        [
            (args[tensor_pos[j]], args[tensor_pos[j]]._grad_node,
             args[tensor_pos[j]]._out_index)
            for j in diff_j
        ],
        out_avals,
        multi,
        name=op_name or getattr(fn, "__name__", "op"),
        # snapshot: static (non-tensor) args + forward-time tensor VALUES
        # (post-AMP-cast, matching vjp_fn's residuals); no Tensor refs so
        # stop-grad/int inputs are not pinned beyond their values
        replay=(fn,
                tuple(None if i in set(tensor_pos) else a
                      for i, a in enumerate(args)),
                kwargs, tuple(tensor_pos), tuple(diff_j), tuple(vals)),
    )
    res = _wrap_outputs(outs, node=node, op_name=op_name)
    if _op_recorder is not None:
        _op_recorder(fn, args, kwargs, res, op_name)
    return res


def _debug_check_outputs(out, op_name):
    """FLAGS_check_nan_inf / FLAGS_benchmark per-op modes (reference:
    operator.cc:1300 benchmark sync + :1311 CheckOpHasNanOrInf). Only
    consulted when a flag is on; eager values only (tracers are covered by
    jax_debug_nans via set_flags)."""
    from .flags import _FLAGS

    vals = out if isinstance(out, (tuple, list)) else (out,)
    if _FLAGS.get("FLAGS_benchmark"):
        jax.block_until_ready([v for v in vals if hasattr(v, "dtype")])
    if _FLAGS.get("FLAGS_check_nan_inf"):
        for v in vals:
            if (hasattr(v, "dtype") and not isinstance(v, jax.core.Tracer)
                    and jnp.issubdtype(v.dtype, jnp.floating)):
                if bool(jnp.any(~jnp.isfinite(v))):
                    raise FloatingPointError(
                        f"operator {op_name!r} produced nan/inf "
                        "(FLAGS_check_nan_inf)")


def _wrap_outputs(out, node, op_name=""):
    from .flags import _FLAGS
    from .tensor import Tensor

    if _FLAGS.get("FLAGS_check_nan_inf") or _FLAGS.get("FLAGS_benchmark"):
        _debug_check_outputs(out, op_name)
    if isinstance(out, (tuple, list)):
        res = []
        for i, o in enumerate(out):
            t = Tensor(o, _internal=True)
            if node is not None and _is_floating(o):
                t.stop_gradient = False
                t._grad_node = node
                t._out_index = i
            res.append(t)
        return tuple(res)
    t = Tensor(out, _internal=True)
    if node is not None and _is_floating(out):
        t.stop_gradient = False
        t._grad_node = node
        t._out_index = 0
    return t


def run_backward(
    tensors: Sequence,
    grad_tensors: Optional[Sequence] = None,
    retain_graph: bool = False,
    collect: Optional[List] = None,
    accumulate: bool = True,
):
    """Reverse-topological gradient propagation (BasicEngine::Execute analog).

    If ``collect`` is given (a list of Tensors), returns their gradients in
    order (paddle.grad semantics) instead of/in addition to accumulating into
    ``.grad`` when ``accumulate``.
    """
    from .tensor import Tensor

    collect_map: Dict[int, Any] = {}
    collect_ids = {id(t) for t in collect} if collect else set()

    # grad-ready notification (distributed/overlap.py): when a hook is
    # installed and grads actually accumulate, count how many deposit edges
    # each leaf will receive; the hook fires on the deposit that brings a
    # leaf's pending count to zero — its .grad is final from then on
    ready_hook = _grad_ready_hook if accumulate else None
    pending_leaf: Optional[Dict[int, int]] = {} if ready_hook else None

    def deposit(t, g):
        _deposit(t, g, collect_ids, collect_map, accumulate)
        if pending_leaf is None:
            return
        n_left = pending_leaf.get(id(t), 1) - 1
        pending_leaf[id(t)] = n_left
        if n_left <= 0 and not t.stop_gradient and t.grad is not None:
            try:
                ready_hook(t)
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "grad-ready hook failed; backward continues")

    # --- seed ---
    roots: List[GradNode] = []
    direct: List = []   # node-less seeds, deposited after counts are known
    for k, t in enumerate(tensors):
        g = None if grad_tensors is None else grad_tensors[k]
        if g is None:
            if t._value.size != 1:
                raise RuntimeError(
                    "backward() on a non-scalar Tensor requires grad_tensors"
                )
            g = jnp.ones_like(t._value)
        elif isinstance(g, Tensor):
            g = g._value
        node = t._grad_node
        if node is None:
            direct.append((t, g))
        else:
            if node.released:
                raise RuntimeError(
                    "Trying to backward through the graph a second time "
                    "(set retain_graph=True if you need to)"
                )
            node.seed(t._out_index, g)
            roots.append(node)

    # --- build reachable graph & consumer counts ---
    indeg: Dict[int, int] = {}
    nodes: Dict[int, GradNode] = {}
    # dedupe: two outputs of one multi-output op seed the SAME node; pushing
    # it twice would double-count its producers' indegree and starve them
    stack = list({id(n): n for n in roots}.values())
    for n in stack:
        nodes.setdefault(id(n), n)
        indeg.setdefault(id(n), 0)
    while stack:
        n = stack.pop()
        for t, p, _oi in n.inputs:
            if p is None or p is n:
                continue
            indeg[id(p)] = indeg.get(id(p), 0) + 1
            if id(p) not in nodes:
                nodes[id(p)] = p
                stack.append(p)

    if pending_leaf is not None:
        # expected deposit edges per leaf: one per node-less seed plus one
        # per reachable node input that deposits directly (p None / self)
        for t, _g in direct:
            pending_leaf[id(t)] = pending_leaf.get(id(t), 0) + 1
        for n in nodes.values():
            for t, p, _oi in n.inputs:
                if p is None or p is n:
                    pending_leaf[id(t)] = pending_leaf.get(id(t), 0) + 1
    for t, g in direct:
        deposit(t, g)

    # --- Kahn walk ---
    ready = [n for n in nodes.values() if indeg.get(id(n), 0) == 0]
    processed = set()
    while ready:
        n = ready.pop()
        if id(n) in processed:
            continue
        processed.add(id(n))
        cots = []
        for i, av in enumerate(n.out_avals):
            c = n.pending.get(i)
            if c is not None and hasattr(c, "dtype") and c.dtype != av.dtype and jnp.issubdtype(
                av.dtype, jnp.floating
            ):
                # AMP: consumer may have upcast the value; pullback wants the
                # producer's dtype
                c = c.astype(av.dtype)
            if c is None:
                if jnp.issubdtype(av.dtype, jnp.floating) or jnp.issubdtype(
                    av.dtype, jnp.complexfloating
                ):
                    c = jnp.zeros(av.shape, av.dtype)
                else:
                    # non-differentiable output (e.g. argmax indices): jax
                    # pullbacks expect a float0 cotangent for integer primals
                    c = np.zeros(av.shape, jax.dtypes.float0)
            cots.append(c)
        n.pending = {}  # reset so a retained graph starts clean next backward
        cot = tuple(cots) if n.multi_output else cots[0]
        grads_in = n.vjp_fn(cot)
        for (t, p, oi), g in zip(n.inputs, grads_in):
            for hook in t._hooks:
                out = hook(Tensor(g, _internal=True))
                if out is not None:
                    g = out._value if isinstance(out, Tensor) else out
            if p is None or p is n:
                deposit(t, g)
            else:
                p.seed(oi, g)
                indeg[id(p)] -= 1
                if indeg[id(p)] == 0:
                    ready.append(p)
        if not retain_graph:
            n.release()

    if collect:
        out = []
        for t in collect:
            g = collect_map.get(id(t))
            out.append(Tensor(g, _internal=True) if g is not None else None)
        return out
    return None


def _replay_node_grads(n, cot_tensors):
    """Differentiable pullback for create_graph: re-derive the node's vjp
    THROUGH call_op, so the produced grads are tape-recorded Tensors whose
    graph reaches both the op's inputs and the incoming cotangents
    (reference: double-grad ops emitted by grad_op_desc_maker).

    Inputs are reconstructed from the FORWARD-TIME value snapshot with the
    record-time tape linkage (GradNode docstring invariant: later in-place
    rebinds of the same Tensor must not change this op's gradients)."""
    from .tensor import Tensor

    fn, static_args, kwargs, tensor_pos, diff_j, snap_vals = n.replay
    float_out = [i for i, av in enumerate(n.out_avals)
                 if jnp.issubdtype(av.dtype, jnp.floating)
                 or jnp.issubdtype(av.dtype, jnp.complexfloating)]
    avals = list(n.out_avals)
    multi = n.multi_output

    def grad_fn(*vals):
        n_in = len(tensor_pos)
        in_vals = list(vals[:n_in])
        cot_vals = list(vals[n_in:])

        def closure(*dvals):
            merged = list(in_vals)
            for j, dv in zip(diff_j, dvals):
                merged[j] = dv
            full = list(static_args)
            for j, i in enumerate(tensor_pos):
                full[i] = merged[j]
            return fn(*full, **kwargs)

        primals = tuple(in_vals[j] for j in diff_j)
        _, vjp = jax.vjp(closure, *primals)
        full_cots = []
        it = iter(cot_vals)
        for i, av in enumerate(avals):
            if i in float_out:
                full_cots.append(next(it))
            else:
                full_cots.append(np.zeros(av.shape, jax.dtypes.float0))
        cot = tuple(full_cots) if multi else full_cots[0]
        out = vjp(cot)
        return tuple(out) if len(out) > 1 else out[0]

    # snapshot tensors: values from forward time; diff positions carry the
    # record-time producer linkage from node.inputs
    linkage = {j: trip for j, trip in zip(diff_j, n.inputs)}
    arg_tensors = []
    snap_to_orig = {}
    for j, v in enumerate(snap_vals):
        t = Tensor(v, _internal=True)
        if j in linkage:
            orig, prod, oi = linkage[j]
            t.stop_gradient = False
            t._grad_node = prod
            t._out_index = oi
            snap_to_orig[id(t)] = orig
        arg_tensors.append(t)
    res = call_op(grad_fn, *arg_tensors, *cot_tensors, op_name=f"grad_{n.name}")
    outs = list(res) if isinstance(res, tuple) else [res]
    # retarget the recorded grad-op's input entries from the snapshot
    # wrappers to the ORIGINAL tensors (deposit/collect match by identity)
    gnode = next((o._grad_node for o in outs
                  if getattr(o, "_grad_node", None) is not None), None)
    if gnode is not None and gnode.inputs:
        gnode.inputs = [
            (snap_to_orig.get(id(t), t), p, oi) for (t, p, oi) in gnode.inputs
        ]
    return outs


def _run_backward_create_graph(tensors, grad_tensors, collect):
    """Tensor-mode Kahn walk: cotangents are live Tensors and every node
    pullback is itself recorded on the tape (double grad)."""
    from .tensor import Tensor

    collect_map: Dict[int, Any] = {}
    collect_ids = {id(t) for t in collect} if collect else set()

    def as_tensor(g):
        return g if isinstance(g, Tensor) else Tensor(jnp.asarray(g),
                                                      _internal=True)

    roots: List[GradNode] = []
    pending: Dict[int, Dict[int, Any]] = {}

    def seed_t(node, idx, g):
        slot = pending.setdefault(id(node), {})
        cur = slot.get(idx)
        slot[idx] = g if cur is None else cur + g

    def deposit_t(t, g):
        if id(t) in collect_ids:
            cur = collect_map.get(id(t))
            collect_map[id(t)] = g if cur is None else cur + g

    for k, t in enumerate(tensors):
        g = None if grad_tensors is None else grad_tensors[k]
        if g is None:
            if t._value.size != 1:
                raise RuntimeError(
                    "backward() on a non-scalar Tensor requires grad_tensors")
            g = Tensor(jnp.ones_like(t._value), _internal=True)
        else:
            g = as_tensor(g)
        node = t._grad_node
        if node is None:
            deposit_t(t, g)
        else:
            if node.released:
                raise RuntimeError(
                    "Trying to backward through the graph a second time "
                    "(set retain_graph=True if you need to)")
            seed_t(node, t._out_index, g)
            roots.append(node)

    indeg: Dict[int, int] = {}
    nodes: Dict[int, GradNode] = {}
    # dedupe: two outputs of one multi-output op seed the SAME node; pushing
    # it twice would double-count its producers' indegree and starve them
    stack = list({id(n): n for n in roots}.values())
    for n in stack:
        nodes.setdefault(id(n), n)
        indeg.setdefault(id(n), 0)
    while stack:
        n = stack.pop()
        for t, p, _oi in n.inputs:
            if p is None or p is n:
                continue
            indeg[id(p)] = indeg.get(id(p), 0) + 1
            if id(p) not in nodes:
                nodes[id(p)] = p
                stack.append(p)

    ready = [n for n in nodes.values() if indeg.get(id(n), 0) == 0]
    processed = set()
    while ready:
        n = ready.pop()
        if id(n) in processed:
            continue
        processed.add(id(n))
        if n.replay is None:
            raise NotImplementedError(
                f"create_graph=True cannot differentiate through op "
                f"{n.name!r} (no differentiable replay); ops dispatched "
                "outside call_op do not support double grad")
        slot = pending.get(id(n), {})
        cot_tensors = []
        for i, av in enumerate(n.out_avals):
            if not (jnp.issubdtype(av.dtype, jnp.floating)
                    or jnp.issubdtype(av.dtype, jnp.complexfloating)):
                continue
            c = slot.get(i)
            if c is None:
                c = Tensor(jnp.zeros(av.shape, av.dtype), _internal=True)
            elif c._value.dtype != av.dtype:
                # cast THROUGH the tape: a detached rebuild would zero
                # higher-order derivatives across mixed-dtype edges
                c = call_op(lambda v: v.astype(av.dtype), c,
                            op_name="grad_cast")
            cot_tensors.append(c)
        pending.pop(id(n), None)
        grads_in = _replay_node_grads(n, cot_tensors)
        for (t, p, oi), g in zip(n.inputs, grads_in):
            for hook in t._hooks:
                out = hook(g)
                if out is not None:
                    g = out if isinstance(out, Tensor) else as_tensor(out)
            if p is None or p is n:
                deposit_t(t, g)
            else:
                seed_t(p, oi, g)
                indeg[id(p)] -= 1
                if indeg[id(p)] == 0:
                    ready.append(p)
        # create_graph implies the graph survives for the next-order pass

    if collect:
        return [collect_map.get(id(t)) for t in collect]
    return None


def _deposit(t, g, collect_ids, collect_map, accumulate):
    from .tensor import Tensor

    if id(t) in collect_ids:
        cur = collect_map.get(id(t))
        collect_map[id(t)] = g if cur is None else cur + g
    if accumulate and not t.stop_gradient:
        if t.grad is None:
            t.grad = Tensor(g, _internal=True)
        else:
            t.grad._value = t.grad._value + g


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph=None,
    create_graph=False,
    only_inputs=True,
    allow_unused=False,
):
    """paddle.grad (reference: imperative/partial_grad_engine.cc).

    create_graph=True returns gradients that are themselves on the tape
    (each pullback replayed differentiably through call_op), so a second
    grad()/backward() computes true higher-order derivatives — the
    reference's double-grad op path (grad_op_desc_maker)."""
    from .tensor import Tensor

    outputs = [outputs] if isinstance(outputs, Tensor) else list(outputs)
    inputs = [inputs] if isinstance(inputs, Tensor) else list(inputs)
    if grad_outputs is not None and isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]
    if create_graph:
        res = _run_backward_create_graph(outputs, grad_outputs, inputs)
        if not allow_unused:
            for t, g in zip(inputs, res):
                if g is None:
                    raise RuntimeError(
                        "one of the inputs received no gradient "
                        "(allow_unused=False)")
        return res
    res = run_backward(
        outputs,
        grad_outputs,
        retain_graph=bool(retain_graph),
        collect=inputs,
        accumulate=False,
    )
    if not allow_unused:
        for t, g in zip(inputs, res):
            if g is None:
                raise RuntimeError(
                    "One of the differentiated Tensors appears to not have "
                    "been used in the graph (set allow_unused=True to allow)"
                )
    return res
