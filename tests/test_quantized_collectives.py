"""In-trace quantized collectives + blockwise wire codecs (ISSUE 8, EQuARX).

Covers the tentpole contract: the blockwise int8/fp8 codecs are pure-jnp
transforms shared bit-for-bit by the eager and compiled paths (jit vs eager
encode/decode parity), `sync_async` honors the configured codec inside a
shard_map trace with the error-feedback residual threaded as carried state,
`jit.TrainStep(grad_comm=...)` runs the quantize -> psum-of-int ->
dequantize sequence inside the compiled train step (fp32 wire bit-identical
to the implicit-psum path; quantized wire convergence-parity on gpt-test),
the traced wire-bytes counters show the >=2x reduction vs bf16, the EQuARX
§RS quantized reduce_scatter decomposition, and the strategy/cost-model
wiring.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as optim
import paddle_tpu.distributed.collective as coll
import paddle_tpu.distributed.mesh as mesh_mod
from paddle_tpu.distributed import fleet, grad_comm
from paddle_tpu.distributed.overlap import OverlappedGradCommunicator
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
rng = np.random.RandomState(0)

BLOCK = grad_comm.BLOCK_CODECS


@pytest.fixture(autouse=True)
def reset_mesh(fresh_mesh):
    yield  # fresh_mesh (conftest) owns save/clear/restore


def _two_rank_sum_doubles(calls=None):
    """Two identical emulated ranks: every SUM doubles (int payload AND the
    fp32 abs-max scale vector — both ride sum-typed exchanges), MAX/AVG are
    identity."""
    def fake(t, op=None, group=None, **kw):
        if calls is not None:
            calls.append((str(t._value.dtype), op, tuple(t._value.shape)))
        if op == coll.ReduceOp.SUM:
            t._value = t._value * 2
        return t
    return fake


def _mlp(seed=7):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))


X = rng.standard_normal((16, 8)).astype(np.float32)
Y = rng.standard_normal((16, 1)).astype(np.float32)


# ------------------------------------------------------------ codec layer
@pytest.mark.parametrize("codec", BLOCK)
def test_blockwise_roundtrip_and_residual_exactness(codec):
    bs = 256
    x = jnp.asarray(rng.standard_normal(5000).astype(np.float32) * 3.0)
    scales = grad_comm.block_scales(grad_comm.block_absmax(x, bs), codec)
    q = grad_comm.block_encode(x, scales, bs, codec)
    deq = grad_comm.block_decode(q, scales, world=1, dtype=np.float32,
                                 numel=5000)
    if codec == "int8_block":
        # per-BLOCK half-step bound — the whole point of blockwise scales:
        # a quiet block's error is bounded by ITS scale, not the bucket's
        per_elem_bound = np.repeat(np.asarray(scales) * 0.5001, bs)[:5000]
        assert np.all(np.abs(np.asarray(deq - x)) <= per_elem_bound)
    else:
        # e4m3: 3 mantissa bits -> ~6.25% relative error, plus the
        # subnormal floor of the blockwise scale
        err = np.abs(np.asarray(deq - x))
        bound = np.abs(np.asarray(x)) * 0.0723 + np.repeat(
            np.asarray(scales), bs)[:5000]
        assert np.all(err <= bound)
    # the error-feedback residual is exactly what the wire dropped
    res = grad_comm.block_residual(x, q, scales, 5000)
    np.testing.assert_allclose(np.asarray(deq + res), np.asarray(x),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("codec", BLOCK)
def test_codec_eager_vs_jit_wire_parity(codec):
    """The shared-verbatim contract at world=1: the WIRE payload (the bits
    a collective would actually move) is identical whether the codec runs
    eagerly or inside a compiled program; the decoded update agrees to the
    last place XLA's fusion is allowed to touch (one multiply rounding),
    and decode+residual reproduce the input exactly on both paths."""
    bs = 128
    x = jnp.asarray(rng.standard_normal(1000).astype(np.float32))

    def pipeline(v):
        scales = grad_comm.block_scales(grad_comm.block_absmax(v, bs),
                                        codec)
        q = grad_comm.block_encode(v, scales, bs, codec)
        deq = grad_comm.block_decode(q, scales, 1, jnp.float32, 1000)
        return q, scales, deq, grad_comm.block_residual(v, q, scales, 1000)

    eq, es, edeq, eres = pipeline(x)
    jq, js, jdeq, jres = jax.jit(pipeline)(x)
    # wire bits: the quantized payload exactly; the fp32 scale vector to
    # the one multiply rounding XLA's fusion may move
    assert np.array_equal(np.asarray(eq), np.asarray(jq))
    np.testing.assert_allclose(np.asarray(es), np.asarray(js),
                               rtol=2e-7, atol=0)
    # decode: identical payload x identical scales — ulp-level agreement
    np.testing.assert_allclose(np.asarray(edeq), np.asarray(jdeq),
                               rtol=0, atol=1e-6)
    # the lossless invariant holds bit-for-bit on each path separately
    np.testing.assert_allclose(np.asarray(edeq + eres), np.asarray(x),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jdeq + jres), np.asarray(x),
                               rtol=0, atol=1e-6)


def test_blockwise_eager_sync_stats_and_wire(monkeypatch):
    calls = []
    monkeypatch.setattr(coll, "all_reduce", _two_rank_sum_doubles(calls))
    params = []
    for i, shp in enumerate([(64, 64), (64,)]):
        p = Tensor(np.zeros(shp, np.float32))
        p.stop_gradient = False
        p.name = f"p{i}"
        p.grad = Tensor(rng.standard_normal(shp).astype(np.float32))
        params.append(p)
    comm = grad_comm.GradCommunicator(
        grad_comm.GradCommConfig("int8_block", block_size=256))
    comm.sync(params, world=2)
    numel = 64 * 64 + 64
    nb = -(-numel // 256)
    # one per-block scale-vector SUM + one int payload SUM per bucket
    assert [c[1] for c in calls] == [coll.ReduceOp.SUM, coll.ReduceOp.SUM]
    assert calls[0][0] == "float32" and calls[0][2] == (nb,)
    assert calls[1][0] == "int32"
    assert comm.stats["collectives"] == 2
    assert comm.stats["comm_bytes"] == numel * 1 + 4 * nb
    assert comm.stats["path"] == "eager"
    assert 0 in comm._residuals     # error feedback recorded


@pytest.mark.parametrize("codec", BLOCK)
def test_blockwise_error_feedback_convergence(codec, monkeypatch):
    """PR-1 acceptance style: an MLP trained with the blockwise quantized
    sync + error feedback lands within the int8 tolerance of the
    un-quantized run."""
    x = rng.standard_normal((32, 8)).astype(np.float32)
    w_true = rng.standard_normal((8, 1)).astype(np.float32)
    y = np.tanh(x @ w_true).astype(np.float32)

    def train(c, steps=60):
        paddle.seed(11)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
        opt = optim.SGD(learning_rate=0.3, parameters=net.parameters())
        comm = (None if c is None else grad_comm.GradCommunicator(
            grad_comm.GradCommConfig(c, block_size=64)))
        losses = []
        for _ in range(steps):
            loss = F.mse_loss(net(paddle.to_tensor(x)), paddle.to_tensor(y))
            loss.backward()
            if comm is not None:
                comm.sync([p for p in net.parameters()
                           if not p.stop_gradient], world=2)
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses

    monkeypatch.setattr(coll, "all_reduce", _two_rank_sum_doubles())
    exact = train(None)
    quant = train(codec)
    assert exact[-1] < exact[0] * 0.1, "reference run failed to converge"
    assert quant[-1] < quant[0] * 0.1, f"{codec}+EF run failed to converge"
    assert abs(quant[-1] - exact[-1]) <= max(0.05 * exact[-1], 0.005), \
        (codec, quant[-1], exact[-1])


def test_config_block_size_validation_and_state_guard():
    with pytest.raises(ValueError):
        grad_comm.GradCommConfig("int8_block", block_size=0)
    with pytest.raises(ValueError):
        grad_comm.GradCommConfig("int8_block", block_size="big")
    c1 = grad_comm.GradCommunicator(
        grad_comm.GradCommConfig("int8_block", block_size=1024))
    state = c1.state_dict()
    assert state["block_size"] == 1024
    c2 = grad_comm.GradCommunicator(
        grad_comm.GradCommConfig("int8_block", block_size=512))
    with pytest.raises(ValueError, match="block_size mismatch"):
        c2.load_state_dict(state)


# ----------------------------------------------------- in-trace sync_async
def test_sync_async_in_trace_honors_blockwise_codec():
    """Inside a shard_map trace the blockwise codec actually runs: the
    decoded values equal the hand-applied pure-codec pipeline over the
    REAL 2-device psum, the futures carry the residuals (carried state),
    and stats report the actual (quantized) wire with path=traced."""
    from jax.sharding import PartitionSpec as P

    m = mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    shapes = [(3, 5), (7,), (2, 2, 4)]
    gs = [rng.standard_normal((2,) + s).astype(np.float32) for s in shapes]
    bs = 8
    cfg = grad_comm.GradCommConfig("int8_block", block_size=bs)
    comm = OverlappedGradCommunicator(cfg)

    def make_params(vals):
        params = []
        for v in vals:
            p = Tensor(jnp.zeros(v.shape), _internal=True)
            p.stop_gradient = False
            p.grad = Tensor(v, _internal=True)
            params.append(p)
        return params

    def body(*rank_grads):
        vals = [g.reshape(s) for g, s in zip(rank_grads, shapes)]
        params = make_params(vals)
        buckets = comm.buckets_for(params)
        res = {b.index: jnp.zeros((b.size,), jnp.float32) for b in buckets}
        futs = comm.sync_async(params, world=2, residuals=res)
        # reference: the same pure codec functions over an explicit psum
        refs = []
        for b in buckets:
            flat = jnp.concatenate([vals[pi].reshape(-1)
                                    for pi in b.param_indices]) \
                if len(b.param_indices) > 1 \
                else vals[b.param_indices[0]].reshape(-1)
            am = jax.lax.psum(grad_comm.block_absmax(flat, bs), "data")
            sc = grad_comm.block_scales(am, "int8_block")
            q = grad_comm.block_encode(flat, sc, bs, "int8_block")
            qs = jax.lax.psum(q, "data")
            refs.append(grad_comm.block_decode(qs, sc, 2, jnp.float32,
                                               b.size))
        return (tuple(f.wait() for f in futs) + tuple(refs)
                + tuple(f.residual for f in futs))

    outs = mesh_mod.compat_shard_map(
        body, m, P("data"), P())(*gs)
    n = len(comm._buckets)
    got, ref, res_out = outs[:n], outs[n:2 * n], outs[2 * n:]
    for g, r in zip(got, ref):
        assert np.array_equal(np.asarray(g), np.asarray(r))
    for r in res_out:
        assert np.all(np.isfinite(np.asarray(r)))
    assert comm.stats["path"] == "traced"
    total = sum(b.size for b in comm._buckets)
    scale_b = sum(grad_comm.scale_bytes(b.size, bs) for b in comm._buckets)
    assert comm.stats["comm_bytes"] == total * 1 + scale_b
    # no tracer ever landed in the host-side residual store
    assert comm._residuals == {}


def test_traced_sync_with_error_feedback_refuses_host_residuals():
    """sync() inside a trace with an EF codec must fail loudly instead of
    leaking a tracer into self._residuals (the carried-state contract)."""
    from jax.sharding import PartitionSpec as P

    m = mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    g = rng.standard_normal((2, 64)).astype(np.float32)
    comm = grad_comm.GradCommunicator(
        grad_comm.GradCommConfig("int8_block"))

    def body(v):
        p = Tensor(jnp.zeros((64,)), _internal=True)
        p.stop_gradient = False
        p.grad = Tensor(v.reshape(64), _internal=True)
        comm.sync([p], world=2)
        return p.grad._value

    with pytest.raises(RuntimeError, match="carried state"):
        mesh_mod.compat_shard_map(body, m, P("data"), P())(g)


def test_fused_step_commits_future_residuals(monkeypatch):
    """FusedFlatUpdater consumes sync_async futures without unflattening —
    and commits their error-feedback residuals back to the communicator so
    the skip-the-scatter path keeps cross-step feedback."""
    from paddle_tpu.optimizer.fused import FusedFlatUpdater

    monkeypatch.setattr(coll, "all_reduce", _two_rank_sum_doubles())
    net = _mlp()
    opt = optim.Adam(learning_rate=0.05, parameters=net.parameters())
    params = [p for p in net.parameters() if not p.stop_gradient]
    comm = OverlappedGradCommunicator(
        grad_comm.GradCommConfig("int8_block", comm_buffer_size=0.0002,
                                 last_comm_buffer_size=0.0001))
    fused = FusedFlatUpdater(opt, params, communicator=comm)
    F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y)).backward()
    buckets = comm.buckets_for(params)
    # explicit residuals => sync_async does NOT store them host-side...
    res = {b.index: jnp.zeros((b.size,), jnp.float32) for b in buckets}
    futs = comm.sync_async(params, world=2, residuals=res)
    assert comm._residuals == {}
    fused.step(futures=futs)          # ...the fused consumer commits them
    assert sorted(comm._residuals) == sorted(b.index for b in buckets)
    for f in futs:
        assert np.array_equal(np.asarray(comm._residuals[f.bucket.index]),
                              np.asarray(f.residual))


# -------------------------------------------- EQuARX §RS (ZeRO-2 traced)
def test_traced_reduce_scatter_quantized():
    """Both halves of the ring decomposition ship the 1-byte wire: the
    reduce_scatter half under shared blockwise scales, the all_gather half
    requantized per rank — and the reassembled average stays within the
    two quantization steps of the true mean."""
    from jax.sharding import PartitionSpec as P

    m = mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    n = 3000
    g = rng.standard_normal((2, n)).astype(np.float32)
    cfg = grad_comm.GradCommConfig("int8_block", block_size=256)

    def body(x):
        full, shard, res, wire, ncoll = \
            grad_comm.traced_reduce_scatter_quantized(
                x.reshape(n), "data", 2, cfg)
        return full, shard, res

    full, shard, res = mesh_mod.compat_shard_map(
        body, m, P("data"), (P(), P("data"), P()))(g)
    ref = g.mean(axis=0)
    step = 2.0 * np.abs(g).max() * 2 / 127   # two (summed-absmax) steps
    assert np.abs(np.asarray(full) - ref).max() <= step
    assert np.asarray(res).shape == (n,)
    # reduce_bucket routes the traced ZeRO-2 form through the §RS path
    comm = grad_comm.GradCommunicator(cfg)

    def body2(x):
        b = grad_comm.GradBucket(0, np.dtype(np.float32))
        b.add(0, (n,))
        reduced, nr, wire, ncoll = comm.reduce_bucket(
            b, x.reshape(n), 2, use_reduce_scatter=True,
            residual=jnp.zeros((n,), jnp.float32))
        return reduced, nr

    reduced, nr = mesh_mod.compat_shard_map(
        body2, m, P("data"), P())(g)
    assert np.abs(np.asarray(reduced) - ref).max() <= step
    assert np.asarray(nr).shape == (n,)


# ------------------------------------------------- TrainStep in-trace comm
def _train_mlp_step(codec, steps=4, mesh_devices=2):
    if mesh_devices:
        mesh_mod.set_mesh(mesh_mod.build_mesh(
            {"data": mesh_devices}, devices=jax.devices()[:mesh_devices]))
    else:
        mesh_mod._current[0] = None
    paddle.seed(7)
    net = _mlp()
    opt = optim.AdamW(learning_rate=1e-2, parameters=net.parameters())
    gc = None if codec is None else grad_comm.GradCommConfig(
        codec, comm_buffer_size=0.0002, last_comm_buffer_size=0.0001,
        block_size=64)
    step = TrainStep(net, F.mse_loss, opt, grad_comm=gc)
    losses = [float(step(inputs=(paddle.to_tensor(X),),
                         labels=(paddle.to_tensor(Y),)))
              for _ in range(steps)]
    return losses, step


def test_trainstep_gc_fp32_bit_identical_to_implicit_psum():
    """The explicit-SPMD wire path with an fp32 codec must reproduce the
    implicit-psum pjit step EXACTLY — same math, different spelling."""
    l_plain, _ = _train_mlp_step(None)
    l_fp32, step = _train_mlp_step("fp32")
    assert l_plain == l_fp32
    assert step.comm_stats["path"] == "traced"
    assert step.comm_stats["n_buckets"] >= 3


@pytest.mark.parametrize("codec", BLOCK)
def test_trainstep_gc_quantized_convergence(codec):
    """Quantized wire inside the compiled step: loss curve tracks the fp32
    one within the PR-1 int8 tolerance, residuals persist across calls."""
    l_fp32, _ = _train_mlp_step("fp32", steps=6)
    l_q, step = _train_mlp_step(codec, steps=6)
    assert l_q[-1] < l_q[0], "quantized compiled run failed to improve"
    assert abs(l_q[-1] - l_fp32[-1]) <= max(0.05 * l_fp32[-1], 0.01), \
        (codec, l_q[-1], l_fp32[-1])
    assert step._gc_comm._residuals, "no carried residuals after steps"
    # inert without a >1-replica mesh: bit-identical to the plain step
    l_off, step_off = _train_mlp_step(codec, mesh_devices=0)
    l_plain_off, _ = _train_mlp_step(None, mesh_devices=0)
    assert l_off == l_plain_off
    assert step_off.comm_stats is None


def test_trainstep_gc_wire_counters_on_gpt_test():
    """The acceptance counter: inside a jitted train step on gpt-test the
    int8_block wire bytes are ~4x under fp32 and ~2x under bf16, recorded
    per executed step in grad_comm_bytes_total{codec=,path=traced}."""
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )
    from paddle_tpu.observability import get_registry

    mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 256, (4, 16)).astype(np.int64)
    labels = rs.randint(0, 256, (4, 16)).astype(np.int64)
    reg = get_registry()
    fam = reg.counter("grad_comm_bytes_total", labels=("codec", "path"))

    def run(codec, steps=2):
        paddle.seed(1234)
        m = GPTForCausalLM(gpt_presets("gpt-test"), seed=7)
        crit = GPTPretrainingCriterion()
        o = optim.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = TrainStep(m, lambda lg, lb: crit(lg, lb), o,
                         grad_comm=grad_comm.GradCommConfig(codec))
        c0 = fam.labels(codec=codec, path="traced").value
        losses = [float(step(inputs=(paddle.to_tensor(ids, dtype="int64"),),
                             labels=(paddle.to_tensor(labels,
                                                      dtype="int64"),)))
                  for _ in range(steps)]
        return losses, step, \
            fam.labels(codec=codec, path="traced").value - c0

    l32, s32, bytes_fp32 = run("fp32")
    lb, sb, bytes_bf16 = run("bf16")
    lq, sq, bytes_blk = run("int8_block")
    # counters tick per EXECUTED step with the actual traced wire bytes
    assert bytes_fp32 == 2 * s32.comm_stats["comm_bytes"]
    assert bytes_blk == 2 * sq.comm_stats["comm_bytes"]
    # int8_block: 4x under fp32; vs bf16 the payload halves again and the
    # fp32-scale-per-1024-elements overhead costs ~0.4% (1.99x)
    assert bytes_fp32 >= 3.9 * bytes_blk
    assert bytes_bf16 >= 1.98 * bytes_blk
    # and the quantized compiled run still trains
    assert lq[-1] <= lq[0] * 1.02
    assert abs(lq[0] - l32[0]) / l32[0] < 0.05


def _train_mlp_step_flagged(codec, flag, steps=4, clip=None,
                            block_size=128):
    """_train_mlp_step with FLAGS_kernel_autotune toggled for the run —
    the fused dequant+update wiring (ISSUE 13 follow-on, PR 15) keys off
    the flag at trace time."""
    from paddle_tpu.framework import flags as flags_mod

    flags_mod.set_flags({"FLAGS_kernel_autotune": bool(flag)})
    try:
        mesh_mod.set_mesh(mesh_mod.build_mesh(
            {"data": 2}, devices=jax.devices()[:2]))
        paddle.seed(7)
        net = _mlp()
        opt = optim.AdamW(learning_rate=1e-2, parameters=net.parameters(),
                          grad_clip=clip)
        gc = grad_comm.GradCommConfig(
            codec, comm_buffer_size=0.0002, last_comm_buffer_size=0.0001,
            block_size=block_size)
        step = TrainStep(net, F.mse_loss, opt, grad_comm=gc)
        losses = [float(step(inputs=(paddle.to_tensor(X),),
                             labels=(paddle.to_tensor(Y),)))
                  for _ in range(steps)]
        params = [np.asarray(p._value) for p in net.parameters()]
        slots = [{k: np.asarray(v) for k, v in s.items()}
                 for s in step._slots]
        return losses, params, slots, step
    finally:
        flags_mod.set_flags({"FLAGS_kernel_autotune": False})


def test_trainstep_gc_fused_dequant_update_parity():
    """ISSUE 13 follow-on (PR 15 satellite): with the kernel flag on, the
    compiled TrainStep(grad_comm=) keeps the summed blockwise payload and
    the fused pallas dequant+update kernel consumes it — the decoded
    gradient never materializes in HBM. Parity pin vs the jnp decode
    path: same losses, params and moments (CPU interpret mode runs the
    kernel's exact op sequence; documented fma freedom is below these
    tolerances on this model)."""
    l_jnp, p_jnp, s_jnp, _ = _train_mlp_step_flagged("int8_block", False)
    l_fused, p_fused, s_fused, step = _train_mlp_step_flagged(
        "int8_block", True)
    np.testing.assert_allclose(l_fused, l_jnp, rtol=1e-6)
    for a, b in zip(p_fused, p_jnp):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    for sa, sb in zip(s_fused, s_jnp):
        for k in sb:
            np.testing.assert_allclose(sa[k], sb[k], rtol=1e-6,
                                       atol=1e-7, err_msg=k)
    # wire accounting is the same payload either way
    assert step.comm_stats["path"] == "traced"
    assert step.comm_stats["codec"] == "int8_block"


def test_trainstep_gc_fused_gated_off_by_clip():
    """grad_clip needs the decoded gradients — the fused payload path
    must step aside (flag on, clip configured) and still match the
    flag-off run exactly (both run the jnp decode + clip)."""
    from paddle_tpu.nn import ClipGradByGlobalNorm

    l_off, p_off, _, _ = _train_mlp_step_flagged(
        "int8_block", False, clip=ClipGradByGlobalNorm(0.5))
    l_on, p_on, _, _ = _train_mlp_step_flagged(
        "int8_block", True, clip=ClipGradByGlobalNorm(0.5))
    assert l_on == l_off
    for a, b in zip(p_on, p_off):
        np.testing.assert_array_equal(a, b)


def test_trainstep_gc_rejects_unsupported_compositions():
    net = _mlp()
    opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
    with pytest.raises(ValueError, match="grad_accum"):
        TrainStep(net, F.mse_loss, opt, grad_accum_steps=2,
                  grad_comm="int8_block")
    with pytest.raises(ValueError, match="unknown grad_comm codec"):
        TrainStep(net, F.mse_loss, opt, grad_comm="fp8")


# ------------------------------------------------------- hapi + strategy
def test_strategy_block_size_reaches_config():
    st = fleet.DistributedStrategy()
    st.grad_comm = True
    st.grad_comm_configs = {"codec": "fp8_block", "block_size": 512}
    cfg = grad_comm.config_from_strategy(st)
    assert cfg.codec == "fp8_block" and cfg.block_size == 512
    with pytest.raises(ValueError):
        st.grad_comm_configs = {"bogus_knob": 1}


def test_hapi_fused_step_picks_up_strategy_grad_comm():
    """Model.prepare(jit_compile)'s TrainStep carries the strategy codec
    when fleet ran with grad_comm on (and stays inert without a mesh)."""
    from paddle_tpu.hapi import Model

    strategy = fleet.DistributedStrategy()
    strategy.grad_comm = True
    strategy.grad_comm_configs = {"codec": "int8_block"}
    from paddle_tpu.distributed.fleet import _fleet_state

    saved = dict(_fleet_state)
    try:
        fleet.init(is_collective=True, strategy=strategy)
        net = _mlp()
        model = Model(net)
        model.prepare(optimizer=optim.SGD(learning_rate=0.1,
                                          parameters=net.parameters()),
                      loss=F.mse_loss)
        model.train_batch([X], [Y])
        assert model._train_step is not None
        assert model._train_step._gc_comm is not None
        assert model._train_step._gc_comm.config.codec == "int8_block"
    finally:
        _fleet_state.clear()
        _fleet_state.update(saved)


# --------------------------------------------------- cost model + tooling
def test_comm_cost_blockwise_pricing():
    from paddle_tpu.cost_model import comm_cost

    gb = 350e6
    bf16 = comm_cost(gb, world=8, codec="bf16")
    blk = comm_cost(gb, world=8, codec="int8_block")
    fp8 = comm_cost(gb, world=8, codec="fp8_block", block_size=512)
    int8 = comm_cost(gb, world=8, codec="int8")
    assert bf16["time_s"] > blk["time_s"]
    # scale overhead priced: 4B per block_size elements of fp32 grads
    assert blk["wire_bytes"] == int(gb * 0.25 + gb / 1024)
    assert fp8["wire_bytes"] == int(gb * 0.25 + gb / 512)
    assert blk["wire_bytes"] > int8["wire_bytes"] - 1  # scales cost a bit
    # blockwise pays the scale-exchange collective per bucket, like int8
    import math
    assert blk["collectives"] == 2 * math.ceil(
        blk["wire_bytes"] / (25 * 1024 * 1024))


@pytest.mark.parametrize("codec", grad_comm.CODECS)
def test_traced_sync_moves_the_planned_bytes(codec):
    """The bucket sync compiled into a 2-device shard_map program (the
    sync_async / jit.TrainStep path) over the test GPT config's gradients:
    the wire it traces is the codec's planned bytes and collectives, not
    raw fp32, with the error-feedback residuals threaded as carried state."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.models import GPTForCausalLM, gpt_presets

    mesh = mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    model = GPTForCausalLM(gpt_presets("gpt-test"), seed=0)
    shapes = [(tuple(p._value.shape), np.dtype(p._value.dtype))
              for p in model.parameters() if not p.stop_gradient]
    stacked = [rng.standard_normal((2,) + s).astype(dt) * 1e-2
               for s, dt in shapes]

    def fakes(vals):
        ps = []
        for v, (s, dt) in zip(vals, shapes):
            p = Tensor(jnp.zeros(s, dt), _internal=True)
            p.stop_gradient = False
            p.grad = Tensor(v.reshape(s), _internal=True)
            ps.append(p)
        return ps

    cfg = grad_comm.GradCommConfig(codec=codec)
    comm = OverlappedGradCommunicator(cfg)
    host = fakes([v[0] for v in stacked])
    buckets = comm.buckets_for(host)
    ef = cfg.error_feedback and codec in grad_comm.EF_CODECS
    stats = {}

    def body(*rank_grads):
        res = ({b.index: jnp.zeros((b.size,), jnp.float32) for b in buckets}
               if ef else None)
        futs = comm.sync_async(fakes(rank_grads), world=2, residuals=res)
        stats.update(comm.stats)
        return tuple(f.wait() for f in futs)

    outs = jax.jit(mesh_mod.compat_shard_map(
        body, mesh, P("data"), tuple([P()] * len(buckets))))(*stacked)
    assert all(np.all(np.isfinite(np.asarray(o))) for o in outs)
    plan = grad_comm.comm_plan(host, cfg)
    assert stats["path"] == "traced"
    assert stats["comm_bytes"] == plan["comm_bytes_per_step"]
    assert stats["collectives"] == plan["collectives_per_step"]
    assert stats["n_buckets"] == plan["n_buckets"] < len(shapes)


# ------------------------------------------------------- static analysis
def test_codec_purity_rule_t002():
    from paddle_tpu.analysis import analyze_sources

    dirty = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def block_encode(flat, scales, bs, codec):\n"
        "    return np.round(flat / scales)\n")
    clean = (
        "import jax.numpy as jnp\n"
        "def block_encode(flat, scales, bs, codec):\n"
        "    return jnp.round(flat / scales)\n")
    path = "paddle_tpu/distributed/grad_comm.py"
    findings = analyze_sources({path: dirty})
    assert any(f.rule == "T002" for f in findings), findings
    assert not any(f.rule == "T002"
                   for f in analyze_sources({path: clean}))
    # same source elsewhere is not a codec module — rule scoped tight
    assert not any(f.rule == "T002"
                   for f in analyze_sources({"paddle_tpu/x.py": dirty}))
    from paddle_tpu.analysis import RULES

    assert "T002" in RULES and all(RULES["T002"])


def test_repo_codecs_clean_under_t002():
    """The real codec module passes its own rule (the static gate keeps
    the shared-verbatim contract enforced in tier-1)."""
    from paddle_tpu.analysis import analyze_sources

    path = os.path.join(REPO, "paddle_tpu", "distributed", "grad_comm.py")
    findings = analyze_sources(
        {"paddle_tpu/distributed/grad_comm.py": open(path).read()})
    assert not [f for f in findings if f.rule == "T002"]
