"""Multi-process hybrid parallelism with loss parity (VERDICT r3 next #3).

The reference proves its distributed runtime with real subprocesses per
rank (test_dist_base.py:783,1032 spawns pservers/trainers; collective
tests launch 2 ranks). Single-process SPMD over a virtual mesh hides
cross-host init, device-ordering and sharding-transfer bugs — so here TWO
spawned processes (4 XLA host devices each) rendezvous via
init_parallel_env -> jax.distributed.initialize and run REAL training
steps over meshes that span the process boundary:

  config A  GSPMD MLP train step on a data4 x model2 mesh (tensor-parallel
            matmuls + cross-process data parallelism, GSPMD-partitioned)
  config B  the segmented 1F1B pipeline schedule on a pipe2 x data4 mesh
            whose PIPE axis crosses the process boundary — every
            ppermute hop is a cross-process transfer

Both loss sequences must match an in-process single-device oracle (same
seeds, same math) and agree exactly across ranks.
"""
import os
import socket
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipeline_toy import DIN, DOUT, embed_fn, loss_fn, make_params, stage_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STEPS = 4
LR = 0.05
HID = 32
PIPE, KPER = 2, 2
M, MB = 4, 4         # 1F1B micro-batches

WORKER = textwrap.dedent("""
    import os, sys
    rank = int(sys.argv[1]); port = sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = "2"
    os.environ["PADDLE_MASTER"] = "127.0.0.1:" + port
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {repo!r} + "/tests")

    import paddle_tpu.distributed as dist
    env = dist.init_parallel_env()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    jax.config.update("jax_default_matmul_precision", "highest")
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    from pipeline_toy import (DIN, DOUT, SPECS, embed_fn, loss_fn,
                              make_params, stage_fn)
    from paddle_tpu.distributed.pipeline import pipeline_1f1b

    STEPS, LR, HID = {steps}, {lr}, {hid}
    PIPE, KPER, M, MB = {pipe}, {kper}, {m}, {mb}

    def gshard(mesh, spec, arr):
        s = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(
            arr.shape, s, lambda idx: arr[idx])

    # ---- config A: GSPMD MLP on data4 x model2 (data crosses procs) ----
    mesh_a = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    rs = np.random.RandomState(0)
    w1 = (rs.randn(DIN, 64) * 0.3).astype(np.float32)
    w2 = (rs.randn(64, DOUT) * 0.3).astype(np.float32)
    xb = rs.randn(32, DIN).astype(np.float32)
    yb = rs.randn(32, DOUT).astype(np.float32)

    def loss_a(params, x, y):
        h = jnp.tanh(x @ params[0])
        return jnp.mean((h @ params[1] - y) ** 2)

    @jax.jit
    def step_a(params, x, y):
        l, g = jax.value_and_grad(loss_a)(params, x, y)
        return l, tuple(p - LR * gi for p, gi in zip(params, g))

    params = (gshard(mesh_a, P(None, "model"), w1),
              gshard(mesh_a, P("model", None), w2))
    x = gshard(mesh_a, P("data", None), xb)
    y = gshard(mesh_a, P("data", None), yb)
    la = []
    for _ in range(STEPS):
        l, params = step_a(params, x, y)
        la.append(float(l))
    print("LOSSES_A", rank, " ".join(f"{{v:.8f}}" for v in la), flush=True)

    # ---- config B: 1F1B on pipe2 x data4 — pipe crosses processes ----
    mesh_b = Mesh(np.array(jax.devices()).reshape(2, 4), ("pipe", "data"))
    rs2 = np.random.RandomState(1)
    tparams = make_params(rs2, PIPE * KPER, HID)
    xb2 = rs2.randn(M * MB, DIN).astype(np.float32)
    yb2 = rs2.randn(M * MB, DOUT).astype(np.float32)

    @jax.jit
    def step_b(p, x, lbl):
        loss, grads = pipeline_1f1b(
            embed_fn, stage_fn, loss_fn, p, x, lbl,
            mesh=mesh_b, param_specs=SPECS, microbatches=M)
        new = jax.tree.map(
            lambda w, g: (w - LR * g).astype(w.dtype), p, grads)
        return loss, new

    tp = {{k: gshard(mesh_b, SPECS[k], np.asarray(v))
          for k, v in tparams.items()}}
    xg = gshard(mesh_b, P("data", None), xb2)
    yg = gshard(mesh_b, P("data", None), yb2)
    lb = []
    for _ in range(STEPS):
        l, tp = step_b(tp, xg, yg)
        lb.append(float(l))
    print("LOSSES_B", rank, " ".join(f"{{v:.8f}}" for v in lb), flush=True)
    print("RANK_OK", rank, flush=True)
""").format(repo=REPO, steps=STEPS, lr=LR, hid=HID, pipe=PIPE, kper=KPER,
            m=M, mb=MB)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _oracle_a():
    rs = np.random.RandomState(0)
    w1 = (rs.randn(DIN, 64) * 0.3).astype(np.float32)
    w2 = (rs.randn(64, DOUT) * 0.3).astype(np.float32)
    xb = rs.randn(32, DIN).astype(np.float32)
    yb = rs.randn(32, DOUT).astype(np.float32)

    def loss(params, x, y):
        h = jnp.tanh(x @ params[0])
        return jnp.mean((h @ params[1] - y) ** 2)

    params = (jnp.asarray(w1), jnp.asarray(w2))
    out = []
    step = jax.jit(lambda p, x, y: jax.value_and_grad(loss)(p, x, y))
    for _ in range(STEPS):
        l, g = step(params, xb, yb)
        params = tuple(p - LR * gi for p, gi in zip(params, g))
        out.append(float(l))
    return out


def _oracle_b():
    rs2 = np.random.RandomState(1)
    params = make_params(rs2, PIPE * KPER, HID)
    xb2 = rs2.randn(M * MB, DIN).astype(np.float32)
    yb2 = rs2.randn(M * MB, DOUT).astype(np.float32)

    def seq_loss(p, x, lbl):
        h = embed_fn(p, x)
        h = stage_fn(p, h)
        return loss_fn(p, h, lbl)

    step = jax.jit(lambda p, x, y: jax.value_and_grad(seq_loss)(p, x, y))
    out = []
    for _ in range(STEPS):
        l, g = step(params, xb2, yb2)
        params = jax.tree.map(
            lambda w, gi: (w - LR * gi).astype(w.dtype), params, g)
        out.append(float(l))
    return out


@pytest.mark.timeout(420)
@pytest.mark.requires_cpu_multiprocess
def test_two_process_hybrid_training_parity(tmp_path):
    port = str(_free_port())
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = [subprocess.Popen([sys.executable, str(script), str(r), port],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for r in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=390)
            outs.append(out)
    finally:
        # a crashed rank leaves its peer blocked in rendezvous forever;
        # never leak a hung worker past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-4000:]}"
        assert f"RANK_OK {r}" in out

    def parse(tag, out):
        for line in out.splitlines():
            if line.startswith(tag):
                return [float(v) for v in line.split()[2:]]
        raise AssertionError(f"{tag} not found in:\n{out[-2000:]}")

    for tag, oracle in (("LOSSES_A", _oracle_a()), ("LOSSES_B", _oracle_b())):
        seq0 = parse(tag, outs[0])
        seq1 = parse(tag, outs[1])
        # both ranks observe the same replicated loss
        np.testing.assert_allclose(seq0, seq1, rtol=1e-6, err_msg=tag)
        # and it matches the in-process single-device oracle
        np.testing.assert_allclose(seq0, oracle, rtol=2e-4, atol=1e-6,
                                   err_msg=tag)
