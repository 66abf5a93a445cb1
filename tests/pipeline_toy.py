"""Shared toy pipeline model for the 1F1B tests and throughput bench.

One definition of the stacked-tanh stage model (embed -> P stages of
KPER scanned layers -> linear head + MSE), its pipe-sharded PartitionSpecs
and the GPipe fill-drain baseline — used by the test_pipeline_1f1b,
test_pipeline_throughput and test_multiproc_hybrid tests so the three
can't drift apart.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

DIN, DOUT = 32, 8

SPECS = {"we": P(), "w": P("pipe", None, None), "b": P("pipe", None),
         "wh": P()}


def make_params(rs, l_total, hid, din=DIN, dout=DOUT):
    return {
        "we": jnp.asarray(rs.randn(din, hid) * 0.3, jnp.float32),
        "w": jnp.asarray(rs.randn(l_total, hid, hid) * 0.3, jnp.float32),
        "b": jnp.asarray(rs.randn(l_total, hid) * 0.1, jnp.float32),
        "wh": jnp.asarray(rs.randn(hid, dout) * 0.3, jnp.float32),
    }


def embed_fn(p, r):
    return jnp.tanh(r @ p["we"])


def stage_fn(p, h):
    def one(carry, wl):
        w, b = wl
        return jnp.tanh(carry @ w + b), None

    out, _ = jax.lax.scan(one, h, (p["w"], p["b"]))
    return out


def loss_fn(p, y, lbl):
    return jnp.mean((y @ p["wh"] - lbl) ** 2)


def gpipe_value_and_grad(mesh, M, p, x, lbl, remat):
    """GPipe fill-drain train step: AD through pipeline_spmd, optionally
    with jax.checkpoint on the stage body (recompute parity with 1F1B).
    The comparison baseline of the throughput test."""
    from paddle_tpu.distributed.pipeline import pipeline_spmd

    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def train_loss(p):
        h = embed_fn(p, x)
        y = pipeline_spmd(
            lambda sp, mbx: body({"w": sp[0], "b": sp[1]}, mbx),
            (p["w"], p["b"]), h, mesh=mesh,
            param_specs=(SPECS["w"], SPECS["b"]), microbatches=M)
        return loss_fn(p, y, lbl)

    return jax.value_and_grad(train_loss)(p)
