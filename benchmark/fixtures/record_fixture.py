"""Records the small trace the reduction is tested on (run once, on the
chip): three calls of a jitted step holding two matmuls and the program's
Mosaic flash-attention kernel, inside the benchmark's trace window.

    chiprun -- python3 benchmark/fixtures/record_fixture.py

writes chiprun_out/fixture/tiny_tpu.xplane.pb; copy it beside this file.
"""
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from benchmark import tracewin
    from paddle_tpu.ops.flash_attention import flash_attention_val

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture: needs a TPU")

    @jax.jit
    def step(x, w, q):
        y = jnp.tanh(x @ w) @ w.T
        a = flash_attention_val(q, q, q, causal=True)
        return y.sum() + a.astype(jnp.float32).sum()

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (512, 512), jnp.bfloat16)
    w = jax.random.normal(k, (512, 512), jnp.bfloat16)
    q = jax.random.normal(k, (1, 256, 2, 64), jnp.bfloat16)
    step(x, w, q).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "fixture")
    tdir = os.path.join(out, "trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(tdir)
    import time

    with tracewin.device_trace(tdir) as tw:
        for _ in range(3):
            with tw.annotate("bench.enqueue_chunk"):
                v = step(x, w, q)
            with tw.annotate("bench.wait_chunk"):
                v.block_until_ready()
            time.sleep(0.002)
    src = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "tiny_tpu.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tdir)
    print("wrote", dst, os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main()
