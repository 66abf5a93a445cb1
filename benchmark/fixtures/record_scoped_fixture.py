"""Records the small trace the program-scope and program-span readers are
tested on (run once, on the chip): a few calls of a `jit.TrainStep` over
the program's GPT at gpt-test widths (2 layers, h64, 4 heads x 16, batch 4
x 128 tokens, flash on), inside the benchmark's trace window, the way the
train kind drives it (chunks of steps, one enqueued ahead).

    chiprun -- python3 benchmark/fixtures/record_scoped_fixture.py

writes chiprun_out/fixture/tiny_scoped.xplane.pb; copy it beside this file.
"""
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS_PER_CHUNK, CHUNKS = 2, 3


def main():
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from benchmark import tracewin
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt_presets)

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_fixture: needs a TPU")

    cfg = gpt_presets("gpt-test")
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    rs = np.random.RandomState(0)

    def one():
        ids = rs.randint(0, cfg.vocab_size, (4, 129))
        return step(inputs=(paddle.to_tensor(ids[:, :-1], dtype="int64"),),
                    labels=(paddle.to_tensor(ids[:, 1:], dtype="int64"),)
                    )._value

    for _ in range(3):                       # compile, then settle
        jax.block_until_ready(one())
    program = step._cache[step._last_ckey].lower(
        *step._last_abstract).as_text()
    if program.count("tpu_custom_call") != 3 * cfg.num_layers:
        raise SystemExit("record_scoped_fixture: the step holds "
                         f"{program.count('tpu_custom_call')} Mosaic calls")

    out = os.path.join(ROOT, "chiprun_out", "fixture")
    tdir = os.path.join(out, "trace_scoped")
    shutil.rmtree(tdir, ignore_errors=True)
    os.makedirs(tdir)
    import time

    with tracewin.device_trace(tdir) as tw:
        pending = None
        for _ in range(CHUNKS):
            with tw.annotate("bench.enqueue_chunk"):
                for _ in range(STEPS_PER_CHUNK):
                    last = one()
            if pending is not None:
                with tw.annotate("bench.wait_chunk"):
                    jax.block_until_ready(pending)
            pending = last
            time.sleep(0.003)     # an idle gap outside every pt.* span
        with tw.annotate("bench.wait_chunk"):
            jax.block_until_ready(pending)
    src = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "tiny_scoped.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tdir)
    print("wrote", dst, os.path.getsize(dst), "bytes;",
          STEPS_PER_CHUNK * CHUNKS, "steps traced")


if __name__ == "__main__":
    main()
