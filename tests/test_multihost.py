"""Multi-host runtime: real two-process rendezvous + cross-process collective.

The reference proves its comm backend with two-rank local processes
(test_collective_base.py pattern, SURVEY.md §4). Here two spawned Python
processes each run init_parallel_env (-> jax.distributed.initialize,
the PJRT coordination-service rendezvous that replaces
gen_comm_id_helper.cc:343), form one global 8-device CPU view, and a jitted
reduction over a mesh spanning both processes must see both processes' data.
"""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys
    rank = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = "2"
    os.environ["PADDLE_MASTER"] = "127.0.0.1:" + port
    sys.path.insert(0, {repo!r})
    import paddle_tpu.distributed as dist
    env = dist.init_parallel_env()
    assert dist.is_initialized()
    assert env.rank == rank and env.world_size == 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    mesh = Mesh(np.array(jax.devices()), ("data",))
    local = np.full((4, 2), rank + 1, np.float32)
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), local)
    total = jax.jit(lambda a: jnp.sum(a),
                    out_shardings=NamedSharding(mesh, P()))(garr)
    # rank0 rows of 1s + rank1 rows of 2s: 4*2*1 + 4*2*2 = 24
    assert float(total) == 24.0, float(total)
    print("RANK_OK", rank)
""").format(repo=REPO)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.timeout(180)
@pytest.mark.requires_cpu_multiprocess
def test_two_process_rendezvous_and_collective(tmp_path):
    port = str(_free_port())
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = [subprocess.Popen([sys.executable, str(script), str(r), port],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for r in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=150)
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
        assert f"RANK_OK {r}" in out
