"""Launcher implementation (reference: fleet/launch.py + launch_utils.py)."""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch", "main", "watch_local_procs"]


def _parse_args(argv=None):
    parser = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch distributed training "
                    "(reference CLI: python -m paddle.distributed.launch)")
    parser.add_argument("--nnodes", type=str, default=None,
                        help="node count or range 'N' / 'N:M' (elastic)")
    parser.add_argument("--nproc_per_node", type=int, default=None,
                        help="processes per node (default: 1 — one process "
                             "drives all local TPU chips)")
    parser.add_argument("--ips", type=str, default="127.0.0.1",
                        help="comma-separated host list")
    parser.add_argument("--master", type=str, default=None,
                        help="coordination service address host:port")
    parser.add_argument("--rank", type=int, default=None,
                        help="node rank (defaults to POD_INDEX / 0)")
    parser.add_argument("--log_dir", type=str, default="log")
    parser.add_argument("--run_mode", type=str, default="collective",
                        choices=["collective", "ps"])
    parser.add_argument("--server_num", type=int, default=0)
    parser.add_argument("--worker_num", type=int, default=0)
    parser.add_argument("--heter_worker_num", type=int, default=0)
    parser.add_argument("--elastic_server", type=str, default=None,
                        help="etcd://host:port for elastic membership")
    parser.add_argument("--job_id", type=str, default="default")
    parser.add_argument("--devices", "--gpus", "--xpus", type=str,
                        default=None, dest="devices",
                        help="accepted for CLI parity; TPU chips are driven "
                             "by the mesh, not per-process pinning")
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


def _one_process_per_chip(n_children: int, what: str):
    """A chip belongs to one process: every child of this launcher would
    initialise JAX on the same local chips, and the second one fails or
    hangs. So more than one device-using child per host is refused unless
    the children are held to the CPU. (The launcher itself never touches
    JAX, so it cannot count chips; it reads what the children will read.)"""
    if n_children > 1 and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit(
            f"{what}={n_children}: one process drives all local chips "
            f"(the mesh spans jax.devices()); start ONE process per host. "
            f"For a CPU-only multi-process run export JAX_PLATFORMS=cpu.")


def _build_env(rank, nranks, master, endpoints, base_env=None):
    """The PADDLE_TRAINER_* env protocol (launch_utils.py get_cluster)."""
    env = dict(base_env if base_env is not None else os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nranks),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_MASTER": master,
        "FLAGS_selected_tpus": "all",
    })
    return env


def _launch_elastic(args, node_ip, nproc):
    """Elastic mode (reference manager.py main loop): membership lives in
    etcd (--elastic_server etcd://host:port), endpoints derive from the
    observed member set, and scale events kill + relaunch the local
    workers with rewritten endpoints."""
    from ..fleet.elastic import ElasticController, ElasticManager
    from ..fleet.elastic.etcd_store import Etcd3GatewayStore

    store = Etcd3GatewayStore(args.elastic_server)
    mgr = ElasticManager(node_ip, str(args.nnodes or "1"), store=store,
                         job_id=args.job_id)
    os.makedirs(args.log_dir, exist_ok=True)
    lifes = [0]

    def launch_fn(node_eps):
        hosts = [e.rsplit(":", 1)[0] for e in node_eps]
        if node_ip not in hosts:
            # our own registration hasn't landed in the store yet (e.g.
            # transient put failure at startup, heartbeat will retry):
            # tell the controller to hold, not crash
            return None
        endpoints = [f"{h}:{8091 + j}" for h in hosts for j in range(nproc)]
        master = f"{hosts[0]}:8090"
        node_rank = hosts.index(node_ip)
        lifes[0] += 1
        procs = []
        for local in range(nproc):
            rank = node_rank * nproc + local
            env = _build_env(rank, len(endpoints), master, endpoints)
            # the child dups the fd at spawn; closing the parent's handle
            # immediately avoids leaking one per worker per life
            with open(os.path.join(
                    args.log_dir,
                    f"workerlog.{local}.life{lifes[0]}"), "w") as lf:
                procs.append(subprocess.Popen(
                    [sys.executable, "-u", args.training_script,
                     *args.training_script_args],
                    env=env, stdout=lf, stderr=lf))
        return procs

    return ElasticController(mgr, launch_fn).run()


def watch_local_procs(procs, log_files=None):
    """Watchdog (launch_utils.py watch_local_trainers): if any proc exits
    non-zero, terminate the rest and propagate the failure."""
    try:
        while True:
            alive = False
            for i, p in enumerate(procs):
                ret = p.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    for q in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    return ret
            if not alive:
                return 0
            time.sleep(1)
    except KeyboardInterrupt:
        for q in procs:
            if q.poll() is None:
                q.send_signal(signal.SIGTERM)
        return 1


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_ps(args, ips):
    """PS-mode launcher (reference: fleet launch_ps / launch_utils
    get_ps_cluster): spawn --server_num PSERVER processes and --worker_num
    TRAINER processes on this node, wiring the PADDLE_PSERVERS_IP_PORT_LIST
    / TRAINING_ROLE env protocol the role makers read."""
    n_servers = int(args.server_num or 1)
    n_workers = int(args.worker_num or 1)
    n_heter = int(args.heter_worker_num or 0)
    _one_process_per_chip(n_workers + n_heter,
                          "--worker_num + --heter_worker_num")
    host = ips[0] if ips else "127.0.0.1"
    server_eps = [f"{host}:{_free_port()}" for _ in range(n_servers)]
    heter_eps = [f"{host}:{_free_port()}" for _ in range(n_heter)]

    os.makedirs(args.log_dir, exist_ok=True)
    procs, logs = [], []

    def spawn(role, idx, extra_env):
        env = dict(os.environ)
        env.update({
            "PADDLE_PSERVERS_IP_PORT_LIST": ",".join(server_eps),
            "PADDLE_HETER_TRAINER_IP_PORT_LIST": ",".join(heter_eps),
            "PADDLE_TRAINERS_NUM": str(n_workers),
            "TRAINING_ROLE": role,
            **extra_env,
        })
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        lf = open(os.path.join(args.log_dir,
                               f"{role.lower()}log.{idx}"), "w")
        logs.append(lf)
        procs.append(subprocess.Popen(cmd, env=env, stdout=lf, stderr=lf))

    for i, ep in enumerate(server_eps):
        # a server holds its tables on the host: keep it off the chip
        spawn("PSERVER", i, {"PADDLE_PORT": ep.rsplit(":", 1)[1],
                             "POD_IP": host,
                             "PADDLE_PSERVER_ID": str(i),
                             "PADDLE_TRAINER_ID": str(i),
                             "JAX_PLATFORMS": "cpu"})
    server_procs = procs[:]
    procs_before = len(procs)
    for i in range(n_workers):
        spawn("TRAINER", i, {"PADDLE_TRAINER_ID": str(i)})
    # heterogeneous device workers (reference: launch_utils
    # get_heter_worker_endpoints + TRAINING_ROLE=HETER_TRAINER)
    for i in range(n_heter):
        spawn("HETER_TRAINER", i, {
            "PADDLE_TRAINER_ID": str(i),
            "PADDLE_PORT": heter_eps[i].rsplit(":", 1)[1],
        })
    trainer_procs = procs[procs_before:]
    # servers park in run_server(); watch the trainers, then retire servers
    # (reference watch_local_trainers semantics)
    ret = watch_local_procs(trainer_procs)
    for p in server_procs:
        if p.poll() is None:
            p.terminate()
    for lf in logs:
        lf.close()
    return ret


def launch(args=None):
    args = args if args is not None else _parse_args()
    ips = [h for h in args.ips.split(",") if h]
    # --nnodes N (or elastic "N:M": use the floor) overrides the ip-list size,
    # for clusters where each node runs the launcher with its own --rank
    nnodes = (int(str(args.nnodes).split(":")[0]) if args.nnodes
              else len(ips))
    if len(ips) < nnodes:
        ips = ips + [ips[0]] * (nnodes - len(ips))
    node_rank = args.rank
    if node_rank is None:
        node_rank = int(os.environ.get("POD_INDEX",
                                       os.environ.get("PADDLE_TRAINER_ID", 0)))
    nproc = args.nproc_per_node or 1
    master = args.master or f"{ips[0]}:8090"

    if args.run_mode == "ps":
        return _launch_ps(args, ips)
    _one_process_per_chip(nproc, "--nproc_per_node")

    if args.elastic_server:
        return _launch_elastic(args, ips[min(node_rank, len(ips) - 1)],
                               nproc)

    nranks = nnodes * nproc
    endpoints = []
    for ip in ips:
        for j in range(nproc):
            endpoints.append(f"{ip}:{8091 + j}")

    os.makedirs(args.log_dir, exist_ok=True)
    procs, logs = [], []
    for local in range(nproc):
        rank = node_rank * nproc + local
        env = _build_env(rank, nranks, master, endpoints)
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        lf = open(os.path.join(args.log_dir, f"workerlog.{local}"), "w")
        logs.append(lf)
        procs.append(subprocess.Popen(cmd, env=env, stdout=lf, stderr=lf)
                     if nproc > 1 or nnodes > 1 else
                     subprocess.Popen(cmd, env=env))
    ret = watch_local_procs(procs)
    for lf in logs:
        lf.close()
    return ret


def main():
    sys.exit(launch() or 0)


if __name__ == "__main__":
    main()
