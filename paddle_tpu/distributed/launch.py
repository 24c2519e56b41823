"""Multi-process launcher: ``python -m paddle_tpu.distributed.launch``.

Reference: python/paddle/distributed/launch.py:281 (one trainer process per
GPU with PADDLE_* env vars; :147 start_procs, :141 terminate_procs). The TPU
shape is one process per HOST (JAX owns every local chip in-process), so
--nproc_per_node defaults to 1 on real hardware; >1 is the multi-host
simulation mode on the CPU backend (--backend cpu) used by the distributed
tests — the role the reference's test_dist_base localhost subprocesses play.

Usage:
  python -m paddle_tpu.distributed.launch --nproc_per_node 2 \
      --backend cpu train.py --my-flag ...
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "find_free_ports"]


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--cluster_node_ips", type=str, default="127.0.0.1",
                   help="comma-separated node ips (reference flag)")
    p.add_argument("--node_ip", type=str, default="127.0.0.1",
                   help="this node's ip")
    p.add_argument("--started_port", type=int, default=0,
                   help="first endpoint port; 0 picks free ports")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="trainer processes on this node")
    p.add_argument("--backend", type=str, default="",
                   choices=["", "cpu", "tpu"],
                   help="cpu = multi-host simulation with gloo collectives")
    p.add_argument("--local_devices", type=int, default=1,
                   help="devices per process on the cpu backend")
    p.add_argument("--log_dir", type=str, default=None,
                   help="redirect each rank's output to {log_dir}/workerlog.N")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def find_free_ports(n: int) -> list:
    """Bind-then-release to reserve n distinct free ports (the reference's
    dist_test.sh retried on conflicts; reserving up front avoids the retry)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def launch(args=None) -> int:
    args = args or _parse_args()
    node_ips = [ip for ip in args.cluster_node_ips.split(",") if ip]
    nproc = args.nproc_per_node
    if nproc > 1 and args.backend != "cpu":
        # a chip belongs to one process: every child would initialise the
        # real backend on this host, and all but the first would fail or
        # hang waiting for chips the first one holds
        raise ValueError(
            f"--nproc_per_node {nproc} needs --backend cpu (the multi-host "
            f"simulation): on real hardware ONE process drives every chip "
            f"of the host, so launch one process per node")
    if args.started_port:
        ports = [args.started_port + i for i in range(nproc)]
    else:
        if len(node_ips) > 1:
            # auto-discovered ports are LOCAL: other nodes would pick
            # different ones and the cross-node endpoint lists (and the
            # rank-0 coordinator address) would disagree
            raise ValueError(
                "multi-node launch (cluster_node_ips has "
                f"{len(node_ips)} nodes) requires an explicit "
                "--started_port so every node builds the same endpoint "
                "list; port auto-discovery only works single-node")
        ports = find_free_ports(nproc)
    # endpoints for ALL nodes; this launcher starts only this node's procs
    endpoints = []
    for ip in node_ips:
        endpoints += [f"{ip}:{p}" for p in ports]
    node_rank = node_ips.index(args.node_ip)

    procs, log_files = [], []
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    for local_rank in range(nproc):
        rank = node_rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(len(node_ips) * nproc),
            "PADDLE_CURRENT_ENDPOINT": f"{args.node_ip}:{ports[local_rank]}",
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "FLAGS_selected_tpus": str(local_rank),
        })
        if args.backend:
            env["PADDLE_DIST_BACKEND"] = args.backend
            env["PADDLE_LOCAL_DEVICES"] = str(args.local_devices)
        cmd = [sys.executable, "-u", args.training_script] \
            + args.training_script_args
        out = None
        if args.log_dir:
            out = open(os.path.join(args.log_dir, f"workerlog.{local_rank}"),
                       "w")
            log_files.append(out)
        procs.append(subprocess.Popen(cmd, env=env, stdout=out,
                                      stderr=subprocess.STDOUT if out else None))

    rc = 0
    try:
        alive = set(range(nproc))
        while alive:
            for i in list(alive):
                r = procs[i].poll()
                if r is None:
                    continue
                alive.discard(i)
                if r != 0:
                    rc = r
                    # one trainer died: kill the rest (reference
                    # terminate_procs — a hung collective never recovers)
                    for j in alive:
                        procs[j].send_signal(signal.SIGTERM)
                    for j in alive:
                        try:
                            procs[j].wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            procs[j].kill()
                    alive.clear()
            time.sleep(0.2)
    finally:
        for f in log_files:
            f.close()
    return rc


if __name__ == "__main__":
    sys.exit(launch())
