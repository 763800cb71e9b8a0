#!/usr/bin/env python
"""Cluster launcher (parity: tools/launch.py + dmlc-tracker local mode).

Spawns 1 server + N worker processes on this host, each running the given
command with the MXTPU_* cluster env set (the reference sets DMLC_ROLE /
DMLC_PS_ROOT_* the same way; both spellings are honored by
mxtpu.kvstore_server.cluster_env). This is how multi-node is exercised
without a cluster — the reference's own trick (tests/nightly/test_all.sh).

CPU-only: every process it starts runs with JAX_PLATFORMS=cpu. A chip
belongs to one process, so N workers on one host cannot share it.

Usage:
  python tools/launch.py -n 4 python train.py --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=1,
                    help="only 1 server process is supported")
    ap.add_argument("--launcher", default="local", choices=["local"],
                    help="ssh/mpi/sge/yarn launchers are not ported; local "
                         "mode covers the multi-process test strategy")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    port = _free_port()
    base_env = dict(os.environ)
    base_env.update({
        "JAX_PLATFORMS": "cpu",
        "MXTPU_ROOT_URI": "127.0.0.1",
        "MXTPU_ROOT_PORT": str(port),
        "MXTPU_NUM_WORKERS": str(args.num_workers),
    })

    procs = []
    server_env = dict(base_env, MXTPU_ROLE="server")
    procs.append(subprocess.Popen(
        [sys.executable, "-c",
         "from mxtpu.kvstore_server import _init_kvstore_server_module; "
         "_init_kvstore_server_module()"],
        env=server_env))

    for rank in range(args.num_workers):
        env = dict(base_env, MXTPU_ROLE="worker", MXTPU_WORKER_ID=str(rank))
        procs.append(subprocess.Popen(args.command, env=env))

    rc = 0
    for p in procs[1:]:
        rc |= p.wait()
    try:
        procs[0].wait(timeout=30)
    except subprocess.TimeoutExpired:
        procs[0].terminate()  # workers crashed before sending STOP
    sys.exit(rc)


if __name__ == "__main__":
    main()
