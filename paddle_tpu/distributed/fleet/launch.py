"""Launcher — ``python -m paddle_tpu.distributed.launch train.py``.

Analog of python/paddle/distributed/fleet/launch.py (launch_collective:188,
launch_ps:227) + launch_utils.py. Execution-model translation: the
reference spawns one process per GPU and wires NCCL ranks through
PADDLE_TRAINER_* env vars. On TPU, one python process drives all local
chips SPMD, so the collective launcher's per-host job is: initialize
jax.distributed (multi-host rendezvous over DCN — the analog of the
gen_nccl_id gRPC exchange), set the PADDLE_* env vars for RoleMaker
parity, and exec the training script once per host. PS mode spawns server
and worker processes like the reference.
"""

from __future__ import annotations

import argparse
import os
import runpy
import subprocess
import sys
import time
from typing import List


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--ips", default="127.0.0.1",
                   help="comma-separated host ips (multi-host DCN)")
    p.add_argument("--host_rank", type=int,
                   default=int(os.getenv("HOST_RANK", "0")))
    p.add_argument("--coordinator", default=None,
                   help="coordinator address host:port for jax.distributed")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (reference launch_utils "
                        "get_cluster_from_args parity; >1 spawns ranked "
                        "children that jax.distributed-join one world — "
                        "CPU platform plane only, see --dist_platform)")
    p.add_argument("--dist_platform", default=None,
                   help="force jax platform in ranked children "
                        "(cpu = virtual-device CI mode with gloo "
                        "cross-process collectives)")
    p.add_argument("--devices_per_proc", type=int, default=0,
                   help="virtual devices per child (cpu CI mode)")
    p.add_argument("--servers", default="",
                   help="PS mode: comma-separated server endpoints")
    p.add_argument("--workers", default="",
                   help="PS mode: comma-separated worker endpoints")
    p.add_argument("--server_num", type=int, default=0)
    p.add_argument("--worker_num", type=int, default=0)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch_collective(args):
    hosts = args.ips.split(",")
    nhosts = len(hosts)
    nproc = max(1, args.nproc_per_node)
    world = nhosts * nproc
    if nproc > 1:
        return _launch_collective_multiproc(args, hosts, nproc, world)
    # the CLI args are the source of truth — force-set so stale ambient
    # PADDLE_* values from a prior run can't override --ips/--host_rank
    os.environ["PADDLE_TRAINER_ID"] = str(args.host_rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nhosts)
    os.environ["PADDLE_TRAINER_ENDPOINTS"] = \
        ",".join(f"{h}:8910" for h in hosts)
    os.environ["PADDLE_CURRENT_ENDPOINT"] = f"{hosts[args.host_rank]}:8910"
    if nhosts > 1:
        # export the coordinator plane AND join the world here, so
        # scripts that never call init_parallel_env still see global
        # devices; init_parallel_env's is_initialized() check keeps its
        # own join a no-op afterwards
        coordinator = args.coordinator or f"{hosts[0]}:8476"
        os.environ["PADDLE_COORDINATOR"] = coordinator
        from ..parallel import _maybe_init_multiprocess
        _maybe_init_multiprocess()
    sys.argv = [args.training_script] + args.training_script_args
    runpy.run_path(args.training_script, run_name="__main__")


def _launch_collective_multiproc(args, hosts, nproc, world):
    """Spawn ``nproc`` ranked trainer processes on this host, one global
    jax.distributed world across all of them (reference: one process per
    GPU, launch_utils.start_local_trainers / get_cluster_from_args).

    Each child re-runs the training script with the PADDLE_* rank plane
    set; the script joins the world by calling
    ``paddle_tpu.distributed.init_parallel_env()``. Children are watched
    pod-style: any non-zero exit terminates the rest (launch.py:188-226).
    """
    platform = args.dist_platform or os.getenv("PADDLE_DIST_PLATFORM", "")
    if not platform.startswith("cpu"):
        # decided from the arguments alone: this parent never asks JAX
        # what devices exist (a parent that has touched JAX holds the
        # chips its children need)
        sys.exit(
            f"paddle_tpu.distributed.launch: refusing --nproc_per_node "
            f"{nproc} on an accelerator host. One process drives all "
            "local chips (a chip belongs to one process at a time; N "
            "children that each open every chip fail or hang). Run the "
            "script once per host (--nproc_per_node 1), or pass "
            "--dist_platform cpu (or PADDLE_DIST_PLATFORM=cpu) for the "
            "virtual-device CPU mode.")
    coordinator = args.coordinator or f"{hosts[0]}:8476"
    procs: List[subprocess.Popen] = []
    for i in range(nproc):
        rank = args.host_rank * nproc + i
        env = dict(os.environ,
                   PADDLE_TRAINER_ID=str(rank),
                   PADDLE_TRAINERS_NUM=str(world),
                   PADDLE_COORDINATOR=coordinator,
                   PADDLE_TRAINER_ENDPOINTS=",".join(
                       f"{h}:{8910 + j}" for h in hosts
                       for j in range(nproc)),
                   PADDLE_CURRENT_ENDPOINT=f"{hosts[args.host_rank]}:"
                                           f"{8910 + i}")
        env["PADDLE_DIST_PLATFORM"] = platform
        if args.devices_per_proc:
            env["PADDLE_DIST_DEVICES_PER_PROC"] = str(args.devices_per_proc)
        procs.append(subprocess.Popen(
            [sys.executable, "-u", args.training_script] +
            args.training_script_args, env=env))
    _watch_pod(procs)


def _watch_pod(procs: List[subprocess.Popen]):
    try:
        while procs:
            for p in list(procs):
                ret = p.poll()
                if ret is None:
                    continue
                procs.remove(p)
                if ret != 0:
                    for q in procs:
                        q.terminate()
                    sys.exit(ret)
            time.sleep(0.2)
    except KeyboardInterrupt:
        for p in procs:
            p.terminate()


def launch_ps(args):
    """Spawn PS server + worker subprocesses on this host
    (launch_ps:227 analog)."""
    servers = (args.servers.split(",") if args.servers else
               [f"127.0.0.1:{8700 + i}" for i in range(args.server_num)])
    n_workers = args.worker_num or 1
    procs: List[subprocess.Popen] = []
    for i, ep in enumerate(servers):
        env = dict(os.environ,
                   TRAINING_ROLE="PSERVER",
                   PADDLE_PSERVERS_IP_PORT_LIST=",".join(servers),
                   PADDLE_PORT_ID=str(i))
        procs.append(subprocess.Popen(
            [sys.executable, args.training_script] +
            args.training_script_args, env=env))
    for i in range(n_workers):
        env = dict(os.environ,
                   TRAINING_ROLE="TRAINER",
                   PADDLE_TRAINER_ID=str(i),
                   PADDLE_TRAINERS_NUM=str(n_workers),
                   PADDLE_PSERVERS_IP_PORT_LIST=",".join(servers))
        procs.append(subprocess.Popen(
            [sys.executable, args.training_script] +
            args.training_script_args, env=env))
    # watch children; terminate the pod on any failure (launch.py:188-226)
    _watch_pod(procs)


def main(argv=None):
    args = _parse_args(argv)
    if args.servers or args.server_num:
        launch_ps(args)
    else:
        launch_collective(args)


if __name__ == "__main__":
    main()
