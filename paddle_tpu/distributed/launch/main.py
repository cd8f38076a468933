"""Distributed launcher CLI.

ref: python/paddle/distributed/launch/main.py + controllers/
(CollectiveController at controllers/collective.py:23, HTTP/ETCD Master at
controllers/master.py:65,177, watch loop controller.py:74, elastic variant
collective.py:184).

TPU-native shape: one process per HOST (a single controller drives all
local chips — unlike the reference's one-proc-per-GPU), rendezvous via
jax.distributed (coordinator = rank-0 host). With `--nproc_per_node` > 1
every worker is bound to ONE local chip before it imports jax
(chip.chip_env; `--devices` picks which), because a chip belongs to one
process; the launcher itself never touches the backend. Production pieces:
  - multi-node: rank-0 hosts an HTTP master (launch/master.py); every node
    syncs its endpoint list through it before spawning workers
    (ref: _build_pod_with_master, collective.py:96);
  - watch loop restarts failed workers up to --max_restart times
    (ref: controller.py watch + elastic restart), re-running the whole
    local pod so ranks come back consistent;
  - per-rank logs under --log_dir (workerlog.N, ref: controller.py:189).
"""
import argparse
import os
import signal
import socket
import subprocess
import sys
import time

from ...chip import chip_env, enable_compile_cache


def _parse():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--master", default=None,
                   help="master endpoint ip:port (rank-0 host)")
    p.add_argument("--nnodes", type=int,
                   default=int(os.getenv("PADDLE_NNODES", "1")))
    p.add_argument("--rank", type=int,
                   default=int(os.getenv("PADDLE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--log_dir", default="log")
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", default=None)
    p.add_argument("--elastic_level", type=int, default=-1)
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


class Container:
    """One launched worker process (ref: launch/job/container.py)."""

    def __init__(self, cmd, env, log_path):
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc = None

    def start(self):
        os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
        self._log = open(self.log_path, "ab")
        full_env = dict(os.environ)
        full_env.update(self.env)
        self.proc = subprocess.Popen(self.cmd, env=full_env,
                                     stdout=self._log, stderr=self._log)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    @property
    def returncode(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def _local_ip():
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def _sync_nodes(args):
    """Multi-node rendezvous through the HTTP master on rank 0
    (ref: collective.py:96 _build_pod_with_master). Returns the
    jax.distributed coordinator endpoint. --master must be an explicit
    ip:port so every node can reach it."""
    from .master import HTTPMaster, MasterClient
    host, _, port = (args.master or "").partition(":")
    if not host or not port:
        print("[launch] --master must be ip:port for --nnodes > 1",
              file=sys.stderr)
        sys.exit(2)
    master = None
    if args.rank == 0:
        master = HTTPMaster(int(port))
    client = MasterClient(f"{host}:{port}")
    client.wait_healthy()
    my_ep = _local_ip() if args.rank else host
    peers = client.sync_peers(args.job_id, args.rank, my_ep, args.nnodes)
    coordinator = f"{peers[0]}:{int(port) + 1}"
    return master, coordinator


def _build_containers(args, nproc, world, master_ep):
    # one chip per worker when a node runs several: --devices names the
    # local chip ids (in order), else worker i takes chip i
    chips = [int(c) for c in args.devices.split(",")] if args.devices \
        else list(range(nproc))
    if len(chips) < nproc:
        print(f"[launch] --devices names {len(chips)} chips for "
              f"--nproc_per_node {nproc}", file=sys.stderr)
        sys.exit(2)
    containers = []
    for local_rank in range(nproc):
        rank = args.rank * nproc + local_rank
        env = {
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "MASTER_ADDR": master_ep.split(":")[0],
            "MASTER_PORT": master_ep.split(":")[1],
            "PADDLE_JOB_ID": args.job_id,
            "PADDLE_LOCAL_IP": _local_ip(),
        }
        if args.devices:
            env["FLAGS_selected_tpus"] = args.devices
        if nproc > 1:
            env.update(chip_env(chips[local_rank]))
        cmd = [sys.executable, args.script] + args.script_args
        log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
        containers.append(Container(cmd, env, log_path))
    return containers


def launch():
    args = _parse()
    enable_compile_cache()      # exported: every worker shares one cache
    nproc = args.nproc_per_node
    world = args.nnodes * nproc

    master = None
    if args.nnodes > 1:
        if not args.master:
            print("[launch] --master ip:port is required for --nnodes > 1",
                  file=sys.stderr)
            sys.exit(2)
        master, coordinator = _sync_nodes(args)
        master_ep = coordinator
    else:
        master_ep = args.master or "127.0.0.1:49178"

    containers = _build_containers(args, nproc, world, master_ep)
    for c in containers:
        c.start()

    def shutdown(sig=None, frame=None):
        for c in containers:
            c.terminate()
        if master is not None:
            master.stop()
        sys.exit(1)

    signal.signal(signal.SIGINT, shutdown)
    signal.signal(signal.SIGTERM, shutdown)

    # watch loop with restart-on-failure (ref: controller.py:74 watch;
    # elastic manager restart semantics — a failed worker takes the whole
    # local pod down and the pod relaunches, so ranks restart consistent).
    # Restart only covers single-node jobs: relaunching one node's pod in
    # an nnodes>1 job would rejoin a coordinator whose session the other
    # nodes still hold — multi-node failures fail fast and the cluster
    # scheduler (or elastic manager) restarts the whole job.
    can_restart = args.nnodes == 1
    status = 0
    restarts = 0
    while True:
        done = [not c.alive() for c in containers]
        failed = [c for c in containers if c.returncode not in (None, 0)]
        if failed:
            rc = failed[0].returncode
            if can_restart and restarts < args.max_restart:
                restarts += 1
                print(f"[launch] worker failed (rc={rc}); restart "
                      f"{restarts}/{args.max_restart} — see "
                      f"{failed[0].log_path}", file=sys.stderr)
                for c in containers:
                    c.terminate()
                time.sleep(1)
                containers = _build_containers(args, nproc, world, master_ep)
                for c in containers:
                    c.start()
                continue
            reason = (f"after {args.max_restart} restarts; giving up"
                      if can_restart else
                      "multi-node job: failing fast (no local restart)")
            print(f"[launch] worker failed (rc={rc}) {reason} — see "
                  f"{failed[0].log_path}", file=sys.stderr)
            for c in containers:
                c.terminate()
            status = 1
            break
        if all(done):
            break
        time.sleep(1)
    if master is not None:
        master.stop()
    sys.exit(status)


if __name__ == "__main__":
    launch()
