#!/usr/bin/env python3
"""launch — start a distributed training job.

TPU-native equivalent of the reference cluster launcher
(``tools/launch.py`` + dmlc-tracker in the reference tree).  The
reference spawned scheduler/server/worker processes for the ps-lite
parameter server; here every process is an SPMD worker — the
"scheduler" role collapses into jax.distributed's coordinator, which
is simply process 0.  The launcher's job is therefore: start N copies
of the command with the right environment:

  DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT  -> coordinator address
  DMLC_WORKER_ID / DMLC_NUM_WORKER      -> process_id / num_processes
  DMLC_ROLE=worker

(the same env names the reference's tracker exported, so reference
training scripts and our ``mxnet_tpu.parallel.init_distributed`` both
understand them).

Launchers:
  local : spawn all N workers on this host — a CPU simulation of a
          multi-process job (the pattern the reference used for
          nightly dist tests).  A chip belongs to one process at a
          time and the N workers get identical environments, none
          pinned to a chip of its own, so local workers run with
          JAX_PLATFORMS=cpu unless --env/--env-worker names
          JAX_PLATFORMS explicitly
  ssh   : one worker per host from --hostfile
  mpi   : delegate process placement to mpirun
"""
from __future__ import annotations

import argparse
import logging
import os
import signal
import subprocess
import sys


def worker_env(args, worker_id):
    env = dict(os.environ)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": args.root_uri,
        "DMLC_PS_ROOT_PORT": str(args.root_port),
        "DMLC_WORKER_ID": str(worker_id),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    })
    for pair in args.env_worker + args.env:
        if ":" in pair:
            k, v = pair.split(":", 1)
            env[k] = v
    return env


def submit_local(args):
    import time
    procs = []
    pinned = any(pair.split(":", 1)[0] == "JAX_PLATFORMS"
                 for pair in args.env_worker + args.env)
    for wid in range(args.num_workers):
        logging.info("starting local worker %d", wid)
        env = worker_env(args, wid)
        if not pinned:
            env["JAX_PLATFORMS"] = "cpu"    # module docstring: local
        procs.append(subprocess.Popen(args.command, env=env))
    # poll rather than wait sequentially: when any worker fails, kill the
    # survivors (they may be blocked in coordinator init waiting for it)
    rc = 0
    live = list(procs)
    while live:
        time.sleep(0.2)
        for p in list(live):
            code = p.poll()
            if code is None:
                continue
            live.remove(p)
            if code:
                rc = code
                logging.error("worker exited with %d; stopping job", code)
                for q in live:
                    q.kill()
                live = []
                break
    return rc


def submit_ssh(args):
    if not args.hostfile:
        raise SystemExit("ssh launcher requires --hostfile")
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < args.num_workers:
        raise SystemExit("hostfile has %d hosts, need %d"
                         % (len(hosts), args.num_workers))
    import shlex
    procs = []
    cwd = os.getcwd()
    for wid in range(args.num_workers):
        env = worker_env(args, wid)
        exports = " ".join("export %s=%s;" % (k, shlex.quote(env[k]))
                           for k in ("DMLC_ROLE", "DMLC_PS_ROOT_URI",
                                     "DMLC_PS_ROOT_PORT", "DMLC_WORKER_ID",
                                     "DMLC_NUM_WORKER", "DMLC_NUM_SERVER"))
        remote = "%s cd %s; %s" % (exports, shlex.quote(cwd),
                                   " ".join(shlex.quote(c)
                                            for c in args.command))
        logging.info("ssh %s: worker %d", hosts[wid], wid)
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no",
                                       hosts[wid], remote]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def submit_mpi(args):
    cmd = ["mpirun", "-n", str(args.num_workers)]
    if args.hostfile:
        cmd += ["--hostfile", args.hostfile]
    for k in ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT", "DMLC_NUM_WORKER",
              "DMLC_NUM_SERVER"):
        cmd += ["-x", k]
    os.environ.update({
        "DMLC_PS_ROOT_URI": args.root_uri,
        "DMLC_PS_ROOT_PORT": str(args.root_port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    })
    # under mpi the worker id comes from the MPI rank; our bootstrap reads
    # OMPI_COMM_WORLD_RANK / PMI_RANK when DMLC_WORKER_ID is absent
    cmd += args.command
    return subprocess.call(cmd)


def main():
    p = argparse.ArgumentParser(description="Launch a distributed job")
    p.add_argument("-n", "--num-workers", required=True, type=int)
    p.add_argument("-s", "--num-servers", type=int, default=None,
                   help="accepted for reference CLI compatibility; the "
                        "collective backend has no server processes")
    p.add_argument("-H", "--hostfile", type=str, default=None)
    p.add_argument("--launcher", type=str, default="local",
                   choices=["local", "ssh", "mpi"])
    p.add_argument("--root-uri", type=str, default="127.0.0.1",
                   help="coordinator (process 0) address")
    p.add_argument("--root-port", type=int, default=9111)
    p.add_argument("--env-worker", action="append", default=[],
                   help="KEY:VALUE set on worker processes")
    p.add_argument("--env-server", action="append", default=[],
                   help="accepted for compatibility; unused")
    p.add_argument("--env", action="append", default=[],
                   help="KEY:VALUE set on all processes")
    p.add_argument("--sync-dst-dir", type=str, default=None,
                   help="accepted for compatibility; unused")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        raise SystemExit("no command given")
    if args.num_servers is None:
        args.num_servers = args.num_workers

    submit = {"local": submit_local, "ssh": submit_ssh,
              "mpi": submit_mpi}[args.launcher]
    sys.exit(submit(args))


def _sigint(signum, frame):
    logging.info("stopping launcher")
    sys.exit(0)


if __name__ == "__main__":
    logging.basicConfig(format="%(asctime)s %(levelname)s %(message)s",
                        level=logging.INFO)
    signal.signal(signal.SIGINT, _sigint)
    main()
