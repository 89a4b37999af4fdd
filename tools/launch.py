#!/usr/bin/env python
"""Multi-process job launcher.

TPU-native analog of the reference's cluster launcher (ref: tools/launch.py
→ dmlc-core tracker): where the reference's tracker spawns
scheduler + servers + workers and wires them with DMLC_ROLE /
DMLC_PS_ROOT_URI / DMLC_NUM_WORKER / DMLC_NUM_SERVER env vars, this spawns
N worker processes wired to one jax.distributed coordinator (process 0's
host:port) with MXTPU_COORDINATOR / MXTPU_NUM_PROCS / MXTPU_PROC_ID.
There is no separate server role: parameter aggregation is XLA collectives
over ICI/DCN (Gloo on CPU), so every process is a worker
(SURVEY.md §5 "distributed communication backend").

Usage (mirrors the reference CLI):
    python tools/launch.py -n 4 python train_script.py --args...
    python tools/launch.py -n 4 --launcher local python train.py

`--launcher ssh -H hostfile` distributes over hosts via ssh, one process
per host line (the reference's ssh launcher analog); `local` (default)
runs all processes on this machine — the CPU simulation of a cluster
that the dist tests use, like the reference's `--launcher local` used
by tests/nightly/test_all.sh. It needs `JAX_PLATFORMS=cpu` for more
than one worker: the chips of one TPU host are one process.
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _ps_secret():
    """One shared secret for the whole job, generated by the launcher so
    every rank (local or remote) can authenticate the async-PS
    set_optimizer payload (kvstore_async.py HMAC)."""
    import secrets
    return os.environ.get("MXTPU_PS_SECRET") or secrets.token_hex(32)


def force_virtual_cpu_devices(n):
    """Point this process's JAX at n virtual CPU devices — the explicit
    choice of the CPU simulation (tests, the CPU dryrun, the CPU
    contract gates). Call it before the first JAX operation:
    ``jax_num_cpu_devices`` can only be set while no backend exists. A
    process whose CPU backend is already up with at least n devices (the
    test session, whose conftest sized it) is accepted as it is; any
    other initialized backend raises. In multi-process workers this must
    run BEFORE jax.distributed.initialize.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        devs = jax.devices()
        if devs[0].platform != "cpu" or len(devs) < n:
            raise


def launch_local(n, command, env_extra=None):
    """Spawn n local worker processes; returns the list of exit codes.

    Local mode is the CPU simulation of a cluster. On one TPU host
    several chips belong to ONE process (``jax.devices()``), and a chip
    belongs to one process at a time: n local workers left to pick their
    own backend would each try to take every chip, and all but the
    first would fail or hang. So more than one local worker is refused
    unless the workers' environment pins ``JAX_PLATFORMS=cpu``. The
    launcher itself never touches JAX."""
    if n > 1 and dict(os.environ, **(env_extra or {})).get(
            "JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "launch_local: %d workers on one host need JAX_PLATFORMS=cpu "
            "in their environment (local mode is the CPU simulation); on "
            "a TPU host all chips are one process, and across hosts use "
            "--launcher ssh with one process per host" % n)
    port = _free_port()
    coordinator = "127.0.0.1:%d" % port
    secret = _ps_secret()
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update(env_extra or {})
        env.update({
            "MXTPU_PS_SECRET": secret,
            "MXTPU_COORDINATOR": coordinator,
            "MXTPU_NUM_PROCS": str(n),
            "MXTPU_PROC_ID": str(rank),
            # DMLC-compatible aliases so reference-era scripts that read
            # these still see a consistent world
            "DMLC_NUM_WORKER": str(n),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
        })
        procs.append(subprocess.Popen(command, env=env))
    codes = []
    try:
        for p in procs:
            codes.append(p.wait())
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        raise
    return codes


def elastic_ok(codes, min_workers=1):
    """Success predicate for an elastic job (ISSUE 7): worker deaths are
    EXPECTED (preemption, chaos-test SIGKILL), so the job succeeds when
    rank 0 — the coordinator/server host, the rank the elastic stack
    does not preempt — exits 0 and at least ``min_workers`` ranks exit
    0. A non-elastic job requires every rank to exit 0."""
    clean = sum(1 for c in codes if c == 0)
    return codes and codes[0] == 0 and clean >= max(1, int(min_workers))


def launch_ssh(n, hosts, command):
    """One process per host over ssh (ref: dmlc-core ssh tracker)."""
    assert len(hosts) >= 1, "ssh launcher needs a non-empty hostfile"
    coordinator = "%s:%d" % (hosts[0], 29400)
    secret = _ps_secret()
    procs = []
    for rank in range(n):
        host = hosts[rank % len(hosts)]
        envs = " ".join("%s=%s" % kv for kv in [
            ("MXTPU_COORDINATOR", coordinator),
            ("MXTPU_NUM_PROCS", str(n)),
            ("MXTPU_PROC_ID", str(rank)),
            ("MXTPU_PS_SECRET", secret),
        ])
        remote = "cd %s && env %s %s" % (
            shlex.quote(os.getcwd()), envs,
            " ".join(shlex.quote(c) for c in command))
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no", host,
                                       remote]))
    return [p.wait() for p in procs]


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job "
                    "(ref: tools/launch.py)")
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of worker processes "
                             "(ref: launch.py -n num_workers)")
    parser.add_argument("--launcher", choices=["local", "ssh"],
                        default="local")
    parser.add_argument("-H", "--hostfile", default=None,
                        help="hostfile for the ssh launcher")
    parser.add_argument("--elastic", action="store_true",
                        help="tolerate worker deaths: the job succeeds "
                             "when rank 0 and at least --min-workers "
                             "ranks exit 0 (preemption/chaos runs; the "
                             "survivors reshard via parallel.elastic)")
    parser.add_argument("--min-workers", type=int, default=1,
                        help="with --elastic: minimum clean exits for "
                             "job success (default 1)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the worker command to run")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    if args.launcher == "local":
        codes = launch_local(args.num_workers, args.command)
    else:
        if not args.hostfile:
            parser.error("the ssh launcher requires -H/--hostfile")
        with open(args.hostfile) as f:
            hosts = [ln.strip() for ln in f if ln.strip()]
        codes = launch_ssh(args.num_workers, hosts, args.command)
    if args.elastic:
        if not elastic_ok(codes, args.min_workers):
            print("elastic launch failed: exit codes %s (need rank 0 "
                  "clean and >= %d clean ranks)"
                  % (codes, args.min_workers), file=sys.stderr)
            return 1
        dead = [r for r, c in enumerate(codes) if c != 0]
        if dead:
            print("elastic launch: ranks %s died (codes %s); survivors "
                  "completed" % (dead, codes), file=sys.stderr)
        return 0
    bad = [c for c in codes if c != 0]
    if bad:
        print("launch failed: exit codes %s" % codes, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
