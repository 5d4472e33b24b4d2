#!/usr/bin/env python
"""Cluster/process launcher (reference: tools/launch.py over the
dmlc-core trackers — ssh/mpi/local).

TPU-native shape: for `dist_sync` there are no parameter-server
processes — workers form a jax.distributed process group (DCN
collectives), so `-n N` launches N worker processes with the same DMLC_*
env contract the reference sets (DMLC_ROLE/DMLC_WORKER_ID/
DMLC_NUM_WORKER/DMLC_PS_ROOT_*), which DistKVStore reads
(mxnet_tpu/kvstore/kvstore.py).  For `dist_async`, `-s N` additionally
spawns N host-side PS server processes (mxnet_tpu/kvstore_server.py);
their ports are handed to workers via MXTPU_PS_PORTS.  Only the local
launcher is implemented; ssh/mpi cluster modes are host-scheduling
concerns outside this container.

On a TPU host a chip belongs to one process, so worker `r` is bound to
chip `r` (libtpu's TPU_VISIBLE_CHIPS / TPU_PROCESS_* variables) and the
N one-chip processes join one jax.distributed group; `-n` must then
equal the host's chip count.  Under JAX_PLATFORMS=cpu nothing is bound.

Supervisor mode (`MXNET_TPU_SUPERVISE=N`): while workers are still
running, a parameter-server process that exits NONZERO (crash, fault
drill, signal) is relaunched on the same port, up to N times per
server — exit 0 is the clean stop-command path and is left alone (a
worker's final `stop` racing the supervisor poll must not burn a
restart on a finished job).  The revived
server self-restores its store from its durable shard checkpoint
(`MXNET_TPU_PS_CKPT`, docs/CHECKPOINTING.md "Server-side durability") —
when supervision is requested without a checkpoint dir, one is
defaulted (with a per-mutation interval) so revival actually recovers
state.  `MXNET_TPU_FAULT` is stripped from a relaunched server's env:
the injected fault already simulated the crash it was scripted for, and
re-arming it would just crash-loop the drill to the restart bound.

The supervisor also honors WORKER relaunch requests: the observability
autopilot's kv-straggler reflex parks `restart_rank` commands on PS
shard 0 (mxnet_tpu/kvstore/ps.py reserved heads); the loop polls the
shard's `restart_poll` head (~1 s cadence, raw sockets — the launcher
never imports mxnet_tpu, so it stays jax-free) and relaunches the named
worker with its original env, bounded by the same per-process restart
budget.  The relaunched worker resumes through the normal
`checkpoint.auto_resume` path.
"""

import argparse
import glob
import json
import os
import pickle
import shutil
import socket
import struct
import subprocess
import sys
import tempfile
import time


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# libtpu's process grid over the chips of one host, by chip count (v5e
# hosts hold 1, 4 (2x2) or 8 (2x4) chips)
_TPU_PROCESS_BOUNDS = {4: "2,2,1", 8: "2,4,1"}


def local_tpu_chips():
    """Chips on this host, counted from the device files (a v5e host
    shows one /dev/vfio/<n> per chip): the launcher never imports jax,
    so it cannot take a chip its workers need."""
    return len(glob.glob("/dev/vfio/[0-9]*"))


def tpu_worker_env(rank, ports):
    """libtpu environment that gives worker ``rank`` chip ``rank`` alone
    while keeping all ``len(ports)`` one-chip processes in one slice
    (so collectives between them ride ICI)."""
    return {
        "TPU_VISIBLE_CHIPS": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _TPU_PROCESS_BOUNDS[len(ports)],
        "TPU_PROCESS_ADDRESSES": ",".join(
            "localhost:%d" % p for p in ports),
        "TPU_PROCESS_PORT": str(ports[rank]),
        "CLOUD_TPU_TASK_ID": str(rank),
    }


def _poll_restart_requests(port, timeout=1.0):
    """Drain parked worker-relaunch requests from the PS shard on
    ``port`` (the ``restart_poll`` reserved head) and return them as a
    list of ``{"rank", "reason", "t"}`` dicts.  The wire format mirrors
    mxnet_tpu/kvstore/ps.py's length-prefixed pickle (reimplemented
    inline: the launcher must stay importable without jax); ANY failure
    — server busy, mid-restart, protocol surprise — returns [] and the
    next poll tries again."""
    try:
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=timeout) as s:
            payload = pickle.dumps(("command", "restart_poll", ""),
                                   protocol=pickle.HIGHEST_PROTOCOL)
            s.sendall(struct.pack(">Q", len(payload)) + payload)
            head = b""
            while len(head) < 8:
                chunk = s.recv(8 - len(head))
                if not chunk:
                    return []
                head += chunk
            (n,) = struct.unpack(">Q", head)
            buf = b""
            while len(buf) < n:
                chunk = s.recv(min(1 << 16, n - len(buf)))
                if not chunk:
                    return []
                buf += chunk
        reply = pickle.loads(buf)
        if not (isinstance(reply, tuple) and len(reply) == 2
                and reply[0] == "ok"):
            return []
        reqs = json.loads(reply[1] or "[]")
        return [r for r in reqs if isinstance(r, dict)
                and isinstance(r.get("rank"), int)]
    except (OSError, ValueError, pickle.PickleError, EOFError):
        return []


# observability env vars whose value is a FILE PATH: every spawned
# process gets its own rank-suffixed copy, so a distributed run is
# traceable end-to-end without manual env plumbing (per-rank trace /
# diag-dump / flight-dump / metrics-JSONL files merge later via
# `tools/diagnose.py --cluster` / `--merge-traces` / `--timeline`)
_PATH_ENVS = ("MXNET_TPU_PROFILE", "MXNET_TPU_DIAG",
              "MXNET_TPU_HEALTH_DUMP", "MXNET_TPU_METRICS")


def rank_suffix_observability(env, role, rank):
    """Rewrite the path-valued observability vars in ``env`` to
    ``<base>.<role><rank><ext>`` (flag-valued vars like
    MXNET_TPU_HEALTH=1 are inherited untouched)."""
    for var in _PATH_ENVS:
        val = env.get(var)
        if val:
            base, ext = os.path.splitext(val)
            env[var] = "%s.%s%d%s" % (base, role, rank, ext)
    return env


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed job locally",
        usage="launch.py -n 4 python train.py ...")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="parameter-server processes to spawn "
                             "(dist_async; dist_sync needs none)")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local"])
    parser.add_argument("--sync-dst-dir", type=str, default=None)
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    tpu_ports = None
    chips = 0 if os.environ.get("JAX_PLATFORMS") == "cpu" \
        else local_tpu_chips()
    if chips and args.num_workers > 1:
        if args.num_workers != chips or chips not in _TPU_PROCESS_BOUNDS:
            parser.error(
                "this host has %d TPU chip(s) and a chip belongs to one "
                "process: -n must be %s (one worker per chip), or set "
                "JAX_PLATFORMS=cpu to run the workers on the CPU"
                % (chips, chips if chips in _TPU_PROCESS_BOUNDS else 1))
        tpu_ports = [free_port() for _ in range(chips)]

    port = free_port()
    default_ckpt_dir = None
    common = {
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
    }
    try:
        supervise = int(os.environ.get("MXNET_TPU_SUPERVISE", "0") or 0)
    except ValueError:
        supervise = 0

    def server_env(sid, fault=True):
        env = dict(os.environ)
        env.update(common)
        env.update({"DMLC_ROLE": "server",
                    "MXTPU_PS_SERVER_ID": str(sid),
                    # the PS is numpy/host-side; keep jax off any
                    # accelerator the workers may be using
                    "JAX_PLATFORMS": "cpu"})
        if not fault:
            env.pop("MXNET_TPU_FAULT", None)
        rank_suffix_observability(env, "server", sid)
        return env

    def spawn_server(sid, fault=True):
        return subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.kvstore_server"],
            env=server_env(sid, fault=fault))

    server_procs = []
    if args.num_servers > 0:
        ports = [free_port() for _ in range(args.num_servers)]
        common["MXTPU_PS_PORTS"] = ",".join(str(p) for p in ports)
        if supervise > 0 and not os.environ.get("MXNET_TPU_PS_CKPT"):
            # a revived server can only self-restore if its shard is
            # durable: default a checkpoint dir (per-mutation interval,
            # so no acknowledged mutation can be lost across a restart)
            default_ckpt_dir = tempfile.mkdtemp(prefix="mxtpu-ps-ckpt-")
            common["MXNET_TPU_PS_CKPT"] = default_ckpt_dir
            common.setdefault("MXNET_TPU_PS_CKPT_INTERVAL",
                              os.environ.get("MXNET_TPU_PS_CKPT_INTERVAL",
                                             "1"))
            print("launch.py: MXNET_TPU_SUPERVISE without "
                  "MXNET_TPU_PS_CKPT — defaulting server durability to "
                  "%s (interval %s)"
                  % (common["MXNET_TPU_PS_CKPT"],
                     common["MXNET_TPU_PS_CKPT_INTERVAL"]), flush=True)
        for sid in range(args.num_servers):
            server_procs.append(spawn_server(sid))

    def spawn_worker(rank):
        env = dict(os.environ)
        env.update(common)
        env.update({"DMLC_ROLE": "worker", "DMLC_WORKER_ID": str(rank)})
        if tpu_ports:
            env.update(tpu_worker_env(rank, tpu_ports))
        rank_suffix_observability(env, "worker", rank)
        return subprocess.Popen(args.command, env=env)

    procs = [spawn_worker(rank) for rank in range(args.num_workers)]
    rc = 0
    if supervise > 0 and server_procs:
        # supervisor loop: while any worker is still running, relaunch
        # dead server processes (bounded restarts per server); the
        # revived server self-restores from its durable checkpoint.
        # Worker relaunches are REQUEST-driven: the autopilot's
        # straggler reflex parks restart_rank on shard 0, polled here.
        restarts = [0] * len(server_procs)
        w_restarts = [0] * len(procs)
        last_poll = 0.0
        while any(p.poll() is None for p in procs):
            for sid, sp in enumerate(server_procs):
                code = sp.poll()
                # code 0 = the clean stop-command exit: not a failure
                # (and possibly racing the workers' own shutdown)
                if code is None or code == 0 or \
                        restarts[sid] >= supervise:
                    continue
                restarts[sid] += 1
                print("launch.py supervisor: server %d exited rc=%s — "
                      "restart %d/%d" % (sid, code, restarts[sid],
                                         supervise), flush=True)
                server_procs[sid] = spawn_server(sid, fault=False)
            now = time.monotonic()
            if now - last_poll >= 1.0:
                last_poll = now
                for req in _poll_restart_requests(ports[0]):
                    rank = req["rank"]
                    if not 0 <= rank < len(procs):
                        print("launch.py supervisor: restart_rank %r "
                              "out of range — ignored" % (rank,),
                              flush=True)
                        continue
                    if w_restarts[rank] >= supervise:
                        print("launch.py supervisor: worker %d restart "
                              "budget (%d) exhausted — request ignored"
                              % (rank, supervise), flush=True)
                        continue
                    w_restarts[rank] += 1
                    print("launch.py supervisor: restart_rank worker "
                          "%d (%s) — restart %d/%d"
                          % (rank, req.get("reason") or "no reason",
                             w_restarts[rank], supervise), flush=True)
                    wp = procs[rank]
                    if wp.poll() is None:
                        wp.terminate()
                        try:
                            wp.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            wp.kill()
                            wp.wait()
                    procs[rank] = spawn_worker(rank)
            time.sleep(0.2)
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    for p in server_procs:
        # servers exit on the workers' stop command; reap stragglers so
        # no zombies outlive the launcher
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.terminate()
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        else:
            # a server that failed on its own (port bind, bad optimizer)
            # is the real fault even when workers also errored
            if p.returncode > 0:
                rc = rc or p.returncode
    if default_ckpt_dir is not None:
        if rc == 0:
            # we created it, the job finished cleanly: per-mutation
            # full-store snapshots must not pile up in /tmp
            shutil.rmtree(default_ckpt_dir, ignore_errors=True)
        else:
            # the shards' durable state IS the resume point — keep it
            print("launch.py: job failed (rc=%d); server checkpoints "
                  "kept at %s (MXNET_TPU_PS_CKPT)" % (rc,
                                                      default_ckpt_dir),
                  flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
