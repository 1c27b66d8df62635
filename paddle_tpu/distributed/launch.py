"""Process launcher: ``python -m paddle_tpu.distributed.launch``.

Reference: python/paddle/distributed/launch.py → fleet/launch.py —
``launch_collective`` (launch.py:333) builds a Cluster/Pod, spawns one
process per device with PADDLE_* env vars (launch_utils.py), watches
children and aborts/restarts on failure; elastic mode re-execs with a new
world (fleet/elastic/manager.py:130).

TPU-native: one process per *host* (not per chip — XLA owns all local chips
in a single process), ``jax.distributed`` coordination service in place of
the TCP comm-id rendezvous, and the watch loop keeps the reference's
exit-code protocol (ELASTIC_EXIT_CODE=101 → relaunch with current peers).
On a single host with N chips the launcher runs ONE process: a chip belongs
to one process at a time, so a second process on the host would fail or hang
waiting for it, and device parallelism comes from the mesh.  nproc_per_node
above 1 is therefore refused except for the CPU simulation
(`--devices cpu --nproc_per_node N` sets
xla_force_host_platform_device_count).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

ELASTIC_EXIT_CODE = 101   # reference fleet/elastic: restart-me protocol
RESCALE_EXIT_CODE = 102   # restart with a recomputed world size


def _drain(procs, grace: float = 10.0):
    """Wait for SIGTERM'd children to exit; escalate to SIGKILL after the
    grace period so a relaunch never overlaps stale trainers."""
    deadline = time.time() + grace
    for p in procs.values():
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a distributed paddle_tpu training job")
    p.add_argument("--nnodes", type=str, default="1",
                   help="node count, or elastic range 'min:max'")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER", ""),
                   help="coordinator host:port (first node's address)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per node; above 1 only with --devices "
                        "cpu (on a TPU host one process drives all local "
                        "chips)")
    p.add_argument("--devices", type=str, default="",
                   help="'cpu' forces CPU simulation with "
                        "xla_force_host_platform_device_count=nproc_per_node")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="restarts allowed on ELASTIC_EXIT_CODE before giving up")
    p.add_argument("--elastic_level", type=int, default=0,
                   help="0: restart only on exit code 101/102; 1 "
                        "(fault-tolerant, ≙ reference manager.py:178): also "
                        "restart the pod when a trainer crashes abnormally")
    p.add_argument("--elastic_store", type=str,
                   default=os.environ.get("PADDLE_ELASTIC_STORE", ""),
                   help="ElasticManager store dir; enables RESCALE (102) "
                        "handling: world is recomputed from alive membership "
                        "on relaunch")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.nproc_per_node > 1 and args.devices != "cpu":
        p.error("--nproc_per_node above 1 needs --devices cpu: on a TPU "
                "host one process drives all local chips, and a second "
                "process would fail or hang waiting for them")
    return args


def _child_env(args, local_rank: int, world: int, nproc: int) -> dict:
    env = dict(os.environ)
    rank = args.node_rank * nproc + local_rank
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TRAINERS_NUM"] = str(world)
    if args.master:
        env["PADDLE_MASTER"] = args.master
    env["PADDLE_LOCAL_RANK"] = str(local_rank)
    env["FLAGS_selected_tpus"] = str(local_rank)
    if args.elastic_store:
        # children see the store target without re-plumbing it themselves
        env["PADDLE_ELASTIC_STORE"] = str(args.elastic_store)
    if args.devices == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        prev = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in prev:
            env["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count="
                                + str(max(nproc, 1))).strip()
    return env


def _rescaled_world(args, world: int, nproc: int):
    """Recompute (world, nproc) from alive elastic-store membership.

    ≙ fleet/elastic/manager.py: on RESCALE the new world is the set of hosts
    with fresh heartbeat leases.  Without a store we can only restart with
    the same world (and say so).
    """
    is_tcp = str(args.elastic_store or "").startswith("tcp://")
    if not args.elastic_store or (not is_tcp and
                                  not os.path.isdir(args.elastic_store)):
        print("[launch] RESCALE requested but no --elastic_store; "
              "relaunching with unchanged world", file=sys.stderr)
        return world, nproc
    from .fleet.elastic import read_alive_ranks
    ttl = float(os.environ.get("PADDLE_ELASTIC_TTL", "10"))
    alive = len(read_alive_ranks(args.elastic_store, ttl))
    lo, _, hi = str(args.nnodes).partition(":")
    np_min = int(lo) if lo else 1
    np_max = int(hi) if hi else np_min  # fixed --nnodes N means N is the cap
    single_node = np_max <= 1
    if args.devices == "cpu":
        # the nnodes range counts nodes; the simulated world counts processes
        np_min *= max(args.nproc_per_node, 1)
        np_max *= max(args.nproc_per_node, 1)
    new_world = max(np_min, min(alive or world, np_max))
    if args.devices == "cpu":
        if single_node:
            # children are the simulated "hosts", so nproc tracks the world
            return new_world, new_world
        print("[launch] multi-node CPU-sim rescale keeps nproc_per_node "
              "(per-node process counts cannot be re-split safely)",
              file=sys.stderr)
        return new_world, nproc
    return new_world, nproc


def _maybe_host_store(args):
    """Host the native TCP store in-process when this launcher is the store's
    home (≙ fleet/elastic/manager.py assuming an ambient etcd — here the
    framework carries its own): for ``--elastic_store tcp://host:port``, the
    node whose rank is 0 (or a loopback host) binds the port; peers dial it.
    Returns the StoreServer handle (kept alive for the launcher's lifetime)
    or None."""
    target = str(args.elastic_store or "")
    if not target.startswith("tcp://"):
        return None
    host, _, port = target[len("tcp://"):].rpartition(":")
    local = host in ("127.0.0.1", "localhost", "0.0.0.0", "")
    if not (local or args.node_rank == 0):
        return None
    from .store import StoreServer
    from ..csrc import load_library
    load_library("kv_store")  # outside the try: a missing / unbuildable
    # native library must surface as itself, not as a port error
    try:
        return StoreServer(port=int(port or 0))
    except OSError as e:
        # Bind failed.  Only "another launcher on this host already owns the
        # port" is benign — confirm by dialing it; any other failure
        # (permission, bad port) must surface, or the workers hang forever
        # dialing a store that never comes up.
        import socket as _socket
        try:
            with _socket.create_connection(
                    ("127.0.0.1", int(port or 0)), timeout=2.0):
                return None  # live listener: another launcher hosts the store
        except OSError:
            raise RuntimeError(
                f"--elastic_store {target}: could not bind the store port "
                f"and nothing is listening on it") from e


def launch(argv=None) -> int:
    args = _parse_args(argv)
    nnodes = int(str(args.nnodes).split(":")[0])
    nproc = args.nproc_per_node     # 1 off the CPU simulation (_parse_args)
    world = nnodes * nproc
    os.makedirs(args.log_dir, exist_ok=True)
    _store_server = _maybe_host_store(args)  # noqa: F841 (lifetime anchor)

    restarts = 0
    while True:
        procs = []
        for lr in range(nproc):
            log = open(os.path.join(args.log_dir, f"workerlog.{lr}"), "a")
            cmd = [sys.executable, args.training_script] + args.training_script_args
            procs.append((subprocess.Popen(
                cmd, env=_child_env(args, lr, world, nproc),
                stdout=log if lr > 0 else None,
                stderr=subprocess.STDOUT if lr > 0 else None), log))

        # watch loop (≙ launch_utils.py watch_local_trainers): abort the pod
        # if any child fails; honor the elastic restart/rescale exit codes
        exit_code, restart, rescale = 0, False, False
        crash_rc = 0  # real failure code behind a level-1 crash restart
        try:
            alive = {p.pid: p for p, _ in procs}
            while alive:
                for pid, p in list(alive.items()):
                    rc = p.poll()
                    if rc is None:
                        continue
                    del alive[pid]
                    if rc == ELASTIC_EXIT_CODE:
                        restart = True
                    elif rc == RESCALE_EXIT_CODE:
                        restart = rescale = True
                        # all peers must re-form the world: stop them cleanly
                        for q in alive.values():
                            q.send_signal(signal.SIGTERM)
                    elif rc != 0:
                        if args.elastic_level >= 1:
                            # fault-tolerant: a crashed trainer (incl. signal
                            # deaths, rc<0) restarts the pod like a 101
                            restart = True
                            crash_rc = rc
                        else:
                            exit_code = rc
                        for q in alive.values():
                            q.send_signal(signal.SIGTERM)
                        # reap the peers before relaunching: stale trainers
                        # hold the coordinator port / device claims and the
                        # log files of the next pod
                        _drain(alive)
                        alive = {}
                        break
                # tpulint: disable=unbounded-retry(child-process poll cadence, not a retry against a failing service — the outer restart loop is bounded by max_restarts and the sleep paces p.poll(), where backoff would only delay crash detection)
                time.sleep(0.5)
        finally:
            for _, log in procs:
                log.close()

        # once a restart/rescale is requested, peer crash codes don't veto it
        # (a 102-exiting trainer routinely breaks peers' live collectives)
        if restart:
            if restarts >= args.max_restarts:
                # a crash-looping job must not report success (ADVICE r1);
                # a level-1 crash loop reports the REAL failure code, not
                # "please restart me" (101 would loop outer supervisors)
                print("[launch] restart budget exhausted", file=sys.stderr)
                return crash_rc if crash_rc else ELASTIC_EXIT_CODE
            restarts += 1
            if rescale:
                world, nproc = _rescaled_world(args, world, nproc)
            print(f"[launch] elastic {'rescale' if rescale else 'restart'} "
                  f"{restarts}/{args.max_restarts} (world={world})",
                  file=sys.stderr)
            continue
        return exit_code


if __name__ == "__main__":
    sys.exit(launch())
