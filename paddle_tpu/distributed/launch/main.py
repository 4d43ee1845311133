"""Launcher CLI (reference launch/main.py:18 + controllers/collective.py).

TPU-native process model: one process per HOST (JAX single-controller),
not one per accelerator — a v5p-16 pod slice with 4 hosts is
`--nnodes 4`, each host process sees its 4 local chips and
`jax.distributed.initialize` federates them. The launcher:

- on a single node (`--nnodes 1`, the default) can still spawn N local
  processes with a virtual CPU mesh for testing multi-process rendezvous
  (`--nproc_per_node N --devices cpu`) — the reference's
  single-node-multi-proc dev loop. Without `--devices cpu` it refuses
  N > 1: a chip belongs to one process at a time, every local worker
  would see every local chip, and the second to reach them fails or
  hangs;
- exports the PADDLE_* env contract consumed by parallel/env.py
  (PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM, PADDLE_MASTER), mirroring the
  reference's env contract;
- elastic-lite: `--max_restart K` watches children and restarts the whole
  local pod up to K times when any worker exits nonzero (the reference
  ElasticManager's restart loop, minus etcd — the coordination service
  owns membership);
- liveness (reference fleet/elastic/manager.py:124): with
  `--hang_timeout S` each worker heartbeats a file through the boot shim
  and the controller restarts the pod when any worker's beat goes stale —
  hung workers (deadlock, wedged backend init), not just exited ones;
- scale-down continuation: `--min_procs M` lets the pod relaunch with
  one fewer worker (down to M) after restarts are exhausted — the
  reference's nnodes-1 "job proceeds after grace period" behavior, with
  the world size re-exported so rendezvous re-forms at the smaller size.

Usage:
  python -m paddle_tpu.distributed.launch --nnodes 2 --node_rank 0 \
      --master 10.0.0.1:12355 train.py --my-args ...
  python -m paddle_tpu.distributed.launch --nproc_per_node 2 \
      --devices cpu smoke.py
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

from .heartbeat import (ELASTIC_EXIT_CODE, ENV_WORLD, ENV_WORLD_FILE,
                        read_world_spec)


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="multi-host launcher (reference launch/main.py)")
    p.add_argument("--nnodes", type=int, default=int(
        os.environ.get("PADDLE_NNODES", "1")),
        help="number of hosts in the job")
    p.add_argument("--node_rank", type=int, default=int(
        os.environ.get("PADDLE_NODE_RANK", "0")),
        help="this host's rank [0, nnodes)")
    p.add_argument("--master", default=os.environ.get(
        "PADDLE_MASTER", "127.0.0.1:12355"),
        help="coordinator address host:port (rank-0 host)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="local worker processes (1 for TPU hosts; >1 only "
                        "for CPU-mesh testing)")
    p.add_argument("--devices", default=None,
                   help="'cpu' forces the CPU platform with a virtual "
                        "device count per proc (testing)")
    p.add_argument("--cpus_per_proc", type=int, default=1,
                   help="virtual CPU devices per process when "
                        "--devices cpu")
    p.add_argument("--max_restart", type=int, default=0,
                   help="elastic-lite: restart the local pod up to K "
                        "times on worker failure")
    p.add_argument("--hang_timeout", type=float, default=0.0,
                   help="liveness: treat a worker as failed when its "
                        "heartbeat file is older than this many seconds "
                        "(0 disables the watchdog)")
    p.add_argument("--heartbeat_interval", type=float, default=1.0,
                   help="worker heartbeat period when --hang_timeout is "
                        "set")
    p.add_argument("--step_heartbeat", action="store_true",
                   help="liveness tracks STEP progress: no background "
                        "beat thread; only the resilient step loop's "
                        "per-step pulse refreshes the lease, so a hung "
                        "dispatch goes stale after --hang_timeout even "
                        "while the process lives (size the timeout for "
                        "boot + compile + slowest step)")
    p.add_argument("--max_elastic_restart", type=int, default=16,
                   help="restarts granted to workers that exit with the "
                        "elastic protocol code "
                        f"({ELASTIC_EXIT_CODE}: 'restart me, I will "
                        "resume from my checkpoint') — budgeted "
                        "separately from --max_restart crash restarts")
    p.add_argument("--min_procs", type=int, default=0,
                   help="scale-down floor: after restarts are exhausted, "
                        "relaunch with one fewer local worker down to "
                        "this count (0 disables scale-down)")
    p.add_argument("--scale_grace", type=float, default=3.0,
                   help="grace period before a scaled-down relaunch")
    p.add_argument("--log_dir", default=None,
                   help="write per-worker logs under this dir")
    p.add_argument("--run_mode", default="collective",
                   help="collective (the only mode; ps is descoped)")
    p.add_argument("training_script", help="entry script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.nproc_per_node > 1 and args.devices != "cpu":
        p.error(
            f"--nproc_per_node {args.nproc_per_node} without --devices "
            "cpu: one process drives all of a host's chips (a chip "
            "belongs to one process at a time, and every local worker "
            "would open all of them). Use --nproc_per_node 1 on an "
            "accelerator host, or --devices cpu for the virtual-device "
            "dev loop.")
    return args


def _worker_env(args, local_rank: int) -> dict:
    """The PADDLE_* env contract (reference launch/controllers/collective.py
    builds the same block per worker)."""
    nprocs = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    env = dict(os.environ)
    host, port = args.master.rsplit(":", 1)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": args.master,
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(args.nnodes),
        "PADDLE_NODE_RANK": str(args.node_rank),
        # torch-style aliases (env.py accepts both)
        "RANK": str(rank),
        "WORLD_SIZE": str(nprocs),
        "MASTER_ADDR": host,
        "MASTER_PORT": port,
    })
    if args.devices == "cpu":
        from ...device import cpu_pin_env
        env = cpu_pin_env(args.cpus_per_proc, base_env=env)
        env["PADDLE_LAUNCH_CPU_DEVICES"] = str(args.cpus_per_proc)
    # degraded-world handshake (heartbeat.py): the worker writes its
    # wanted world spec here before an elastic exit; the launcher reads
    # it back in launch() and re-exports it to the restarted pod
    env.setdefault(ENV_WORLD_FILE,
                   os.path.join(_hb_dir(args), "elastic_world.json"))
    granted = getattr(args, "_elastic_world", None)
    if granted:
        env[ENV_WORLD] = granted
    # crash flight recorder (profiler/flight_recorder.py): every worker
    # gets a dump directory so a dead pod leaves a black box the operator
    # (and tools/chaos_drill.py) can read — an explicit
    # PADDLE_TPU_FLIGHT_DIR in the caller's env wins
    if "PADDLE_TPU_FLIGHT_DIR" not in env:
        env["PADDLE_TPU_FLIGHT_DIR"] = os.path.join(_hb_dir(args), "flight")
    return env


class _Worker:
    """One spawned worker + the liveness state the watchdog tracks."""

    def __init__(self, proc: subprocess.Popen, hb_path: Optional[str]):
        self.proc = proc
        self.hb_path = hb_path
        self.started = time.time()

    def stale_for(self) -> float:
        """Seconds since the last heartbeat (spawn time counts as the
        first beat, so slow boots are not misread as hangs)."""
        last = self.started
        if self.hb_path:
            try:
                last = max(last, os.stat(self.hb_path).st_mtime)
            except OSError:
                pass
        return time.time() - last


def _hb_dir(args) -> str:
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        return args.log_dir
    import tempfile
    d = getattr(args, "_hb_tmp", None)
    if d is None:
        d = tempfile.mkdtemp(prefix="paddle_launch_hb_")
        args._hb_tmp = d
    return d


def _spawn(args) -> List[_Worker]:
    workers = []
    for lr in range(args.nproc_per_node):
        out = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            out = open(os.path.join(
                args.log_dir,
                f"worker.{args.node_rank}.{lr}.log"), "ab")
        try:
            workers.append(_popen(args, lr, out))
        finally:
            if out is not None:
                out.close()          # the child inherited the fd
    return workers


def _popen(args, lr, out) -> _Worker:
    env = _worker_env(args, lr)
    hb_path = None
    if args.hang_timeout > 0:
        hb_path = os.path.join(
            _hb_dir(args), f"hb.{args.node_rank}.{lr}")
        try:                         # fresh lease per (re)spawn
            os.remove(hb_path)
        except OSError:
            pass
        env["PADDLE_HEARTBEAT_FILE"] = hb_path
        env["PADDLE_HEARTBEAT_INTERVAL"] = str(args.heartbeat_interval)
        if args.step_heartbeat:
            env["PADDLE_HEARTBEAT_STEP_MODE"] = "1"
    if args.devices == "cpu" or hb_path:
        # route through the bootstrap: the CPU pin must happen in-process
        # before jax initializes (device.pin_cpu) and the heartbeat
        # thread must start before the user script (see heartbeat.py)
        cmd = [sys.executable, "-m",
               "paddle_tpu.distributed.launch._boot",
               args.training_script, *args.training_script_args]
    else:
        cmd = [sys.executable, args.training_script,
               *args.training_script_args]
    proc = subprocess.Popen(
        cmd, env=env, stdout=out,
        stderr=subprocess.STDOUT if out else None)
    return _Worker(proc, hb_path)


def _terminate(workers: List[_Worker]):
    """SIGTERM then escalate to SIGKILL: a worker wedged in backend init
    can mask/ignore SIGTERM and would otherwise orphan, holding the
    coordinator port."""
    for w in workers:
        w.proc.send_signal(signal.SIGTERM)
    for w in workers:
        try:
            w.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            w.proc.kill()


# _wait's sentinel for "a worker stopped heartbeating": distinct from any
# real exit code so launch() can log the right reason
HUNG = -257


def _wait(workers: List[_Worker], hang_timeout: float = 0.0) \
        -> Optional[int]:
    """Wait for all workers; on first nonzero exit, kill the rest and
    return that code (the collective controller's fail-fast). With
    hang_timeout > 0 a worker whose heartbeat goes stale counts as failed
    (returns HUNG). Returns None on KeyboardInterrupt — distinct from any
    worker exit code."""
    try:
        while workers:
            for w in list(workers):
                rc = w.proc.poll()
                if rc is None:
                    if hang_timeout > 0 and w.stale_for() > hang_timeout:
                        print(f"[launch] worker pid={w.proc.pid} hung "
                              f"(no heartbeat for "
                              f"{w.stale_for():.1f}s); restarting pod",
                              file=sys.stderr, flush=True)
                        _terminate(workers)
                        return HUNG
                    continue
                workers.remove(w)
                if rc != 0:
                    _terminate(workers)
                    return rc
            time.sleep(0.2)
        return 0
    except KeyboardInterrupt:
        _terminate(workers)
        return None


def launch(argv: Optional[List[str]] = None) -> int:
    """Programmatic entry (returns the job's exit code)."""
    # controller-side observability: phase spans + restart counters
    # (import-light — profiler/monitor pulls in no jax); the worker-side
    # black box is env-wired in _worker_env
    from ...profiler import RecordEvent, monitor
    from ...profiler import flight_recorder
    mon_restart = monitor.counter("launch_pod_restart")
    mon_elastic = monitor.counter("launch_elastic_restart")
    mon_hung = monitor.counter("launch_hung_worker")
    mon_scale = monitor.counter("launch_scale_down")
    args = _parse_args(argv)
    attempt = 0
    elastic = 0
    while True:
        if attempt:
            # crash-budget restarts; rc=ELASTIC_EXIT_CODE restarts print
            # their own distinctly-worded line below
            print(f"[launch] pod restart {attempt}/{args.max_restart} "
                  f"(crash budget)", file=sys.stderr, flush=True)
        with RecordEvent("launch.spawn"):
            workers = _spawn(args)
        with RecordEvent("launch.wait"):
            rc = _wait(workers, args.hang_timeout)
        flight_recorder.note(phase="pod_exit", rc=rc, attempt=attempt,
                             elastic=elastic)
        if rc == HUNG:
            mon_hung.add()
        if rc == 0:
            return 0
        if rc is None:
            # launcher-level interrupt is not a worker failure — never
            # restart it (a worker's own exit 130 still restarts)
            return 130
        if rc == ELASTIC_EXIT_CODE and elastic < args.max_elastic_restart:
            # the worker ASKED for this restart (resilience watchdog: a
            # hung step it will recover from by resuming at the LATEST
            # snapshot) — reference ELASTIC_EXIT_CODE=101 protocol,
            # fleet/elastic/manager.py:30. Budgeted separately so hung
            # steps don't consume the crash-restart budget.
            elastic += 1
            mon_elastic.add()
            # degraded-world handshake: a worker that lost devices
            # leaves a world spec (heartbeat.write_world_spec) naming
            # the SURVIVING world; the restarted pod must not assume
            # the old one. The spec re-exports as $PADDLE_TPU_ELASTIC_
            # WORLD to every later spawn, and a cpu_devices entry
            # re-shapes the virtual CPU platform (the --devices cpu
            # simulation of a physically smaller slice).
            wpath = os.environ.get(ENV_WORLD_FILE) or os.path.join(
                _hb_dir(args), "elastic_world.json")
            spec = read_world_spec(wpath)
            if spec is not None:
                import json as _json
                args._elastic_world = _json.dumps(spec)
                try:            # consumed: one spec per elastic exit
                    os.remove(wpath)
                except OSError:
                    pass
                if args.devices == "cpu" and spec.get("cpu_devices"):
                    args.cpus_per_proc = int(spec["cpu_devices"])
                mon_degraded = monitor.counter("launch_degraded_world")
                mon_degraded.add()
                print(f"[launch] elastic restart carries a DEGRADED "
                      f"world spec: {spec}", file=sys.stderr, flush=True)
            print(f"[launch] worker requested elastic restart "
                  f"({elastic}/{args.max_elastic_restart}, "
                  f"rc={ELASTIC_EXIT_CODE})", file=sys.stderr, flush=True)
            continue
        if attempt >= args.max_restart:
            if (args.min_procs > 0
                    and args.nnodes == 1
                    and args.nproc_per_node - 1 >= args.min_procs):
                # single-node only: shrinking one host's proc count in a
                # multi-node job would desync WORLD_SIZE/rank bases across
                # hosts — true multi-node membership changes belong to the
                # coordination service (reference: etcd in
                # fleet/elastic/manager.py)
                # scale-down continuation (reference elastic manager's
                # "nnodes-1 proceeds after the grace window"): re-form
                # the pod one worker smaller; the env contract re-exports
                # the reduced world size so rendezvous matches
                args.nproc_per_node -= 1
                attempt = 0
                mon_scale.add()
                print(f"[launch] restarts exhausted (rc={rc}); scaling "
                      f"down to {args.nproc_per_node} workers after "
                      f"{args.scale_grace}s grace",
                      file=sys.stderr, flush=True)
                time.sleep(args.scale_grace)
                continue
            print(f"[launch] workers failed (rc={rc}); restarts exhausted",
                  file=sys.stderr, flush=True)
            # the job is dying: leave the CONTROLLER's black box (pod
            # exit history + restart counters) beside the workers' dumps
            flight_recorder.recorder().set_dir(
                os.environ.get("PADDLE_TPU_FLIGHT_DIR")
                or os.path.join(_hb_dir(args), "flight"))
            flight_recorder.dump("launch_failed")
            return 1 if rc == HUNG else rc
        attempt += 1
        mon_restart.add()


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
