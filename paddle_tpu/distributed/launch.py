"""Launcher — parity with `python -m paddle.distributed.launch`
(`fleet/launch.py:386`, `launch_utils.py` Cluster/Pod model,
start_local_trainers:464, watch_local_trainers:573).

TPU-native shape: JAX is single-controller per HOST (one process drives all
local chips), so "nproc per device" disappears. On a multi-host pod slice,
run this once per host with --nnodes/--node_rank/--master (or under a cluster
scheduler exporting PADDLE_* envs); it wires `jax.distributed.initialize`
over DCN and execs the training script in-process. Failure of any host
surfaces as a collective error; the elastic wrapper relaunches (exit-code
protocol kept from the reference: ELASTIC_EXIT_CODE=101,
`fleet/elastic/manager.py:26`).
"""
import argparse
import glob
import os
import runpy
import signal
import socket
import subprocess
import sys
import time

ELASTIC_EXIT_CODE = 101

# exit-code protocol (see README "Elastic mesh resilience"):
#   101 ELASTIC_EXIT_CODE   relaunch onto a NEW world (mesh changed;
#                           resume reshards via resilience.reshard)
#   102 RESUMABLE_EXIT_CODE graceful preemption exit, state committed —
#                           relaunch and auto-resume onto the SAME world
# Both relaunch paths are CAPPED (101 by --max_restarts, 102 by
# --max_resumes) and back off exponentially between attempts: an
# unbounded relaunch loop around a deterministic failure used to burn
# the fleet replaying the same crash forever.
_sleep = time.sleep       # module-level so tests can pin the schedule


def _restart_delay(restarts, base_s, cap_s=60.0):
    """Exponential backoff before relaunch #`restarts` (1-based)."""
    if base_s <= 0:
        return 0.0
    return min(float(cap_s), float(base_s) * (2.0 ** (restarts - 1)))


def _backoff(restarts, base_s):
    delay = _restart_delay(restarts, base_s)
    if delay > 0:
        _sleep(delay)
    return delay


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_TRAINERS_NUM", "1")))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER", ""))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="spawn N local processes (multi-host emulation on "
                        "the CPU; refused on a TPU host, where one "
                        "process drives all local chips)")
    p.add_argument("--devices", "--gpus", "--xpus", type=str, default="",
                   help="accepted for CLI parity; chip selection is "
                        "topology-driven on TPU")
    p.add_argument("--elastic_level", type=int, default=int(
        os.environ.get("PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL", "0")))
    p.add_argument("--max_restarts", type=int, default=3,
                   help="cap on ELASTIC_EXIT_CODE(101) relaunches")
    p.add_argument("--max_resumes", type=int, default=32,
                   help="cap on RESUMABLE_EXIT_CODE(102) resume "
                        "relaunches (each one made checkpointed "
                        "progress, so the cap is generous)")
    p.add_argument("--restart_backoff", type=float, default=0.5,
                   help="base seconds of the exponential relaunch "
                        "backoff (doubles per consecutive restart, "
                        "capped at 60s; 0 disables)")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tpu_host(env):
    """True when children started with `env` would each claim this
    host's TPU chips. Judged from the environment and the device nodes,
    never by initialising a backend here: a launcher that has touched
    JAX holds the chips, and its children then fail or hang."""
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def start_local_trainers(nproc, script, script_args, master=None,
                         base_env=None):
    """Spawn one training process per local rank (reference
    `launch_utils.py:464` start_local_trainers). Every child gets the
    same environment, which on a TPU host would make each of them claim
    all local chips — so more than one local process is refused there
    (`distributed.spawn` takes the same line)."""
    if nproc > 1 and _tpu_host(os.environ if base_env is None
                               else base_env):
        raise RuntimeError(
            f"--nproc_per_node {nproc} on a TPU host: one process "
            "drives all local chips (a chip belongs to one process at "
            "a time, and every local child would claim all of them). "
            "Run one process per host, or set JAX_PLATFORMS=cpu for "
            "multi-process emulation on the CPU.")
    master = master or f"127.0.0.1:{_free_port()}"
    procs = []
    for rank in range(nproc):
        env = dict(os.environ if base_env is None else base_env)
        env.update({
            "PADDLE_TRAINERS_NUM": str(nproc),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_MASTER": master,
            "PADDLE_TRAINER_ENDPOINTS": master,
        })
        procs.append(subprocess.Popen(
            [sys.executable, script] + list(script_args), env=env))
    return procs


def watch_local_trainers(procs, poll_interval=0.5):
    """Wait for all trainers; on any failure terminate the pod and return
    that exit code (reference `launch_utils.py:573`)."""
    try:
        while True:
            codes = [p.poll() for p in procs]
            for c in codes:
                if c not in (None, 0):
                    for p in procs:
                        if p.poll() is None:
                            p.send_signal(signal.SIGTERM)
                    for p in procs:
                        try:
                            p.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            p.kill()
                    return c
            if all(c == 0 for c in codes):
                return 0
            time.sleep(poll_interval)
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        raise


def _relaunch_decision(rc, args, restarts, resumes):
    """Shared relaunch policy for both launcher paths. Returns
    (relaunch?, restarts, resumes); a granted relaunch has already
    slept its backoff."""
    from ..resilience.preempt import RESUMABLE_EXIT_CODE
    if rc == ELASTIC_EXIT_CODE and args.elastic_level > 0 and \
            restarts < args.max_restarts:
        restarts += 1
        _backoff(restarts, args.restart_backoff)
        return True, restarts, resumes
    if rc == RESUMABLE_EXIT_CODE and resumes < args.max_resumes:
        # a graceful preemption exit: state is committed, the relaunch
        # auto-resumes — separate (generous) cap because every resume
        # made real progress, unlike a crash loop
        resumes += 1
        _backoff(resumes, args.restart_backoff)
        return True, restarts, resumes
    return False, restarts, resumes


def launch(argv=None):
    args = _parse_args(argv)
    if args.nproc_per_node > 1:
        restarts = resumes = 0
        while True:
            procs = start_local_trainers(args.nproc_per_node,
                                         args.training_script,
                                         args.training_script_args,
                                         master=args.master or None)
            rc = watch_local_trainers(procs)
            again, restarts, resumes = _relaunch_decision(
                rc, args, restarts, resumes)
            if again:
                continue
            return rc
    os.environ["PADDLE_TRAINERS_NUM"] = str(args.nnodes)
    os.environ["PADDLE_TRAINER_ID"] = str(args.node_rank)
    if args.master:
        os.environ["PADDLE_MASTER"] = args.master
        os.environ.setdefault("PADDLE_TRAINER_ENDPOINTS", args.master)
    if args.nnodes > 1:
        import jax
        jax.distributed.initialize(
            coordinator_address=args.master or None,
            num_processes=args.nnodes, process_id=args.node_rank)

    sys.argv = [args.training_script] + args.training_script_args
    restarts = resumes = 0
    while True:
        try:
            runpy.run_path(args.training_script, run_name="__main__")
            return 0
        except SystemExit as e:
            if e.code in (0, None):
                return 0
            again, restarts, resumes = _relaunch_decision(
                e.code, args, restarts, resumes)
            if again:
                continue
            raise


if __name__ == "__main__":
    sys.exit(launch())
