"""Launcher + elastic tests (reference pattern: subprocess pods on one
host, `test_dist_base.py:734`; elastic membership, `test_fleet_elastic_*`)."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.distributed.launch import (start_local_trainers,
                                           watch_local_trainers,
                                           ELASTIC_EXIT_CODE)
from paddle_tpu.distributed.elastic import (ElasticManager, ElasticStatus,
                                            elastic_run)


def test_local_pod_spawn_and_watch(tmp_path):
    """2-process pod: each rank writes its env contract; watcher reaps 0."""
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys\n"
        "rank = os.environ['PADDLE_TRAINER_ID']\n"
        "n = os.environ['PADDLE_TRAINERS_NUM']\n"
        "master = os.environ['PADDLE_MASTER']\n"
        f"open(r'{tmp_path}' + f'/out-{{rank}}.txt', 'w')"
        ".write(f'{rank}/{n}@{master}')\n")
    procs = start_local_trainers(2, str(script), [])
    assert watch_local_trainers(procs) == 0
    outs = sorted(p.name for p in tmp_path.glob("out-*.txt"))
    assert outs == ["out-0.txt", "out-1.txt"]
    body = (tmp_path / "out-1.txt").read_text()
    assert body.startswith("1/2@127.0.0.1:")


def test_watch_kills_pod_on_failure(tmp_path):
    """Rank 1 fails fast; rank 0 sleeps long — the watcher must terminate
    it and report the failure code."""
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['PADDLE_TRAINER_ID'] == '1':\n"
        "    sys.exit(7)\n"
        "time.sleep(60)\n")
    t0 = time.time()
    procs = start_local_trainers(2, str(script), [])
    code = watch_local_trainers(procs)
    assert code == 7
    assert time.time() - t0 < 30  # did not wait for the sleeper


def test_elastic_membership_and_levels(tmp_path):
    reg = str(tmp_path / "reg")
    m0 = ElasticManager(reg, np=2, host_id="0", timeout=2.0,
                        fault_tolerance_level=1).register()
    m1 = ElasticManager(reg, np=2, host_id="1", timeout=2.0,
                        fault_tolerance_level=1).register()
    assert m0.alive_hosts() == ["0", "1"]
    assert m0.check() == ElasticStatus.HOLD
    # host 1 disappears
    m1.deregister()
    assert m0.check() == ElasticStatus.RESTART  # level 1: relaunch
    m0.level = 0
    assert m0.check() == ElasticStatus.EXIT     # level 0: fail the job


def test_elastic_exit_code_protocol(tmp_path):
    with pytest.raises(SystemExit) as e:
        elastic_run(lambda: (_ for _ in ()).throw(RuntimeError("ici down")))
    assert e.value.code == ELASTIC_EXIT_CODE


def test_launch_relaunches_on_elastic_exit(tmp_path):
    """launch() retries scripts exiting with ELASTIC_EXIT_CODE."""
    from paddle_tpu.distributed.launch import launch
    marker = tmp_path / "attempts.txt"
    script = tmp_path / "train.py"
    script.write_text(
        "import sys\n"
        f"p = r'{marker}'\n"
        "n = int(open(p).read()) if __import__('os').path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        f"sys.exit({ELASTIC_EXIT_CODE} if n < 2 else 0)\n")
    rc = launch(["--elastic_level", "1", "--max_restarts", "5",
                 str(script)])
    assert rc == 0
    assert marker.read_text() == "3"  # two elastic restarts then success


def test_multiproc_pod_elastic_relaunch(tmp_path):
    """nproc_per_node pod exiting 101 is relaunched under elastic_level."""
    from paddle_tpu.distributed.launch import launch
    marker = tmp_path / "n.txt"
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys\n"
        f"p = r'{marker}'\n"
        "if os.environ['PADDLE_TRAINER_ID'] != '0':\n"
        "    sys.exit(0)\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        f"sys.exit({ELASTIC_EXIT_CODE} if n < 1 else 0)\n")
    rc = launch(["--nproc_per_node", "2", "--elastic_level", "1",
                 str(script)])
    assert rc == 0
    assert marker.read_text() == "2"


def test_multiproc_refused_on_tpu_host(tmp_path, monkeypatch):
    """On a TPU host every local child would claim all the chips, so
    more than one local process is refused — judged from the device
    nodes and the environment, without touching JAX; children pinned to
    the CPU are still allowed."""
    launch_mod = sys.modules[start_local_trainers.__module__]
    monkeypatch.setattr(launch_mod.glob, "glob",
                        lambda pat: ["/dev/accel0"] if "accel" in pat else [])
    assert launch_mod._tpu_host({})
    assert not launch_mod._tpu_host({"JAX_PLATFORMS": "cpu"})
    script = tmp_path / "train.py"
    script.write_text("pass\n")
    with pytest.raises(RuntimeError, match="one process drives all"):
        start_local_trainers(2, str(script), [], base_env={})
    procs = start_local_trainers(2, str(script), [],
                                 base_env={**os.environ,
                                           "JAX_PLATFORMS": "cpu"})
    assert watch_local_trainers(procs) == 0
