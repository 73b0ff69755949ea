"""Shared harness for the real-subprocess rendezvous tests.

Both multi-process tiers (test_multiprocess.py: plain 2-process training;
test_elastic_multiprocess.py: supervised kill-and-resume) spawn worker
scripts that must rendezvous over a TCP port with identical env plumbing.
The subtleties live here once: the XLA device-count flag must be SET (not
inherited — pytest's conftest already exported device_count=8, and the
workers' own launcher only appends the flag when absent), the workers run
on the CPU like the suite that spawns them, and worker pipes must be drained concurrently with a kill-on-failure guarantee (a
blocked pipe on one worker deadlocks its peers through the collectives).
"""

import os
import socket
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rendezvous_env(tmp_path, port, *, device_count, num_processes=2):
    """Base env for one worker process (add FRL_TPU_PROCESS_ID per worker)."""
    return {
        **os.environ,
        "FRL_TPU_COORDINATOR": f"127.0.0.1:{port}",
        "FRL_TPU_NUM_PROCESSES": str(num_processes),
        "FRL_TEST_WORKDIR": str(tmp_path),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={device_count}",
        # Script-by-path puts tests/ on sys.path, not the repo root.
        "PYTHONPATH": REPO_ROOT
        + (os.pathsep + os.environ["PYTHONPATH"] if os.environ.get("PYTHONPATH") else ""),
    }


def run_workers(script, envs, *, timeout):
    """Spawn one worker per env, drain all pipes concurrently, return
    (returncodes, outputs). Any failure path kills the whole set — leaked
    workers would hold the rendezvous port and retry initialization for
    minutes."""
    name = os.path.join(os.path.dirname(os.path.abspath(__file__)), script)
    procs = [
        subprocess.Popen(
            [sys.executable, name],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=REPO_ROOT,
        )
        for env in envs
    ]
    try:
        with ThreadPoolExecutor(max_workers=len(procs)) as pool:
            futures = [
                pool.submit(p.communicate, timeout=timeout) for p in procs
            ]
            outputs = [f.result(timeout=timeout + 30)[0] for f in futures]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return [p.returncode for p in procs], outputs
