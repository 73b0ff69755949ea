"""Per-process supervisor half of the multi-process elastic test.

Launched (once per simulated host) by tests/test_elastic_multiprocess.py.
Each instance is exactly what a real pod host runs: the launcher CLI in
``--elastic`` mode (supervisor wrapping a multi-process training child that
rendezvouses over ``jax.distributed``). The test kills one *child* mid-run
via fault injection; this script only stands in for "one host's command
line" — all logic lives in the launcher itself.

Env contract (set by the test): FRL_TPU_COORDINATOR, FRL_TPU_NUM_PROCESSES,
FRL_TPU_PROCESS_ID, FRL_TEST_WORKDIR; FRL_FAULT_AT_STEP optionally set for
exactly one process's environment.
"""

import os
import sys


def main() -> int:
    from frl_distributed_ml_scaffold_tpu.launcher.launch import main as launch_main

    rc = launch_main(
        [
            "--config", "mnist_mlp",
            "--device", "cpu",
            "--sim-devices", "2",
            "--coordinator", os.environ["FRL_TPU_COORDINATOR"],
            "--num-processes", os.environ["FRL_TPU_NUM_PROCESSES"],
            "--process-id", os.environ["FRL_TPU_PROCESS_ID"],
            "--elastic",
            "trainer.total_steps=12",
            "trainer.log_every=4",
            "trainer.eval_every=0",
            "data.global_batch_size=64",
            "data.prefetch=0",
            "model.hidden_sizes=32",
            "precision.policy=fp32",
            "checkpoint.save_every=4",
            "checkpoint.async_save=false",
            "elastic.backoff_s=0.1",
            "workdir=" + os.environ["FRL_TEST_WORKDIR"],
        ]
    )
    # A chip belongs to one process at a time: the supervisor (this
    # process) must never have initialized a backend its children need.
    from jax._src import xla_bridge

    print(f"SUPERVISOR_BACKENDS={len(xla_bridge._backends)}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
