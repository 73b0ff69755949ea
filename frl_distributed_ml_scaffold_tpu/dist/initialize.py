"""Process bring-up (SURVEY C1, call stack (a)).

Reference behavior: torchrun spawns N workers per node and each calls
``dist.init_process_group("nccl")`` with a TCP rendezvous. TPU-native: JAX is
multi-controller SPMD — ONE process per host, each owning its local chips;
``jax.distributed.initialize`` is the only cross-host control point. On a
single host (or under test) initialization is a no-op.

Environment contract (mirrors torchrun's env:// rendezvous, TPU-flavored):
``FRL_TPU_COORDINATOR`` (host:port), ``FRL_TPU_NUM_PROCESSES``,
``FRL_TPU_PROCESS_ID`` — all optional; on Cloud TPU pod slices JAX
auto-detects all three from the metadata server.
"""

from __future__ import annotations

import os

import jax

_INITIALIZED = False


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Bring up multi-host JAX if configured; safe to call unconditionally.

    Resolution order: explicit args > FRL_TPU_* env vars > JAX autodetection
    (Cloud TPU metadata). Single-process runs skip initialization entirely.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator_address = coordinator_address or os.environ.get("FRL_TPU_COORDINATOR")
    if num_processes is None and "FRL_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["FRL_TPU_NUM_PROCESSES"])
    if process_id is None and "FRL_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["FRL_TPU_PROCESS_ID"])

    if num_processes == 1:
        # Explicit single-process topology (e.g. the elastic supervisor
        # shrinking to the last survivor): nothing to initialize, even when
        # a stale FRL_TPU_COORDINATOR is still in the environment.
        return
    if num_processes is not None and num_processes > 1:
        _enable_cpu_collectives()
        # Bounded rendezvous: when a peer host is gone for good, the default
        # 300 s initialization timeout is what the elastic supervisor's
        # shrink policy (launcher/elastic.py) waits on — let deployments
        # (and the shrink tests) tighten it.
        timeout_s = int(os.environ.get("FRL_TPU_INIT_TIMEOUT_S", "300"))
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=timeout_s,
        )
        _INITIALIZED = True
    elif coordinator_address is not None:
        # Pod-slice autodetect path: let JAX fill in counts from the platform.
        jax.distributed.initialize(coordinator_address=coordinator_address)
        _INITIALIZED = True
    # else: single process — nothing to initialize.


def _enable_cpu_collectives() -> None:
    """Multi-process compiled collectives on the CPU backend need an
    explicit cross-process implementation (jax's default is 'none', which
    raises "Multiprocess computations aren't implemented on the CPU
    backend" at the first psum). Select gloo BEFORE the backend
    initializes — this is what makes the 2-process CPU-sim tests
    (test_multiprocess / test_elastic_multiprocess) real collectives
    rather than a capability of some boxes and not others. Set
    unconditionally for multi-process topologies: it only configures the
    CPU backend's cross-process transport, so on TPU pods it is inert
    (platform sniffing here is a trap — probing the backend would
    initialize it prematurely)."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def shutdown_distributed() -> None:
    global _INITIALIZED
    if _INITIALIZED:
        jax.distributed.shutdown()
        _INITIALIZED = False
