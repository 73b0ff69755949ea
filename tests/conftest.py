"""Test harness: simulated 8-device CPU mesh (SURVEY §4, C20).

Must run before jax is imported anywhere: forces the host platform and 8
virtual CPU devices so every parallelism mode (DP/FSDP/TP/PP/SP/EP) runs real
meshes and real collectives in pytest without TPU hardware — the TPU-native
replacement for the reference's Gloo/fake-process-group test tier.
"""

import os
import sys

# Overwrite (not setdefault): tests run on the simulated CPU mesh whatever
# the environment selects (on a machine with a chip JAX defaults to it).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Repo root on sys.path so the package imports without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# The HF-interop tests load host torch into this (shared) pytest process;
# the launcher's *runtime* no-CUDA tier would then trip on every later
# launch-path test. That tier is for real launch processes — waive it
# suite-wide and exercise its semantics explicitly in test_train_mnist.
os.environ.setdefault("FRL_ALLOW_HOST_TORCH", "1")

# Persistent compilation cache (repo-local, gitignored): the suite's wall
# time is dominated by XLA compiles of the same tiny models on the same
# 8-device mesh; caching them across runs cuts repeat `pytest` runs by
# minutes on this 1-core box. One shared helper with the launcher/bench;
# tests lower the thresholds because their compiles are tiny but numerous.
from frl_distributed_ml_scaffold_tpu.launcher.launch import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# Measured and rejected (2026-07-30): jax_disable_most_optimizations=True
# cuts per-test XLA compile by ~1/3 but makes the *runtime* of the conv- and
# step-heavy tests 1.7-2x slower — net suite time went 703s -> 767s. The
# suite's budget is better served by keeping shapes tiny per-test.
#
# Measured and adopted (2026-07-30): tests must jax.jit their flax
# init/apply/grad calls instead of running them eagerly — eager dispatch
# walks hundreds of tiny ops one by one on this 1-core box (11.8s for an
# eager RN50 init vs <1s as one cached program). Jitting the hot test
# bodies cut the warm suite 394s -> 255s at identical coverage.

import contextlib  # noqa: E402
import logging  # noqa: E402


@contextlib.contextmanager
def capture_frl_logs():
    """Collect framework log messages. The framework logger sets
    ``propagate=False`` (process-0 stdout gating), so pytest's ``caplog``
    never sees its records — tests attach a handler directly instead."""
    from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

    records: list[str] = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = get_logger()
    handler = _Capture()
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)
