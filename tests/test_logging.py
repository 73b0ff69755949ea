"""Logging must stay backend-free: a host-side code path that merely wants
a logger (native core loader, offline tools, the elastic supervisor) must
never trigger device bring-up — a chip belongs to one process at a time, so
a parent that initialized a backend just to log would hold the chip its
training child needs."""


import pytest as _pytest_mark  # noqa: E402

# Sub-2-minute smoke tier (COVERAGE.md "Test tiers"): this module's
# measured wall time keeps `pytest -m fast` under the tier budget.
pytestmark = _pytest_mark.mark.fast
import os
import subprocess
import sys


def test_is_primary_process_initializes_no_backend():
    code = (
        "import sys; sys.path.insert(0, '.')\n"
        "from frl_distributed_ml_scaffold_tpu.utils.logging import (\n"
        "    get_logger, is_primary_process)\n"
        "assert is_primary_process() is True\n"
        "get_logger().info('hello')\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('NO_BACKEND_OK')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}  # harmless if it DID init
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "NO_BACKEND_OK" in r.stdout


def test_tensorboard_sink_writes_event_file(tmp_path):
    """trainer.tensorboard=true writes TB scalar events next to the JSONL
    (lazy TF import; JSONL stays the record of truth)."""
    import glob

    import pytest

    pytest.importorskip("tensorflow")  # the sink degrades without TF
    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(
        get_config("mnist_mlp"),
        ["trainer.total_steps=4", "trainer.log_every=2",
         "trainer.tensorboard=true", "data.global_batch_size=16",
         "model.hidden_sizes=16", "checkpoint.enabled=false",
         f"workdir={tmp_path}"],
    )
    Trainer(cfg).fit()
    events = glob.glob(str(tmp_path / "mnist_mlp" / "tb" / "events.*"))
    assert events, "no TensorBoard event file written"
