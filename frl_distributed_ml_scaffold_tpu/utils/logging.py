"""Logging + metric emission (SURVEY C18).

Design: metrics are accumulated *on device* inside the compiled step (the
trainer returns a small metrics pytree); the host only periodically
``device_get``s and writes them. Process-0 gating replaces the reference's
rank-0 gating. Output is both human stdout and machine JSONL — samples/sec/
chip and step time are first-class because they ARE the baseline metric
(BASELINE.md measurement protocol).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time
from typing import Any, IO, Mapping

import jax

_LOGGERS: dict[str, logging.Logger] = {}


def is_primary_process() -> bool:
    """True on the process that should write logs (reference: rank 0).

    Deliberately never *initializes* a backend: a chip belongs to one
    process at a time, so a host-side code path that merely wants to log
    (the elastic supervisor, the native data core loader, offline tools)
    would take the chip from the process that trains if this called
    ``jax.process_index()`` cold. Resolution order:

    1. the distributed runtime's process id (backend-free; set whenever
       ``jax.distributed.initialize`` ran — the launcher's multi-process
       path);
    2. ``jax.process_index()`` — but only when a backend already exists,
       so the call cannot trigger bring-up (covers multi-host stacks that
       know their rank from topology without explicit distributed init);
    3. primary — no distributed runtime and no backend means there is
       nobody else to defer to.

    Residual caveat: on path-3 hosts that later become non-primary, early
    log lines (before backend init) may appear on every host — cosmetic,
    and strictly better than holding the chip.
    """
    try:
        from jax._src import distributed

        pid = getattr(distributed.global_state, "process_id", None)
        if pid is not None:
            return pid == 0
    except Exception as e:  # private-API drift: fall through
        logging.getLogger(__name__).debug(
            "distributed-runtime process-id probe failed (%s)", e
        )
    try:
        from jax._src import xla_bridge

        if getattr(xla_bridge, "_backends", None):
            return jax.process_index() == 0
    except Exception as e:  # private-API drift: fall through
        logging.getLogger(__name__).debug(
            "backend process-index probe failed (%s)", e
        )
    return True


def get_logger(name: str = "frl_tpu") -> logging.Logger:
    """Process-0-gated stdout logger; non-primary processes log at ERROR."""
    if name in _LOGGERS:
        return _LOGGERS[name]
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s] %(message)s")
        )
        logger.addHandler(handler)
        logger.setLevel(logging.INFO if is_primary_process() else logging.ERROR)
        logger.propagate = False
    _LOGGERS[name] = logger
    return logger


def _truncate_partial_line(path: str) -> None:
    """Crash-safety on reopen: a process killed mid-``write`` (OOM,
    SIGKILL, preemption without grace) leaves a torn final line, which
    poisons every later line-by-line reader of the file. Drop everything
    after the last newline BEFORE appending resumes — the torn record is
    unrecoverable either way; the file staying parseable is what
    matters."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return  # no file yet: nothing to repair
    if size == 0:
        return
    with open(path, "rb+") as fh:
        fh.seek(size - 1)
        if fh.read(1) == b"\n":
            return  # clean shutdown last time
        pos = size
        while pos > 0:
            step = min(65536, pos)
            fh.seek(pos - step)
            idx = fh.read(step).rfind(b"\n")
            if idx >= 0:
                fh.truncate(pos - step + idx + 1)
                return
            pos -= step
        fh.truncate(0)  # single torn line: the whole file is the tear


class JsonlWriter:
    """Append-only JSONL metric sink, primary-process only. Reopening an
    existing file first truncates any torn final line (crash-safety —
    see ``_truncate_partial_line``)."""

    def __init__(self, path: str | None):
        self._fh: IO[str] | None = None
        if path and is_primary_process():
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            _truncate_partial_line(path)
            self._fh = open(path, "a", buffering=1)

    def write(self, record: Mapping[str, Any]) -> None:
        if self._fh is not None:
            self._fh.write(json.dumps(record, default=_json_default) + "\n")

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


def _json_default(x: Any) -> Any:
    if hasattr(x, "item"):
        return x.item()
    if hasattr(x, "tolist"):
        return x.tolist()
    return str(x)


class TensorBoardWriter:
    """Optional TensorBoard scalar sink (``tf.summary``), primary-only.

    TensorFlow is imported lazily and failures downgrade to a warning —
    the sink is observability sugar on top of the JSONL record of truth,
    never a dependency of the training path. (jax.profiler traces already
    land in TensorBoard; this adds the scalar curves next to them.)
    """

    def __init__(self, logdir: str | None):
        self._writer = None
        self._tf = None
        if logdir and is_primary_process():
            try:
                import tensorflow as tf

                self._tf = tf
                self._writer = tf.summary.create_file_writer(logdir)
            except Exception as e:  # missing/broken TF: sink off, run on
                get_logger().warning("tensorboard sink disabled: %s", e)

    def write(self, step: int, record: Mapping[str, Any]) -> None:
        if self._writer is None:
            return
        with self._writer.as_default(step=int(step)):
            for k, v in record.items():
                if k != "step" and isinstance(v, (int, float)):
                    self._tf.summary.scalar(k, float(v))
        self._writer.flush()

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None


class MetricLogger:
    """Periodic metric emitter: stdout line + JSONL record.

    ``log(step, metrics, extra)`` converts device scalars to Python floats
    (one ``device_get`` for the whole dict) and writes both sinks.
    """

    def __init__(
        self,
        jsonl_path: str | None = None,
        name: str = "frl_tpu",
        tb_dir: str | None = None,
    ):
        self._logger = get_logger(name)
        self._jsonl = JsonlWriter(jsonl_path)
        self._tb = TensorBoardWriter(tb_dir)
        self._start = time.monotonic()

    def log(
        self,
        step: int,
        metrics: Mapping[str, Any],
        extra: Mapping[str, Any] | None = None,
    ) -> dict[str, Any]:
        host_metrics = jax.device_get(dict(metrics))
        record: dict[str, Any] = {
            "step": int(step),
            "wall_time_s": round(time.monotonic() - self._start, 3),
        }
        for k, v in host_metrics.items():
            try:
                record[k] = float(v)
            except (TypeError, ValueError):
                record[k] = v
        if extra:
            record.update(extra)
        parts = [f"step={record['step']}"]
        parts += [
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in record.items()
            if k not in ("step",)
        ]
        self._logger.info(" ".join(parts))
        self._jsonl.write(record)
        self._tb.write(record["step"], record)
        return record

    def close(self) -> None:
        self._jsonl.close()
        self._tb.close()
