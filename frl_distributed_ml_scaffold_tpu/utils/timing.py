"""Step timing + throughput measurement (SURVEY C19, BASELINE.md protocol).

The contract: timings exclude compile (warmup window), force true device
completion via ``device_get`` of the step's scalar outputs (see ``_force``),
and report median + p90 e2e step time plus samples/sec/chip — the benchmark
harness and the trainer both use this one implementation so numbers agree.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np


def _force(out) -> None:
    """Force true device completion of ``out`` (per-step scalars, e.g. loss).

    JAX dispatch is asynchronous, so a window's clock may stop only once
    its last step has finished on the device. The loss depends on the
    whole step, so fetching it (``device_get``) cannot return earlier;
    ``jax.block_until_ready`` on the same scalar waits for the same event
    and is equally correct on the chip (chip_smoke.py times both once and
    prints them). The fetch is kept because it is the exact operation the
    training loop performs at a log boundary — it needs the VALUES — so a
    timed window contains what a production window contains, including
    its one scalar device-to-host copy.
    """
    if out is None:
        return
    jax.device_get(out)  # one fetch for the whole (scalar-leaved) pytree


@dataclass
class StepTimer:
    """Collects per-step wall times after a warmup window.

    Usage::

        timer = StepTimer(warmup=3)
        for batch in data:
            state, metrics = train_step(state, batch)
            timer.tick(metrics["loss"])  # force a per-step SCALAR + record
                                         # (never the state: _force fetches
                                         # everything it is handed)
    """

    warmup: int = 3
    _times: list[float] = field(default_factory=list)
    _seen: int = 0
    _last: float | None = None

    def tick(self, out=None) -> float | None:
        """Mark the end of a step; returns this step's time (or None in warmup)."""
        _force(out)
        now = time.perf_counter()
        dt = None
        if self._last is not None:
            self._seen += 1
            if self._seen > self.warmup:
                dt = now - self._last
                self._times.append(dt)
        self._last = now
        return dt

    def tick_window(self, out, steps: int) -> float | None:
        """Record a window of ``steps`` steps ending now; appends the
        *per-step average* for the window. Used by the training loop, which
        only blocks on device output at log boundaries (blocking every step
        would serialize the async dispatch pipeline). The first window is
        dropped (contains compile)."""
        _force(out)
        now = time.perf_counter()
        dt = None
        if self._last is not None:
            self._seen += 1
            if self._seen > self.warmup:
                dt = (now - self._last) / max(steps, 1)
                self._times.extend([dt] * steps)
        self._last = now
        return dt

    def reset(self) -> None:
        self._times.clear()
        self._seen = 0
        self._last = None

    @property
    def count(self) -> int:
        return len(self._times)

    def summary(self, samples_per_step: int | None = None) -> dict:
        """Step-time percentiles (p50/p90/p95/p99) plus mean and
        samples/sec/chip if batch size given. Granularity follows the
        feed mode: ``tick()`` every step (benchmark harness) gives true
        per-step tails; ``tick_window()`` (training loop) records one
        averaged value per log window, so the tail is across *windows* —
        a straggler step inside a window is folded into that window's
        mean and only shows up if it moves the whole window."""
        if not self._times:
            return {"steps_timed": 0}
        arr = np.asarray(self._times)
        out = {
            "steps_timed": int(arr.size),
            "step_time_median_s": float(np.median(arr)),
            "step_time_p50_s": float(np.median(arr)),
            "step_time_p90_s": float(np.percentile(arr, 90)),
            "step_time_p95_s": float(np.percentile(arr, 95)),
            "step_time_p99_s": float(np.percentile(arr, 99)),
            "step_time_mean_s": float(arr.mean()),
            "steps_per_sec": float(1.0 / np.median(arr)),
        }
        if samples_per_step is not None:
            n_chips = jax.device_count()
            out["samples_per_sec"] = float(samples_per_step / np.median(arr))
            out["samples_per_sec_per_chip"] = out["samples_per_sec"] / n_chips
        return out
