"""Window driver for cells of kind "train": the program's own `Trainer.fit`.

One Trainer, one state. Set-up drives it from the seed through its first
steps (whose loss, first gradient and parameter change the reference follows),
warms it up, and hands the same state to the window, which is ONE `fit` call
timed from the call to its return (the last step's log boundary fetches the
loss, so the device has finished).
"""

from __future__ import annotations

import json
import os
import time

from . import correct, trace as tracelib
from .common import ROOT, limits, log, memory_peak_bytes, phase, reference_of, sized
from .traffic import synthetic_lm_batch
from .weights import flat, leaf_paths, make_params

# `fit` probes the step's FLOPs (a lowering and a trace of the whole step, with
# the device idle) at the third log boundary of every call. A real run pays
# that once in 100000 steps; a window of seconds must not hold it, so a fit
# call here never reaches a third boundary (PERF.md, Open questions).
MAX_BOUNDARIES = 2
TRACED_STEPS = 12


def build_config(cell: dict, seed: int, rehearse: bool):
    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config

    cfg_file = cell["config_file"]
    overrides = list(cfg_file["overrides"])
    if rehearse:
        overrides += cfg_file["rehearse"]["overrides"]
    overrides += [
        f"data.shuffle_seed={seed}",
        "workdir=" + os.path.join(ROOT, ".bench_work", cell["name"]),
    ]
    return apply_overrides(get_config(cfg_file["recipe"]), overrides)


def _first_moment(opt_state):
    """The Adam first-moment tree inside an optax state, wherever it sits."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _first_moment(part)
            if found is not None:
                return found
    return None


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(tree)
    return {k: float(v) for k, v in zip(leaf_paths(tree), jax.tree.leaves(norms))}


def _boundaries(start: int, last: int, every: int) -> int:
    """Log boundaries a fit call from step `start` to `last` crosses."""
    return len({s for s in range(start + 1, last + 1) if s % every == 0} | {last})


def fresh_state(trainer, seed: int):
    """The program's initial state with the benchmark's weights in it."""
    state = trainer.init_state()
    return state.replace(params=make_params(
        trainer.state_shapes.params, seed, shardings=trainer.state_shardings.params))


def program_readings(trainer, state, seed: int, steps: int, b1: float):
    """Drive the program's first `steps` steps through `fit`. Returns the
    readings the reference is compared with, and the state after them."""
    import jax
    import jax.numpy as jnp

    losses: list = []

    def keep(step, metrics):
        losses.append(metrics["loss"])

    state, _ = trainer.fit(state, num_steps=1, on_step=keep)
    phase("first step (compile or cache load)")
    mu = _first_moment(state.opt_state)
    grad_norm = {k: v / (1.0 - b1) for k, v in _leaf_norms(mu).items()}
    # The first gradient itself goes to the host (its direction is compared
    # leaf by leaf once the reference has run): no device memory is held for it.
    first_grad = dict(zip(leaf_paths(mu), jax.tree.leaves(jax.device_get(mu))))
    phase("first gradient fetched")
    state, _ = trainer.fit(state, num_steps=steps, on_step=keep)
    shapes, shardings = trainer.state_shapes.params, trainer.state_shardings.params
    delta = jax.jit(lambda p, q: jax.tree.map(jnp.subtract, p, q))(
        state.params, make_params(shapes, seed, shardings=shardings))
    update_norm = _leaf_norms(delta)
    del delta
    return {
        "loss": [float(x) for x in jax.device_get(losses)],
        "grad_norm": grad_norm,
        "update_norm": update_norm,
        "first_grad": first_grad,
    }, state


def reference_readings(cell: dict, cfg, seed: int, steps: int, rehearse: bool,
                       lowp: bool = False, fault=None, rows_per_block: int = 1):
    """The configuration's plain reference over the same first steps, from
    weights and rows the benchmark makes itself."""
    import jax

    cfg_file = cell["config_file"]
    ref = reference_of(cfg_file)
    model = sized(cfg_file, "model", rehearse)
    opt = sized(cfg_file, "optimizer", rehearse)
    params = flat(make_params(ref.param_shapes(model), seed))
    batches = [
        synthetic_lm_batch(seed, s, cfg.data.global_batch_size, model["seq_len"],
                           model["vocab_size"])
        for s in range(steps)
    ]
    with jax.default_matmul_precision("highest"):
        return ref.train_steps(params, batches, opt, model, lowp=lowp, fault=fault,
                               rows_per_block=rows_per_block)


def _window_records(run_dir: str, first: int, last: int, every: int):
    """fit's own log records of the window's boundaries, with the steps each
    covers (the record's `data_wait_s` is the mean over those steps)."""
    out, prev = [], first
    with open(os.path.join(run_dir, "metrics.jsonl")) as fh:
        records = [json.loads(line) for line in fh if line.strip()]
    for rec in records:
        step = rec.get("step")
        if step is None or "data_wait_s" not in rec or not first < step <= last:
            continue
        out.append((step - prev, rec))
        prev = step
    return out


def run(cell: dict, args, dev: dict, t_start: float, peaks: dict | None) -> dict:
    import jax
    import numpy as np
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg_file = cell["config_file"]
    ref_shapes = reference_of(cfg_file).param_shapes  # a file that names none ends the run here
    seed, rehearse = args.seed, args.rehearse
    cfg = build_config(cell, seed, rehearse)
    batch = cfg.data.global_batch_size
    every = cfg.trainer.log_every
    steps_checked = cfg_file["correct"]["steps"]
    run_dir = os.path.join(cfg.workdir, cfg.name)
    # A fresh record of this run only (fit appends).
    if os.path.exists(os.path.join(run_dir, "metrics.jsonl")):
        os.remove(os.path.join(run_dir, "metrics.jsonl"))

    trainer = Trainer(cfg)
    phase("trainer built")
    if leaf_paths(trainer.state_shapes.params) != leaf_paths(
            ref_shapes(sized(cfg_file, "model", rehearse))):
        raise SystemExit("the program's parameter tree is not the reference's layout")
    state = fresh_state(trainer, seed)
    jax.block_until_ready(state.params)
    phase("state and weights on the device")

    # The rows the pipeline feeds at step 0 against the benchmark's own copy.
    fed = np.asarray(jax.device_get(trainer.pipeline.global_batch(0)["tokens"]))
    own = synthetic_lm_batch(seed, 0, batch, cfg.data.seq_len, cfg.data.vocab_size)
    input_mismatch = int((fed != own).sum()) if fed.shape == own.shape else own.size

    b1 = cfg_file["optimizer"]["b1"]
    prog, state = program_readings(trainer, state, seed, steps_checked, b1)
    phase("checked steps")

    warm = max(1, int(cfg_file["warmup_steps_before_window"]))
    first = steps_checked + warm
    t0 = time.perf_counter()
    state, _ = trainer.fit(state, num_steps=first)
    est = (time.perf_counter() - t0) / warm
    phase("warm-up steps")
    steps = max(1, int(round(args.seconds / est)))
    while steps > 1 and _boundaries(first, first + steps, every) > MAX_BOUNDARIES:
        steps -= 1
    log(f"warm-up {warm} steps at {est * 1e3:.1f} ms; window of {steps} steps")

    losses, seen = [], []

    def on_step(step, metrics):
        losses.append(metrics["loss"])
        seen.append(time.perf_counter())

    last = first + steps
    t_open = time.perf_counter()
    state, _ = trainer.fit(state, num_steps=last, on_step=on_step)
    t_close = time.perf_counter()
    window_s = t_close - t_open
    setup_s = t_open - t_start
    rate = steps * batch / window_s / dev["count"]
    loss_host = np.asarray(jax.device_get(losses), np.float64)
    failed = int((~np.isfinite(loss_host)).sum())
    log(f"window {window_s:.3f} s, {steps} steps, {rate:.4f} samples/s/chip; "
        f"fit call to first step's end {seen[0] - t_open:.3f} s, "
        f"last step's end to return {t_close - seen[-1]:.3f} s")

    ctx = {
        "kind": "train", "config": cfg_file, "traffic": cell["traffic_file"],
        "chips": dev["count"], "peaks": peaks,
        "window": {"seconds": window_s, "steps": steps, "samples": steps * batch,
                   "samples_per_s_chip": rate},
        "model": dict(sized(cfg_file, "model", rehearse), batch=batch),
        "log_records": _window_records(run_dir, first, last, every),
        "trace": None,
    }
    device = dict(dev)
    if args.trace and not args.rehearse:
        prof_dir = os.path.join(cfg.workdir, "profile")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=options)
        try:
            state, _ = trainer.fit(state, num_steps=last + TRACED_STEPS)
        finally:
            jax.profiler.stop_trace()
        t_reduce = time.perf_counter()
        ctx["trace"] = traced_steps(tracelib.read_xplane(tracelib.find_xplane(prof_dir)))
        ctx["trace"]["reduce_s"] = time.perf_counter() - t_reduce
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    device["memory_peak_bytes"] = memory_peak_bytes()

    # Free the program's state before the reference runs.
    del state, trainer
    t_ref = time.perf_counter()
    ref = reference_readings(cell, cfg, seed, steps_checked, rehearse)
    log(f"reference: {time.perf_counter() - t_ref:.2f} s after the window")
    numbers = correct.train_numbers(prog, ref)
    numbers["input_mismatch"] = input_mismatch
    ok, compared = correct.decide(numbers, dict(limits(cfg_file, rehearse), input_mismatch=0),
                                  extra_ok=failed == 0)
    log(f"worst leaves: {numbers['_at']}")
    return {
        "correct": ok, "attempted": steps, "failed": failed,
        "end_to_end": {"train_samples_per_s_chip": rate, "setup_s": setup_s},
        "ctx": ctx, "device": device, "compared": compared,
    }


def traced_steps(raw: dict) -> dict:
    """Reduce the traced fit call over its steady middle: from the start of
    the third run of the step program on the device to the start of its last,
    so that the call's own start and end are left out. The step program is
    the module that took most of the device's time."""
    modules = next(iter(raw["devices"].values()))["modules"]
    by_name: dict[str, float] = {}
    for name, a, b in modules:
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    step_module = max(by_name, key=by_name.get) if by_name else None
    starts = sorted(a for name, a, _ in modules if name == step_module)
    if len(starts) < 5:
        raise RuntimeError(f"the trace holds {len(starts)} runs of the step program")
    out = tracelib.reduce(raw, starts[2], starts[-1])
    out["steps"] = len(starts) - 3
    out["step_module"] = step_module
    return out
