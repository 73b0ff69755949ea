#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline metric (BASELINE.md): ImageNet samples/sec/chip on ResNet-50
training (fwd+bwd+update, bf16 mixed precision, synthetic data so the loader
can't be the bottleneck).

``vs_baseline``: BASELINE.json's ``published`` is empty (reference repo
absent — see BASELINE.md); the comparison constant below is the documented
*assumed* A100-DDP ResNet-50 figure (2500 samples/sec/chip, bf16) so the
ratio is meaningful the day real numbers surface. Target from the north
star: >= 0.9 * A100 -> vs_baseline >= 0.9.

One process, because a chip belongs to one process at a time: the device
is queried and the benchmark runs right here. A run that finds no TPU, or
whose benchmark raises, exits non-zero and prints no number — a
measurement path never falls back to the CPU or to an older capture.
Progress/diagnostics go to stderr, flushed.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Assumed reference numbers (documented stand-ins; see module docstring).
ASSUMED_BASELINE = {
    "rn50_imagenet_samples_per_sec_per_chip": 2500.0,
}


def _progress(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def bench_config(name: str, overrides: list[str], *, steps: int, warmup: int):
    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.launcher.launch import enable_compile_cache
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer
    from frl_distributed_ml_scaffold_tpu.utils.timing import StepTimer

    # Repeat bench runs of the same config hit the persistent compile cache.
    enable_compile_cache()

    # prefetch=0: the benchmark reuses one device-resident batch; background
    # prefetch would only add host/device contention inside timed windows.
    cfg = apply_overrides(get_config(name), ["data.prefetch=0"] + overrides)
    trainer = Trainer(cfg)
    state = trainer.init_state()
    # One device-resident batch, reused (global_batch returns sharded
    # jax.Arrays): the benchmark measures the chip (fwd+bwd+update), not the
    # host loader (BASELINE.md protocol).
    batch = trainer.pipeline.global_batch(0)
    # FLOPs of one compiled step, from XLA's own cost model (counts every op
    # the step actually runs: fwd+bwd+optimizer, all grad-accum microbatches).
    cost = trainer.step_cost_analysis(state, batch)
    step_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    # Windowed timing: sync on the loss once per window, steps inside a
    # window pipeline as in a real training loop (per-step syncs would
    # charge the host<->device round-trip latency to every step).
    # ``warmup`` counts windows (the first ones contain compile + ramp).
    # 30 steps/window amortizes the one host sync per window; real
    # training loops sync once per log_every (100s of steps).
    window = int(os.environ.get("FRL_BENCH_WINDOW", "30"))
    n_windows = max(1, -(-steps // window))  # ceil; at least one measured
    timer = StepTimer(warmup=warmup)
    for _ in range(n_windows + warmup + 1):
        for _ in range(window):
            state, metrics = trainer.train_step(state, batch)
        timer.tick_window(metrics["loss"], window)
    perf = timer.summary(cfg.data.global_batch_size)
    if "samples_per_sec_per_chip" not in perf:
        raise RuntimeError(f"benchmark produced no timed windows: {perf}")
    perf["_record"] = protocol_record(cfg, trainer, perf, step_flops=step_flops)
    # The protocol line must say exactly what ran — config name + the
    # non-default knobs (stem, remat, chunking, ...) that produced it.
    perf["_record"]["overrides"] = list(overrides)
    return perf


def protocol_record(cfg, trainer, perf, *, step_flops: float = 0.0) -> dict:
    """The BASELINE.md measurement-protocol record (one JSONL line/run)."""
    import jax

    n_chips = jax.device_count()
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", str(dev))
    rec = {
        "config": cfg.name,
        "model": getattr(cfg.model, "family", type(cfg.model).__name__),
        "global_batch_size": cfg.data.global_batch_size,
        "per_chip_batch_size": cfg.data.global_batch_size // n_chips,
        "mesh": dict(trainer.env.mesh.shape),
        "param_sharding": cfg.parallel.param_sharding,
        "precision": cfg.precision.policy,
        "grad_accum": cfg.trainer.grad_accum,
        "remat": cfg.trainer.remat,
        "n_chips": n_chips,
        "platform": dev.platform,
        "chip": kind,
        "steps_per_sec": round(perf["steps_per_sec"], 4),
        "samples_per_sec_per_chip": round(perf["samples_per_sec_per_chip"], 2),
        "step_time_median_s": round(perf["step_time_median_s"], 6),
        "step_time_p90_s": round(perf["step_time_p90_s"], 6),
        # Capture-time provenance travels WITH the row.
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    from frl_distributed_ml_scaffold_tpu.utils.flops import peak_flops_per_chip
    from frl_distributed_ml_scaffold_tpu.utils.profiling import device_memory_stats

    rec.update(device_memory_stats())
    if step_flops > 0:
        rec["model_flops_per_sample"] = round(
            step_flops / cfg.data.global_batch_size
        )
        # None on the CPU (no published peak — the record then carries no
        # MFU); an accelerator missing from the table raises.
        peak = peak_flops_per_chip(dev)
        if peak:
            # MFU: achieved FLOP/s over peak, per chip (flops here is the
            # whole-step XLA count, so this is end-to-end training MFU).
            rec["mfu"] = round(
                step_flops * perf["steps_per_sec"] / (n_chips * peak), 4
            )
    return rec


# The five BASELINE configs, sized for one v5e chip (shrunk only where the
# full model cannot fit / compile on a single chip; recorded in overrides so
# the emitted protocol line says exactly what ran).
ALL_CONFIGS = [
    ("mnist_mlp", ["data.global_batch_size=1024"], 50),
    # Same operating point as the headline candidate below (s2d stem) so
    # regenerating the table reproduces the row BASELINE.md documents.
    ("imagenet_rn50_ddp",
     ["data.global_batch_size=512", "model.stem=s2d"], 20),
    # remat=none: config 3 prescribes activation checkpointing for fitting
    # FSDP shards at scale, but on one chip bs=256 fits without it and the
    # recompute is pure overhead (measured: 865.6 samples/sec/chip remat
    # none vs 616.7 full vs 778.6 dots, 2026-07-30). The protocol line
    # records the remat mode so the tradeoff stays visible.
    ("imagenet_vitb_fsdp",
     ["data.global_batch_size=256", "trainer.remat=none"], 20),
    (
        # Round-4 operating point: per-block remat (model.block_remat)
        # caps backward residency at one block's internals, unlocking
        # microbatch 8 — measured 33.6 samples/sec/chip vs 24.25 at the
        # old mb4/remat=dots knee (+39%, MFU 0.337 → 0.467). See
        # docs/perf_playbook.md "Per-block remat on the flagship" and
        # tools/perf_sweep.py gpt2_block_remat (mb16/32 measure the same
        # within noise; mb8 recompiles fastest).
        # lm_loss_chunk: chunked-vocab head+CE — skips the [B,T,50257]
        # logits materialization; measured +9% at microbatch 4 (19.78 vs
        # 18.15 samples/sec/chip) on top of the memory saved.
        "gpt2_medium_zero1",
        ["data.global_batch_size=8", "trainer.grad_accum=1",
         "model.attention=flash", "model.lm_loss_chunk=128",
         "trainer.remat=none", "model.block_remat=full"],
        10,
    ),
    (
        # The recorded optimizer decision (VERDICT r4 #1): adafactor beat
        # adamw +4.6% at mb4 remat=none on-chip (31.7 vs 30.3,
        # 2026-07-30) with convergence within tolerance
        # (tools/opt_convergence.py); this row carries the variant at the
        # flagship operating point so regenerating the table keeps the
        # A/B visible next to gpt2_medium_zero1's adamw line.
        "gpt2_medium_adafactor",
        ["data.global_batch_size=8", "trainer.grad_accum=1",
         "model.attention=flash", "model.lm_loss_chunk=128",
         "trainer.remat=none", "model.block_remat=full"],
        10,
    ),
    (
        # On-chip MoE protocol line (SURVEY C9): single chip has no expert
        # axis to shard (mesh.expert=1 — EP itself is sim-verified), but
        # the grouped GSEC dispatch, capacity routing, z-loss, and the
        # stacked-expert FFN einsums all run at real shapes here.
        # 908M params: AdamW's fp32 mu/nu alone (10.9G) blow the 15.75G
        # chip (first on-chip attempt 2026-07-30 was refused at compile),
        # so the single-chip line runs Adafactor (factored second moment —
        # the standard MoE-scale choice) + per-block remat.
        "gpt2_moe",
        ["data.global_batch_size=8", "trainer.grad_accum=1",
         "model.attention=flash", "model.lm_loss_chunk=128",
         "mesh.expert=1", "optimizer.name=adafactor",
         "trainer.remat=none", "model.block_remat=full"],
        10,
    ),
    ("ego4d_video_elastic", ["data.global_batch_size=32",
                             "checkpoint.enabled=false"], 10),
]


def _ensure_bench_shards(dir_: str, n_shards: int = 4, per: int = 256,
                         size: int = 224) -> str:
    """Generate (once, then reuse) uint8 decoded-image shards at RN50/ViT
    shapes — the exact on-disk format tools/decode_imagenet.py produces.
    Contents are random: the loader bench measures gather+augment+feed
    throughput, which is content-independent."""
    import numpy as np

    os.makedirs(dir_, exist_ok=True)
    for s in range(n_shards):
        ip = os.path.join(dir_, f"train_images_{s:03d}.npy")
        lp = os.path.join(dir_, f"train_labels_{s:03d}.npy")
        if not (os.path.exists(ip) and os.path.exists(lp)):
            rng = np.random.default_rng(s)
            np.save(ip, rng.integers(
                0, 256, size=(per, size, size, 3), dtype=np.uint8))
            np.save(lp, rng.integers(0, 1000, size=per))
    return dir_


def run_real_data() -> int:
    """SURVEY §7 hard part 5: does samples/sec/chip measure the chip or the
    loader? Streams a FRESH batch through the full input tier every step —
    disk shards → memmap gather → native augment → device feed — and
    compares against the identical streaming loop on the synthetic source.
    One JSONL row per mode plus a verdict row. (The protocol benchmark
    deliberately reuses one device-resident batch; this mode exists to
    check that choice against reality.)
    """
    probe_backend()
    import time as _time

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.launcher.launch import enable_compile_cache
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    enable_compile_cache()
    shard_dir = _ensure_bench_shards(
        os.environ.get("FRL_BENCH_DATA_DIR", "/tmp/frl_bench_shards")
    )
    bs, warm, steps = 256, 3, 12
    rows = {}
    for mode, extra in (
        ("synthetic_stream", []),
        ("real_stream", [f"data.data_dir={shard_dir}"]),
    ):
        cfg = apply_overrides(
            get_config("imagenet_rn50_ddp"),
            [f"data.global_batch_size={bs}", "model.stem=s2d",
             "trainer.log_every=1000000", "data.prefetch=2"] + extra,
        )
        trainer = Trainer(cfg)
        # prefetch>0 wraps the pipeline; the source lives on the inner one.
        inner = getattr(trainer.pipeline, "_p", trainer.pipeline)
        if mode == "real_stream" and inner.source.is_synthetic:
            raise RuntimeError("real-data shards not picked up")
        state = trainer.init_state()
        for step in range(warm):
            state, m = trainer.train_step(
                state, trainer.pipeline.global_batch(step)
            )
        import jax

        jax.device_get(m["loss"])
        t0 = _time.perf_counter()
        for step in range(warm, warm + steps):
            state, m = trainer.train_step(
                state, trainer.pipeline.global_batch(step)
            )
        jax.device_get(m["loss"])
        dt = (_time.perf_counter() - t0) / steps
        rows[mode] = bs / dt
        print(json.dumps({
            "mode": mode, "global_batch_size": bs,
            "step_time_ms": round(dt * 1e3, 2),
            "samples_per_sec_per_chip": round(bs / dt, 1),
        }), flush=True)
        del trainer, state, m, inner
        # Release the first mode's params/opt-state/executables (and the
        # pipeline's prefetch buffers held via `inner`) before the second
        # allocates (same settle tools/perf_sweep.py build() uses) —
        # two live Trainers can RESOURCE_EXHAUSTED an HBM-constrained chip.
        import gc

        gc.collect()
        jax.clear_caches()
        gc.collect()
    ratio = rows["real_stream"] / rows["synthetic_stream"]
    print(json.dumps({
        "mode": "verdict",
        "real_over_synthetic": round(ratio, 4),
        "loader_bound": bool(ratio < 0.9),
    }), flush=True)
    return 0


def run_all(out_path: str = os.path.join("chiprun_out", "BENCH_TABLE.jsonl"),
            *, require_tpu: bool = True) -> int:
    """Benchmark every BASELINE config; emit protocol JSONL + a table.
    A run that fails — no device, or any config raising — leaves an
    existing ``out_path`` untouched and returns non-zero."""
    probe_backend(require_tpu=require_tpu)  # raises before out_path is opened
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    rows = []
    # Stage into a temp file; the live table is replaced ALL-OR-NOTHING:
    # it is the evidence artifact, and a partial table would silently
    # drop the last good rows of whichever configs failed this run.
    # Every row (success or error) still streams to stdout regardless.
    tmp_path = out_path + ".tmp"
    try:
        with open(tmp_path, "w") as fh:
            for name, overrides, steps in ALL_CONFIGS:
                _progress(f"benchmarking {name} ...")
                try:
                    perf = bench_config(
                        name, overrides + ["trainer.log_every=1000000"],
                        steps=steps, warmup=2,
                    )
                    rec = perf["_record"]
                except Exception as e:  # record the failure, keep benching
                    rec = {"config": name, "error": str(e)[:300]}
                rows.append(rec)
                fh.write(json.dumps(rec) + "\n")
                fh.flush()
                print(json.dumps(rec))
        ok = [r for r in rows if "error" not in r]
        if len(ok) == len(rows):
            os.replace(tmp_path, out_path)
        else:
            _progress(
                f"{len(rows) - len(ok)} config(s) failed; existing "
                f"{out_path} left untouched"
            )
    finally:
        if os.path.exists(tmp_path):  # error/partial run or interrupt
            os.remove(tmp_path)
    print(f"\n{'config':28s} {'samples/s/chip':>14s} {'step_ms':>9s} {'mfu':>6s}  mesh")
    for r in ok:
        mfu = f"{r['mfu']:.3f}" if "mfu" in r else "-"
        print(
            f"{r['config']:28s} {r['samples_per_sec_per_chip']:14.1f} "
            f"{r['step_time_median_s']*1e3:9.2f} {mfu:>6s}  {r['mesh']}"
        )
    return 0 if len(ok) == len(rows) else 1


# The headline candidate.
HEADLINE = (
    "rn50_imagenet_samples_per_sec_per_chip",
    "imagenet_rn50_ddp",
    # bs=512 is the measured single-chip throughput knee (256: 1905,
    # 512: 2025, 1024: 1842 samples/sec/chip on v5e). s2d stem: the
    # mathematically exact space-to-depth rewrite of the 7x7/s2 stem
    # (models/resnet.py), measured +1.5% over conv7.
    ["data.global_batch_size=512", "model.stem=s2d",
     "trainer.log_every=1000000"],
    90,  # 3 measured 30-step windows (median taken across windows)
)


def _headline_result(metric: str, cfg_name: str, overrides: list[str],
                     steps: int) -> dict:
    perf = bench_config(cfg_name, overrides, steps=steps, warmup=3)
    value = perf["samples_per_sec_per_chip"]
    rec = perf["_record"]
    out = {
        "metric": metric,
        "value": round(value, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(value / ASSUMED_BASELINE[metric], 4),
        "device": {"platform": rec["platform"], "kind": rec["chip"],
                   "count": rec["n_chips"]},
    }
    if "mfu" in rec:
        out["mfu"] = rec["mfu"]
    return out


def probe_backend(*, require_tpu: bool = True) -> str:
    """Query the device in this process; returns its ``device_kind``.

    A benchmark number belongs to the chip: anything but a TPU raises,
    unless the caller asked for whatever device there is
    (``require_tpu=False`` — the CPU tests of this harness do)."""
    import jax

    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py needs a TPU; JAX found platform={dev.platform!r} "
            f"({dev.device_kind}). A CPU is never benched under the "
            "chip's name."
        )
    _progress(f"backend up: {len(jax.devices())} x {dev.device_kind}")
    return dev.device_kind


def main() -> int:
    if "--all" in sys.argv:
        return run_all()
    if "--real-data" in sys.argv:
        return run_real_data()
    probe_backend()
    _progress(f"benchmarking {HEADLINE[1]} ({HEADLINE[0]}) ...")
    # A failure raises: non-zero exit, traceback on stderr, no number.
    print(json.dumps(_headline_result(*HEADLINE)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
