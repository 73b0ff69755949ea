#!/usr/bin/env python
"""Optimizer convergence-sanity harness (VERDICT r4 next-round #1).

The round-4 on-chip sweep measured adafactor/lion ~+5% step throughput over
adamw on the GPT-2 flagship at the same operating point (mb4 remat=none:
31.7 / 31.6 vs 30.3 samples/sec/chip — 2026-07-30), and
adafactor's factored second moment additionally frees ~2 bytes/param of
optimizer HBM. Throughput alone can't justify a recipe change: a faster
optimizer that converges worse is a regression. This harness runs the SAME
tiny GPT LM task under each optimizer for N steps on the CPU sim and
reports final smoothed losses, so the recipe decision is recorded with
loss data next to the throughput data (docs/perf_playbook.md "Optimizer
choice on the flagship").

    JAX_PLATFORMS=cpu python tools/opt_convergence.py [--steps 300]

Emits one JSONL row per optimizer plus a verdict row comparing each
candidate's final loss against adamw's with the tolerance used by the
regression pin in tests/test_optimizers.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _force_cpu() -> None:
    """Pin the CPU backend UNCONDITIONALLY (overwrite, not setdefault)
    before jax is imported: this is a CPU-sim analysis tool; it must never
    take the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"


#: Model-scale presets. "tiny" (~0.9M params) separates optimizers in
#: seconds; "10m" (~10.4M params: d=384, L=4, T=256, V=8192) is the
#: 10–30M-param proxy the adafactor recipe-LR decision is pinned at —
#: big enough that the RELATIVE update's RMS(param) scaling and the
#: factored second moment behave like the flagship's, small enough that
#: >=1k steps complete on the CPU sim (ISSUE r6 satellite; evidence in
#: evidence_r6/opt_convergence_10m.log).
SCALES = {
    "tiny": [
        "model.num_layers=2", "model.num_heads=4", "model.hidden_dim=128",
        "model.seq_len=128", "model.vocab_size=512",
        "data.seq_len=128", "data.vocab_size=512",
    ],
    "10m": [
        "model.num_layers=4", "model.num_heads=6", "model.hidden_dim=384",
        "model.seq_len=256", "model.vocab_size=8192",
        "data.seq_len=256", "data.vocab_size=8192",
    ],
}


def run_one(opt_name: str, steps: int, lr: float, scale: str = "tiny") -> dict:
    import gc

    import jax

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    # Release the previous combo's params/opt-state/executables BEFORE this
    # one allocates (same settle as tools/perf_sweep.py build()): at the
    # 10m scale, three accumulated live Trainers are what silently killed
    # the first 1k-step evidence run between configs 3 and 4.
    gc.collect()
    jax.clear_caches()
    gc.collect()

    # GPT on the synthetic-LM task: same model family and loss surface
    # as the flagship, at a SCALES preset. The
    # synthetic stream has learnable structure (repeating n-gram statistics),
    # so loss drops far below ln(vocab) and optimizers separate.
    cfg = apply_overrides(get_config("gpt2_medium_zero1"), SCALES[scale] + [
        "data.global_batch_size=8",
        "trainer.grad_accum=1", "trainer.remat=none",
        "trainer.log_every=1000000", "trainer.total_steps=%d" % steps,
        "optimizer.name=%s" % opt_name,
        "optimizer.learning_rate=%g" % lr,
        "optimizer.warmup_steps=20",
        "mesh.fsdp=1", "mesh.data=-1",
        "precision.policy=fp32",
        "checkpoint.enabled=false",
    ])
    trainer = Trainer(cfg)
    state = trainer.init_state()
    losses = []
    for step in range(steps):
        batch = trainer.pipeline.global_batch(step)
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    tail = losses[-max(1, steps // 10):]
    return {
        "optimizer": opt_name,
        "lr": lr,
        "steps": steps,
        "scale": scale,
        "loss_first": round(losses[0], 4),
        # Early-trajectory marker: what the regression pin in
        # tests/test_optimizers.py can afford to re-measure.
        "loss_step40": round(losses[min(39, steps - 1)], 4),
        "loss_final_mean": round(sum(tail) / len(tail), 4),
        "loss_min": round(min(losses), 4),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--scale", choices=sorted(SCALES), default="tiny")
    args = ap.parse_args()
    _force_cpu()

    # Per-optimizer LR grids at standard ratios: lion wants ~3-10x below
    # adamw (Chen et al. 2023); adafactor's update is RELATIVE (scaled by
    # RMS(param)), so its working LR sits ~30-100x above adamw's — the
    # first run of this tool proved the point the hard way (adafactor at
    # the adamw 3e-4: loss 6.26 -> 6.20 in 300 steps, i.e. barely moved,
    # vs 4.07 for adamw; see evidence_r5/opt_convergence.log).
    grid = {
        "adamw": [3e-4],
        "adafactor": [1e-2, 3e-2],
        "lion": [1e-4, 3e-4],
    }
    if args.scale == "10m":
        # The recipe-LR de-risk run: bracket the pinned 1e-2 from both
        # sides; lion is out of scope for this decision.
        grid = {"adamw": [3e-4], "adafactor": [3e-3, 1e-2, 3e-2]}
    rows = []
    for name, lrs in grid.items():
        for lr in lrs:
            r = run_one(name, args.steps, lr, scale=args.scale)
            rows.append(r)
            print(json.dumps(r), flush=True)
    best = {}
    for r in rows:
        cur = best.get(r["optimizer"])
        if cur is None or r["loss_final_mean"] < cur["loss_final_mean"]:
            best[r["optimizer"]] = r
    base = best["adamw"]
    verdict = {
        "mode": "verdict",
        "tolerance": 1.10,
        "best_lr_per_optimizer": {
            k: v["lr"] for k, v in sorted(best.items())
        },
        "candidates_within_tolerance": sorted(
            k for k, v in best.items()
            if v["loss_final_mean"] <= base["loss_final_mean"] * 1.10
        ),
    }
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
