#!/usr/bin/env python
"""Activation-residency audit of the pipeline schedules (SURVEY C7).

Answers, with jaxpr-level residual accounting, the question behind 1F1B:
how much activation memory must the backward hold under each schedule?
The scan-autodiff GPipe/circular formulation saves every tick's stage
activations until the reverse timeline consumes them — O(v·M + S) tick
buffers — where a hand-scheduled 1F1B holds O(S) microbatches in flight
per stage. This tool measures the actual forward→backward residuals of
the REAL loss function (``jax._src.ad_checkpoint.saved_residuals`` — the
same accounting ``jax.ad_checkpoint.print_saved_residuals`` prints, and
immune to XLA:CPU's CSE which silently undoes recompute in
``memory_analysis``):

    python tools/pp_memory_audit.py [--layers 8] [--batch 16] [...]

Reported per schedule: total residual bytes, the per-tick-stacked subset
(leading dim = v·M+S-1 — the part 1F1B eliminates), everything else
(embeddings/head — schedule-independent), and the per-stage residency
after ``pipe`` sharding. ``--remat full`` shows jax.checkpoint collapsing
top-level residuals to the inputs (peak then moves inside the recompute).
Emits one JSON line per variant plus a table; docs/perf_playbook.md
records the conclusions.

``--flagship`` switches to the single-chip GPT-2-medium audit (VERDICT r3
next-round #3): sweep (trainer.remat | model.block_remat) x microbatch at
the REAL protocol shapes (L=24, D=1024, T=1024, flash attention, chunked
LM loss) and report, per variant, the forward->backward residual bytes
the backward must hold, next to the config's resident-state bytes (fp32
master params + AdamW mu/nu + fp32 grads + bf16 compute copy), so
"does microbatch 8 fit in 15.75G?" is answerable from residual
accounting BEFORE spending chip time:

    python tools/pp_memory_audit.py --flagship [--mb 4 8 16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _residual_bytes(res) -> tuple[int, dict]:
    by_shape: dict = {}
    total = 0
    for aval, _src in res:
        if not hasattr(aval, "shape"):
            continue
        nbytes = int(aval.size) * aval.dtype.itemsize
        total += nbytes
        key = tuple(aval.shape)
        by_shape[key] = by_shape.get(key, 0) + nbytes
    return total, by_shape


def audit_one(args, sched: str, overrides: list[str], remat: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer
    from frl_distributed_ml_scaffold_tpu.trainer.tasks import example_input
    from frl_distributed_ml_scaffold_tpu.trainer.train_step import _remat_wrap

    base = [
        f"model.num_layers={args.layers}",
        f"model.hidden_dim={args.hidden}",
        f"model.num_heads={args.heads}",
        f"model.seq_len={args.seq}",
        f"model.vocab_size={args.vocab}",
        f"data.seq_len={args.seq}",
        f"data.vocab_size={args.vocab}",
        f"data.global_batch_size={args.batch}",
        "model.lm_loss_chunk=0",
        "trainer.grad_accum=1",
        "checkpoint.enabled=false",
        "data.prefetch=0",
        "precision.policy=bf16_mixed",
        f"trainer.remat={remat}",
    ]
    cfg = apply_overrides(get_config("gpt2_medium_zero1"), base + overrides)
    trainer = Trainer(cfg)
    example = {
        k: jnp.asarray(v)
        for k, v in example_input(
            cfg.data, cfg.model, batch_size=cfg.data.global_batch_size
        ).items()
    }
    wrapped = _remat_wrap(trainer.loss_fn, remat)

    def scalar_loss(params):
        loss, _ = wrapped(
            params, trainer.state_shapes.extras, example,
            jax.random.key(0), True,
        )
        return loss

    res = trainer._mesh_scoped(saved_residuals)(
        scalar_loss, trainer.state_shapes.params
    )
    total, by_shape = _residual_bytes(res)

    s = cfg.model.pipeline_stages
    v = max(1, cfg.model.pipeline_circular_repeat) if s > 1 else 1
    m = cfg.model.pipeline_microbatches or s
    ticks = v * m + s - 1 if s > 1 else 0
    # Param-shaped residuals (the weights the backward re-reads) are
    # resident state, not schedule cost — exclude them from the
    # activation figure by subtracting exact param-leaf sizes.
    param_bytes = sum(
        int(l.size) * l.dtype.itemsize
        for l in jax.tree.leaves(trainer.state_shapes.params)
    )
    ticked = sum(
        b for shape, b in by_shape.items() if ticks and shape[:1] == (ticks,)
    )
    rec = {
        "schedule": sched,
        "remat": remat,
        "ticks": ticks,
        "residual_mb": round(total / 1e6, 1),
        "residual_minus_params_mb": round((total - param_bytes) / 1e6, 1),
        "tick_stacked_mb": round(ticked / 1e6, 1),
        "other_mb": round((total - param_bytes - ticked) / 1e6, 1),
        # Tick-stacked residuals carry [ticks, S, mb, ...] with the S dim
        # pipe-sharded: per-stage residency is the 1/S slice.
        "tick_stacked_per_stage_mb": round(
            ticked / max(1, s) / 1e6, 1
        ),
    }
    print(json.dumps(rec), flush=True)
    return rec


def flagship_one(mb: int, remat: str, block_remat: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer
    from frl_distributed_ml_scaffold_tpu.trainer.tasks import example_input
    from frl_distributed_ml_scaffold_tpu.trainer.train_step import _remat_wrap

    cfg = apply_overrides(
        get_config("gpt2_medium_zero1"),
        [
            # The BENCH_TABLE protocol operating point (bench.py
            # ALL_CONFIGS), batch swept by the caller.
            f"data.global_batch_size={mb}",
            "trainer.grad_accum=1",
            "model.attention=flash",
            "model.lm_loss_chunk=128",
            # Single-chip semantics on the 8-device CPU sim: every mesh
            # axis 1, as on the real v5e chip the numbers are for.
            "mesh.data=1", "mesh.fsdp=1", "mesh.model=1",
            "mesh.pipe=1", "mesh.seq=1", "mesh.expert=1",
            f"trainer.remat={remat}",
            f"model.block_remat={block_remat}",
            "checkpoint.enabled=false",
            "data.prefetch=0",
        ],
    )
    trainer = Trainer(cfg)
    example = {
        k: jnp.asarray(v)
        for k, v in example_input(
            cfg.data, cfg.model, batch_size=mb
        ).items()
    }
    wrapped = _remat_wrap(trainer.loss_fn, remat)

    def scalar_loss(params):
        loss, _ = wrapped(
            params, trainer.state_shapes.extras, example,
            jax.random.key(0), True,
        )
        return loss

    res = trainer._mesh_scoped(saved_residuals)(
        scalar_loss, trainer.state_shapes.params
    )
    total, by_shape = _residual_bytes(res)
    param_bytes = sum(
        int(l.size) * l.dtype.itemsize
        for l in jax.tree.leaves(trainer.state_shapes.params)
    )
    # Resident state for this config (ZeRO-1 on one chip = unsharded):
    # fp32 master params, AdamW mu+nu (fp32, like_params), fp32 grads
    # held across the update, plus the bf16 compute-cast copy alive
    # through the backward.
    resident = param_bytes * (1 + 2 + 1) + param_bytes // 2
    act = total - param_bytes
    rec = {
        "mb": mb,
        "remat": remat,
        "block_remat": block_remat,
        "residual_minus_params_mb": round(act / 1e6, 1),
        "resident_state_mb": round(resident / 1e6, 1),
        "total_mb": round((act + resident) / 1e6, 1),
        "fits_15_75g": bool(act + resident < 15.75e9),
    }
    print(json.dumps(rec), flush=True)
    return rec


def moe_one(g: int, batch: int, experts: int, block_remat: str) -> dict:
    """Residual audit of the FULL MoE train step at real routed shapes
    (VERDICT r3 next-round #5): N = batch*1024 tokens, E experts, k=2,
    G routing groups. Separates the dispatch/combine one-hot tensors
    ([G, S, E, C] — the GSEC memory story) from everything else."""
    import jax
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer
    from frl_distributed_ml_scaffold_tpu.trainer.tasks import example_input

    cfg = apply_overrides(
        get_config("gpt2_moe"),
        [
            f"data.global_batch_size={batch}",
            f"model.moe.num_experts={experts}",
            f"model.moe.num_groups={g}",
            "model.attention=flash",
            "model.lm_loss_chunk=128",
            f"model.block_remat={block_remat}",
            "trainer.grad_accum=1",
            "checkpoint.enabled=false",
            "data.prefetch=0",
            "mesh.data=1", "mesh.fsdp=1", "mesh.model=1",
            "mesh.pipe=1", "mesh.seq=1", "mesh.expert=1",
        ],
    )
    trainer = Trainer(cfg)
    example = {
        k: jnp.asarray(v)
        for k, v in example_input(
            cfg.data, cfg.model, batch_size=batch
        ).items()
    }

    def scalar_loss(params):
        loss, _ = trainer.loss_fn(
            params, trainer.state_shapes.extras, example,
            jax.random.key(0), True,
        )
        return loss

    res = trainer._mesh_scoped(saved_residuals)(
        scalar_loss, trainer.state_shapes.params
    )
    total, by_shape = _residual_bytes(res)
    param_bytes = sum(
        int(l.size) * l.dtype.itemsize
        for l in jax.tree.leaves(trainer.state_shapes.params)
    )
    n = batch * cfg.model.seq_len
    s = n // g
    e = experts
    cap = max(1, int(cfg.model.moe.capacity_factor * s * 2 / e))
    # Dispatch/combine and their einsum partners carry the capacity dim —
    # count every residual whose trailing dims look like [.., E, C] or
    # [E, .., C, ..] (expert_in/out are [E, G, C, D]).
    gsec = sum(
        b for shape, b in by_shape.items()
        if (len(shape) >= 3 and shape[-2:] == (e, cap))
        or (len(shape) == 4 and shape[0] == e and shape[2] == cap)
    )
    rec = {
        "groups": g,
        "batch": batch,
        "experts": e,
        "capacity": cap,
        "block_remat": block_remat,
        "residual_minus_params_mb": round((total - param_bytes) / 1e6, 1),
        "gsec_tensors_mb": round(gsec / 1e6, 1),
        "other_mb": round((total - param_bytes - gsec) / 1e6, 1),
    }
    print(json.dumps(rec), flush=True)
    return rec


def moe_main(args) -> int:
    rows = []
    for br in ("none", "full"):
        for g in args.groups:
            rows.append(moe_one(g, args.batch, args.experts, br))
    print(
        f"\n{'G':>3s} {'block_remat':>11s} {'cap':>5s} "
        f"{'activations MB':>15s} {'GSEC MB':>9s} {'other MB':>9s}"
    )
    for r in rows:
        print(
            f"{r['groups']:3d} {r['block_remat']:>11s} {r['capacity']:5d} "
            f"{r['residual_minus_params_mb']:15.1f} "
            f"{r['gsec_tensors_mb']:9.1f} {r['other_mb']:9.1f}"
        )
    return 0


def flagship_main(args) -> int:
    variants = [
        ("dots", "none"),   # the round-3 protocol line (mb4 knee)
        ("none", "none"),
        ("full", "none"),
        ("none", "full"),
        ("none", "save_attn"),
    ]
    rows = []
    for mb in args.mb:
        for remat, br in variants:
            rows.append(flagship_one(mb, remat, br))
    print(
        f"\n{'mb':>3s} {'remat':>6s} {'block_remat':>11s} "
        f"{'activations MB':>15s} {'resident MB':>12s} {'total MB':>9s}  fits15.75G"
    )
    for r in rows:
        print(
            f"{r['mb']:3d} {r['remat']:>6s} {r['block_remat']:>11s} "
            f"{r['residual_minus_params_mb']:15.1f} "
            f"{r['resident_state_mb']:12.1f} {r['total_mb']:9.1f}  "
            f"{'yes' if r['fits_15_75g'] else 'NO'}"
        )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--flagship", action="store_true",
                    help="single-chip GPT-2-medium remat-mode sweep")
    ap.add_argument("--mb", type=int, nargs="+", default=[4, 8, 16],
                    help="--flagship microbatch sizes")
    ap.add_argument("--moe", action="store_true",
                    help="MoE dispatch-memory audit at real routed shapes")
    ap.add_argument("--groups", type=int, nargs="+", default=[1, 8, 32],
                    help="--moe routing-group counts")
    ap.add_argument("--experts", type=int, default=64)
    args = ap.parse_args()
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # Flagship/MoE modes audit the one-chip config — a single CPU
        # device keeps the mesh honest; the PP audit needs the 8-device sim.
        n = 1 if (args.flagship or args.moe) else 8
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    if args.flagship:
        return flagship_main(args)
    if args.moe:
        return moe_main(args)

    gpipe_ov = [
        f"model.pipeline_stages={args.stages}",
        f"model.pipeline_microbatches={args.microbatches}",
        f"mesh.pipe={args.stages}", "mesh.data=2",
    ]
    circ_ov = gpipe_ov + [
        f"model.pipeline_circular_repeat={args.repeat}",
    ]
    sr = ["model.pipeline_stage_remat=true"]
    variants = [
        ("plain", ["model.pipeline_stages=1", "mesh.pipe=1", "mesh.data=8"]),
        ("gpipe", gpipe_ov),
        ("gpipe+sr", gpipe_ov + sr),
        ("circular", circ_ov),
        ("circular+sr", circ_ov + sr),
    ]
    rows = [audit_one(args, s, o, args.remat) for s, o in variants]
    print(
        f"\n{'schedule':10s} {'ticks':>5s} {'resid-params MB':>16s} "
        f"{'tick-stacked MB':>16s} {'per-stage MB':>13s}"
    )
    for r in rows:
        print(
            f"{r['schedule']:10s} {r['ticks']:5d} "
            f"{r['residual_minus_params_mb']:16.1f} "
            f"{r['tick_stacked_mb']:16.1f} "
            f"{r['tick_stacked_per_stage_mb']:13.1f}"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
