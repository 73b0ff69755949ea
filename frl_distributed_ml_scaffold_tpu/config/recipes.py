"""The five reference recipes (BASELINE.json ``configs``), TPU-native.

Each maps a reference workload onto mesh axes + sharding annotations instead
of DDP/FSDP wrappers:

1. ``mnist_mlp``          — single-process trainer-loop smoke test.
2. ``imagenet_rn50_ddp``  — DP over the ``data`` axis (GSPMD inserts the
                            gradient allreduce that NCCL-DDP did), bf16.
3. ``imagenet_vitb_fsdp`` — params+grads+opt state full-sharded over the
                            ``fsdp`` axis + activation checkpointing.
4. ``gpt2_medium_zero1``  — grad accumulation + ZeRO-1 optimizer-state
                            sharding on a replicated-param transformer.
5. ``ego4d_video_elastic``— video-clip classifier with sharded checkpoints,
                            run under the elastic supervisor.

Plus additional recipes exercising TP/PP/SP/EP, which the task brief makes
first-class even though the reference configs don't name them.
"""

from __future__ import annotations

import dataclasses

from frl_distributed_ml_scaffold_tpu.config.registry import register_config
from frl_distributed_ml_scaffold_tpu.config.schema import (
    CheckpointConfig,
    DataConfig,
    ExperimentConfig,
    GPTConfig,
    MLPConfig,
    MeshConfig,
    MoEConfig,
    OptimizerConfig,
    ParallelConfig,
    PrecisionConfig,
    ResNetConfig,
    TrainerConfig,
    VideoConfig,
    ViTConfig,
)


@register_config("mnist_mlp")
def mnist_mlp() -> ExperimentConfig:
    """BASELINE config 1: MLP on MNIST, single-process smoke test."""
    return ExperimentConfig(
        name="mnist_mlp",
        model=MLPConfig(hidden_sizes=(512, 256), num_classes=10),
        data=DataConfig(name="mnist", global_batch_size=256, image_size=28, channels=1),
        trainer=TrainerConfig(total_steps=1500, log_every=100, eval_every=500, eval_steps=20),
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-3, schedule="cosine", warmup_steps=50),
        mesh=MeshConfig(data=-1),
        precision=PrecisionConfig(policy="fp32"),
    )


@register_config("imagenet_rn50_ddp")
def imagenet_rn50_ddp() -> ExperimentConfig:
    """BASELINE config 2: ResNet-50 ImageNet, DP (the DDP equivalent), bf16."""
    return ExperimentConfig(
        name="imagenet_rn50_ddp",
        model=ResNetConfig(depth=50, num_classes=1000),
        data=DataConfig(
            name="imagenet", global_batch_size=1024, image_size=224, channels=3, num_classes=1000
        ),
        trainer=TrainerConfig(total_steps=112590, log_every=100, eval_every=5000),
        optimizer=OptimizerConfig(
            name="sgd", learning_rate=0.4, momentum=0.9, weight_decay=1e-4,
            schedule="cosine", warmup_steps=1565,
        ),
        mesh=MeshConfig(data=-1),
        parallel=ParallelConfig(param_sharding="replicated"),
        precision=PrecisionConfig(policy="bf16_mixed"),
    )


@register_config("imagenet_vitb_fsdp")
def imagenet_vitb_fsdp() -> ExperimentConfig:
    """BASELINE config 3: ViT-B/16 ImageNet, FSDP full-shard + remat."""
    return ExperimentConfig(
        name="imagenet_vitb_fsdp",
        model=ViTConfig(image_size=224, patch_size=16, hidden_dim=768, num_layers=12,
                        num_heads=12, num_classes=1000),
        data=DataConfig(
            name="imagenet", global_batch_size=1024, image_size=224, channels=3, num_classes=1000
        ),
        trainer=TrainerConfig(total_steps=93500, remat="full", log_every=100, eval_every=5000),
        optimizer=OptimizerConfig(
            name="adamw", learning_rate=3e-3, weight_decay=0.3,
            schedule="cosine", warmup_steps=10000, grad_clip_norm=1.0,
        ),
        mesh=MeshConfig(data=1, fsdp=-1),
        parallel=ParallelConfig(param_sharding="fsdp"),
        precision=PrecisionConfig(policy="bf16_mixed"),
    )


@register_config("gpt2_medium_zero1")
def gpt2_medium_zero1() -> ExperimentConfig:
    """BASELINE config 4: GPT-2-medium LM, grad-accum + ZeRO-1 opt sharding."""
    return ExperimentConfig(
        name="gpt2_medium_zero1",
        model=GPTConfig(
            vocab_size=50257, num_layers=24, num_heads=16, hidden_dim=1024, seq_len=1024
        ),
        data=DataConfig(
            name="lm_synthetic", global_batch_size=64, seq_len=1024, vocab_size=50257
        ),
        trainer=TrainerConfig(total_steps=100000, grad_accum=8, remat="dots", log_every=50),
        optimizer=OptimizerConfig(
            name="adamw", learning_rate=3e-4, weight_decay=0.1, b2=0.95,
            schedule="cosine", warmup_steps=2000, grad_clip_norm=1.0,
        ),
        mesh=MeshConfig(data=1, fsdp=-1),
        parallel=ParallelConfig(param_sharding="replicated", opt_sharding="zero1"),
        precision=PrecisionConfig(policy="bf16_mixed"),
    )


@register_config("ego4d_video_elastic")
def ego4d_video_elastic() -> ExperimentConfig:
    """BASELINE config 5: video-clip classifier, elastic + sharded ckpt resume."""
    return ExperimentConfig(
        name="ego4d_video_elastic",
        model=VideoConfig(num_frames=8, num_classes=400),
        data=DataConfig(
            name="video_synthetic", global_batch_size=64, image_size=224, channels=3,
            num_frames=8, num_classes=400,
        ),
        trainer=TrainerConfig(total_steps=30000, remat="full", log_every=50),
        optimizer=OptimizerConfig(
            name="adamw", learning_rate=1e-3, weight_decay=0.05,
            schedule="cosine", warmup_steps=2500, grad_clip_norm=1.0,
        ),
        mesh=MeshConfig(data=1, fsdp=-1),
        parallel=ParallelConfig(param_sharding="fsdp"),
        precision=PrecisionConfig(policy="bf16_mixed"),
        checkpoint=CheckpointConfig(enabled=True, save_every=500, max_to_keep=3),
    )


@register_config("gpt2_medium_adafactor")
def gpt2_medium_adafactor() -> ExperimentConfig:
    """Flagship LM on Adafactor: the measured-throughput variant of
    ``gpt2_medium_zero1``.

    Round-4 on-chip sweep (2026-07-30, TPU v5e, mb4
    remat=none): adafactor 31.7 vs adamw 30.3 samples/sec/chip (+4.6%),
    lion 31.6; and the factored second moment drops optimizer state from
    8 to ~4 bytes/param — on a 345M-param model that frees ~1.4 GB of
    HBM for activations/microbatch. Convergence sanity (tools/
    opt_convergence.py, evidence_r5/opt_convergence.log, pinned by
    tests/test_optimizers.py): adafactor's update is RELATIVE, so the
    adamw LR must NOT be inherited — at 3e-4 it barely moves (6.26→6.20
    in 300 steps); at its conventional 1e-2 it beats adamw's final loss
    outright (0.83 vs 4.07 on the proxy task; 3e-2 measured better still
    on the proxy, 1e-2 kept for scale-stability convention, T5/PaLM
    practice). De-risked at scale round 6 (ISSUE r6: the 0.48M proxy was
    judged too small to pin a recipe LR): the SAME grid at a 10.34M-param
    proxy for 1000 steps — evidence_r6/opt_convergence_10m.log, pinned by
    test_adafactor_recipe_lr_at_10m_proxy — confirms 1e-2 from both
    sides of the bracket: adafactor@1e-2 0.7274 final loss vs adamw@3e-4
    0.8519 (wins outright at scale too), while 3e-3 under-trains (2.68)
    and 3e-2 ties (0.7342) — at 10M params 1e-2 is already the optimum,
    not just the stability-conservative pick.
    The BASELINE-faithful recipe keeps adamw (reference
    config 4 parity); this variant is the recorded recipe-level decision
    for throughput-first runs. ZeRO-1 is redundant under adafactor's
    factored state, so opt_sharding stays for parity of comparison only.
    """
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_medium_adafactor",
        optimizer=dataclasses.replace(
            base.optimizer, name="adafactor", learning_rate=1e-2,
            weight_decay=0.0,
        ),
    )


@register_config("gpt2_medium_fsdp_overlap")
def gpt2_medium_fsdp_overlap() -> ExperimentConfig:
    """Flagship LM under overlap-scheduled FSDP (parallel/fsdp_overlap.py):
    params full-sharded over ``fsdp`` with EXPLICIT per-block all-gather /
    reduce-scatter and one-block-ahead prefetch, instead of GSPMD's
    gather-up-front schedule. The sweep config for the on-chip A/B
    (tools/perf_sweep.py gpt2_fsdp_overlap, queued in BACKLOG): same
    operating point as the gpt2_medium_zero1 protocol row so the step-time
    delta reads as the scheduling win alone. Correctness is sim-gated in
    tests/test_fsdp_overlap.py (numerics vs the GSPMD FSDP path, blockwise
    gather jaxpr assertion, mesh compositions)."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_medium_fsdp_overlap",
        mesh=MeshConfig(data=1, fsdp=-1),
        parallel=ParallelConfig(
            param_sharding="fsdp",
            opt_sharding="like_params",  # opt state inherits the fsdp shards
            fsdp_overlap=True,
            fsdp_prefetch=1,
        ),
    )


@register_config("gpt2_medium_tp_overlap")
def gpt2_medium_tp_overlap() -> ExperimentConfig:
    """Flagship LM under latency-hiding tensor parallelism
    (parallel/tp_overlap.py): the four per-block TP matmuls run as
    bidirectional collective-matmul rings (ppermute-chained blocks, comm
    hidden under compute) with the residual stream sequence-sharded over
    the model axis, instead of GSPMD's monolithic per-layer allreduces.
    The sweep config for the on-chip A/B (tools/perf_sweep.py
    gpt2_tp_overlap, queued in BACKLOG R7): same operating point as the
    gpt2_tp showcase so the step-time delta reads as the scheduling win
    alone. Correctness is sim-gated in tests/test_tp_overlap.py (numerics
    vs the GSPMD TP path, blockwise-ppermute jaxpr pins, mesh
    compositions)."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_medium_tp_overlap",
        mesh=MeshConfig(data=1, model=-1),
        parallel=ParallelConfig(
            param_sharding="replicated",
            opt_sharding="zero1",
            tp_overlap=True,
        ),
    )


@register_config("gpt2_medium_tp_overlap_int8")
def gpt2_medium_tp_overlap_int8() -> ExperimentConfig:
    """The low-precision fast path on the tp_overlap flagship: the four
    per-block collective-matmul rings ppermute int8 chunks + scales and
    run their matmuls on the MXU's 8-bit path (per-tensor activation /
    per-channel weight scales, bf16 master weights, straight-through
    grads — ops/quantization.py, parallel.low_precision). Comm bytes on
    the model-axis collective-permute class shrink with the element width
    (graft-lint pins it per dtype: a ring that ppermutes wide floats
    under this recipe is a lint error). Numerics vs the bf16/fp32 rings
    are tolerance-gated in tests/test_low_precision.py; the on-chip A/B
    rides the tp_overlap sweep slot (BACKLOG R7)."""
    base = gpt2_medium_tp_overlap()
    return base.replace(
        name="gpt2_medium_tp_overlap_int8",
        parallel=dataclasses.replace(base.parallel, low_precision="int8"),
    )


@register_config("gpt2_medium_fsdp_tp_overlap")
def gpt2_medium_fsdp_tp_overlap() -> ExperimentConfig:
    """The composed overlap schedule (parallel/schedule.py, ROADMAP item
    2's payoff case): BOTH explicit schedules in one scan body — params
    full-sharded over ``fsdp`` with blockwise in-scan all-gather /
    reduce-scatter (one-block-ahead prefetch), AND the four per-block TP
    matmuls running as bidirectional collective-matmul ppermute rings
    over ``model`` — with ZERO monolithic all_gathers in the step
    (jaxpr-pinned via ``analysis.pins.assert_schedule``; the declared
    schedule is ``gather(fsdp,block,prefetch=1)+scatter(fsdp)+
    gather(model,ring_chunk)+scatter(model)``). Correctness is sim-gated
    in tests/test_schedule.py (numerics vs the all-GSPMD fsdp x model
    path, program identity vs the explicit declaration string); the
    on-chip A/B rides ``tools/perf_sweep.py gpt2_fsdp_tp_overlap``
    (ROADMAP A4); its first run on real links is
    ``chip_smoke.py --chips 4``'s second arm."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_medium_fsdp_tp_overlap",
        mesh=MeshConfig(data=1, fsdp=-1, model=2),
        parallel=ParallelConfig(
            param_sharding="fsdp",
            opt_sharding="like_params",
            fsdp_overlap=True,
            fsdp_prefetch=1,
            tp_overlap=True,
        ),
    )


@register_config("gpt2_medium_fsdp_tp_overlap_int8")
def gpt2_medium_fsdp_tp_overlap_int8() -> ExperimentConfig:
    """The composed schedule with low precision as a transfer attribute:
    same blockwise fsdp gathers, but the model-axis rings ppermute int8
    chunks + scales (``lowp=int8`` on the ring pair). Census-pinned via
    ``assert_schedule`` to >= 3.5x lower model-axis ppermute bytes than
    the fp32 composed path (4x element width minus scale traffic);
    numerics tolerance-gated through the shared low-precision bands
    (docs/perf_playbook.md "Low-precision fast path")."""
    base = gpt2_medium_fsdp_tp_overlap()
    return base.replace(
        name="gpt2_medium_fsdp_tp_overlap_int8",
        parallel=dataclasses.replace(base.parallel, low_precision="int8"),
    )


# ----- task-required parallelism showcases beyond the reference configs -----


@register_config("gpt2_tp")
def gpt2_tp() -> ExperimentConfig:
    """Tensor-parallel transformer (SURVEY C6): Megatron column/row sharding."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_tp",
        mesh=MeshConfig(data=-1, model=2),
        parallel=ParallelConfig(param_sharding="replicated"),
        trainer=base.trainer,
    )


@register_config("gpt2_ring")
def gpt2_ring() -> ExperimentConfig:
    """Sequence-parallel long-context LM (SURVEY C8): ring attention."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_ring",
        model=GPTConfig(
            vocab_size=50257, num_layers=24, num_heads=16, hidden_dim=1024,
            seq_len=8192, attention="ring",
        ),
        data=DataConfig(name="lm_synthetic", global_batch_size=8, seq_len=8192),
        mesh=MeshConfig(data=-1, seq=4),
        parallel=ParallelConfig(param_sharding="replicated", sequence="ring"),
        # Long context already divides the batch finely; no microbatching.
        trainer=dataclasses.replace(base.trainer, grad_accum=1),
    )


@register_config("gpt2_long")
def gpt2_long() -> ExperimentConfig:
    """Single-chip long context (SURVEY C8 complement to ``gpt2_ring``):
    8k tokens through the Pallas flash kernel (O(block) memory, measured
    to 32k on one v5e — BASELINE.md) with the chunked-vocab loss and full
    remat keeping activations off HBM. No sequence axis needed until the
    context outgrows the chip."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_long",
        model=GPTConfig(
            vocab_size=50257, num_layers=24, num_heads=16, hidden_dim=1024,
            seq_len=8192, attention="flash", lm_loss_chunk=256,
        ),
        data=DataConfig(name="lm_synthetic", global_batch_size=8, seq_len=8192),
        mesh=MeshConfig(data=-1),
        parallel=ParallelConfig(param_sharding="replicated"),
        trainer=dataclasses.replace(base.trainer, grad_accum=8, remat="full"),
    )


@register_config("gpt2_moe")
def gpt2_moe() -> ExperimentConfig:
    """Expert-parallel MoE LM (SURVEY C9)."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_moe",
        model=GPTConfig(
            vocab_size=50257, num_layers=12, num_heads=16, hidden_dim=1024,
            seq_len=1024, moe=MoEConfig(num_experts=8, top_k=2),
        ),
        mesh=MeshConfig(data=-1, expert=4),
        parallel=ParallelConfig(param_sharding="replicated"),
    )


@register_config("gpt2_pp")
def gpt2_pp() -> ExperimentConfig:
    """Pipeline-parallel LM (SURVEY C7): 4 stages over the ``pipe`` axis,
    GPipe schedule with 8 microbatches (bubble = 3/11 of a step)."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_pp",
        model=GPTConfig(
            vocab_size=50257, num_layers=24, num_heads=16, hidden_dim=1024,
            seq_len=1024, pipeline_stages=4, pipeline_microbatches=8,
        ),
        mesh=MeshConfig(data=-1, pipe=4),
        parallel=ParallelConfig(param_sharding="replicated"),
        trainer=dataclasses.replace(base.trainer, grad_accum=1),
    )


@register_config("gpt2_pipeline_mpmd")
def gpt2_pipeline_mpmd() -> ExperimentConfig:
    """MPMD pipeline parallelism (ISSUE 14): the ``gpt2_pp`` operating
    point on the per-stage-program backend (parallel/mpmd_pipeline.py) —
    each of the 4 stages is its own jitted program on its pipe-slice
    submesh, a host-side 1F1B driver moves activations/gradients as
    explicit ``device_put`` transfers, and steady state holds min(S, M)=4
    in-flight microbatch activations instead of GPipe's 8. Loss/token
    parity with the SPMD backend is sim-gated in
    tests/test_mpmd_pipeline.py; the step-time A/B rides
    ``tools/perf_sweep.py gpt2_pipeline_mpmd`` (BACKLOG R17-1)."""
    base = gpt2_pp()
    return base.replace(
        name="gpt2_pipeline_mpmd",
        model=dataclasses.replace(base.model, pipeline_impl="mpmd"),
    )


@register_config("gpt2_pp_circular")
def gpt2_pp_circular() -> ExperimentConfig:
    """Circular (interleaved) pipeline: same 4 physical stages as
    ``gpt2_pp`` but each holds 2 virtual layer groups, cutting the bubble
    from 3/11 to 3/19 of a step at the cost of rotating activations
    through the ring twice."""
    base = gpt2_pp()
    return base.replace(
        name="gpt2_pp_circular",
        model=dataclasses.replace(base.model, pipeline_circular_repeat=2),
    )


@register_config("imagenet_rn101_ddp")
def imagenet_rn101_ddp() -> ExperimentConfig:
    """Deeper-variant showcase: ResNet-101 on the RN50 recipe (the torch
    zoo's standard scale-up; same schedule, depth=101 bottleneck stacks)."""
    base = imagenet_rn50_ddp()
    return base.replace(
        name="imagenet_rn101_ddp",
        model=dataclasses.replace(base.model, depth=101),
    )


@register_config("imagenet_vitl_fsdp")
def imagenet_vitl_fsdp() -> ExperimentConfig:
    """Scale-up showcase: ViT-L/16 (307M params) on the ViT-B FSDP recipe —
    the config where FSDP sharding and remat stop being optional on small
    slices."""
    base = imagenet_vitb_fsdp()
    return base.replace(
        name="imagenet_vitl_fsdp",
        model=dataclasses.replace(
            base.model, hidden_dim=1024, num_layers=24, num_heads=16
        ),
    )


@register_config("gpt2_medium_serve")
def gpt2_medium_serve() -> ExperimentConfig:
    """Flash-decode serving operating point (the BACKLOG R8-1 on-chip
    A/B): the gpt2_medium flagship weights served through
    ``serving/engine.py`` with the fused split-KV decode kernel
    (``model.decode_attention=flash``, the default) and the KV cache
    model-sharded over a 2-way ``model`` axis. ``tools/serve_bench.py``
    measures the four (decode impl x cache sharding) arms; this recipe
    records the mesh/model shape those arms load."""
    base = gpt2_medium_zero1()
    return base.replace(
        name="gpt2_medium_serve",
        model=dataclasses.replace(base.model, decode_attention="flash"),
        mesh=MeshConfig(data=-1, fsdp=1, model=2),
        parallel=ParallelConfig(param_sharding="replicated"),
    )
