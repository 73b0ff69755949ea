#!/usr/bin/env python
"""Single-chip perf bisection for the RN50/ViT-B headline configs.

Run on a live TPU to localize where step time goes before optimizing
(BASELINE.md backlog). Each experiment is one JSONL line to stdout;
timing uses device_get of the loss, which depends on the whole step (the
sync utils/timing.py uses).

    python tools/perf_sweep.py            # full sweep
    python tools/perf_sweep.py rn50_bs    # one experiment group
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")


def timed_steps(trainer, state, batch, n=12, warm=3):
    """Returns (per-step seconds, final state). The final state matters:
    train_step donates its input state, so callers must NEVER reuse the
    state they passed in (deleted buffers on real TPU)."""
    import jax

    for _ in range(warm):
        state, m = trainer.train_step(state, batch)
    jax.device_get(m["loss"])
    t0 = time.perf_counter()
    for _ in range(n):
        state, m = trainer.train_step(state, batch)
    jax.device_get(m["loss"])
    return (time.perf_counter() - t0) / n, state


def build(name, overrides):
    import gc

    import jax

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    # Release the previous combo's buffers BEFORE allocating this one's:
    # sweeping big configs in one process otherwise accumulates the old
    # trainer's params/opt-state/executables (reference cycles defer GC;
    # the jit cache pins executables) until HBM-heavy combos that fit in
    # isolation die with RESOURCE_EXHAUSTED — exactly what the first
    # on-chip run of gpt2_opt produced (2026-07-30: 17/18 combos failed
    # after combo 1 succeeded).
    gc.collect()
    jax.clear_caches()
    gc.collect()
    cfg = apply_overrides(
        get_config(name),
        ["data.prefetch=0", "trainer.log_every=1000000"] + overrides,
    )
    trainer = Trainer(cfg)
    state = trainer.init_state()
    batch = trainer.pipeline.global_batch(0)
    return trainer, state, batch


def emit(tag, bs, dt, extra=None):
    rec = {
        "experiment": tag,
        "global_batch_size": bs,
        "step_time_ms": round(dt * 1e3, 2),
        "samples_per_sec_per_chip": round(bs / dt, 1),
    }
    rec.update(extra or {})
    print(json.dumps(rec), flush=True)



def measure(name, overrides, n=12, warm=3):
    """Build -> time -> release. Holds no refs to the previous combo while
    the next one allocates (build() collects the garbage); use this for
    every multi-combo sweep over HBM-heavy configs."""
    t, s, b = build(name, overrides)
    dt, s = timed_steps(t, s, b, n=n, warm=warm)
    del t, s, b
    return dt


def measure_or_emit(experiment, bs, name, overrides, tag, *, n=12, warm=3):
    """measure() + emit(), recording failures as rows instead of aborting
    the sweep — HBM-rejected combos are DATA (they map the memory wall).
    One implementation for every grid that wants keep-sweeping semantics.
    """
    try:
        dt = measure(name, overrides, n=n, warm=warm)
        emit(experiment, bs, dt, tag)
    except Exception as e:
        print(
            json.dumps(
                {"experiment": experiment, "global_batch_size": bs,
                 **tag, "error": str(e)[:160]}
            ),
            flush=True,
        )

def rn50_bs():
    """Throughput knee: where does adding batch stop helping?"""
    for bs in (256, 512, 768, 1024):
        dt = measure("imagenet_rn50_ddp", [f"data.global_batch_size={bs}"])
        emit("rn50_bs", bs, dt)


def rn50_precision():
    for policy in ("bf16_mixed", "bf16", "fp32"):
        dt = measure(
            "imagenet_rn50_ddp",
            ["data.global_batch_size=512", f"precision.policy={policy}"],
        )
        emit("rn50_precision", 512, dt, {"policy": policy})


def rn50_fwd_only():
    """Eval step ~= forward: splits fwd from bwd+update in the step time."""
    import jax

    t, s, b = build("imagenet_rn50_ddp", ["data.global_batch_size=512"])
    dt, s = timed_steps(t, s, b)  # s was donated; use the returned state
    emit("rn50_train", 512, dt)
    for _ in range(3):
        m = t.eval_step(s, b)
    jax.device_get(m["loss"])
    t0 = time.perf_counter()
    for _ in range(10):
        m = t.eval_step(s, b)
    jax.device_get(m["loss"])
    emit("rn50_eval_fwd", 512, (time.perf_counter() - t0) / 10)


def rn50_depth():
    """Stem vs body: depth-18 shares the stem; scaling with depth separates
    the (fixed) stem+head cost from the residual body."""
    for depth in (18, 34, 50):
        dt = measure(
            "imagenet_rn50_ddp",
            ["data.global_batch_size=512", f"model.depth={depth}"],
        )
        emit("rn50_depth", 512, dt, {"depth": depth})


def rn50_stem():
    """conv7 vs the exact space-to-depth rewrite (MLPerf stem)."""
    for stem in ("conv7", "s2d"):
        dt = measure(
            "imagenet_rn50_ddp",
            ["data.global_batch_size=512", f"model.stem={stem}"],
        )
        emit("rn50_stem", 512, dt, {"stem": stem})


def rn50_split():
    """Where does the 228ms step go? fwd+loss (train-mode BN) vs fwd+bwd
    (grad, no update) vs the full step — separates forward, backward and
    optimizer/update costs with the real training-mode graph."""
    import jax
    import jax.numpy as jnp

    t, s, b = build("imagenet_rn50_ddp", ["data.global_batch_size=512"])
    dt, s = timed_steps(t, s, b)
    emit("rn50_split_full_step", 512, dt)

    lf = t.loss_fn
    rng = jax.random.key(0)

    fwd = jax.jit(lambda st, bt: lf(st.params, st.extras, bt, rng, True)[0])
    for _ in range(3):
        l = fwd(s, b)
    jax.device_get(l)
    t0 = time.perf_counter()
    for _ in range(10):
        l = fwd(s, b)
    jax.device_get(l)
    emit("rn50_split_fwd_train", 512, (time.perf_counter() - t0) / 10)

    grad = jax.jit(
        lambda st, bt: jax.grad(
            lambda p: lf(p, st.extras, bt, rng, True)[0]
        )(st.params)
    )

    def gnorm(g):
        return jnp.sqrt(sum(jnp.vdot(x, x) for x in jax.tree.leaves(g)))

    for _ in range(3):
        g = grad(s, b)
    jax.device_get(gnorm(g))
    t0 = time.perf_counter()
    for _ in range(10):
        g = grad(s, b)
    jax.device_get(gnorm(g))
    emit("rn50_split_fwd_bwd", 512, (time.perf_counter() - t0) / 10)


def vitb():
    for bs in (128, 256, 512):
        dt = measure("imagenet_vitb_fsdp", [f"data.global_batch_size={bs}"])
        emit("vitb_bs", bs, dt)


def rn50_headline():
    """Exactly the bench.py headline candidate (s2d stem, bs=512), timed
    with a long window so XLA-flag experiments (tools/xla_flag_sweep.py —
    flags must be set before jax init, hence one subprocess per flag set)
    compare step time, not the per-window sync."""
    import os

    t, s, b = build(
        "imagenet_rn50_ddp",
        ["data.global_batch_size=512", "model.stem=s2d"],
    )
    dt, _ = timed_steps(t, s, b, n=30, warm=4)
    emit("rn50_headline", 512, dt,
         {"xla_flags": os.environ.get("XLA_FLAGS", "")})


def rn50_pool():
    """select_and_scatter vs the mask-based custom-VJP maxpool backward
    (models/resnet.py::_max_pool_mask_grad) on the headline candidate."""
    for pg in ("scatter", "mask"):
        dt = measure(
            "imagenet_rn50_ddp",
            ["data.global_batch_size=512", "model.stem=s2d",
             f"model.pool_grad={pg}"],
            n=30, warm=4,
        )
        emit("rn50_pool", 512, dt, {"pool_grad": pg})


def gpt2_opt():
    """Attack the worst headline number (GPT-2-medium 33.7% MFU, VERDICT r2):
    the binding constraint is AdamW's ~4.3 GB fp32 state, and the repo
    already ships two state-lean optimizers — Adafactor (sublinear) and
    Lion (half). Sweep optimizer x microbatch x remat; HBM-rejected combos
    are recorded as rows (the compiler rejects them), so the output maps
    the memory wall, not just the throughput."""
    base = [
        "model.attention=flash",
        "model.lm_loss_chunk=128",
        "trainer.grad_accum=1",
    ]
    for opt in ("adamw", "adafactor", "lion"):
        for mb in (4, 8, 16):
            for remat in ("dots", "none"):
                measure_or_emit(
                    "gpt2_opt", mb, "gpt2_medium_zero1",
                    base + [
                        f"optimizer.name={opt}",
                        f"data.global_batch_size={mb}",
                        f"trainer.remat={remat}",
                    ],
                    {"optimizer": opt, "remat": remat},
                    n=10, warm=3,
                )


def gpt2_block_remat():
    """The round-4 attack on the 33.7% MFU wall: per-block remat
    (model.block_remat) caps backward residency at the L carry boundaries
    plus one block's internals — the flagship audit (pp_memory_audit
    --flagship) shows mb8 needs 6.8G (full) / 7.2G (save_attn) vs 24.5G
    with remat=dots — so the microbatch can finally grow past 4. Sweep
    the unlocked operating points against the mb4/dots protocol line."""
    base = [
        "model.attention=flash",
        "model.lm_loss_chunk=128",
        "trainer.grad_accum=1",
        "trainer.remat=none",
    ]
    # Protocol baseline first so every run of this group is self-contained.
    dt = measure(
        "gpt2_medium_zero1",
        ["model.attention=flash", "model.lm_loss_chunk=128",
         "trainer.grad_accum=1", "data.global_batch_size=4",
         "trainer.remat=dots"],
        n=10, warm=3,
    )
    emit("gpt2_block_remat", 4, dt, {"remat": "dots", "block_remat": "none"})
    for br in ("save_attn", "full"):
        for mb in (8, 16, 32):
            measure_or_emit(
                "gpt2_block_remat", mb, "gpt2_medium_zero1",
                base + [
                    f"model.block_remat={br}",
                    f"data.global_batch_size={mb}",
                ],
                {"remat": "none", "block_remat": br},
                n=10, warm=3,
            )


def gpt2_fsdp_overlap():
    """Round-6 A/B, queued for a multi-chip run (ROADMAP A4):
    overlap-scheduled FSDP (parallel.fsdp_overlap — explicit per-block
    all-gather/reduce-scatter with one-block-ahead prefetch) vs the plain
    GSPMD FSDP schedule, at the flagship operating point of the
    gpt2_medium_fsdp_overlap recipe. Needs >= 2 devices for a real fsdp
    axis; on a single chip it emits a skip row instead of a
    meaningless comm-free "A/B". Correctness is already sim-gated
    (tests/test_fsdp_overlap.py); this measures whether the explicit
    schedule recovers the hidden gather time (docs/perf_playbook.md
    "Overlap-scheduled FSDP")."""
    import jax

    n = jax.device_count()
    if n < 2:
        print(json.dumps({
            "experiment": "gpt2_fsdp_overlap",
            "skipped": f"needs >=2 devices for an fsdp axis (have {n})",
        }), flush=True)
        return
    base = [
        "model.attention=flash",
        "model.lm_loss_chunk=128",
        "trainer.grad_accum=1",
        "trainer.remat=none",
        "model.block_remat=full",
        "mesh.data=1",
        f"mesh.fsdp={n}",
    ]
    for overlap in ("false", "true"):
        for per_chip in (8, 16):
            bs = per_chip * n
            measure_or_emit(
                "gpt2_fsdp_overlap", bs, "gpt2_medium_fsdp_overlap",
                base + [
                    f"parallel.fsdp_overlap={overlap}",
                    f"data.global_batch_size={bs}",
                ],
                {"fsdp_overlap": overlap, "n_chips": n},
                n=10, warm=3,
            )


def gpt2_tp_overlap():
    """Round-7 A/B, queued for a multi-chip run (ROADMAP A4, BACKLOG
    R7): latency-hiding tensor parallelism (parallel.tp_overlap — the
    collective-matmul ppermute rings of ops/collective_matmul.py) vs the
    plain GSPMD TP schedule, at the gpt2_medium_tp_overlap operating
    point. Needs >= 2 devices for a real model axis; on a single chip
    it emits a skip row instead of a meaningless comm-free "A/B".
    Correctness is already sim-gated (tests/test_tp_overlap.py); this
    measures whether the rings actually hide the per-layer model-axis
    comm — capture a trace alongside and read tools/trace_analyze.py's
    per-class overlap summary (collective-permute hidden vs exposed)."""
    import jax

    n = jax.device_count()
    if n < 2:
        print(json.dumps({
            "experiment": "gpt2_tp_overlap",
            "skipped": f"needs >=2 devices for a model axis (have {n})",
        }), flush=True)
        return
    base = [
        "trainer.grad_accum=1",
        "trainer.remat=none",
        "model.block_remat=full",
        "mesh.data=1",
        f"mesh.model={n}",
    ]
    for overlap in ("false", "true"):
        for global_bs in (8, 16):
            measure_or_emit(
                "gpt2_tp_overlap", global_bs, "gpt2_medium_tp_overlap",
                base + [
                    f"parallel.tp_overlap={overlap}",
                    f"data.global_batch_size={global_bs}",
                ],
                {"tp_overlap": overlap, "n_chips": n},
                n=10, warm=3,
            )


def moe_dispatch():
    """Round-5 A/B the FLOP table predicts sort wins (einsum exchange =
    66% of step FLOPs at the audited shapes; sort cuts total 1.79x —
    tools/moe_dispatch_cost.py / docs/perf_playbook.md "Dispatch
    FLOPs"). Measures the full gpt2_moe single-chip protocol operating
    point under each moe.dispatch; the recipe default flips only if the
    measured step time agrees with the cost model (BACKLOG R5-2)."""
    base = [
        "data.global_batch_size=8", "trainer.grad_accum=1",
        "model.attention=flash", "model.lm_loss_chunk=128",
        "mesh.expert=1", "optimizer.name=adafactor",
        "trainer.remat=none", "model.block_remat=full",
    ]
    for dispatch in ("einsum", "sort"):
        measure_or_emit(
            "moe_dispatch", 8, "gpt2_moe",
            base + [f"model.moe.dispatch={dispatch}"],
            {"dispatch": dispatch}, n=10, warm=3,
        )


def gpt2_offload():
    """Re-test opt-state host offload under bigger batches: the ~17x
    pinned_host streaming cost (docs/perf_playbook.md) amortizes
    differently when the freed HBM buys 2-4x microbatch."""
    base = [
        "model.attention=flash",
        "model.lm_loss_chunk=128",
        "trainer.grad_accum=1",
        "trainer.offload_opt_state=true",
    ]
    for opt in ("adamw", "adafactor"):
        for mb in (8, 16, 32):
            measure_or_emit(
                "gpt2_offload", mb, "gpt2_medium_zero1",
                base + [
                    f"optimizer.name={opt}",
                    f"data.global_batch_size={mb}",
                    "trainer.remat=dots",
                ],
                {"optimizer": opt}, n=8, warm=3,
            )


def rn50_fused_opt():
    """BACKLOG-5 experiment: the RN50 optimizer+casts segment is ~7 ms/step
    of pure bandwidth; compare the recipe default (sgd), optax adamw, and
    the single-Pallas-pass fused_adamw (ops/fused_adamw.py). Ship
    fused_adamw as a recommendation only if this measures a win."""
    for opt in ("sgd", "adamw", "fused_adamw"):
        dt = measure(
            "imagenet_rn50_ddp",
            ["data.global_batch_size=512", "model.stem=s2d",
             f"optimizer.name={opt}"],
            n=30, warm=4,
        )
        emit("rn50_fused_opt", 512, dt, {"optimizer": opt})


def gpt2_fsdp_tp_overlap():
    """The composed-schedule A/B (ISSUE 13, queued for a multi-chip
    run alongside R6-1/R7-1, ROADMAP A4): the unified overlap
    schedule with BOTH axes declared — blockwise fsdp gathers AND
    model-axis collective-matmul rings in one scan body
    (gpt2_medium_fsdp_tp_overlap) — vs the all-GSPMD fsdp x model
    schedule, plus the int8 transfer arm (lowp as a schedule attribute).
    Needs >= 4 devices (a real fsdp axis x model=2); on fewer
    it emits a skip row. Correctness is sim-gated (tests/test_schedule.py
    numerics grid + assert_schedule jaxpr/census pins); this measures
    whether the composed explicit schedules hide BOTH collective classes
    at once — capture a trace and read tools/trace_analyze.py's
    per-class overlap summary (all-gather AND collective-permute hidden
    vs exposed)."""
    import jax

    n = jax.device_count()
    if n < 4:
        print(json.dumps({
            "experiment": "gpt2_fsdp_tp_overlap",
            "skipped": f"needs >=4 devices for fsdp x model (have {n})",
        }), flush=True)
        return
    base = [
        "trainer.grad_accum=1",
        "trainer.remat=none",
        "model.block_remat=full",
        "mesh.data=1",
        f"mesh.fsdp={n // 2}",
        "mesh.model=2",
    ]
    for overlap, lowp in (("false", "none"), ("true", "none"),
                          ("true", "int8")):
        for per_chip in (4, 8):
            bs = per_chip * n
            measure_or_emit(
                "gpt2_fsdp_tp_overlap", bs, "gpt2_medium_fsdp_tp_overlap",
                base + [
                    f"parallel.fsdp_overlap={overlap}",
                    f"parallel.tp_overlap={overlap}",
                    f"parallel.low_precision={lowp}",
                    f"data.global_batch_size={bs}",
                ],
                {"overlap": overlap, "lowp": lowp, "n_chips": n},
                n=10, warm=3,
            )


def gpt2_pipeline_mpmd():
    """The MPMD-vs-SPMD pipeline backend A/B (ISSUE 14, queued as
    BACKLOG R17-1 for a multi-chip run, ROADMAP A4): the gpt2_pp
    operating point (4 stages x 8 microbatches) under the stage-vmap
    GPipe program vs the per-stage-program 1F1B driver
    (model.pipeline_impl) — the step-time delta reads as
    schedule+memory-profile win alone (loss/token parity is sim-gated in
    tests/test_mpmd_pipeline.py). Needs >= 4 devices for the pipe axis;
    capture a trace and check the driver's explicit device_put transfers
    overlap the per-stage compute (trace_analyze lanes), plus HBM
    headroom at larger microbatch counts — 1F1B's min(S, M) live
    activations vs GPipe's M is the lever that buys bigger M (smaller
    bubble) at flat memory."""
    import jax

    n = jax.device_count()
    if n < 4:
        print(json.dumps({
            "experiment": "gpt2_pipeline_mpmd",
            "skipped": f"needs >=4 devices for the pipe axis (have {n})",
        }), flush=True)
        return
    for impl in ("spmd", "mpmd"):
        for micro in (8, 16):
            bs = 64
            measure_or_emit(
                "gpt2_pipeline_mpmd", bs, "gpt2_pipeline_mpmd",
                [
                    f"model.pipeline_impl={impl}",
                    f"model.pipeline_microbatches={micro}",
                    "mesh.pipe=4",
                    f"mesh.data={n // 4}",
                    f"data.global_batch_size={bs}",
                ],
                {"impl": impl, "microbatches": micro, "n_chips": n},
                n=10, warm=3,
            )


def reshard_train_to_serve():
    """The train→serve handoff A/B (ISSUE 15, queued as BACKLOG R18-1):
    redistribute a gpt2 fsdp×model training params tree onto the
    serving TP layout via the plan executor vs the replicated-staging
    reference (device_get the full tree, device_put per the serving
    specs). The sim-gated side pins bit-identity and the scratch budget
    (tests/test_redistribute.py); this measures the wall-clock and
    effective GB/s of both paths on real ICI, where the executor's
    shard-delta transfers should win by roughly the replication factor.
    Needs >= 4 devices (a real fsdp axis x model=2)."""
    import jax
    import numpy as np

    n = jax.device_count()
    if n < 4:
        print(json.dumps({
            "experiment": "reshard_train_to_serve",
            "skipped": f"needs >=4 devices for fsdp x model (have {n})",
        }), flush=True)
        return
    from frl_distributed_ml_scaffold_tpu import redistribute
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        MeshConfig, build_mesh,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import gpt_tp_rules
    from frl_distributed_ml_scaffold_tpu.parallel.partition import (
        shard_params_for_serving,
    )

    trainer, state, _ = build(
        "gpt2_medium_zero1",
        [f"mesh.fsdp={n // 2}", "mesh.model=2",
         "data.global_batch_size=16", "checkpoint.enabled=false"],
    )
    serve_env = build_mesh(
        MeshConfig(data=1, model=2), devices=jax.devices()[:2]
    )
    for arm in ("redistribute", "replicated_staging"):
        t0 = time.perf_counter()
        if arm == "redistribute":
            placed, plan = redistribute.train_to_serve(
                state.params, serve_env, gpt_tp_rules()
            )
            moved = plan.bytes_moved
        else:
            host = jax.device_get(state.params)  # the staging the
            # executor exists to avoid — measured as the reference
            placed = shard_params_for_serving(host, serve_env, gpt_tp_rules())
            moved = sum(
                np.prod(l.shape) * l.dtype.itemsize
                for l in jax.tree.leaves(host)
            )
        jax.block_until_ready(placed)
        dt = time.perf_counter() - t0
        print(json.dumps({
            "experiment": "reshard_train_to_serve",
            "arm": arm,
            "wall_s": round(dt, 4),
            "bytes_moved": int(moved),
            "gbytes_per_s": round(moved / dt / 1e9, 3),
            "n_chips": n,
        }), flush=True)
        del placed


def rn50_fused_bn():
    """The priced HBM-ceiling fix, bought (BACKLOG R5-4): the roofline
    pins ~150 ms of the 227 ms headline step in BN-backward HBM traffic
    (docs/perf_playbook.md); A/B the fused two-pass Pallas BN backward
    (ops/fused_bn.py, model.fused_bn) against the autodiff reference at
    the exact headline operating point. Long windows: the delta at stake
    is ~15-20% of step time, but per-window noise was ~1% (2026-07-30)."""
    for fused in ("false", "true"):
        dt = measure(
            "imagenet_rn50_ddp",
            ["data.global_batch_size=512", "model.stem=s2d",
             f"model.fused_bn={fused}"],
            n=30, warm=4,
        )
        emit("rn50_fused_bn", 512, dt, {"fused_bn": fused})


GROUPS = {f.__name__: f for f in (rn50_bs, rn50_precision, rn50_fwd_only,
                                  rn50_depth, rn50_stem, rn50_split, vitb,
                                  rn50_headline, rn50_pool, gpt2_opt,
                                  gpt2_block_remat, gpt2_offload,
                                  rn50_fused_opt, rn50_fused_bn,
                                  moe_dispatch, gpt2_fsdp_overlap,
                                  gpt2_tp_overlap, gpt2_fsdp_tp_overlap,
                                  gpt2_pipeline_mpmd,
                                  reshard_train_to_serve)}

if __name__ == "__main__":
    which = sys.argv[1:] or list(GROUPS)
    for g in which:
        try:
            GROUPS[g]()
        except Exception as e:  # keep sweeping; record the failure
            print(json.dumps({"experiment": g, "error": str(e)[:200]}),
                  flush=True)
