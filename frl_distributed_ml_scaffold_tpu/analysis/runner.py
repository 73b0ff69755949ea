"""graft-lint driver: run the analyzer passes over every registered
recipe's train step (and the serving decode step) on the CPU-sim mesh.

Everything here is TRACE-ONLY: the train step is inspected via
``jax.make_jaxpr`` on abstract inputs (the Trainer's ``state_shapes``
eval_shape tree) and via AOT ``.lower()`` — no XLA compile, so linting
all 17 recipes stays inside the fast-tier budget.  Compile-level checks
(GSPMD-inserted collectives, executable alias tables) are the pin tests'
job, which afford one tiny compile each.

Per-recipe invariants enforced as ``severity:error``:

- donation: every params/opt-state leaf of the train state is donated in
  the lowered step (the jit's ``donate_argnums=(0,)`` actually took).
- overlap recipes: the declared-schedule checker (analysis/schedule.py,
  ISSUE 13) — expectations derived from the recipe's ``OverlapSchedule``
  declaration itself, absorbing PR 3's zero-all_gather and PR 2's
  blockwise/reduce-scatter pins plus PR 6's lowp payload/bytes pins.
  Also emitted per recipe as the ``schedule:<name>`` program family.
- optional materialization budget (``--budget-mb``).

The serving decode lint builds the tiny-GPT decode step at a 16-token
bucket of a 64-token model and pins: no full-``seq_len`` intermediate
(PR 4), and the engine's decode/graft programs donate the cache (PR 5's
leak fix).
"""

from __future__ import annotations

from typing import Any, Callable, Iterable

import numpy as np

from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
    census_by_dtype,
    census_summary,
    collective_census,
)
from frl_distributed_ml_scaffold_tpu.analysis.donation import (
    donation_findings,
)
from frl_distributed_ml_scaffold_tpu.analysis.findings import Report
from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
    materialization_findings,
)

_COMMON = [
    "precision.policy=fp32",
    "trainer.log_every=100000",
    "checkpoint.enabled=false",
    "optimizer.warmup_steps=0",
]

_GPT_TINY = [
    "model.vocab_size=128", "model.num_layers=2", "model.num_heads=4",
    "model.hidden_dim=64", "model.seq_len=32",
    "data.vocab_size=128", "data.seq_len=32", "data.global_batch_size=16",
    "trainer.grad_accum=2",
]

_VIT_TINY = [
    "model.image_size=32", "model.patch_size=8", "model.hidden_dim=64",
    "model.num_layers=2", "model.num_heads=4", "model.num_classes=8",
    "data.image_size=32", "data.num_classes=8", "data.global_batch_size=16",
]

_RN_TINY = [
    "model.depth=10", "model.num_classes=8",
    "data.image_size=32", "data.num_classes=8", "data.global_batch_size=16",
]

_VIDEO_TINY = [
    "model.image_size=16", "model.num_frames=4", "model.tubelet_size=2,8,8",
    "model.hidden_dim=64", "model.num_layers=2", "model.num_heads=4",
    "model.num_classes=8",
    "data.image_size=16", "data.num_frames=4", "data.num_classes=8",
    "data.global_batch_size=16",
]

_PP_TINY = [
    "model.vocab_size=128", "model.num_layers=8", "model.num_heads=2",
    "model.hidden_dim=32", "model.seq_len=32",
    "model.pipeline_microbatches=4",
    "data.vocab_size=128", "data.seq_len=32", "data.global_batch_size=8",
    "trainer.grad_accum=1",
]

#: Wide-dtype ppermute payloads at or under this many bytes/call are
#: quantization SCALES, not chunk traffic — the carve-out is owned by
#: the declarative schedule checker; aliased here for back-compat.
from frl_distributed_ml_scaffold_tpu.analysis.schedule import (
    SCALE_BYTES_PER_CALL as _SCALE_BYTES_PER_CALL,
)

# CPU-sim (8 virtual devices) shrink overrides per registered recipe —
# the test_recipes.py discipline, centralized. A NEW recipe must either
# inherit a family entry below or add its own; ``lint_recipe`` raises on
# unknown names so the CLI catches unshrunk recipes instead of tracing a
# 345M-param program.
RECIPE_OVERRIDES: dict[str, list[str]] = {
    "mnist_mlp": ["data.global_batch_size=16"],
    "imagenet_rn50_ddp": _RN_TINY + ["mesh.data=8"],
    "imagenet_rn101_ddp": _RN_TINY + ["model.depth=10", "mesh.data=8"],
    "imagenet_vitb_fsdp": _VIT_TINY
    + ["mesh.fsdp=8", "parallel.fsdp_min_size=64"],
    "imagenet_vitl_fsdp": _VIT_TINY
    + ["mesh.fsdp=8", "parallel.fsdp_min_size=64", "trainer.remat=none"],
    "gpt2_medium_zero1": _GPT_TINY + ["mesh.fsdp=8"],
    "gpt2_medium_adafactor": _GPT_TINY + ["mesh.fsdp=8"],
    "ego4d_video_elastic": _VIDEO_TINY
    + ["mesh.fsdp=8", "parallel.fsdp_min_size=64"],
    "gpt2_medium_fsdp_overlap": _GPT_TINY
    + ["mesh.fsdp=8", "parallel.fsdp_min_size=16"],
    "gpt2_medium_tp_overlap": _GPT_TINY
    + ["mesh.data=1", "mesh.model=8"],
    "gpt2_medium_tp_overlap_int8": _GPT_TINY
    + ["mesh.data=1", "mesh.model=8"],
    "gpt2_medium_fsdp_tp_overlap": _GPT_TINY
    + ["mesh.fsdp=4", "mesh.model=2", "parallel.fsdp_min_size=16"],
    "gpt2_medium_fsdp_tp_overlap_int8": _GPT_TINY
    + ["mesh.fsdp=4", "mesh.model=2", "parallel.fsdp_min_size=16"],
    "gpt2_tp": _GPT_TINY + ["mesh.data=4", "mesh.model=2"],
    "gpt2_ring": [
        "model.vocab_size=128", "model.num_layers=2", "model.num_heads=4",
        "model.hidden_dim=64", "model.seq_len=64",
        "data.vocab_size=128", "data.seq_len=64", "data.global_batch_size=8",
        "mesh.data=2", "mesh.seq=4",
    ],
    "gpt2_long": [
        "model.vocab_size=128", "model.num_layers=2", "model.num_heads=4",
        "model.hidden_dim=64", "model.seq_len=256", "model.lm_loss_chunk=64",
        "data.vocab_size=128", "data.seq_len=256", "data.global_batch_size=8",
        "trainer.grad_accum=2", "mesh.data=8",
    ],
    "gpt2_moe": [
        "model.vocab_size=128", "model.num_layers=2", "model.num_heads=4",
        "model.hidden_dim=64", "model.seq_len=32", "model.moe.num_experts=4",
        "data.vocab_size=128", "data.seq_len=32", "data.global_batch_size=16",
        "mesh.data=2", "mesh.expert=4",
    ],
    "gpt2_pp": _PP_TINY + ["mesh.pipe=4", "mesh.data=2"],
    "gpt2_pp_circular": _PP_TINY + ["mesh.pipe=4", "mesh.data=2"],
    "gpt2_pipeline_mpmd": _PP_TINY + ["mesh.pipe=4", "mesh.data=2"],
    "gpt2_medium_serve": _GPT_TINY + ["mesh.data=4", "mesh.model=2"],
}


def _build_trainer(name: str, workdir: str):
    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    if name not in RECIPE_OVERRIDES:
        raise KeyError(
            f"recipe {name!r} has no CPU-sim shrink overrides in "
            "analysis.runner.RECIPE_OVERRIDES — add one so graft_lint "
            "traces a tiny twin, not the production shapes"
        )
    cfg = apply_overrides(
        get_config(name),
        _COMMON + RECIPE_OVERRIDES[name] + [f"workdir={workdir}"],
    )
    return Trainer(cfg, mesh_env=build_mesh(cfg.mesh))


def _abstract_batch(trainer) -> Any:
    import jax
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.trainer.tasks import example_input

    example = example_input(
        trainer.cfg.data, trainer.cfg.model,
        batch_size=trainer.cfg.data.global_batch_size,
    )
    return {
        k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
        for k, v in example.items()
    }


def _recipe_schedule(cfg):
    """The recipe's declared overlap schedule (None when it runs the
    plain GSPMD schedules)."""
    from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
        schedule_from_config,
    )

    return schedule_from_config(cfg)


def _lint_recipe_reports(
    name: str,
    *,
    workdir: str = "/tmp/graft_lint",
    budget_bytes: int | None = None,
) -> list[Report]:
    """One trainer build + trace for a recipe, emitted as up to two
    reports: the per-recipe report (every pass) and — when the recipe
    declares an overlap schedule — the ``schedule:<name>`` program
    family report (the declaration-first view of the same schedule
    findings, with the declaration in ``meta`` so
    ``--save-census``/``--against`` diffs key per schedule)."""
    import jax

    report = Report(program=f"recipe:{name}")
    trainer = _build_trainer(name, workdir)
    cfg = trainer.cfg
    if getattr(trainer, "_mpmd", None) is not None:
        # MPMD pipeline recipes (ISSUE 14) have no single train-step
        # program: the recipe report AND the pipeline:stage_program
        # family both come from the per-stage artifacts.
        return _lint_mpmd_reports(name, trainer)
    state_shapes = trainer.state_shapes
    batch = _abstract_batch(trainer)

    jaxpr = trainer._mesh_scoped(jax.make_jaxpr(trainer._train_step_fn))(
        state_shapes, batch
    )

    # -- pass 1: collective census (info; the diffable artifact) --------
    census = collective_census(jaxpr)
    report.meta["collective_census"] = [r.to_dict() for r in census]
    for prim, agg in sorted(census_summary(census).items()):
        report.add(
            "collective_census", "info", "census",
            f"{prim}: {agg['eqns']} eqn(s), {agg['calls']} call(s)/step, "
            f"{agg['total_bytes']} bytes",
            primitive=prim, **agg,
        )

    # -- pass 2: declared-schedule invariants (ISSUE 13) ----------------
    # The recipe's OverlapSchedule declaration IS the expectation: one
    # derivation (analysis/schedule.py) replaces the hand-written
    # tp_overlap zero-all_gather and fsdp_overlap blockwise /
    # reduce-scatter pins this pass used to carry — same finding codes,
    # now derived from what the recipe DECLARES instead of which knob
    # it flipped.
    sched = _recipe_schedule(cfg)
    sched_report = None
    if sched is not None:
        from frl_distributed_ml_scaffold_tpu.analysis.schedule import (
            schedule_findings,
        )
        from frl_distributed_ml_scaffold_tpu.parallel.partition import (
            block_param_slice_shapes,
        )

        report.meta["schedule"] = sched.describe()
        slices = None
        if sched.block_gather() is not None:
            slices = block_param_slice_shapes(
                state_shapes.params, trainer.env.axis_size("model")
            )
        axis_sizes = {
            a: trainer.env.axis_size(a)
            for a in ("data", "fsdp", "model", "seq", "expert", "pipe")
        }
        found = schedule_findings(
            jaxpr, sched, axis_sizes=axis_sizes, param_slices=slices,
            census=census, label=f"{name}: ",
        )
        report.extend(found)
        # The schedule: family rides the SAME trace — no second trainer
        # build for the declaration-first view.
        sched_report = Report(program=f"schedule:{name}")
        sched_report.meta["schedule"] = sched.describe()
        sched_report.meta["collective_census"] = report.meta[
            "collective_census"
        ]
        sched_report.extend(found)
        if sched_report.ok:
            sched_report.add(
                "schedule", "info", "summary",
                f"{name}: program matches its declared schedule "
                f"{sched.render()!r}",
            )
        ring = sched.ring_gather()
        if ring is not None and ring.lowp is not None:
            # Observability: the per-dtype ppermute breakdown next to the
            # declared-lowp errors above.
            for (prim, dtype), agg in sorted(
                census_by_dtype(census).items()
            ):
                if prim != "ppermute":
                    continue
                report.add(
                    "collective_census", "info", "census-by-dtype",
                    f"ppermute[{dtype}]: {agg['eqns']} eqn(s), "
                    f"{agg['calls']} call(s)/step, "
                    f"{agg['total_bytes']} bytes",
                    primitive=prim, dtype=dtype, **agg,
                )

    # -- pass 3: materialization census / budget ------------------------
    report.extend(
        materialization_findings(
            jaxpr, budget_bytes=budget_bytes, label=f"{name}: "
        )
    )

    # -- pass 4: donation audit on the lowered step ---------------------
    lowered = trainer._mesh_scoped(trainer._train_step_jit.lower)(
        state_shapes, batch
    )
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        args_info_donations,
        lowered_donations,
    )

    pairs = args_info_donations(lowered)
    text_donated = sum(
        1 for d in lowered_donations(lowered.as_text()) if d.donated
    )
    if pairs is None:
        # Old jax without args_info: fall back to marker counting.
        report.extend(
            donation_findings(lowered.as_text(), label=f"{name}: ")
        )
        if text_donated == 0:
            report.add(
                "donation", "error", "not-donated",
                f"{name}: no lowered argument carries a donation marker "
                "— donate_argnums went missing",
            )
        return [report] + ([sched_report] if sched_report else [])
    missing = [
        p
        for p, donated in pairs
        if (".params" in p or ".opt_state" in p) and not donated
    ]
    n_donated = sum(1 for _, d in pairs if d)
    report.add(
        "donation", "info", "summary",
        f"{name}: {n_donated}/{len(pairs)} arg leaves donated "
        f"({text_donated} donation markers survive in lowered StableHLO)",
        donated=n_donated, args=len(pairs), markers=text_donated,
    )
    for p in missing:
        report.add(
            "donation", "error", "not-donated",
            f"{name}: state leaf {p} is not donated — resident train "
            "state doubles",
            path=p,
        )
    if n_donated and text_donated == 0:
        report.add(
            "donation", "error", "donation-dropped",
            f"{name}: donation requested for {n_donated} leaves but no "
            "marker survives in the lowered module — lowering dropped "
            "the donation",
        )
    return [report] + ([sched_report] if sched_report else [])


def _stage_program_findings(report: Report, arts, *, label: str = "") -> None:
    """The ``pipeline:stage_program`` invariants (ISSUE 14), over the
    runner's abstract per-stage artifacts:

    - **No cross-stage collectives.** A per-stage program may collect
      over its submesh's data/fsdp/model/seq axes (grad reductions, fsdp
      gathers, TP rings, ring attention) but NEVER over ``pipe`` —
      boundary traffic is the driver's explicit ``device_put`` transfers
      only. Any ``pipe``-axis collective means a stage program started
      reaching across the stage boundary (error
      ``cross-stage-collective``).
    - **Stage state donated.** The per-stage update program donates every
      params/opt-state leaf (and the EMA mirror when on) — the per-stage
      face of the train step's ``donate_argnums=(0,)``; a dropped
      donation doubles stage state residency (error
      ``stage-not-donated``).
    """
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        args_info_donations,
        lowered_donations,
    )

    census_all = []
    for art in arts:
        j = art["stage"]
        for which in ("fwd_jaxpr", "fwd_bwd_jaxpr"):
            census = collective_census(art[which])
            census_all.extend(r.to_dict() for r in census)
            for r in census:
                if "pipe" in r.axes:
                    report.add(
                        "stage_program", "error", "cross-stage-collective",
                        f"{label}stage {j} {which.replace('_jaxpr', '')} "
                        f"program carries a {r.primitive} over the pipe "
                        f"axis {r.axes} — inter-stage traffic must be the "
                        "driver's explicit transfers, never a collective "
                        "inside a stage program",
                        stage=j, primitive=r.primitive, axes=list(r.axes),
                    )
        lowered = art["update_lowered"]
        pairs = args_info_donations(lowered)
        if pairs is None:
            dons = [d.donated for d in lowered_donations(lowered.as_text())]
            if not any(dons):
                report.add(
                    "donation", "error", "stage-not-donated",
                    f"{label}stage {j} update program carries no donation "
                    "marker — stage params/opt-state double per step",
                    stage=j,
                )
            continue
        # Every state-carrying update arg must be donated: params, opt
        # state, grads — and the EMA mirror when on (the runner records
        # which positions those are; only the clip-factor scalar is
        # legally un-donated).
        expected = tuple(
            f"[0][{i}]" for i in art.get("update_donate_expected", (0, 1))
        )
        undonated = [
            p for p, d in pairs if p.startswith(expected) and not d
        ]
        for p in undonated:
            report.add(
                "donation", "error", "stage-not-donated",
                f"{label}stage {j} update program does not donate state "
                f"leaf {p} — stage params/opt-state double per step",
                stage=j, path=p,
            )
    report.meta["collective_census"] = census_all
    report.meta["stages"] = len(arts)
    if report.ok:
        report.add(
            "stage_program", "info", "summary",
            f"{label}{len(arts)} per-stage programs are free of "
            "cross-stage collectives and donate their stage state",
        )


def _lint_mpmd_reports(name: str, trainer) -> list[Report]:
    """Recipe + ``pipeline:stage_program`` family reports for an MPMD
    pipeline recipe — one artifact build, two views (the schedule:
    family pattern)."""
    from frl_distributed_ml_scaffold_tpu.parallel.mpmd_pipeline import (
        bubble_fraction,
        peak_live_activations,
    )

    runner = trainer._mpmd
    arts = runner.lint_artifacts()
    report = Report(program=f"recipe:{name}")
    report.meta["pipeline"] = {
        "impl": "mpmd",
        "stages": runner.num_stages,
        "microbatches": runner.total_micro,
        "bubble_fraction": bubble_fraction(
            "1f1b", runner.num_stages, runner.total_micro
        ),
        "peak_live_activations": peak_live_activations(
            "1f1b", runner.num_stages, runner.total_micro
        ),
    }
    _stage_program_findings(report, arts, label=f"{name}: ")
    # The stage_program family rides the SAME pass output — no second
    # census/donation walk over identical artifacts (the schedule:
    # family pattern).
    stage_report = Report(program="pipeline:stage_program")
    stage_report.meta["recipe"] = name
    stage_report.meta["pipeline"] = report.meta["pipeline"]
    stage_report.meta["collective_census"] = report.meta[
        "collective_census"
    ]
    stage_report.meta["stages"] = report.meta["stages"]
    stage_report.extend(report.findings)
    return [report, stage_report]


def lint_stage_programs(
    name: str = "gpt2_pipeline_mpmd", *, workdir: str = "/tmp/graft_lint"
) -> Report:
    """The ``pipeline:stage_program`` program family (ISSUE 14) on its
    own: per-stage programs of the MPMD pipeline recipe pinned free of
    cross-stage collectives, stage params/opt-state donation audited.
    Shares the recipe build with ``_lint_mpmd_reports``; mutation-gated
    in tests/test_graft_lint.py."""
    trainer = _build_trainer(name, workdir)
    if getattr(trainer, "_mpmd", None) is None:
        report = Report(program="pipeline:stage_program")
        report.add(
            "stage_program", "error", "not-mpmd",
            f"{name}: recipe does not run the MPMD pipeline backend — "
            "the stage_program family needs pipeline_impl='mpmd'",
        )
        return report
    return _lint_mpmd_reports(name, trainer)[1]


def lint_train_step(
    name: str,
    *,
    workdir: str = "/tmp/graft_lint",
    budget_bytes: int | None = None,
) -> Report:
    """Lint one registered recipe's train step; returns its Report."""
    return _lint_recipe_reports(
        name, workdir=workdir, budget_bytes=budget_bytes
    )[0]


def lint_schedule_program(
    name: str, *, workdir: str = "/tmp/graft_lint"
) -> Report:
    """The ``schedule:`` program family (ISSUE 13): one report per
    overlap recipe whose PROGRAM IS its declared schedule — the recipe's
    train step checked against the expectations derived from its
    ``OverlapSchedule`` declaration alone (analysis/schedule.py), with
    the declaration in ``meta`` so ``--save-census``/``--against`` diffs
    are keyed per schedule, not per recipe. Shares one trainer build +
    trace with the per-recipe report (``_lint_recipe_reports``); a
    recipe with no declared schedule reports ``no-schedule``."""
    reports = _lint_recipe_reports(name, workdir=workdir)
    for r in reports:
        if r.program == f"schedule:{name}":
            return r
    report = Report(program=f"schedule:{name}")
    report.add(
        "schedule", "error", "no-schedule",
        f"{name}: recipe declares no overlap schedule — the "
        "schedule: program family only applies to overlap recipes",
    )
    return report


def build_decode_step_program(
    *, seq_len: int = 96, bucket: int = 16, num_slots: int = 2,
    kv_cache_quant: str = "none",
):
    """The tiny-GPT serving decode step as an ABSTRACT program:
    ``(model, params, cache, tok, jaxpr)``, all shapes eval_shape'd —
    nothing runs. Shared by ``lint_decode_step`` and the perf ledger
    (tools/perf_ledger.py), so the linted program and the one the ledger
    censuses are the same artifact by construction."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        _decode_step,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=64, num_layers=2, num_heads=2, hidden_dim=32,
            seq_len=seq_len, dropout=0.0, kv_cache_quant=kv_cache_quant,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    m = model.clone(cache_len=bucket)
    tok = jax.ShapeDtypeStruct((num_slots, 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((num_slots, 4), jnp.int32),
            train=False,
        )["params"]
    )
    _, cache_vars = jax.eval_shape(
        lambda p, t: m.apply(
            {"params": p}, t, decode=True, mutable=["cache"]
        ),
        params, tok,
    )
    cache = cache_vars["cache"]

    jaxpr = jax.make_jaxpr(
        lambda p, c, t: _decode_step(m, p, c, t[:, 0])
    )(params, cache, tok)
    return model, params, cache, tok, jaxpr


def lint_decode_step(
    *, seq_len: int = 96, bucket: int = 16, num_slots: int = 2,
    kv_cache_quant: str = "none",
) -> Report:
    """Lint the serving decode path (tiny GPT, bucketed cache): PR 4's
    no-full-seq_len pin as a materialization-budget finding, plus the
    engine decode/graft donation audit.

    With ``kv_cache_quant`` set, the program is the QUANTIZED decode step
    and gains the ISSUE-6 pin: no wide-float intermediate carrying the
    cache geometry ``(bucket, H, hd)`` — a step that dequantizes the
    whole cache (instead of per chunk) is an error
    (``analysis.materialization.wide_intermediates_with_dims``)."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.serving.engine import ServingEngine

    quant = kv_cache_quant != "none"
    report = Report(
        program="serving:decode_step_int8kv" if quant
        else "serving:decode_step"
    )
    model, params, cache, tok, jaxpr = build_decode_step_program(
        seq_len=seq_len, bucket=bucket, num_slots=num_slots,
        kv_cache_quant=kv_cache_quant,
    )

    census = collective_census(jaxpr)
    report.meta["collective_census"] = [r.to_dict() for r in census]
    report.extend(
        materialization_findings(
            jaxpr, forbidden_dim=seq_len, label="decode_step: "
        )
    )
    if quant:
        from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
            wide_intermediates_with_dims,
        )

        h = model.config.num_heads
        hd = model.config.hidden_dim // h
        for i in wide_intermediates_with_dims(jaxpr, (bucket, h, hd)):
            report.add(
                "materialization", "error", "dequantized-cache",
                f"quantized decode step materializes a wide-float cache-"
                f"geometry array {i.dtype}{list(i.shape)} ({i.bytes} "
                f"bytes, {i.primitive}) — the whole cache was "
                "dequantized instead of per split-KV chunk",
                intermediate=i.to_dict(), geometry=[bucket, h, hd],
            )

    # Engine decode/graft donation: the KV cache is the serving-side
    # optimizer state — it must be donated or every decode step holds
    # two caches live.
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        lowered_donations,
    )

    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        args_info_donations,
    )

    eng = ServingEngine(model, params, num_slots=num_slots, temperature=0.0)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    flat_tok = jax.ShapeDtypeStruct((num_slots,), jnp.int32)
    dec_lowered = eng._decode_fn(bucket).lower(params, cache, flat_tok, rng)
    n_cache = len(jax.tree.leaves(cache))
    pairs = args_info_donations(dec_lowered)
    if pairs is None:
        # Old jax without args_info: count-level fallback only.
        dons = [d.donated for d in lowered_donations(dec_lowered.as_text())]
        if sum(dons) < n_cache:
            report.add(
                "donation", "error", "cache-not-donated",
                f"serving decode step donates {sum(dons)} args but the "
                f"cache alone has {n_cache} leaves — the engine holds two "
                "caches live per step",
                donated=sum(dons), cache_leaves=n_cache,
            )
        return report
    # Per-path: every CACHE leaf specifically must be donated (a refactor
    # donating params instead would pass a count-only gate). args_info
    # paths root at (args, kwargs): cache is positional arg 1 → "[0][1]".
    undonated_cache = [
        p for p, d in pairs if p.startswith("[0][1]") and not d
    ]
    for p in undonated_cache:
        report.add(
            "donation", "error", "cache-not-donated",
            f"serving decode step does not donate cache leaf {p} — the "
            "engine holds two caches live per step",
            path=p,
        )
    if not undonated_cache:
        report.add(
            "donation", "info", "summary",
            f"decode step donates all {n_cache} cache leaves "
            f"({sum(1 for _, d in pairs if d)}/{len(pairs)} args donated)",
        )
    return report


def build_paged_decode_step_program(
    *, seq_len: int = 96, block_size: int = 16, pool_blocks: int = 9,
    num_slots: int = 2, kv_cache_quant: str = "none",
):
    """The tiny-GPT PAGED serving decode step as an ABSTRACT program
    (ISSUE 10): ``(model, params, cache, tok, jaxpr)``, all shapes
    eval_shape'd — nothing runs. The cache is the block POOL (per-layer
    K/V block pools + block tables + index bookkeeping), so the program
    is the block-table decode shape the paged engine compiles ONCE.
    Shared by ``lint_paged_decode_step`` and the perf ledger, like its
    bucketed sibling ``build_decode_step_program``."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        _decode_step,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT, init_paged_cache
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=64, num_layers=2, num_heads=2, hidden_dim=32,
            seq_len=seq_len, dropout=0.0, kv_cache_quant=kv_cache_quant,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    m = model.clone(kv_block_size=block_size, kv_pool_blocks=pool_blocks)
    tok = jax.ShapeDtypeStruct((num_slots, 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((num_slots, 4), jnp.int32),
            train=False,
        )["params"]
    )
    cache = jax.eval_shape(lambda: init_paged_cache(m, num_slots))

    jaxpr = jax.make_jaxpr(
        lambda p, c, t: _decode_step(m, p, c, t[:, 0])
    )(params, cache, tok)
    return model, params, cache, tok, jaxpr


def build_verify_step_program(
    *, seq_len: int = 96, block_size: int = 16, pool_blocks: int = 9,
    num_slots: int = 2, speculate_k: int = 2, kv_cache_quant: str = "none",
):
    """The tiny-GPT speculative VERIFY step as an ABSTRACT program
    (ISSUE 11): ``(model, params, cache, tile, jaxpr)``, all shapes
    eval_shape'd — nothing runs. The tile is the fixed ``[B, k+1]``
    token block the paged engine compiles ONCE (no per-k ladder); the
    cache is the same block pool as the paged decode step — the verify
    program reads/writes it through the identical table indirection, so
    the same no-cache-clone/no-logical-view pins apply. Shared by
    ``lint_verify_step`` and the perf ledger, like its siblings."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        _verify_step,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT, init_paged_cache
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=64, num_layers=2, num_heads=2, hidden_dim=32,
            seq_len=seq_len, dropout=0.0, kv_cache_quant=kv_cache_quant,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    m = model.clone(kv_block_size=block_size, kv_pool_blocks=pool_blocks)
    tok = jax.ShapeDtypeStruct((num_slots, 1), jnp.int32)
    tile = jax.ShapeDtypeStruct((num_slots, speculate_k + 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((num_slots, 4), jnp.int32),
            train=False,
        )["params"]
    )
    cache = jax.eval_shape(lambda: init_paged_cache(m, num_slots))

    jaxpr = jax.make_jaxpr(
        lambda p, c, t: _verify_step(m, p, c, t)
    )(params, cache, tile)
    return model, params, cache, tile, jaxpr


def lint_verify_step(
    *, seq_len: int = 96, block_size: int = 16, pool_blocks: int = 9,
    num_slots: int = 2, speculate_k: int = 2, kv_cache_quant: str = "none",
) -> Report:
    """Lint the speculative VERIFY step (ISSUE 11) — the paged decode
    pins re-armed on the k+1-position tile:

    - no full-``seq_len`` intermediate: the verify tile must score
      against the pool through the table indirection, never a gathered
      logical view (k+1 queries make the gather temptation bigger, not
      smaller);
    - materialization budget == the largest pool leaf: the step's
      biggest legal array is still the donated in-place pool update —
      a per-k cache clone or a widened score materialization trips it;
    - donation audit on the engine's ONE compiled verify program
      (``ServingEngine._verify_fn``): every cache leaf donated, or each
      verify holds two pools live.

    Mutation-gated in tests/test_graft_lint.py alongside the paged
    decode gates."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.serving.engine import ServingEngine

    quant = kv_cache_quant != "none"
    report = Report(
        program="serving:verify_step_paged_int8kv" if quant
        else "serving:verify_step_paged"
    )
    model, params, cache, tile, jaxpr = build_verify_step_program(
        seq_len=seq_len, block_size=block_size, pool_blocks=pool_blocks,
        num_slots=num_slots, speculate_k=speculate_k,
        kv_cache_quant=kv_cache_quant,
    )

    census = collective_census(jaxpr)
    report.meta["collective_census"] = [r.to_dict() for r in census]
    report.meta["verify_positions"] = speculate_k + 1
    report.extend(
        materialization_findings(
            jaxpr, forbidden_dim=seq_len, label="verify_step: "
        )
    )
    budget = _max_pool_leaf_bytes(cache)
    report.meta["pool_leaf_bytes"] = budget
    from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
        oversized_intermediates,
    )

    for i in oversized_intermediates(jaxpr, budget):
        report.add(
            "materialization", "error", "cache-clone",
            f"verify step materializes {i.dtype}{list(i.shape)} "
            f"({i.bytes} bytes > the {budget}-byte pool leaf, "
            f"{i.primitive}) — the k+1 tile must ride the table "
            "indirection, never clone or widen the pool",
            intermediate=i.to_dict(), budget_bytes=budget,
        )

    # Engine donation audit on the ONE compiled verify program.
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        args_info_donations,
        lowered_donations,
    )

    eng = ServingEngine(
        model, params, num_slots=num_slots, temperature=0.0,
        kv_block_size=block_size, kv_pool_blocks=pool_blocks,
        speculate="ngram", speculate_k=speculate_k,
    )
    ver_lowered = eng._verify_fn().lower(params, cache, tile)
    n_cache = len(jax.tree.leaves(cache))
    pairs = args_info_donations(ver_lowered)
    if pairs is None:
        dons = [d.donated for d in lowered_donations(ver_lowered.as_text())]
        if sum(dons) < n_cache:
            report.add(
                "donation", "error", "cache-not-donated",
                f"verify step donates {sum(dons)} args but the pool "
                f"cache has {n_cache} leaves — two POOLS live per "
                "verify",
                donated=sum(dons), cache_leaves=n_cache,
            )
        return report
    undonated_cache = [
        p for p, d in pairs if p.startswith("[0][1]") and not d
    ]
    for p in undonated_cache:
        report.add(
            "donation", "error", "cache-not-donated",
            f"verify step does not donate cache leaf {p} — the engine "
            "holds two POOLS live per verify",
            path=p,
        )
    if not undonated_cache:
        report.add(
            "donation", "info", "summary",
            f"verify step donates all {n_cache} cache leaves "
            f"({sum(1 for _, d in pairs if d)}/{len(pairs)} args donated)",
        )
    return report


def build_handoff_program(
    *, seq_len: int = 96, block_size: int = 16, pool_blocks: int = 9,
    num_slots: int = 2, prompt_tokens: int = 40, m_shared: int = 0,
    kv_cache_quant: str = "none",
):
    """The prefill→decode HANDOFF SPLICE as an ABSTRACT program (ISSUE
    12): ``(model, pool_cache, slot_cache, blk_ids, jaxpr)``, all shapes
    eval_shape'd — nothing runs. The jaxpr is
    ``generation.splice_pool_blocks`` — the EXACT function both the
    colocated paged graft and the disaggregated handoff jit
    (``ServingEngine._paged_graft_fn``), so the linted artifact and the
    served one cannot drift. The slot cache is the contiguous prefill
    output at the prompt's cache bucket; ``blk_ids`` are the private
    blocks that change owner (``m_shared`` leading blocks stay put —
    the shared-prefix case). Shared with the perf ledger's
    ``serving:handoff`` row, like its decode/verify siblings."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        blocks_for_tokens,
        next_cache_bucket,
        splice_pool_blocks,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT, init_paged_cache
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=64, num_layers=2, num_heads=2, hidden_dim=32,
            seq_len=seq_len, dropout=0.0, kv_cache_quant=kv_cache_quant,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    tok = jax.ShapeDtypeStruct((num_slots, 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((num_slots, 4), jnp.int32),
            train=False,
        )["params"]
    )
    mp = model.clone(kv_block_size=block_size, kv_pool_blocks=pool_blocks)
    pool_cache = jax.eval_shape(lambda: init_paged_cache(mp, num_slots))
    s_c = next_cache_bucket(seq_len, prompt_tokens, floor=block_size)
    mc = model.clone(cache_len=s_c)
    slot_tok = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    _, slot_vars = jax.eval_shape(
        lambda p, t: mc.apply(
            {"params": p}, t, decode=True, mutable=["cache"]
        ),
        params, slot_tok,
    )
    slot_cache = slot_vars["cache"]
    n_priv = blocks_for_tokens(prompt_tokens, block_size) - m_shared
    blk_ids = jax.ShapeDtypeStruct((n_priv,), jnp.int32)
    m0 = jax.ShapeDtypeStruct((), jnp.int32)
    slot = jax.ShapeDtypeStruct((), jnp.int32)

    import functools

    jaxpr = jax.make_jaxpr(
        functools.partial(splice_pool_blocks, block_size=block_size)
    )(pool_cache, slot_cache, blk_ids, m0, slot)
    return model, pool_cache, slot_cache, blk_ids, jaxpr


def lint_handoff(
    *, seq_len: int = 96, block_size: int = 16, pool_blocks: int = 9,
    num_slots: int = 2, prompt_tokens: int = 40,
) -> Report:
    """Lint the prefill→decode HANDOFF splice (ISSUE 12) — the mutation
    gate behind the disaggregated engine's zero-logical-cache-copy
    claim, three teeth:

    - ZERO collectives: the splice is a scatter of owned blocks plus a
      host-side table-row write — any collective in its jaxpr means the
      handoff started resharding (the compiled-HLO reshard-free pin
      lives in tests/test_serving.py under a live model mesh);
    - no full-``seq_len`` intermediate and a materialization budget of
      ONE pool leaf (the donated in-place update): a gather-based
      handoff — materialize the logical cache view, rewrite the pool —
      has to exceed the budget and trips it;
    - donation audit: the engine's splice program donates the pool, or
      every handoff holds two pools live.

    Mutation-gated in tests/test_graft_lint.py (a gather-based handoff
    mutant must trip).

    All three read the JAXPR (and the lowering's donation markers), not
    what the TPU's compiler makes of them — and they passed while every
    graft on the chip transposed the whole donated pool there and back
    around its scatter, 18-23 ms of device time for ten blocks
    (PERF.md §5 of PR 27): the copies came from the pool leaf's device
    layout and are in no jaxpr. What the chip runs is pinned on the
    compiled HLO, for a described v5e at the serving benchmark's size,
    in tests/test_chip_compile.py
    (``test_pool_program_leaves_the_pool_in_place``: no op writes a
    layer's slice of the pool but the in-place update, temporaries of a
    few megabytes). Keep both: this lint is the cheap one that runs on
    every recipe."""
    import jax
    import jax.numpy as jnp

    report = Report(program="serving:handoff")
    model, pool_cache, slot_cache, blk_ids, jaxpr = build_handoff_program(
        seq_len=seq_len, block_size=block_size, pool_blocks=pool_blocks,
        num_slots=num_slots, prompt_tokens=prompt_tokens,
    )

    census = collective_census(jaxpr)
    report.meta["collective_census"] = [r.to_dict() for r in census]
    table_blocks = seq_len // block_size
    report.meta["splice_table_bytes"] = table_blocks * 4
    for r in census:
        report.add(
            "reshard", "error", "handoff-collective",
            f"handoff splice carries a {r.primitive} of "
            f"{[list(s) for s in r.shapes]} — the splice moves only "
            "owned blocks; any collective means the handoff is "
            "resharding the cache",
            primitive=r.primitive, shapes=[list(s) for s in r.shapes],
        )
    report.extend(
        materialization_findings(
            jaxpr, forbidden_dim=seq_len, label="handoff: "
        )
    )
    budget = _max_pool_leaf_bytes(pool_cache)
    report.meta["pool_leaf_bytes"] = budget
    from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
        oversized_intermediates,
    )

    for i in oversized_intermediates(jaxpr, budget):
        report.add(
            "materialization", "error", "cache-copy",
            f"handoff splice materializes {i.dtype}{list(i.shape)} "
            f"({i.bytes} bytes > the {budget}-byte pool leaf, "
            f"{i.primitive}) — the handoff must move only the blocks "
            "that change owner (ownership is a table-row write), never "
            "a logical-cache copy",
            intermediate=i.to_dict(), budget_bytes=budget,
        )

    # Donation audit: jit the splice exactly as the engine does
    # (``_paged_graft_fn``: same function, same donate_argnums) and
    # lower it on the abstract trees — no engine state needed.
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        args_info_donations,
        lowered_donations,
    )

    import functools

    from frl_distributed_ml_scaffold_tpu.models.generation import (
        splice_pool_blocks,
    )

    splice_jit = jax.jit(
        functools.partial(splice_pool_blocks, block_size=block_size),
        donate_argnums=(0,),
    )
    m0 = jax.ShapeDtypeStruct((), jnp.int32)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = splice_jit.lower(pool_cache, slot_cache, blk_ids, m0, slot)
    n_cache = len(jax.tree.leaves(pool_cache))
    pairs = args_info_donations(lowered)
    if pairs is None:
        dons = [d.donated for d in lowered_donations(lowered.as_text())]
        if sum(dons) < n_cache:
            report.add(
                "donation", "error", "cache-not-donated",
                f"handoff splice donates {sum(dons)} args but the pool "
                f"has {n_cache} leaves — two POOLS live per handoff",
                donated=sum(dons), cache_leaves=n_cache,
            )
        return report
    undonated = [p for p, d in pairs if p.startswith("[0][0]") and not d]
    for p in undonated:
        report.add(
            "donation", "error", "cache-not-donated",
            f"handoff splice does not donate pool leaf {p} — two POOLS "
            "live per handoff",
            path=p,
        )
    if not undonated:
        report.add(
            "donation", "info", "summary",
            f"handoff splice donates all {n_cache} pool leaves; splice "
            f"ownership cost is {table_blocks * 4} table bytes/slot",
        )
    return report


def _max_pool_leaf_bytes(cache) -> int:
    """The largest block-pool leaf in a paged cache tree — the paged
    decode step's legal materialization ceiling (its biggest intermediate
    is the donated in-place pool update, which is exactly pool-sized)."""
    import jax
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.models.generation import (
        SLOT_LEAF_OF,
    )

    best = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if getattr(path[-1], "key", None) in SLOT_LEAF_OF:
            best = max(
                best,
                int(np.prod(leaf.shape, dtype=np.int64))
                * np.dtype(leaf.dtype).itemsize,
            )
    return best


def lint_paged_decode_step(
    *, seq_len: int = 96, block_size: int = 16, pool_blocks: int = 9,
    num_slots: int = 2, kv_cache_quant: str = "none",
) -> Report:
    """Lint the PAGED serving decode step (ISSUE 10) — the
    ``assert_no_cache_clone`` discipline, as two teeth:

    - no full-``seq_len`` intermediate: gathering the logical cache view
      out of the pool (``pool[tables]`` reshaped contiguous) is exactly
      the full-context materialization paging exists to avoid;
    - materialization budget == the largest pool leaf: the step's
      biggest legal array is the donated in-place pool update, so any
      clone-per-grow regression (pad the pool, copy it wider) has to
      materialize MORE than one pool and trips the budget.

    Plus the engine donation audit: the paged decode program donates
    every cache leaf (pool included) — without it each step holds two
    POOLS live, a far bigger spike than the bucketed double-cache.
    Mutation-gated in tests/test_graft_lint.py (a clone-per-grow mutant
    and a gather-the-logical-cache mutant must both trip)."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.serving.engine import ServingEngine

    quant = kv_cache_quant != "none"
    report = Report(
        program="serving:decode_step_paged_int8kv" if quant
        else "serving:decode_step_paged"
    )
    model, params, cache, tok, jaxpr = build_paged_decode_step_program(
        seq_len=seq_len, block_size=block_size, pool_blocks=pool_blocks,
        num_slots=num_slots, kv_cache_quant=kv_cache_quant,
    )

    census = collective_census(jaxpr)
    report.meta["collective_census"] = [r.to_dict() for r in census]
    report.extend(
        materialization_findings(
            jaxpr, forbidden_dim=seq_len, label="paged_decode_step: "
        )
    )
    budget = _max_pool_leaf_bytes(cache)
    report.meta["pool_leaf_bytes"] = budget
    from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
        oversized_intermediates,
    )

    for i in oversized_intermediates(jaxpr, budget):
        report.add(
            "materialization", "error", "cache-clone",
            f"paged decode step materializes {i.dtype}{list(i.shape)} "
            f"({i.bytes} bytes > the {budget}-byte pool leaf, "
            f"{i.primitive}) — growth must append a block to a table, "
            "never clone/pad the pool",
            intermediate=i.to_dict(), budget_bytes=budget,
        )

    # Engine donation audit on the ONE paged decode program.
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        args_info_donations,
        lowered_donations,
    )

    eng = ServingEngine(
        model, params, num_slots=num_slots, temperature=0.0,
        kv_block_size=block_size, kv_pool_blocks=pool_blocks,
    )
    rng = jax.eval_shape(lambda: jax.random.key(0))
    flat_tok = jax.ShapeDtypeStruct((num_slots,), jnp.int32)
    dec_lowered = eng._paged_decode_fn().lower(params, cache, flat_tok, rng)
    n_cache = len(jax.tree.leaves(cache))
    pairs = args_info_donations(dec_lowered)
    if pairs is None:
        dons = [d.donated for d in lowered_donations(dec_lowered.as_text())]
        if sum(dons) < n_cache:
            report.add(
                "donation", "error", "cache-not-donated",
                f"paged decode step donates {sum(dons)} args but the "
                f"pool cache has {n_cache} leaves — two POOLS live per "
                "step",
                donated=sum(dons), cache_leaves=n_cache,
            )
        return report
    undonated_cache = [
        p for p, d in pairs if p.startswith("[0][1]") and not d
    ]
    for p in undonated_cache:
        report.add(
            "donation", "error", "cache-not-donated",
            f"paged decode step does not donate cache leaf {p} — the "
            "engine holds two POOLS live per step",
            path=p,
        )
    if not undonated_cache:
        report.add(
            "donation", "info", "summary",
            f"paged decode step donates all {n_cache} cache leaves "
            f"({sum(1 for _, d in pairs if d)}/{len(pairs)} args donated)",
        )
    return report


#: The redistribution executor's same-mesh program classes (ISSUE 15),
#: one per seam shape, on the 8-device sim. ``reshard:<src>to<dst>``
#: naming; ``even_src`` derives the source from the restore layout
#: (redistribute.restore_layout_spec — the elastic-restore seam's even
#: read), ``no_gather`` arms the zero-all_gather pin (a pure axis MOVE
#: must be ONE all_to_all; any all_gather means replicated staging).
RESHARD_PROGRAMS: dict[str, dict] = {
    "reshard:fsdp_to_tp": dict(
        mesh=dict(data=1, fsdp=4, model=2), shape=(64, 64),
        src=("fsdp", None), dst=(None, "model"),
    ),
    "reshard:tp_row_to_col": dict(
        mesh=dict(data=1, model=8), shape=(64, 64),
        src=("model", None), dst=(None, "model"), no_gather=True,
    ),
    "reshard:restore_even_to_fsdp": dict(
        mesh=dict(data=2, fsdp=4), shape=(64, 64),
        src=None, dst=("fsdp", None), even_src=True,
    ),
}


def build_reshard_program(name: str):
    """One redistribution executor program as an ABSTRACT artifact:
    ``(plan, jaxpr, lowered)`` — the jaxpr is the EXACT
    ``redistribute.executor.collective_callable`` the executor jits
    (same body, same shard_map specs), so the linted artifact and the
    executed one cannot drift; the lowered form carries the executor's
    donation (``donate_argnums=(0,)``). Shared with the perf ledger's
    ``redistribute:*`` rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        MeshConfig,
        build_mesh,
    )
    from frl_distributed_ml_scaffold_tpu.redistribute import (
        compile_leaf_plan,
        restore_layout_spec,
    )
    from frl_distributed_ml_scaffold_tpu.redistribute.executor import (
        collective_callable,
    )

    if name not in RESHARD_PROGRAMS:
        raise ValueError(
            f"unknown reshard program {name!r} "
            f"(have {sorted(RESHARD_PROGRAMS)})"
        )
    cfg = RESHARD_PROGRAMS[name]
    env = build_mesh(MeshConfig(**cfg["mesh"]))
    shape = cfg["shape"]
    dst_spec = P(*cfg["dst"])
    src_spec = (
        restore_layout_spec(shape, dst_spec, env.mesh)
        if cfg.get("even_src")
        else P(*cfg["src"])
    )
    plan = compile_leaf_plan(
        shape, jnp.float32,
        NamedSharding(env.mesh, src_spec),
        NamedSharding(env.mesh, dst_spec),
        path=name,
    )
    if plan.kind != "collective":
        raise RuntimeError(
            f"{name}: expected a collective plan, compiled {plan.kind!r} "
            "— the program classes graft-lint pins must stay on the "
            "collective executor"
        )
    fn = collective_callable(plan)
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(shape, jnp.float32))
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=plan.src_sharding
        )
    )
    return plan, jaxpr, lowered


def _shard_map_inner(jaxpr):
    """The shard_map eqn's body jaxpr (per-device LOCAL shapes — the
    altitude the scratch budget is written at; the outer eqn's outvar is
    the global array, which no single device materializes)."""
    for eqn in jaxpr.jaxpr.eqns:
        if "shard_map" in eqn.primitive.name:
            return eqn.params["jaxpr"]
    return jaxpr


def lint_reshard(name: str) -> Report:
    """Lint one redistribution executor program (ISSUE 15) — the
    zero-replicated-staging contract (arXiv 2112.01075), three teeth:

    - materialization budget == the plan's ``peak_scratch_bytes`` (one
      source shard + one destination shard per device), checked on the
      shard_map BODY: a naive gather-then-scatter materializes the full
      logical array on every device and trips it;
    - pure axis MOVES (``no_gather`` programs) additionally pin ZERO
      all_gather: the move is ONE all_to_all — any gather is staging;
    - donation audit: the executor's jitted program donates its source
      (or every reshard holds two copies live).

    Mutation-gated in tests/test_graft_lint.py via the executor's
    ``_NAIVE_GATHER_SCATTER`` reference switch."""
    from frl_distributed_ml_scaffold_tpu.analysis.donation import (
        lowered_donations,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
        oversized_intermediates,
    )

    report = Report(program=name)
    plan, jaxpr, lowered = build_reshard_program(name)
    census = collective_census(jaxpr)
    report.meta["collective_census"] = [r.to_dict() for r in census]
    report.meta["plan"] = plan.to_dict()

    budget = plan.peak_scratch_bytes
    for i in oversized_intermediates(_shard_map_inner(jaxpr), budget):
        report.add(
            "materialization", "error", "replicated-staging",
            f"reshard program materializes {i.dtype}{list(i.shape)} "
            f"({i.bytes} bytes > the {budget}-byte scratch budget, "
            f"{i.primitive}) per device — a redistribution must move "
            "shard deltas, never stage the logical array",
            intermediate=i.to_dict(), budget_bytes=budget,
        )
    if RESHARD_PROGRAMS[name].get("no_gather"):
        for r in census:
            if "all_gather" in r.primitive:
                report.add(
                    "reshard", "error", "gather-on-move",
                    f"pure axis move carries an all_gather of "
                    f"{[list(s) for s in r.shapes]} — the move is ONE "
                    "all_to_all; a gather is replicated staging",
                    primitive=r.primitive,
                    shapes=[list(s) for s in r.shapes],
                )
    dons = lowered_donations(lowered)
    if sum(1 for d in dons if d.donated) < 1:
        report.add(
            "donation", "error", "source-not-donated",
            "reshard program does not donate its source array — every "
            "redistribution holds two copies live",
        )
    else:
        report.add(
            "donation", "info", "summary",
            f"source donated; plan moves {plan.bytes_moved} bytes "
            f"(lower bound {plan.bytes_lower_bound}) at peak scratch "
            f"{plan.peak_scratch_bytes}",
        )
    return report


def lint_reshard_programs() -> list[Report]:
    """All registered ``reshard:*`` executor program classes."""
    return [lint_reshard(name) for name in sorted(RESHARD_PROGRAMS)]


def build_tiny_gpt():
    """THE shrink-shape GPT twin for the redistribute seam artifacts —
    one definition shared by ``build_train_to_serve_plan`` (perf ledger
    + CLI train→serve seam) and ``tools/reshard_plan.py``'s restore /
    respread seams, so editing the twin cannot desynchronize the gated
    ledger row from the operator dry-runs. Returns ``(model,
    abstract_params)``; nothing runs."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=128, num_layers=2, num_heads=4, hidden_dim=64,
            seq_len=32, dropout=0.0,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((2, 8), jnp.int32), train=False,
        )["params"]
    )
    return model, params


def build_train_to_serve_plan():
    """The tiny-GPT train→serve handoff as an ABSTRACT tree plan: params
    shaped/sharded the way the fsdp×model trainer would hold them
    (fsdp=4 × model=2 over the 8-device sim), re-planned onto a 2-device
    serving TP mesh — nothing runs. ONE twin shared by the perf-ledger
    ``redistribute:train_to_serve`` row and the ``reshard_plan.py``
    CLI, so the gated numbers and the operator's dry-run cannot
    drift."""
    import jax

    from frl_distributed_ml_scaffold_tpu import redistribute
    from frl_distributed_ml_scaffold_tpu.config.schema import ParallelConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        MeshConfig,
        build_mesh,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import gpt_tp_rules
    from frl_distributed_ml_scaffold_tpu.parallel.partition import (
        param_specs,
        shardings_from_specs,
    )

    _model, params = build_tiny_gpt()
    train_env = build_mesh(MeshConfig(data=1, fsdp=4, model=2))
    p_specs = param_specs(
        params,
        ParallelConfig(param_sharding="fsdp", fsdp_min_size=16),
        train_env.mesh,
        gpt_tp_rules(),
    )
    src_sh = shardings_from_specs(p_specs, train_env.mesh)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params, src_sh,
    )
    serve_env = build_mesh(
        MeshConfig(data=1, model=2), devices=jax.devices()[:2]
    )
    plan = redistribute.train_to_serve_plan(
        params, serve_env, gpt_tp_rules()
    )
    return plan, train_env, serve_env


def lint_hygiene(paths: Iterable[str] | None = None) -> Report:
    """AST hygiene lint over the repo's traced modules."""
    import glob
    import os

    from frl_distributed_ml_scaffold_tpu.analysis.hygiene import lint_file

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if paths is None:
        paths = (
            sorted(glob.glob(os.path.join(pkg, "ops", "*.py")))
            + sorted(glob.glob(os.path.join(pkg, "parallel", "*.py")))
            + sorted(glob.glob(os.path.join(pkg, "models", "*.py")))
            + [os.path.join(pkg, "trainer", "train_step.py")]
        )
    report = Report(program="hygiene:traced-modules")
    n = 0
    for p in paths:
        n += 1
        report.extend(lint_file(p))
    report.meta["files"] = n
    return report


def lint_robustness(paths: Iterable[str] | None = None) -> Report:
    """Failure-semantics lint (ISSUE 9) over the WHOLE package — host
    orchestration included, because that is exactly where exceptions get
    swallowed and retry loops spin (the traced-module file list the
    hygiene pass uses would miss the engine, the supervisor, and the
    checkpointer)."""
    import glob
    import os

    from frl_distributed_ml_scaffold_tpu.analysis.hygiene import (
        lint_robustness_file,
    )

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if paths is None:
        paths = sorted(
            p
            for p in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True)
            if "__pycache__" not in p
        )
    report = Report(program="robustness:package")
    n = 0
    for p in paths:
        n += 1
        report.extend(lint_robustness_file(p))
    report.meta["files"] = n
    return report


def lint_concurrency(paths: Iterable[str] | None = None) -> Report:
    """Lock-discipline lint (ISSUE 20) over the WHOLE package: guarded-
    attribute inference (``unguarded-shared-write``), the interprocedural
    lock-acquisition-order graph (``lock-order-inversion``), and blocking
    calls under held locks (``blocking-under-lock``).  Whole-package like
    ``robustness:package`` — lock identities and the call graph resolve
    ACROSS modules (the FaultPlan -> MetricsRegistry nesting edge lives
    in two files)."""
    import glob
    import os

    from frl_distributed_ml_scaffold_tpu.analysis.concurrency import (
        lint_concurrency_paths,
    )

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if paths is None:
        paths = sorted(
            p
            for p in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True)
            if "__pycache__" not in p
        )
    paths = list(paths)
    report = Report(program="concurrency:package")
    report.extend(lint_concurrency_paths(paths))
    report.meta["files"] = len(paths)
    return report


def lint_all(
    *,
    recipes: Iterable[str] | None = None,
    serving: bool = True,
    reshard: bool = True,
    hygiene: bool = True,
    robustness: bool = True,
    concurrency: bool = True,
    workdir: str = "/tmp/graft_lint",
    budget_bytes: int | None = None,
    on_report: Callable[[Report], None] | None = None,
) -> list[Report]:
    """Lint every registered recipe (or the named subset) + extras."""
    from frl_distributed_ml_scaffold_tpu.config import list_configs

    names = list(recipes) if recipes is not None else list_configs()
    reports = []

    def emit(r: Report) -> None:
        reports.append(r)
        if on_report is not None:
            on_report(r)

    for name in names:
        try:
            # One build + trace per recipe: the recipe report plus, for
            # overlap recipes, the schedule: program family report
            # (ISSUE 13 — the declaration-first view of the same
            # findings).
            for r in _lint_recipe_reports(
                name, workdir=workdir, budget_bytes=budget_bytes
            ):
                emit(r)
        except Exception as e:  # surface as a finding, not a crash
            r = Report(program=f"recipe:{name}")
            r.add(
                "runner", "error", "lint-crashed",
                f"linting {name} raised {type(e).__name__}: {e}",
            )
            emit(r)
    if serving:
        emit(lint_decode_step())
        # The quantized-cache decode step is its own compiled-shape class
        # in production (model.kv_cache_quant) — lint it as its own
        # program, with the dequantized-cache pin armed.
        emit(lint_decode_step(kv_cache_quant="int8"))
        # The paged (block-table) decode step (ISSUE 10): the engine's
        # ONE compiled decode shape, with the no-cache-clone budget and
        # the no-logical-gather pin armed — plus its int8-pool flavor.
        emit(lint_paged_decode_step())
        emit(lint_paged_decode_step(kv_cache_quant="int8"))
        # The speculative verify step (ISSUE 11): the ONE [B, k+1]
        # compiled verify shape, same pins at tile width.
        emit(lint_verify_step())
        # The prefill→decode handoff splice (ISSUE 12): the block-table
        # re-own pinned clone-free — zero collectives, no logical-cache
        # copy, pool donated.
        emit(lint_handoff())
    if reshard:
        # The redistribution executor's program classes (ISSUE 15):
        # same-mesh reshards pinned staging-free + donated.
        for r in lint_reshard_programs():
            emit(r)
    if hygiene:
        emit(lint_hygiene())
    if robustness:
        emit(lint_robustness())
    if concurrency:
        emit(lint_concurrency())
    return reports
