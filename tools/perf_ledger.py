#!/usr/bin/env python
"""Perf-attribution ledger: census-vs-measured roofline table + gate.

Joins graft-lint's ANALYTIC side (collective census bytes + jaxpr-counted
FLOPs per recipe — deterministic, trace-only, the SimpleFSDP
compile-artifact-accounting shape, arXiv 2411.00284) with the telemetry
layer's MEASURED side (step-time histograms from a tiny CPU-sim fit;
TTFT/TPOT from a tiny serve run) into one per-recipe attribution row:

- ``flops_per_step`` / ``collective_bytes_per_step`` / arithmetic
  intensity, and the roofline verdict (compute- vs comm-bound at the
  configured peaks);
- the recipe's DECLARED overlap schedule (parallel/schedule.py
  ``describe()`` — rows are per-schedule, not per-recipe: what the step
  declares about its gathers/scatters/lowp gates together with the
  census that declaration produces; "gspmd" for plain recipes);
- the CPU sim's ``step_time_p50_s`` as provenance of the run that built
  the row. It is a CPU wall time, not a speed: no FLOP/s, MFU or
  roofline share is derived from it (those come from a chip run only).

This is the repo's CENSUS gate: the analytic side is bit-deterministic on
the CPU sim, so ``--check`` against the committed baseline
(``PERF_LEDGER.json``) catches any change to a step's communication or
compute census — the promoted, blocking form of graft-lint's advisory
census diff. Measured columns are provenance (stamped when the baseline
was built) and are only re-compared under ``--measure-steps``, with a
wide tolerance, because CPU-sim wall time is load-dependent.

    python tools/perf_ledger.py --write PERF_LEDGER.json --measure-steps 6
    python tools/perf_ledger.py --check                  # the CI gate
    python tools/perf_ledger.py --check --measure-steps 6 --tol 3.0

Exit is nonzero when any baseline row's analytic fields drift, a
baseline recipe disappears, or (under ``--measure-steps``) a measured
step time leaves its tolerance band.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Platform pins BEFORE jax imports (the graft_lint.py / conftest.py
# discipline): this tool is a CPU-sim census and never takes the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

#: Default baseline location (committed at the repo root, next to
#: BASELINE.json).
DEFAULT_BASELINE = os.path.join(_REPO, "PERF_LEDGER.json")

#: The committed tiny-recipe set: one replicated-DDP recipe (census is
#: empty at the jaxpr level — GSPMD owns its collectives), one
#: explicit-schedule recipe (the ppermute rings ARE the census), and the
#: composed fsdp x TP overlap schedule (ISSUE 13 — blockwise gathers AND
#: rings in one scan body). Small enough that --check stays inside the
#: lint tier's budget.
DEFAULT_RECIPES = (
    "mnist_mlp", "gpt2_medium_tp_overlap", "gpt2_medium_fsdp_tp_overlap",
)

SERVING_PROGRAM = "serving:decode_step"
PAGED_SERVING_PROGRAM = "serving:decode_step_paged"
VERIFY_SERVING_PROGRAM = "serving:verify_step_paged"
HANDOFF_PROGRAM = "serving:handoff"

#: MPMD pipeline per-stage rows (ISSUE 14): one row per stage program of
#: the tiny-twin MPMD recipe — census/FLOPs of the microbatch
#: fwd+bwd program, the analytic 1F1B bubble/peak-live model, and the
#: explicit boundary-transfer bytes the driver moves per microbatch
#: (which is the whole inter-stage communication story: stage programs
#: are census-pinned collective-free across stages by graft-lint).
MPMD_RECIPE = "gpt2_pipeline_mpmd"
MPMD_STAGE_PREFIX = "pipeline:stage"

#: Redistribution-service migration rows (ISSUE 15): one per lintable
#: same-mesh executor program class (reshard:* — census bytes ARE the
#: wire cost) plus the tree-level train→serve handoff plan over the
#: tiny-GPT twin (chunked cross-mesh — priced by the plan compiler's
#: cost model: bytes_moved vs the shard-delta lower bound, peak
#: scratch). Analytic-only; the measured arm is queued as BACKLOG R18-1
#: (perf_sweep reshard_train_to_serve).
REDISTRIBUTE_PREFIX = "redistribute:"

#: Analytic row fields --check compares EXACTLY. Everything else in a row
#: (intensity, roofline, measured) is either derived from these or
#: measured wall time. ``schedule`` makes the rows per-SCHEDULE (ISSUE
#: 13): each row carries its recipe's declared OverlapSchedule
#: descriptor, so a change to WHAT a recipe declares (axes, granularity,
#: prefetch, lowp) gates exactly like a change to the census the
#: declaration produces.
ANALYTIC_KEYS = (
    "flops_per_step",
    "collective_bytes_per_step",
    "collectives",
    "params_bytes",
    "chips",
    "schedule",
)


#: The chip the analytic roofline is drawn for. The ledger itself always
#: runs on the CPU sim (platform pin above), so this is a TARGET, looked
#: up in the repo's one peaks table — never the device that ran.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _target_peak_flops() -> float:
    from frl_distributed_ml_scaffold_tpu.utils.flops import PEAK_BF16_FLOPS

    return PEAK_BF16_FLOPS[TARGET_DEVICE_KIND]


def peak_ici_bytes_per_chip_s() -> float:
    """Per-chip interconnect bandwidth for the roofline's comm leg —
    v5e ICI (~4.5e10 B/s per link direction x 2 links, a deliberately
    round planning number, not a datasheet quote), overridable via
    ``FRL_PEAK_ICI_BYTES_PER_CHIP`` when the mesh lands elsewhere."""
    return float(os.environ.get("FRL_PEAK_ICI_BYTES_PER_CHIP", 9e10))


def _tree_bytes(tree) -> int:
    import jax
    import numpy as np

    return int(
        sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(tree)
        )
    )


def _roofline(flops: int, comm_bytes: int, chips: int) -> dict:
    """Lower-bound times ON THE TARGET CHIP (``TARGET_DEVICE_KIND``'s
    peaks — an analytic model of where the step would be bound there,
    not a measurement) and the resulting bound verdict. NOT compared by
    --check (env overrides move the ICI peak); recomputed at read time
    for the table."""
    peak_f = _target_peak_flops()
    peak_b = peak_ici_bytes_per_chip_s()
    compute_s = flops / (chips * peak_f) if flops else 0.0
    comm_s = comm_bytes / (chips * peak_b) if comm_bytes else 0.0
    return {
        "compute_s_lower_bound": compute_s,
        "comm_s_lower_bound": comm_s,
        "bound": "compute" if compute_s >= comm_s else "comm",
    }


def analytic_recipe_row(name: str, workdir: str) -> dict:
    """The deterministic half of a recipe's row: jaxpr FLOPs + collective
    census of the (tiny-twin) train step, shapes via analysis.runner."""
    import jax

    from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
        census_summary,
        collective_census,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        _abstract_batch,
        _build_trainer,
    )
    from frl_distributed_ml_scaffold_tpu.utils.flops import jaxpr_flops

    from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
        schedule_from_config,
    )

    trainer = _build_trainer(name, workdir)
    batch = _abstract_batch(trainer)
    jaxpr = trainer._mesh_scoped(jax.make_jaxpr(trainer._train_step_fn))(
        trainer.state_shapes, batch
    )
    census = collective_census(jaxpr)
    flops = jaxpr_flops(jaxpr)
    comm = sum(r.total_bytes for r in census)
    chips = jax.device_count()
    # Rows are per-SCHEDULE (ISSUE 13): the declared OverlapSchedule
    # descriptor rides next to the census it is supposed to produce, and
    # --check gates both together. Recipes with no overlap declaration
    # record the GSPMD schedule explicitly.
    sched = schedule_from_config(trainer.cfg)
    return {
        "flops_per_step": flops,
        "collective_bytes_per_step": comm,
        "collectives": {
            prim: agg for prim, agg in sorted(census_summary(census).items())
        },
        "params_bytes": _tree_bytes(trainer.state_shapes.params),
        "chips": chips,
        "schedule": (
            sched.describe() if sched is not None
            else {"declared": "gspmd", "short": "gspmd"}
        ),
        "intensity_flops_per_byte": round(flops / max(comm, 1), 3),
        "roofline": _roofline(flops, comm, chips),
    }


def analytic_serving_row(
    paged: bool = False, verify: bool = False, handoff: bool = False,
) -> dict:
    """Same, for the serving decode step (the graft-lint program, shared
    via analysis.runner.build_decode_step_program). ``paged=True`` builds
    the ISSUE-10 block-table decode step instead
    (build_paged_decode_step_program — the paged engine's ONE compiled
    decode shape); ``verify=True`` builds the ISSUE-11 speculative
    verify step (build_verify_step_program — the [B, k+1] tile), whose
    row additionally carries the amortization twin: ``positions_per
    _invocation`` = k+1 query positions score against ONE pool read, so
    ``flops_per_position`` sits next to the decode row's whole-step
    FLOPs — the analytic face of serve_bench's measured
    accepted-per-verify / invocations-per-token columns."""
    import jax

    from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
        census_summary,
        collective_census,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        build_decode_step_program,
        build_handoff_program,
        build_paged_decode_step_program,
        build_verify_step_program,
    )
    from frl_distributed_ml_scaffold_tpu.utils.flops import jaxpr_flops

    if handoff:
        # The handoff SPLICE row (ISSUE 12): the analytic cost of moving
        # a finished prefill decode-side. The headline is what the row
        # PINS: ownership moves as one block-table row
        # (``splice_table_bytes`` — int32 per table slot), the program
        # writes only the private blocks that change owner
        # (``splice_blocks_written`` x block bytes), and NOTHING moves
        # collectively (``collective_bytes_per_step`` == 0, the
        # reshard-free splice) — table bytes, not cache bytes.
        from frl_distributed_ml_scaffold_tpu.models.generation import (
            SLOT_LEAF_OF,
            pool_block_bytes,
        )

        model, pool_cache, slot_cache, blk_ids, jaxpr = (
            build_handoff_program()
        )
        census = collective_census(jaxpr)
        flops = jaxpr_flops(jaxpr)
        comm = sum(r.total_bytes for r in census)
        chips = jax.device_count()
        block_size = next(
            l.shape[2]
            for p, l in jax.tree_util.tree_flatten_with_path(pool_cache)[0]
            if getattr(p[-1], "key", None) in SLOT_LEAF_OF
        )
        table_blocks = model.config.seq_len // block_size
        return {
            "flops_per_step": flops,
            "collective_bytes_per_step": comm,
            "collectives": {
                prim: agg
                for prim, agg in sorted(census_summary(census).items())
            },
            "params_bytes": 0,  # the splice never touches params
            "chips": chips,
            "cache_bytes": _tree_bytes(pool_cache),
            "splice_table_bytes": table_blocks * 4,
            "splice_blocks_written": int(blk_ids.shape[0]),
            "splice_block_bytes": pool_block_bytes(pool_cache),
            "intensity_flops_per_byte": round(flops / max(comm, 1), 3),
            "roofline": _roofline(flops, comm, chips),
        }
    build = (
        build_verify_step_program if verify
        else build_paged_decode_step_program if paged
        else build_decode_step_program
    )
    _, params, cache, tok, jaxpr = build()
    census = collective_census(jaxpr)
    flops = jaxpr_flops(jaxpr)
    comm = sum(r.total_bytes for r in census)
    chips = jax.device_count()
    row = {
        "flops_per_step": flops,
        "collective_bytes_per_step": comm,
        "collectives": {
            prim: agg for prim, agg in sorted(census_summary(census).items())
        },
        "params_bytes": _tree_bytes(params),
        "chips": chips,
        "cache_bytes": _tree_bytes(cache),
        "intensity_flops_per_byte": round(flops / max(comm, 1), 3),
        "roofline": _roofline(flops, comm, chips),
    }
    if verify:
        positions = int(tok.shape[1])  # the k+1 tile
        row["positions_per_invocation"] = positions
        row["flops_per_position"] = flops // positions
    return row


def analytic_redistribute_rows() -> dict:
    """Migration rows for the redistribution service (ISSUE 15). The
    executor program rows share graft-lint's ``build_reshard_program``
    artifacts (census bytes = wire cost; ``bytes_moved`` pinned equal to
    the shard-delta ``bytes_lower_bound`` — the 2112.01075 minimality
    claim as a gated number); the ``train_to_serve`` row compiles the
    tiny-GPT fsdp×model → serving-TP tree plan abstractly (nothing
    runs)."""
    from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
        census_summary,
        collective_census,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        RESHARD_PROGRAMS,
        build_reshard_program,
    )
    from frl_distributed_ml_scaffold_tpu.utils.flops import jaxpr_flops

    rows: dict[str, dict] = {}
    sched = {"declared": "redistribute", "short": "reshard"}
    for name in sorted(RESHARD_PROGRAMS):
        plan, jaxpr, _lowered = build_reshard_program(name)
        census = collective_census(jaxpr)
        comm = sum(r.total_bytes for r in census)
        flops = jaxpr_flops(jaxpr)
        chips = plan.dst_sharding.mesh.size
        rows[REDISTRIBUTE_PREFIX + name.split(":", 1)[1]] = {
            "flops_per_step": flops,
            "collective_bytes_per_step": comm,
            "collectives": {
                prim: agg
                for prim, agg in sorted(census_summary(census).items())
            },
            "params_bytes": plan.leaf_bytes,
            "chips": chips,
            "schedule": sched,
            "bytes_moved": plan.bytes_moved,
            "bytes_lower_bound": plan.bytes_lower_bound,
            "peak_scratch_bytes": plan.peak_scratch_bytes,
            "intensity_flops_per_byte": round(flops / max(comm, 1), 3),
            "roofline": _roofline(flops, comm, chips),
        }

    # The train→serve handoff, tree-level: the shared tiny-GPT abstract
    # twin (analysis.runner.build_train_to_serve_plan — the same plan
    # tools/reshard_plan.py prices, so row and dry-run cannot drift).
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        build_train_to_serve_plan,
    )

    plan, train_env, _serve_env = build_train_to_serve_plan()
    rows[REDISTRIBUTE_PREFIX + "train_to_serve"] = {
        "flops_per_step": 0,
        "collective_bytes_per_step": plan.bytes_moved,
        "collectives": {},
        "params_bytes": plan.total_bytes,
        "chips": train_env.mesh.size,
        "schedule": {"declared": "redistribute", "short": "t2s"},
        "bytes_moved": plan.bytes_moved,
        "bytes_lower_bound": plan.bytes_lower_bound,
        "peak_scratch_bytes": plan.peak_scratch_bytes,
        "plan_kinds": sorted(
            {leaf.kind for leaf in plan.leaves}
        ),
        "intensity_flops_per_byte": 0.0,
        "roofline": _roofline(0, plan.bytes_moved, train_env.mesh.size),
    }
    return rows


def analytic_stage_rows(workdir: str = "/tmp/perf_ledger") -> dict:
    """Per-stage rows for the MPMD pipeline recipe (ISSUE 14): stage j's
    row carries the jaxpr FLOPs + collective census of its microbatch
    fwd+bwd program (within-stage collectives only — the graft-lint
    ``pipeline:stage_program`` family errors on any ``pipe``-axis
    collective), the analytic 1F1B schedule model (bubble fraction,
    whole-schedule and per-stage peak live activations — pinned against
    the driver's measured counters in tests/test_mpmd_pipeline.py), and
    the explicit activation-transfer bytes per microbatch boundary."""
    import jax

    from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
        census_summary,
        collective_census,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        _build_trainer,
    )
    from frl_distributed_ml_scaffold_tpu.parallel.mpmd_pipeline import (
        bubble_fraction,
        peak_live_activations,
        stage_peak_live,
    )
    from frl_distributed_ml_scaffold_tpu.utils.flops import jaxpr_flops

    trainer = _build_trainer(MPMD_RECIPE, workdir)
    runner = trainer._mpmd
    s, mt = runner.num_stages, runner.total_micro
    rows = {}
    for art in runner.lint_artifacts():
        j = art["stage"]
        census = collective_census(art["fwd_bwd_jaxpr"])
        flops = jaxpr_flops(art["fwd_bwd_jaxpr"])
        comm = sum(r.total_bytes for r in census)
        rows[f"{MPMD_STAGE_PREFIX}{j}"] = {
            "flops_per_step": flops,
            "collective_bytes_per_step": comm,
            "collectives": {
                prim: agg
                for prim, agg in sorted(census_summary(census).items())
            },
            "params_bytes": _tree_bytes(art["params_shapes"]),
            "chips": art["chips"],
            "schedule": {
                "declared": f"pipeline(mpmd,1f1b,stages={s},micro={mt})",
                "short": "1f1b",
            },
            "bubble_fraction": bubble_fraction("1f1b", s, mt),
            "peak_live_activations": peak_live_activations("1f1b", s, mt),
            "stage_peak_live": stage_peak_live(j, s, mt),
            "boundary_bytes_per_microbatch": art[
                "boundary_bytes_per_microbatch"
            ],
            "intensity_flops_per_byte": round(flops / max(comm, 1), 3),
            "roofline": _roofline(flops, comm, art["chips"]),
        }
    return rows


def measure_recipe(name: str, steps: int, workdir: str) -> dict:
    """The measured half: a tiny real fit on the CPU sim, reading the
    step-time percentiles the telemetry layer already computes. Wall
    time, not a pin — compared only under --measure-steps, with --tol."""
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        RECIPE_OVERRIDES,
        _COMMON,
    )
    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(
        get_config(name),
        _COMMON + RECIPE_OVERRIDES[name] + [
            f"workdir={workdir}",
            f"trainer.total_steps={steps}",
            "trainer.log_every=2",
        ],
    )
    trainer = Trainer(cfg, mesh_env=build_mesh(cfg.mesh))
    _, last = trainer.fit()
    return {
        "steps": steps,
        "step_time_p50_s": float(last.get("step_time_p50_s", 0.0)),
        "step_time_p99_s": float(last.get("step_time_p99_s", 0.0)),
        "samples_per_sec_per_chip": float(
            last.get("samples_per_sec_per_chip", 0.0)
        ),
    }


def measure_serving(n_requests: int = 4) -> dict:
    """TTFT/TPOT percentiles from a tiny warm serve run (the serve_bench
    warm-up discipline: compile-polluted pass dropped via reset_cache)."""
    import jax
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        build_decode_step_program,
    )
    from frl_distributed_ml_scaffold_tpu.serving.engine import ServingEngine

    model, _, _, _, _ = build_decode_step_program()
    tokens = jax.random.randint(jax.random.key(0), (2, 8), 0, 64)
    params = jax.jit(
        lambda: model.init(
            {"params": jax.random.key(0)}, tokens, train=False
        )["params"]
    )()
    rng = np.random.default_rng(0)
    work = [
        (rng.integers(0, 64, size=int(rng.integers(2, 10))).astype(np.int32),
         int(rng.integers(2, 8)))
        for _ in range(n_requests)
    ]
    eng = ServingEngine(model, params, num_slots=2, temperature=0.0)
    try:
        for prompt, n_new in work:  # warm pass: compiles
            eng.submit(prompt, n_new)
        eng.run()
        eng.reset_cache()
        for prompt, n_new in work:  # measured pass
            eng.submit(prompt, n_new)
        eng.run()
        snap = eng.telemetry.snapshot()
        return {
            "requests": n_requests,
            "ttft_p50_s": snap["serve_ttft_seconds"]["p50"],
            "ttft_p99_s": snap["serve_ttft_seconds"]["p99"],
            "tpot_p50_s": snap["serve_tpot_seconds"]["p50"],
            "tpot_p99_s": snap["serve_tpot_seconds"]["p99"],
        }
    finally:
        eng.close()


def build_ledger(
    recipes,
    *,
    serving: bool = True,
    measure_steps: int = 0,
    workdir: str = "/tmp/perf_ledger",
) -> dict:
    rows: dict[str, dict] = {}
    for name in recipes:
        print(f"perf_ledger: tracing recipe:{name}", flush=True)
        row = analytic_recipe_row(name, workdir)
        if measure_steps > 0:
            print(f"perf_ledger: measuring recipe:{name} "
                  f"({measure_steps} steps)", flush=True)
            row["measured"] = measure_recipe(name, measure_steps, workdir)
        rows[f"recipe:{name}"] = row
    if serving:
        print(f"perf_ledger: tracing {SERVING_PROGRAM}", flush=True)
        row = analytic_serving_row()
        if measure_steps > 0:
            print(f"perf_ledger: measuring {SERVING_PROGRAM}", flush=True)
            row["measured"] = measure_serving()
        rows[SERVING_PROGRAM] = row
        # The paged (block-table) decode step (ISSUE 10): analytic-only —
        # its census/FLOPs gate like every other row; the measured paged
        # serving numbers live in tools/serve_bench.py's paged arms.
        print(f"perf_ledger: tracing {PAGED_SERVING_PROGRAM}", flush=True)
        rows[PAGED_SERVING_PROGRAM] = analytic_serving_row(paged=True)
        # The speculative verify step (ISSUE 11): analytic-only — the
        # k+1-position tile amortizes the pool read, so its
        # flops_per_position row is the analytic twin of serve_bench's
        # measured accepted-per-verify / invocations-per-token columns.
        print(f"perf_ledger: tracing {VERIFY_SERVING_PROGRAM}", flush=True)
        rows[VERIFY_SERVING_PROGRAM] = analytic_serving_row(verify=True)
        # The prefill→decode handoff splice (ISSUE 12): analytic-only —
        # the row pins the splice at table bytes, not cache bytes
        # (ownership = one int32 table row; zero collective bytes), the
        # analytic face of serve_bench's *_disagg tail-isolation columns.
        print(f"perf_ledger: tracing {HANDOFF_PROGRAM}", flush=True)
        rows[HANDOFF_PROGRAM] = analytic_serving_row(handoff=True)
    # MPMD pipeline per-stage rows (ISSUE 14): analytic-only — the
    # measured A/B vs the SPMD backend rides perf_sweep
    # gpt2_pipeline_mpmd (BACKLOG R17-1).
    print(f"perf_ledger: tracing {MPMD_STAGE_PREFIX}* "
          f"({MPMD_RECIPE})", flush=True)
    rows.update(analytic_stage_rows(workdir))
    # Redistribution-service migration rows (ISSUE 15): analytic-only —
    # the measured train→serve arm is queued as BACKLOG R18-1.
    print(f"perf_ledger: tracing {REDISTRIBUTE_PREFIX}*", flush=True)
    rows.update(analytic_redistribute_rows())
    return {
        "version": 1,
        "generated_by": "tools/perf_ledger.py",
        "target_device_kind": TARGET_DEVICE_KIND,
        "peak_flops_per_chip": _target_peak_flops(),
        "peak_ici_bytes_per_chip_s": peak_ici_bytes_per_chip_s(),
        "rows": rows,
    }


def check_ledger(
    baseline: dict,
    *,
    measure_steps: int = 0,
    tol: float = 3.0,
    workdir: str = "/tmp/perf_ledger",
) -> list[str]:
    """Drift findings (empty = green). Analytic fields compare exactly;
    measured step time within a factor of ``tol`` when re-measured."""
    problems: list[str] = []
    stage_rows: dict | None = None  # rebuilt once on first pipeline: row
    redist_rows: dict | None = None  # rebuilt once on first redistribute:
    for program, base in sorted(baseline.get("rows", {}).items()):
        if program.startswith(REDISTRIBUTE_PREFIX):
            if redist_rows is None:
                try:
                    redist_rows = analytic_redistribute_rows()
                except Exception as e:
                    problems.append(
                        f"{program}: redistribute rows no longer compile "
                        f"({type(e).__name__}: {e})"
                    )
                    redist_rows = {}
            cur = redist_rows.get(program)
            if cur is None:
                if redist_rows:
                    problems.append(
                        f"{program}: baseline redistribute row no longer "
                        f"produced (have: {sorted(redist_rows)})"
                    )
                continue
        elif program.startswith(MPMD_STAGE_PREFIX):
            if stage_rows is None:
                try:
                    stage_rows = analytic_stage_rows(workdir)
                except Exception as e:
                    problems.append(
                        f"{program}: stage rows no longer trace "
                        f"({type(e).__name__}: {e})"
                    )
                    stage_rows = {}
            cur = stage_rows.get(program)
            if cur is None:
                if stage_rows:
                    problems.append(
                        f"{program}: baseline stage row no longer produced "
                        f"(stages: {sorted(stage_rows)})"
                    )
                continue
        elif program in (
            SERVING_PROGRAM, PAGED_SERVING_PROGRAM, VERIFY_SERVING_PROGRAM,
            HANDOFF_PROGRAM,
        ):
            try:
                cur = analytic_serving_row(
                    paged=program == PAGED_SERVING_PROGRAM,
                    verify=program == VERIFY_SERVING_PROGRAM,
                    handoff=program == HANDOFF_PROGRAM,
                )
            except Exception as e:
                problems.append(
                    f"{program}: baseline program no longer traces "
                    f"({type(e).__name__}: {e})"
                )
                continue
        elif program.startswith("recipe:"):
            name = program.split(":", 1)[1]
            try:
                cur = analytic_recipe_row(name, workdir)
            except Exception as e:
                problems.append(
                    f"{program}: baseline recipe no longer traces "
                    f"({type(e).__name__}: {e})"
                )
                continue
        else:
            problems.append(f"{program}: unknown program class in baseline")
            continue
        for key in ANALYTIC_KEYS:
            if base.get(key) != cur.get(key):
                problems.append(
                    f"{program}: {key} drifted — baseline "
                    f"{json.dumps(base.get(key))} vs current "
                    f"{json.dumps(cur.get(key))}"
                )
        for extra in ("cache_bytes", "splice_table_bytes",
                      "splice_blocks_written", "splice_block_bytes",
                      "bubble_fraction", "peak_live_activations",
                      "stage_peak_live", "boundary_bytes_per_microbatch",
                      "bytes_moved", "bytes_lower_bound",
                      "peak_scratch_bytes", "plan_kinds"):
            if extra in base and base[extra] != cur.get(extra):
                problems.append(
                    f"{program}: {extra} drifted — baseline "
                    f"{base[extra]} vs current {cur.get(extra)}"
                )
        if measure_steps > 0 and program.startswith("recipe:"):
            base_t = (base.get("measured") or {}).get("step_time_p50_s", 0.0)
            if base_t > 0:
                name = program.split(":", 1)[1]
                now_t = measure_recipe(name, measure_steps, workdir)[
                    "step_time_p50_s"
                ]
                if now_t > base_t * tol or now_t < base_t / tol:
                    problems.append(
                        f"{program}: measured step_time_p50_s {now_t:.6f}s "
                        f"outside [{base_t / tol:.6f}, {base_t * tol:.6f}] "
                        f"({tol}x band around baseline {base_t:.6f}s)"
                    )
    return problems


def render(ledger: dict, out=sys.stdout) -> None:
    rows = ledger.get("rows", {})
    if not rows:
        return
    width = max(len(p) for p in rows)
    swidth = max(
        [len("schedule")]
        + [
            len((r.get("schedule") or {}).get("short", "-"))
            for r in rows.values()
        ]
    )
    print(
        f"  {'program':<{width}s} {'schedule':<{swidth}s} "
        f"{'flops/step':>12s} {'comm B/step':>12s} "
        f"{'F/B':>10s} {'bound':>8s} {'p50 step s':>11s}",
        file=out,
    )
    for program, r in sorted(rows.items()):
        measured = r.get("measured") or {}
        t = measured.get("step_time_p50_s", measured.get("tpot_p50_s", 0.0))
        sched = (r.get("schedule") or {}).get("short", "-")
        print(
            f"  {program:<{width}s} {sched:<{swidth}s} "
            f"{r['flops_per_step']:>12.3e} "
            f"{r['collective_bytes_per_step']:>12d} "
            f"{r['intensity_flops_per_byte']:>10.1f} "
            f"{r['roofline']['bound']:>8s} "
            f"{t:>11.6f}",
            file=out,
        )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--write", metavar="PATH", default=None,
        help="build the ledger and write it here (the baseline refresh)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="recompute the analytic side and gate against --baseline",
    )
    ap.add_argument(
        "--baseline", default=DEFAULT_BASELINE,
        help=f"baseline path (default: {DEFAULT_BASELINE})",
    )
    ap.add_argument(
        "--recipes", default=",".join(DEFAULT_RECIPES),
        help="comma-separated recipe names for --write",
    )
    ap.add_argument(
        "--no-serving", action="store_true",
        help="skip the serving decode-step row",
    )
    ap.add_argument(
        "--measure-steps", type=int, default=0, metavar="N",
        help="also run N-step CPU-sim fits for the measured columns "
        "(and, under --check, re-compare step time within --tol)",
    )
    ap.add_argument(
        "--tol", type=float, default=3.0,
        help="relative band for re-measured step time under --check "
        "(default 3.0 = within 3x either way)",
    )
    ap.add_argument(
        "--workdir", default="/tmp/perf_ledger",
        help="scratch workdir for recipe construction",
    )
    args = ap.parse_args(argv)
    if not args.write and not args.check:
        ap.error("pass --write PATH or --check")

    if args.write:
        ledger = build_ledger(
            [r for r in args.recipes.split(",") if r],
            serving=not args.no_serving,
            measure_steps=args.measure_steps,
            workdir=args.workdir,
        )
        with open(args.write, "w") as fh:
            json.dump(ledger, fh, indent=1, sort_keys=True)
            fh.write("\n")
        render(ledger)
        print(f"wrote {len(ledger['rows'])} rows to {args.write}")
        return 0

    with open(args.baseline) as fh:
        baseline = json.load(fh)
    problems = check_ledger(
        baseline,
        measure_steps=args.measure_steps,
        tol=args.tol,
        workdir=args.workdir,
    )
    render(baseline)
    if problems:
        for p in problems:
            print(f"DRIFT: {p}")
        print(
            f"perf_ledger: {len(problems)} drift finding(s) vs "
            f"{args.baseline} — if the change is intended, refresh the "
            "baseline in the same commit (--write)"
        )
        return 1
    print(
        f"perf_ledger: {len(baseline.get('rows', {}))} rows match "
        f"{args.baseline}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
