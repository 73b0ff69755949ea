"""Trainer: sharded init + compiled step + host dispatch loop (SURVEY C3).

Call stack (a)/(b) TPU-native: build mesh → init state *directly sharded*
(``jit(create_state, out_shardings=...)`` — parameters materialize on their
home devices, no host-side full copy, which is what makes FSDP-init of
models bigger than one chip's HBM possible) → dispatch loop. The loop's only
per-step work is building the next batch and dispatching the async step;
metrics are fetched every ``log_every`` steps.
"""

from __future__ import annotations

import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from frl_distributed_ml_scaffold_tpu import faults
from frl_distributed_ml_scaffold_tpu.config.schema import ExperimentConfig
from frl_distributed_ml_scaffold_tpu.data.pipeline import build_pipeline
from frl_distributed_ml_scaffold_tpu.dist.mesh import MeshEnv, build_mesh
from frl_distributed_ml_scaffold_tpu.models import create_model
from frl_distributed_ml_scaffold_tpu.parallel.partition import (
    PartitionRules,
    opt_state_specs,
    param_specs,
    shardings_from_specs,
)
from frl_distributed_ml_scaffold_tpu.trainer.optimizers import make_optimizer
from frl_distributed_ml_scaffold_tpu.precision import get_policy
from frl_distributed_ml_scaffold_tpu.trainer.tasks import example_input, make_loss_fn
from frl_distributed_ml_scaffold_tpu.trainer.train_state import TrainState
from frl_distributed_ml_scaffold_tpu.trainer.train_step import (
    make_eval_step,
    make_train_step,
)
from frl_distributed_ml_scaffold_tpu.utils.logging import MetricLogger, get_logger
from frl_distributed_ml_scaffold_tpu.utils.timing import StepTimer
from frl_distributed_ml_scaffold_tpu.utils.trees import tree_param_count


def model_partition_rules(model_cfg: Any, env: MeshEnv) -> PartitionRules | None:
    """TP/EP/PP rules when the model/expert/pipe axis is populated
    (SURVEY C6/C7/C9).

    The rules name all axes; size-1 axes in a spec are no-ops, so applying
    them with model=1, expert=4 still shards the MoE expert weights.
    """
    pipelined = getattr(model_cfg, "pipeline_stages", 1) > 1
    if (
        env.axis_size("model") <= 1
        and env.axis_size("expert") <= 1
        and not pipelined
    ):
        return None
    family = getattr(model_cfg, "family", None)
    if family == "gpt":
        from frl_distributed_ml_scaffold_tpu.models.gpt import gpt_tp_rules
        from frl_distributed_ml_scaffold_tpu.parallel.pipeline import circular_repeat

        return gpt_tp_rules(
            pipelined=pipelined, circular=circular_repeat(model_cfg) > 1
        )
    if family in ("vit", "video"):
        from frl_distributed_ml_scaffold_tpu.models.vit import vit_tp_rules

        return vit_tp_rules()
    if env.axis_size("model") > 1:
        # ResNet has no TP rules by design (conv channel counts don't split
        # Megatron-style); a model>1 mesh would silently replicate — refuse.
        raise ValueError(
            f"model family {family!r} has no tensor-parallel partition "
            "rules; mesh.model must be 1"
        )
    return None


class Trainer:
    """End-to-end training driver for one ExperimentConfig."""

    def __init__(self, cfg: ExperimentConfig, *, mesh_env: MeshEnv | None = None):
        self.cfg = cfg
        self.logger = get_logger()
        # Labels/tokens >= the model's output range make the CE loss NaN
        # while the grads stay finite (XLA clamps the out-of-bounds label
        # gather), which trains garbage that *looks* alive in the logs —
        # refuse up front. num_classes covers the classifiers, vocab_size
        # the LMs; the invariant is the same label-range one.
        for attr in ("num_classes", "vocab_size"):
            d_v = getattr(cfg.data, attr, None)
            m_v = getattr(cfg.model, attr, None)
            if d_v is not None and m_v is not None and d_v != m_v:
                raise ValueError(
                    f"config {cfg.name}: data.{attr}={d_v} != "
                    f"model.{attr}={m_v}; labels out of the model's range "
                    "silently NaN the loss — override both together"
                )
        if cfg.optimizer.name == "fused_adamw" and (
            cfg.parallel.opt_sharding != "like_params"
            or cfg.parallel.param_sharding != "replicated"
            or cfg.mesh.model > 1
            or cfg.mesh.expert > 1
            or cfg.mesh.pipe > 1
        ):
            # The fused kernel is opaque to GSPMD: sharded mu/nu/params
            # would be silently all-gathered every step, defeating the
            # exact memory savings ZeRO/FSDP exist for (ops/fused_adamw.py
            # honesty contract) — refuse rather than de-optimize quietly.
            # mesh.model/expert/pipe > 1 shard params via partition rules
            # even under param_sharding=replicated (TP column/row splits,
            # expert stacks, pipeline stage dims), so those meshes are
            # refused on the same grounds as ZeRO/FSDP.
            raise ValueError(
                "optimizer.name=fused_adamw requires replicated state "
                "(parallel.param_sharding=replicated, "
                "opt_sharding=like_params) on a mesh with model=1, "
                "expert=1 and pipe=1; use adamw for sharded-state configs"
            )
        # The unified overlap-schedule layer (parallel/schedule.py,
        # ROADMAP item 2): derive the declared per-axis gather/scatter
        # schedule — from the legacy fsdp_overlap/tp_overlap/low_precision
        # knobs or an explicit parallel.schedule string — and refuse
        # contradictory declarations HERE, with a typed ScheduleError
        # naming the attribute, instead of as shape errors in the scan
        # body. (lowp without a ring axis, prefetch out of window, and
        # the per-mechanism family/pipeline/sequence checks all live in
        # schedule_from_config/validate_schedule_config.)
        from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
            schedule_from_config,
            validate_schedule_config,
        )

        self.overlap_schedule = schedule_from_config(cfg)
        if self.overlap_schedule is not None:
            validate_schedule_config(self.overlap_schedule, cfg)
        self.env = mesh_env if mesh_env is not None else build_mesh(cfg.mesh)
        self.policy = get_policy(cfg.precision)
        self.model = create_model(cfg.model, self.policy)
        self.tx, self.schedule = make_optimizer(cfg.optimizer, cfg.trainer)
        self.loss_fn = make_loss_fn(self.model, cfg.data.name)
        # Pipeline backend selection (ISSUE 14): ``pipeline_impl="mpmd"``
        # replaces the single compiled step with per-stage programs + the
        # host-side 1F1B driver (parallel/mpmd_pipeline.py). The runner
        # owns state layout ({"stage_j": ...} trees on pipe-slice
        # submeshes), per-stage init/shardings, and both steps; the rest
        # of the Trainer (fit loop, telemetry, checkpointing surface)
        # drives it through the same train_step/eval_step contract.
        self._mpmd = None
        impl = getattr(cfg.model, "pipeline_impl", "spmd")
        if getattr(cfg.model, "pipeline_stages", 1) > 1:
            if impl == "mpmd":
                from frl_distributed_ml_scaffold_tpu.parallel.mpmd_pipeline import (
                    MpmdPipelineRunner,
                )

                self._mpmd = MpmdPipelineRunner(cfg, self.env, self.policy)
            elif impl != "spmd":
                raise KeyError(
                    f"unknown model.pipeline_impl={impl!r} (spmd | mpmd)"
                )
        self.pipeline = build_pipeline(cfg.data, self.env, split="train")
        self._eval_pipeline = None
        self.checkpointer = None  # attached by attach_checkpointer()
        if cfg.checkpoint.enabled:
            from frl_distributed_ml_scaffold_tpu.checkpoint.manager import (
                Checkpointer,
            )

            self.attach_checkpointer(
                Checkpointer(os.path.join(cfg.workdir, cfg.name, "ckpt"), cfg.checkpoint)
            )

        if self._mpmd is not None:
            # The runner already derived per-stage shapes/specs/shardings
            # (and attached the overlap schedule per stage program).
            self.state_shapes = self._mpmd.state_shapes
            self.state_specs = self._mpmd.state_specs
            self.state_shardings = self._mpmd.state_shardings
            self._train_step_fn = None
            self._train_step_jit = None
            self.train_step = self._mpmd.train_step
            self.eval_step = self._mpmd.eval_step
        else:
            self._build_state_shardings()
            if self.overlap_schedule is not None:
                # Hooks need the partition specs, so they attach only after
                # the (unhooked) model produced the state shapes above; the
                # params tree is identical with hooks on or off.
                self._attach_schedule()
            self._compile_steps()

    # ---------------------------------------------------------------- setup

    def _init_state_fn(self, rng):
        # The init example must stay batch-axis-divisible AFTER the pipeline
        # splits it into microbatches (each microbatch crosses the ring/
        # Ulysses shard_map batch specs on its own).
        from frl_distributed_ml_scaffold_tpu.parallel.pipeline import (
            effective_microbatches,
        )

        micro = effective_microbatches(self.cfg.model)
        x = example_input(
            self.cfg.data, self.cfg.model, batch_size=self.env.batch_axis_size * micro
        )
        key = "tokens" if "tokens" in x else ("video" if "video" in x else "image")
        inp = jnp.asarray(x[key][:, :-1] if key == "tokens" else x[key])
        variables = dict(self.model.init({"params": rng}, inp, train=False))
        params = variables.pop("params")
        return TrainState.create(
            params,
            self.tx,
            extras=variables,
            with_ema=self.cfg.trainer.ema_decay > 0.0,
        )

    def _build_state_shardings(self) -> None:
        cfg, env = self.cfg, self.env
        rng = jax.random.key(cfg.trainer.seed)
        state_shapes = self._mesh_scoped(jax.eval_shape)(self._init_state_fn, rng)
        rules = model_partition_rules(cfg.model, env)
        p_specs = param_specs(state_shapes.params, cfg.parallel, env.mesh, rules)
        o_specs = opt_state_specs(
            state_shapes.opt_state, state_shapes.params, p_specs, cfg.parallel, env.mesh
        )
        # Non-param collections (BatchNorm stats etc.) are small — replicate.
        e_specs = jax.tree.map(lambda _: P(), state_shapes.extras)
        self.state_specs = TrainState(
            step=P(),
            params=p_specs,
            opt_state=o_specs,
            extras=e_specs,
            # EMA mirrors params exactly, so it rides the same specs.
            ema_params=p_specs if state_shapes.ema_params is not None else None,
        )
        self.state_shardings = shardings_from_specs(self.state_specs, env.mesh)
        if cfg.trainer.offload_opt_state:
            # Probe a device THIS process owns: on a multi-host mesh,
            # devices.flat[0] belongs to process 0 and its
            # addressable_memories() is not queryable from other hosts.
            dev0 = next(
                (d for d in env.mesh.devices.flat
                 if d.process_index == jax.process_index()),
                env.mesh.devices.flat[0],
            )
            kinds = {m.kind for m in dev0.addressable_memories()}
            # The CPU backend LISTS pinned_host but its SPMD partitioner
            # cannot place arrays there (RET_CHECK crash) — refuse by
            # platform, not just by advertised memory kinds.
            if dev0.platform == "cpu" or "pinned_host" not in kinds:
                raise ValueError(
                    "trainer.offload_opt_state=true is a TPU capacity "
                    f"feature (platform={dev0.platform!r}, memory kinds "
                    f"{sorted(kinds)}); the CPU sim cannot partition "
                    "host-memory arrays — see docs/perf_playbook.md"
                )
            self.state_shardings = self.state_shardings.replace(
                opt_state=jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"),
                    self.state_shardings.opt_state,
                )
            )
        self.state_shapes = state_shapes
        self._rng = rng

    def _attach_schedule(self) -> None:
        """Rebind the loss model to the declared overlap schedule
        (parallel/schedule.py ``hooked_model``): a blockwise fsdp gather
        rule lowers to the explicit per-block all-gather / reduce-scatter
        hooks, a ring-chunk model rule to the collective-matmul ppermute
        rings — both stacked onto one clone when the schedule declares
        both axes, so the gathers and rings overlap in the same scan
        body. Hooked clone for APPLY only (train/eval loss): the hook
        mechanisms cannot create params, so init/eval_shape keep the
        plain self.model — the params tree is identical either way.
        Requires the partition specs from _build_state_shardings."""
        from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
            hooked_model,
        )

        model = hooked_model(
            self.overlap_schedule, self.model, self.cfg, self.env,
            self.state_specs.params,
        )
        if self.overlap_schedule.block_gather() is not None:
            # Kept for introspection/back-compat: the fsdp-hooked clone
            # (without the ring hooks when both are declared).
            self._overlap_model = self.model.clone(
                param_hooks=model.param_hooks
            )
        if self.overlap_schedule.ring_gather() is not None:
            self._tp_model = model
        self.loss_fn = make_loss_fn(model, self.cfg.data.name)

    def _mesh_scoped(self, fn):
        """Run ``fn`` with this trainer's mesh as the ambient context.

        Tracing is lazy — the context must hold when a compiled fn first
        traces (ring/Ulysses shard_map regions read it), not at Trainer
        construction, or two Trainers with different meshes would poison
        each other's traces.
        """
        from frl_distributed_ml_scaffold_tpu.dist.mesh import mesh_context

        def wrapped(*args, **kwargs):
            with mesh_context(self.env):
                return fn(*args, **kwargs)

        return wrapped

    def init_state(self) -> TrainState:
        """Initialize the train state directly into its shardings."""
        if self._mpmd is not None:
            state = self._mpmd.init_state()
            if self.cfg.trainer.init_params_path:
                host = self._load_init_params_plain(
                    self.cfg.trainer.init_params_path
                )
                new_params = self._mpmd.place_plain_params(host)
                replacements = {"params": new_params}
                if state.ema_params is not None:
                    replacements["ema_params"] = self._mpmd.place_plain_params(
                        host
                    )
                state = state.replace(**replacements)
            self.logger.info(
                "initialized %s (mpmd pipeline): %.2fM params over mesh %s",
                self.cfg.name,
                tree_param_count(state.params) / 1e6,
                dict(self.env.mesh.shape),
            )
            from frl_distributed_ml_scaffold_tpu.parallel.pipeline import (
                pipeline_summary,
            )

            summary = pipeline_summary(self.cfg.model)
            if summary:
                self.logger.info("%s", summary)
            return state
        state = self._mesh_scoped(
            jax.jit(self._init_state_fn, out_shardings=self.state_shardings)
        )(self._rng)
        if self.cfg.trainer.init_params_path:
            host = self._load_init_params(self.cfg.trainer.init_params_path)
            # Free the random-init buffers BEFORE transferring the loaded
            # ones: otherwise peak HBM transiently holds 2x params, which
            # can OOM a model that otherwise fits. The EMA (when on) must
            # start from the loaded weights too — seeding it with the
            # discarded random init would make early evals score garbage.
            stale = [state.params] + (
                [state.ema_params] if state.ema_params is not None else []
            )
            for leaf in jax.tree.leaves(stale):
                if hasattr(leaf, "delete"):
                    leaf.delete()
            new_params = jax.device_put(host, self.state_shardings.params)
            replacements = {"params": new_params}
            if state.ema_params is not None:
                replacements["ema_params"] = jax.device_put(
                    host, self.state_shardings.params
                )
            state = state.replace(**replacements)
        n_params = tree_param_count(state.params)
        self.logger.info(
            "initialized %s: %.2fM params over mesh %s",
            self.cfg.name,
            n_params / 1e6,
            dict(self.env.mesh.shape),
        )
        from frl_distributed_ml_scaffold_tpu.parallel.pipeline import (
            pipeline_summary,
        )

        summary = pipeline_summary(self.cfg.model)
        if summary:
            # GPipe fill/drain cost — the number to watch when tuning
            # pipeline_microbatches (amortizes as M grows).
            self.logger.info("%s", summary)
        return state

    def _load_init_params_plain(self, path: str):
        """MPMD variant of ``_load_init_params``: checkpoint files carry
        the PLAIN (stages=1) layout, so validation runs against the plain
        twin's init shapes; the runner slices the result into per-stage
        trees (``place_plain_params``)."""
        import dataclasses as _dc

        plain = create_model(
            _dc.replace(self.cfg.model, pipeline_stages=1), self.policy
        )
        x = example_input(
            self.cfg.data, self.cfg.model, batch_size=self.env.batch_axis_size
        )
        inp = jnp.asarray(x["tokens"][:, :-1])
        shapes = jax.eval_shape(
            lambda r: plain.init({"params": r}, inp, train=False)["params"],
            jax.random.key(0),
        )
        return self._load_init_params(path, params_shapes=shapes)

    def _load_init_params(self, path: str, params_shapes=None):
        """Load + validate a flax-msgpack params pytree
        (tools/import_hf_gpt2.py output); returns HOST numpy arrays in the
        policy's param dtype (the caller places them into shardings).

        Structure and shapes are validated against the model's own init
        shapes BEFORE any device transfer — a mismatched checkpoint fails
        with the offending paths, not an opaque XLA shape error.
        """
        from flax import serialization

        with open(path, "rb") as fh:
            loaded = serialization.msgpack_restore(fh.read())
        got_paths = {
            jax.tree_util.keystr(k): tuple(v.shape)
            for k, v in jax.tree_util.tree_leaves_with_path(loaded)
        }
        want_paths = {
            jax.tree_util.keystr(k): tuple(v.shape)
            for k, v in jax.tree_util.tree_leaves_with_path(
                self.state_shapes.params
                if params_shapes is None else params_shapes
            )
        }
        if got_paths.keys() != want_paths.keys():
            missing = sorted(want_paths.keys() - got_paths.keys())[:5]
            extra = sorted(got_paths.keys() - want_paths.keys())[:5]
            raise ValueError(
                f"init_params_path {path!r} does not match the model tree "
                f"(missing {missing}, unexpected {extra})"
            )
        bad = [
            k for k in want_paths
            if tuple(got_paths[k]) != tuple(want_paths[k])
        ]
        if bad:
            raise ValueError(
                f"init_params_path {path!r} shape mismatches at {bad[:5]}: "
                + ", ".join(
                    f"{k}: {got_paths[k]} != {want_paths[k]}" for k in bad[:5]
                )
            )
        dtype = self.policy.param_dtype
        loaded = jax.tree.map(lambda x: np.asarray(x, dtype), loaded)
        self.logger.info(
            "initialized params from %s (%.2fM params)",
            path,
            tree_param_count(loaded) / 1e6,
        )
        return loaded

    def _batch_shardings(self, batch: dict) -> dict:
        return self.pipeline.shardings_for(
            {k: np.asarray(v) for k, v in batch.items()}
        )

    def _compile_steps(self) -> None:
        cfg = self.cfg
        step_fn = make_train_step(
            self.loss_fn,
            self.tx,
            self.policy,
            seed=cfg.trainer.seed,
            grad_accum=cfg.trainer.grad_accum,
            remat=cfg.trainer.remat,
            ema_decay=cfg.trainer.ema_decay,
            offload_opt_state=cfg.trainer.offload_opt_state,
            # FSDP: pin the grad-accum accumulator to the params' sharded
            # layout, so microbatch grads accumulate as SHARDS (post
            # reduce-scatter), never as gathered full-model fp32 tensors.
            grad_shardings=(
                self.state_shardings.params
                if cfg.parallel.param_sharding == "fsdp"
                else None
            ),
        )
        # Batch shardings are inferred from the example batch structure.
        example = example_input(cfg.data, cfg.model, batch_size=self.env.batch_axis_size)
        batch_sh = self._batch_shardings(example)
        self._train_step_fn = step_fn  # unjitted, for jaxpr-level analysis
        self._train_step_jit = jax.jit(
            step_fn,
            in_shardings=(self.state_shardings, batch_sh),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        )
        self.train_step = self._mesh_scoped(self._train_step_jit)
        eval_fn = make_eval_step(self.loss_fn, self.policy, seed=cfg.trainer.seed)
        self.eval_step = self._mesh_scoped(
            jax.jit(eval_fn, in_shardings=(self.state_shardings, batch_sh))
        )

    def lower_train_step(self, state, batch):
        """The train step lowered for ``state``/``batch`` (arrays or
        shapes) under this trainer's mesh — what cost analysis reads, and
        what a caller compiles to inspect the program (chip_smoke.py
        checks that the flash kernel is in it). None under the MPMD
        pipeline, which has no single step program."""
        if self._mpmd is not None:
            return None
        return self._mesh_scoped(self._train_step_jit.lower)(state, batch)

    def step_cost_analysis(self, state, batch) -> dict | None:
        """FLOPs (and, when supported, bytes) of ONE train step, with the
        source of the count under ``flops_source``. Used by bench.py and
        the fit loop to report model FLOPs and MFU (BASELINE.md
        protocol)."""
        if self._mpmd is not None:
            # Per-stage programs have no single lowered step; the runner
            # sums jaxpr FLOPs over stages x microbatches.
            return self._mpmd.step_cost_analysis()
        # Pre-optimization analysis of the lowered step: no backend compile
        # (the jit call path would not reuse an AOT executable, so
        # compiling here would double the heaviest compile), and
        # theoretical model FLOPs — the MFU convention — rather than
        # post-fusion counts.
        cost = self.lower_train_step(state, batch).cost_analysis()
        if cost and float(cost.get("flops", 0.0)) > 0:
            return {**cost, "flops_source": "xla_cost_analysis"}
        # XLA's pre-compile count does not see inside every program (it
        # gives none for some): count matmul/conv FLOPs straight off the
        # train-step jaxpr — exact for fwd+bwd+optimizer.
        from frl_distributed_ml_scaffold_tpu.utils.flops import fn_flops

        flops = self._mesh_scoped(fn_flops)(self._train_step_fn, state, batch)
        return {"flops": float(flops), "flops_source": "jaxpr"}

    # ----------------------------------------------------------------- loop

    def attach_checkpointer(self, checkpointer) -> None:
        self.checkpointer = checkpointer

    def fit(
        self,
        state: TrainState | None = None,
        *,
        num_steps: int | None = None,
        on_step: Callable[[int, dict], None] | None = None,
    ) -> tuple[TrainState, dict]:
        """Run the training loop; returns (final_state, last_metrics)."""
        cfg = self.cfg
        total = num_steps if num_steps is not None else cfg.trainer.total_steps

        if state is None:
            if self.checkpointer is not None and cfg.checkpoint.resume:
                state = self.checkpointer.restore_or_init(self)
            else:
                state = self.init_state()
        # The state's own step counter is the resume point — holds for both
        # checkpoint restores and explicitly passed states, and keeps the
        # step-indexed data stream aligned with what the model has seen.
        start_step = int(jax.device_get(state.step))

        # The resolved config IS the experiment record: offline tools
        # (tools/avg_checkpoints.py) and future resumes rebuild the exact
        # model/optimizer from it without guessing CLI overrides.
        from frl_distributed_ml_scaffold_tpu.config import config_to_dict
        from frl_distributed_ml_scaffold_tpu.utils.logging import (
            is_primary_process,
        )

        run_dir = os.path.join(cfg.workdir, cfg.name)
        if is_primary_process():
            os.makedirs(run_dir, exist_ok=True)
            import json as _json

            with open(os.path.join(run_dir, "config.json"), "w") as fh:
                _json.dump(config_to_dict(cfg), fh, indent=1)

        metric_logger = MetricLogger(
            os.path.join(run_dir, "metrics.jsonl"),
            tb_dir=(
                os.path.join(run_dir, "tb")
                if cfg.trainer.tensorboard
                else None
            ),
        )
        timer = StepTimer(warmup=1)  # first window contains compile
        samples_per_step = cfg.data.global_batch_size
        last_record: dict = {}
        last_logged = start_step

        from frl_distributed_ml_scaffold_tpu.utils.profiling import (
            WindowProfiler,
            annotate,
            annotate_step,
            device_memory_stats,
        )

        # Telemetry (ISSUE 7): one registry per fit() run, exported at
        # every log boundary as a JSONL snapshot record (telemetry.jsonl,
        # next to the metrics.jsonl record of truth) and an atomic
        # Prometheus sidecar file (metrics.prom — textfile-collector
        # shape). The per-step timeline (load_batch/dispatch phases)
        # ring-buffers between boundaries and drains into the same JSONL.
        from frl_distributed_ml_scaffold_tpu.telemetry import (
            MetricsRegistry,
            StallWatchdog,
            Timeline,
            Tracer,
            jsonl_record,
            write_prometheus_file,
        )
        from frl_distributed_ml_scaffold_tpu.utils.logging import JsonlWriter
        from frl_distributed_ml_scaffold_tpu.utils.flops import (
            peak_flops_per_chip,
        )

        # None on the CPU (the records then carry no MFU); an accelerator
        # missing from the peaks table raises here, before any step runs.
        peak_flops = peak_flops_per_chip()
        telem = MetricsRegistry()
        timeline = Timeline()
        # Tracing (ISSUE 8): per-step spans (step → load_batch/dispatch,
        # plus checkpoint/eval) on one "train" lane. The span context
        # managers wrap jax.profiler Trace/StepTrace annotations
        # (annotate=True), so when the profiler window above is armed the
        # host spans line up with the device trace; the ring additionally
        # exports Chrome-trace-event JSON (<run_dir>/trace_events.json)
        # for runs where no window was armed. Spans tee into the Timeline
        # → telemetry.jsonl, replacing the old bare timeline events.
        tracer = Tracer(
            enabled=cfg.trainer.tracing, annotate=True, timeline=timeline
        )
        train_trace = tracer.new_trace(cfg.name)
        if tracer.enabled:
            def _span_load(step):
                return tracer.span("load_batch", cat="train", step=step)

            def _span_disp(step):
                return tracer.span(
                    "dispatch", cat="train", step=step, step_num=step
                )
        else:
            # tracing=false must not strip the profiler annotations the
            # profile_steps window relies on — the two knobs are
            # independent (a disabled tracer's spans carry no annotation).
            _span_load = lambda step: annotate("load_batch")  # noqa: E731
            _span_disp = annotate_step
        telemetry_jsonl = JsonlWriter(os.path.join(run_dir, "telemetry.jsonl"))
        prom_path = os.path.join(run_dir, "metrics.prom")
        m_step = telem.histogram(
            "train_step_seconds",
            help="per-step e2e wall time (window average, post-warmup)",
        )
        m_wait = telem.histogram(
            "train_data_wait_seconds",
            help="host wait for the next batch, per step",
        )
        m_sps = telem.gauge(
            "train_samples_per_sec_per_chip", help="the north-star metric"
        )
        m_mfu = telem.gauge("train_mfu", help="model FLOPs / chip peak")
        m_wait_frac = telem.gauge(
            "train_data_wait_fraction",
            help="data-wait share of the step (input-bound when near 1)",
        )
        m_hbm_used = telem.gauge("train_hbm_in_use_gib")
        m_hbm_peak = telem.gauge(
            "train_hbm_peak_gib", help="HBM high-watermark per log window"
        )
        m_steps = telem.counter("train_steps_total")
        watchdog = StallWatchdog(
            cfg.trainer.stall_timeout_s,
            name=cfg.name,
            registry=telem,
            timeline=timeline,
            dump_path=os.path.join(run_dir, "stall_dump.txt"),
            # Beats only flow once dispatch does: the first deadline must
            # absorb the initial XLA compile, not false-fire on it.
            first_beat_scale=cfg.trainer.stall_timeout_first_beat_scale,
        )
        if self._mpmd is not None:
            # 1F1B driver telemetry (ISSUE 14): per-stage idle gauges +
            # bubble fraction + boundary-transfer counter into THIS fit's
            # registry, stage-lane spans on the tracer, and watchdog
            # beats from inside the driver loop (a wedged inter-stage
            # transfer fires the stall dump instead of hanging silently).
            self._mpmd.attach_telemetry(
                registry=telem, tracer=tracer, trace=train_trace,
                watchdog=watchdog,
            )
        flops_per_step: float | None = None  # lazy; False once probing failed
        window_wait = 0.0

        profiler = WindowProfiler(
            os.path.join(run_dir, "trace"),
            start_step=start_step + cfg.trainer.profile_start_step,
            num_steps=cfg.trainer.profile_steps,
        )

        # Graceful preemption (TPU maintenance events deliver SIGTERM):
        # finish the in-flight step, checkpoint, exit cleanly. On a
        # full-slice preemption every host gets the signal, so the
        # collective Orbax save below has all participants. Handlers are
        # process-wide state — install only from the main thread and always
        # restore (the Trainer may be driven from tests or a supervisor).
        import signal as _signal
        import threading as _threading

        preempt = {"signum": None}
        prev_handlers = {}
        if _threading.current_thread() is _threading.main_thread():
            for _sig in (_signal.SIGTERM,):
                def _graceful(signum, frame, _p=preempt):
                    _p["signum"] = signum

                prev_handlers[_sig] = _signal.signal(_sig, _graceful)

        try:
            import time as _time

            for step in range(start_step, total):
                profiler.step_start(step)
                with tracer.span(
                    "step", trace=train_trace, cat="train", step=step
                ):
                    t_load = _time.perf_counter()
                    with _span_load(step):
                        batch = self.pipeline.global_batch(step)
                    data_wait = _time.perf_counter() - t_load
                    window_wait += data_wait
                    m_wait.observe(data_wait)
                    # H2D + enqueue of the async device step: the
                    # StepTraceAnnotation (step_num) groups it with the
                    # device timeline in the profiler trace.
                    t_disp = _time.perf_counter()
                    with _span_disp(step):
                        state, metrics = self.train_step(state, batch)
                # Fault sites (ISSUE 9, faults/plan.py): a hung step is
                # the stall watchdog's prey (the sleep lands between
                # beats, exactly like a wedged collective); a preempt
                # fires our own SIGTERM so the graceful checkpoint-and-
                # exit path below runs. Both no-op unarmed.
                faults.maybe_hang("trainer.hung_step", key=step)
                if faults.fire("trainer.preempt", key=step) is not None:
                    os.kill(os.getpid(), _signal.SIGTERM)
                if not tracer.enabled:
                    # tracing=false must not silence telemetry.jsonl's
                    # phase records — fall back to bare timeline events.
                    timeline.event("load_batch", dur_s=data_wait, step=step)
                    timeline.event(
                        "dispatch",
                        dur_s=_time.perf_counter() - t_disp, step=step,
                    )
                watchdog.beat()
                if (step + 1) % cfg.trainer.log_every == 0 or step + 1 == total:
                    win_steps = step + 1 - last_logged
                    dt = timer.tick_window(metrics["loss"], win_steps)
                    last_logged = step + 1
                    perf = timer.summary(samples_per_step)
                    # Step split: the host waits data_wait for the batch;
                    # the rest of the e2e step is device compute (the loop
                    # only blocks at this boundary, so the split is
                    # window-averaged — the veScale host-side discipline).
                    avg_wait = window_wait / max(win_steps, 1)
                    window_wait = 0.0
                    mem = device_memory_stats()
                    extra = {
                        "lr": float(self.schedule(step)),
                        **{
                            k: round(v, 6)
                            for k, v in perf.items()
                            if k in (
                                "step_time_median_s",
                                "step_time_p50_s",
                                "step_time_p95_s",
                                "step_time_p99_s",
                                "samples_per_sec_per_chip",
                            )
                        },
                        "data_wait_s": round(avg_wait, 6),
                        **mem,
                    }
                    if dt is not None:
                        m_step.observe(dt)
                        extra["compute_s"] = round(max(dt - avg_wait, 0.0), 6)
                        m_wait_frac.set(min(avg_wait / max(dt, 1e-12), 1.0))
                        # MFU: probe step FLOPs once, lazily, only after
                        # the warmup window (single-boundary test fits
                        # never pay the AOT lower it costs) and only where
                        # there is a peak to divide by. A failed probe
                        # costs the run its MFU column, not the run.
                        if flops_per_step is None and peak_flops:
                            try:
                                cost = self.step_cost_analysis(state, batch)
                                flops_per_step = (
                                    float(cost["flops"]) if cost else False
                                )
                                if cost:
                                    self.logger.info(
                                        "step FLOPs %.4g (source: %s)",
                                        flops_per_step,
                                        cost.get("flops_source"),
                                    )
                            except Exception as e:
                                self.logger.warning(
                                    "step FLOPs probe failed (%s: %s); "
                                    "records will carry no mfu",
                                    type(e).__name__, e,
                                )
                                flops_per_step = False
                    med = perf.get("step_time_median_s", 0.0)
                    if flops_per_step and med > 0 and peak_flops:
                        mfu = flops_per_step / (
                            med * jax.device_count() * peak_flops
                        )
                        extra["mfu"] = mfu
                        m_mfu.set(mfu)
                    m_sps.set(perf.get("samples_per_sec_per_chip", 0.0))
                    m_hbm_used.set(mem.get("hbm_in_use_gib", 0.0))
                    m_hbm_peak.set(mem.get("hbm_peak_gib", 0.0))
                    m_steps.inc(win_steps)
                    last_record = metric_logger.log(step + 1, metrics, extra)
                    for rec in timeline.drain():
                        telemetry_jsonl.write(rec)
                    telemetry_jsonl.write(jsonl_record(telem, step=step + 1))
                    if is_primary_process():
                        write_prometheus_file(telem, prom_path)
                if on_step is not None:
                    on_step(step, metrics)
                if (
                    self.checkpointer is not None
                    and (step + 1) % cfg.checkpoint.save_every == 0
                ):
                    with tracer.span(
                        "checkpoint", trace=train_trace, cat="train",
                        step=step + 1,
                    ):
                        self.checkpointer.save(step + 1, state)
                if cfg.trainer.eval_every and (step + 1) % cfg.trainer.eval_every == 0:
                    with tracer.span(
                        "eval", trace=train_trace, cat="train", step=step + 1
                    ):
                        eval_metrics = self.evaluate(state)
                    metric_logger.log(step + 1, eval_metrics, {"split": "eval"})
                if preempt["signum"] is not None:
                    self.logger.warning(
                        "signal %d: checkpointing at step %d and exiting "
                        "cleanly (preemption)", preempt["signum"], step + 1
                    )
                    if self.checkpointer is not None:
                        # Skip the forced save when the periodic one just
                        # covered this step — re-serializing an identical
                        # checkpoint burns the fixed preemption grace window.
                        # trainer.preempt_save=false skips the forced save
                        # entirely (externally managed checkpoints) but
                        # still waits: in-flight periodic saves must land
                        # their commit markers before the clean exit.
                        if (
                            cfg.trainer.preempt_save
                            and (step + 1) % cfg.checkpoint.save_every != 0
                        ):
                            self.checkpointer.save(step + 1, state, force=True)
                        self.checkpointer.wait()
                    last_record = metric_logger.log(
                        step + 1, metrics, {"event": "preempted"}
                    )
                    preempt["exited_early"] = True
                    break
            # Final-state save runs INSIDE the signal-protected region: a
            # SIGTERM here (e.g. preemption right as the run finishes) just
            # sets the flag while the save completes, instead of killing
            # the process mid-serialization with default disposition. Only
            # the mid-run preemption break skips it — that path already
            # saved and waited.
            if not preempt.get("exited_early") and self.checkpointer is not None:
                if total % cfg.checkpoint.save_every != 0:
                    # Final state not yet covered by the periodic save above.
                    with tracer.span(
                        "checkpoint", trace=train_trace, cat="train",
                        step=total, final=True,
                    ):
                        self.checkpointer.save(total, state, force=True)
                self.checkpointer.wait()
        finally:
            # A crash mid-window must still flush the captured trace (and
            # release the process-wide profiler) — the crash run is exactly
            # when the trace is wanted. Same for telemetry: the final
            # snapshot + timeline tail are most valuable on the bad exit.
            profiler.stop()
            watchdog.stop()
            try:
                for rec in timeline.drain():
                    telemetry_jsonl.write(rec)
                telemetry_jsonl.write(jsonl_record(telem, step=last_logged))
                if is_primary_process():
                    write_prometheus_file(telem, prom_path)
                    if tracer.enabled:
                        # The span tree (ring tail on long runs) as
                        # Chrome-trace-event JSON — the Perfetto view of
                        # what the host loop was doing, crash runs
                        # included.
                        tracer.write_chrome_trace(
                            os.path.join(run_dir, "trace_events.json")
                        )
            except Exception as e:  # observability must not mask the real error
                self.logger.warning(
                    "final telemetry flush failed (%s: %s); continuing "
                    "shutdown", type(e).__name__, e,
                )
            telemetry_jsonl.close()
            if hasattr(self.pipeline, "close"):
                self.pipeline.close()  # stop prefetch worker + in-flight work
            for _sig, _prev in prev_handlers.items():
                _signal.signal(_sig, _prev)
        metric_logger.close()
        return state, last_record

    def evaluate(self, state: TrainState, num_steps: int | None = None) -> dict:
        if self._eval_pipeline is None:
            self._eval_pipeline = build_pipeline(self.cfg.data, self.env, split="eval")
        if state.ema_params is not None:
            # The point of keeping an EMA: evaluation runs with it. Same
            # TrainState structure/shardings, so the compiled eval reuses.
            state = state.replace(params=state.ema_params)
        n = num_steps or self.cfg.trainer.eval_steps
        acc: dict[str, Any] = {}
        for step in range(n):
            batch = self._eval_pipeline.global_batch(step)
            m = self.eval_step(state, batch)
            acc = m if not acc else jax.tree.map(lambda a, b: a + b, acc, m)
        mean = jax.tree.map(lambda x: x / n, acc)
        return {f"eval_{k}": v for k, v in jax.device_get(mean).items()}
