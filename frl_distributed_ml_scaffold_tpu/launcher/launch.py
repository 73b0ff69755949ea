"""``launch.py`` — the torchrun-equivalent entrypoint (SURVEY C1).

Usage::

    python launch.py --config=mnist_mlp [--device=tpu|cpu] [--sim-devices=N]
                     [--list-configs] [--elastic] [path.to.field=value ...]

Reference stack (a): torchrun forks N workers, each joins an NCCL process
group. Here: one process per host; ``--device=tpu`` brings up the pod slice
via ``initialize_distributed`` (autodetected on Cloud TPU, FRL_TPU_* env
overrides for manual clusters) and exits non-zero when JAX finds no TPU;
``--device=cpu --sim-devices=8`` gives the simulated multi-chip CPU mesh
used by the test tier (SURVEY C20).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description="FRL-TPU scaffold launcher")
    p.add_argument("--config", help="registered config name (see --list-configs)")
    p.add_argument("--device", default="tpu", choices=["tpu", "cpu"])
    p.add_argument(
        "--sim-devices",
        type=int,
        default=0,
        help="with --device=cpu: number of simulated devices",
    )
    p.add_argument("--list-configs", action="store_true")
    p.add_argument(
        "--elastic",
        action="store_true",
        help="run under the elastic checkpoint-restart supervisor (SURVEY C14)",
    )
    p.add_argument(
        "--eval-only",
        action="store_true",
        help="restore the latest checkpoint and run the eval loop only",
    )
    p.add_argument(
        "--describe",
        action="store_true",
        help="print resolved config, mesh, parameter shardings, FLOPs and "
        "pipeline bubble, then exit without training (dry run)",
    )
    p.add_argument(
        "--coordinator", default=None, help="host:port for multi-host bring-up"
    )
    p.add_argument(
        "--hlo-dump",
        default=None,
        metavar="DIR",
        help="dump optimized HLO per compilation to DIR (SURVEY C19)",
    )
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument(
        "overrides", nargs="*", help="config overrides: path.to.field=value"
    )
    return p.parse_args(argv)


def hlo_dump_flags(dump_dir: str) -> str:
    """XLA_FLAGS value for optimized-HLO dumps (SURVEY C19).

    Lives here (jax-free module), NOT in utils.profiling: that module
    imports jax at top level, which would freeze JAX_PLATFORMS before
    ``_configure_platform``'s CPU forcing below could run.
    """
    return f"--xla_dump_to={dump_dir} --xla_dump_hlo_as_text"


def _configure_platform(args) -> None:
    """Must run before jax initializes a backend."""
    if args.hlo_dump:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + hlo_dump_flags(args.hlo_dump)
        ).strip()
    if args.device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.sim_devices > 1:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + f" --xla_force_host_platform_device_count={args.sim_devices}"
                ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache — the ONE place this repo places it
    (launcher, bench, chip_smoke and tests all call this).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code, so the cache can be placed from outside.
    Otherwise it lives at the fixed ``<checkout>/.jax_cache`` (git-ignored):
    the path is part of the cache key, so a directory that moves between
    runs would never hit. Cache write failures are non-fatal inside jax.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def require_tpu_backend() -> None:
    """``--device=tpu`` means the chip: with none attached JAX would
    quietly train on the CPU, so a run that asked for the TPU and did not
    get it stops here. Initializes the backend — call it only in the
    process that trains (never in the elastic supervisor, whose child
    needs the chip)."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"--device=tpu but JAX's default backend is {backend!r} "
            f"(devices: {jax.devices()}): no TPU is attached or visible "
            "to this process. Pass --device=cpu [--sim-devices=N] to run "
            "on the CPU on purpose."
        )


def run_experiment(cfg, *, check_imports: bool = True):
    """Train one config to completion; returns (state, last_metrics)."""
    if check_imports:
        _assert_no_cuda_imports()
    from frl_distributed_ml_scaffold_tpu.launcher.elastic import fault_hook_from_env
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    trainer = Trainer(cfg)
    return trainer.fit(on_step=fault_hook_from_env(cfg))


_BANNED_IMPORT_PREFIXES = ("torch", "cupy", "nccl")


def _imported_names(tree) -> "list[str]":
    """Every module name a parsed source imports: Import/ImportFrom plus
    the dynamic forms ``importlib.import_module("x")`` / ``__import__("x")``
    with literal arguments. Module-level so tests can pin the semantics."""
    import ast

    names: list[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.extend(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.append(node.module)
        elif (
            isinstance(node, ast.Call)
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and (
                (isinstance(node.func, ast.Attribute)
                 and node.func.attr == "import_module")
                or (isinstance(node.func, ast.Name)
                    and node.func.id == "__import__")
            )
        ):
            names.append(node.args[0].value)
    return names


def _assert_no_cuda_imports() -> None:
    """The north-star constraint: zero CUDA/NCCL imports in the TPU path.

    Two complementary tiers (neither alone is sufficient):

    - **Static** AST scan over the framework's own sources — proves *this
      framework's code* declares no CUDA-stack dependency, including
      dynamic ``importlib.import_module("...")`` forms with literal
      arguments. Blind to what third parties import at runtime.
    - **Runtime** ``sys.modules`` check — catches a banned module pulled
      in transitively (a dependency importing torch behind our back) or
      via a non-literal dynamic import the AST scan cannot see. An
      embedding process that legitimately holds host torch (e.g.
      tools/import_hf_gpt2.py converts HF checkpoints on the host) opts
      out explicitly with ``FRL_ALLOW_HOST_TORCH=1`` — the escape hatch
      is deliberate and narrow: it waives only the runtime tier, never
      the source scan.
    """
    import ast

    if os.environ.get("FRL_ALLOW_HOST_TORCH", "") in ("", "0"):
        loaded = [
            m for m in _BANNED_IMPORT_PREFIXES
            if m in sys.modules
            or any(n.startswith(m + ".") for n in sys.modules)
        ]
        if loaded:
            raise RuntimeError(
                f"CUDA-path modules loaded in the launch process: {loaded} "
                "(set FRL_ALLOW_HOST_TORCH=1 if this embedding process "
                "holds host torch deliberately)"
            )

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    unparseable = []
    for dirpath, _, files in os.walk(pkg_root):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            rel = os.path.relpath(path, pkg_root)
            try:
                with open(path, encoding="utf-8") as fh:
                    tree = ast.parse(fh.read(), filename=path)
            except (SyntaxError, UnicodeDecodeError) as e:
                # A .py the interpreter could never import can't be
                # cleared by the scan — report it as what it is (a broken
                # source file), not as a CUDA dependency.
                unparseable.append(f"{rel}: {e}")
                continue
            if any(
                n == b or n.startswith(b + ".")
                for n in _imported_names(tree)
                for b in _BANNED_IMPORT_PREFIXES
            ):
                offenders.append(rel)
    if unparseable:
        raise RuntimeError(
            "unparseable .py files in the scaffold package (the no-CUDA "
            f"scan cannot clear them): {unparseable}"
        )
    if offenders:
        raise RuntimeError(
            f"CUDA-path imports in TPU scaffold sources: {offenders}"
        )


def main(argv=None) -> int:
    args = _parse_args(argv)
    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
        list_configs,
        pretty_config,
    )

    if args.list_configs:
        print("\n".join(list_configs()))
        return 0
    if not args.config:
        print("--config is required (see --list-configs)", file=sys.stderr)
        return 2

    _configure_platform(args)

    cfg = apply_overrides(get_config(args.config), args.overrides)

    if args.elastic:
        from frl_distributed_ml_scaffold_tpu.launcher.elastic import supervise

        return supervise(args, cfg)

    from frl_distributed_ml_scaffold_tpu.dist.initialize import initialize_distributed
    from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

    initialize_distributed(args.coordinator, args.num_processes, args.process_id)
    if args.device == "tpu":
        require_tpu_backend()
    from frl_distributed_ml_scaffold_tpu.utils.debugging import sanitize_from_env

    sanitize_from_env()  # FRL_TPU_SANITIZE=nans,infs,leaks (SURVEY §5)
    logger = get_logger()
    if args.describe:
        return describe(cfg)  # prints the resolved config itself
    logger.info("launching %s\n%s", cfg.name, pretty_config(cfg))
    if args.eval_only:
        last = run_eval(cfg)
    else:
        _, last = run_experiment(cfg)
    logger.info("done: %s", json.dumps(last, default=str))
    return 0


def describe(cfg) -> int:
    """Dry run: resolve everything a training run would — mesh, sharding
    specs, per-step FLOPs, pipeline bubble — and print it. Nothing trains;
    nothing is written (checkpointing and prefetch are forced off so no
    directory is created and no worker thread started)."""
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, pretty_config
    from frl_distributed_ml_scaffold_tpu.parallel.pipeline import pipeline_summary
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer
    from frl_distributed_ml_scaffold_tpu.trainer.tasks import example_input
    from frl_distributed_ml_scaffold_tpu.utils.flops import fn_flops
    from frl_distributed_ml_scaffold_tpu.utils.trees import tree_path_names

    _assert_no_cuda_imports()
    print(pretty_config(cfg))
    cfg = apply_overrides(cfg, ["checkpoint.enabled=false", "data.prefetch=0"])
    trainer = Trainer(cfg)
    print(f"\nmesh: {dict(trainer.env.mesh.shape)} "
          f"({trainer.env.num_devices} devices)")
    summary = pipeline_summary(cfg.model)
    if summary:
        print(summary)

    shapes = trainer.state_shapes.params
    specs = trainer.state_specs.params
    names = tree_path_names(shapes)
    import jax

    total = 0
    print(f"\n{'parameter':58s} {'shape':20s} sharding")
    for name, shape_leaf, spec in zip(
        names, jax.tree.leaves(shapes), jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "index") and not hasattr(x, "shape")
        )
    ):
        total += int(np.prod(shape_leaf.shape))
        print(f"{name:58s} {str(tuple(shape_leaf.shape)):20s} {spec}")
    print(f"\ntotal params: {total / 1e6:.2f}M")

    # The real global batch size — divisible by every axis/accum factor by
    # construction (only shapes are traced; nothing is materialized on
    # device).
    x = example_input(
        cfg.data, cfg.model, batch_size=cfg.data.global_batch_size
    )
    batch = {k: np.asarray(v) for k, v in x.items()}
    try:
        if getattr(trainer, "_mpmd", None) is not None:
            # MPMD pipeline: no single train-step program — sum the
            # per-stage fwd+bwd jaxpr FLOPs over all microbatches.
            cost = trainer._mpmd.step_cost_analysis()
            if cost is None:
                raise RuntimeError("per-stage FLOPs unavailable")
            flops = float(cost["flops"])
        else:
            flops = trainer._mesh_scoped(fn_flops)(
                trainer._train_step_fn, trainer.state_shapes, batch
            )
        per_sample = flops / batch[next(iter(batch))].shape[0]
        print(f"train-step FLOPs (example batch): {flops / 1e9:.2f} G "
              f"({per_sample / 1e9:.2f} G/sample)")
    except Exception as e:  # describe must never fail a dry run
        print(f"train-step FLOPs: unavailable ({type(e).__name__}: {e})")
    return 0


def run_eval(cfg) -> dict:
    """Reference call stack (e): restore → eval loop, no training."""
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    _assert_no_cuda_imports()
    trainer = Trainer(cfg)
    if trainer.checkpointer is None or trainer.checkpointer.latest_step() is None:
        raise RuntimeError(
            "--eval-only needs checkpoint.enabled=true and an existing "
            f"checkpoint under {cfg.workdir}/{cfg.name}/ckpt"
        )
    state = trainer.checkpointer.restore_or_init(trainer)
    return trainer.evaluate(state)


if __name__ == "__main__":
    raise SystemExit(main())
