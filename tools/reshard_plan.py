#!/usr/bin/env python
"""reshard_plan CLI: price a mesh-to-mesh redistribution WITHOUT running it.

Compiles the redistribution plan (ISSUE 15, redistribute/plan.py) for a
named seam on the CPU sim and prints the per-leaf program + cost table —
kind (identity / collective / chunked / host), bytes moved vs the
shard-delta lower bound, and the peak scratch transient — the dry-run an
operator reads before a live migration (docs/operations.md "State
redistribution").

    python tools/reshard_plan.py --seam train_to_serve --dry-run
    python tools/reshard_plan.py --seam restore --dry-run
    python tools/reshard_plan.py --seam respread --from-model 2 --to-model 4
    python tools/reshard_plan.py --seam train_to_serve --json plan.json

Seams (all tiny-GPT twins, the graft-lint shrink-shape discipline):

- ``train_to_serve``: fsdp×model training layout → serving TP mesh
  (the ``build_engine(rules=...)`` handoff);
- ``restore``: the even restore layout → fsdp target shardings on one
  mesh (what ``checkpoint.restore_redistribute=true`` executes);
- ``respread``: a paged KV pool re-spread across model-axis sizes
  (``ServingEngine.respread_pool``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Platform pins BEFORE jax imports (the graft_lint.py discipline).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _twin():
    # The SHARED shrink-shape twin (analysis.runner.build_tiny_gpt) —
    # one definition for the ledger row and all three CLI seams.
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        build_tiny_gpt,
    )

    return build_tiny_gpt()


def _with_shardings(tree, shardings):
    import jax

    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings,
    )


def plan_train_to_serve():
    # The SHARED tiny-GPT abstract twin (analysis.runner) — the same
    # plan the perf-ledger redistribute:train_to_serve row gates, so
    # the dry-run an operator reads and the gated numbers cannot drift.
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        build_train_to_serve_plan,
    )

    plan, _train_env, _serve_env = build_train_to_serve_plan()
    return plan


def plan_restore():
    from jax.sharding import NamedSharding

    import jax

    from frl_distributed_ml_scaffold_tpu import redistribute
    from frl_distributed_ml_scaffold_tpu.config.schema import ParallelConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        MeshConfig,
        build_mesh,
    )
    from frl_distributed_ml_scaffold_tpu.parallel.partition import (
        param_specs,
        shardings_from_specs,
    )

    _model, params = _twin()
    env = build_mesh(MeshConfig(data=2, fsdp=4))
    specs = param_specs(
        params,
        ParallelConfig(param_sharding="fsdp", fsdp_min_size=16),
        env.mesh,
        None,
    )
    target = shardings_from_specs(specs, env.mesh)
    even = jax.tree.map(
        lambda s, sh: NamedSharding(
            sh.mesh,
            redistribute.restore_layout_spec(s.shape, sh.spec, sh.mesh),
        ),
        params, target,
    )
    return redistribute.compile_tree_plan(
        _with_shardings(params, even), target
    )


def plan_respread(from_model: int, to_model: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from frl_distributed_ml_scaffold_tpu import redistribute
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        MeshConfig,
        build_mesh,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        pool_leaf_spec,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import init_paged_cache

    base, params = _twin()
    model = base.clone(kv_block_size=8, kv_pool_blocks=9)
    cache = jax.eval_shape(lambda: init_paged_cache(model, 2))
    src_env = build_mesh(
        MeshConfig(data=1, model=from_model),
        devices=jax.devices()[:from_model],
    )
    dst_env = build_mesh(
        MeshConfig(data=1, model=to_model),
        devices=jax.devices()[:to_model],
    )
    from flax.traverse_util import flatten_dict, unflatten_dict

    def shard_tree(env):
        out = {}
        for kp, leaf in flatten_dict(cache).items():
            spec = pool_leaf_spec(kp[-1], leaf) or P()
            out[kp] = redistribute.spec_on(env.mesh, leaf, spec)
        return unflatten_dict(out)

    src = _with_shardings(cache, shard_tree(src_env))
    return redistribute.compile_tree_plan(src, shard_tree(dst_env))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--seam", required=True,
        choices=("train_to_serve", "restore", "respread"),
    )
    ap.add_argument(
        "--dry-run", action="store_true",
        help="compile + print only (the default and ONLY mode: this "
        "tool never moves data)",
    )
    ap.add_argument("--from-model", type=int, default=2,
                    help="respread: source model-axis size")
    ap.add_argument("--to-model", type=int, default=4,
                    help="respread: destination model-axis size")
    ap.add_argument("--json", help="write the plan table as JSON here")
    args = ap.parse_args(argv)

    if args.seam == "train_to_serve":
        plan = plan_train_to_serve()
    elif args.seam == "restore":
        plan = plan_restore()
    else:
        plan = plan_respread(args.from_model, args.to_model)

    d = plan.to_dict()
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(d, fh, indent=1)
        print(f"wrote plan to {args.json}")
    width = max(len(l["path"]) for l in d["leaves"])
    print(f"seam: {args.seam}")
    print(
        f"  {'leaf':<{width}s} {'kind':<10s} {'src':<28s} {'dst':<28s} "
        f"{'bytes':>9s} {'moved':>9s} {'floor':>9s} {'scratch':>9s}"
    )
    for l in d["leaves"]:
        print(
            f"  {l['path']:<{width}s} {l['kind']:<10s} "
            f"{l['src'][:27]:<28s} {l['dst'][:27]:<28s} "
            f"{l['leaf_bytes']:>9d} {l['bytes_moved']:>9d} "
            f"{l['bytes_lower_bound']:>9d} {l['peak_scratch_bytes']:>9d}"
        )
    for line in plan.summary_lines():
        print(line)
    if d["bytes_moved"] > d["bytes_lower_bound"]:
        print(
            f"  note: plan moves {d['bytes_moved'] - d['bytes_lower_bound']}"
            " bytes over the shard-delta floor"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
