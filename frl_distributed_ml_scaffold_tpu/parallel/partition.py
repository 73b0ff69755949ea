"""Partition-spec derivation: name rules + FSDP/ZeRO overlays (SURVEY C4–C5).

Pipeline for deciding where every array lives:

1. **Model rules** (optional): regex ``(pattern, PartitionSpec)`` pairs
   matched against the param's path name — how TP expresses Megatron
   column/row splits. First match wins; no match → replicated.
2. **FSDP overlay** (``param_sharding="fsdp"``): any dimension not already
   sharded gets the ``fsdp`` axis on the largest divisible dim. Leaves
   smaller than ``min_size`` stay replicated (collective latency >> memory
   saved).
3. **Optimizer state** mirrors param specs by path-suffix matching (optax
   states embed params-shaped subtrees, e.g. ``.../mu/<param path>``);
   ``zero1`` instead *shards* those mirrors over ``fsdp`` while params stay
   replicated.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.config.schema import ParallelConfig
from frl_distributed_ml_scaffold_tpu.utils.trees import named_tree_map, tree_path_names


@dataclass(frozen=True)
class PartitionRules:
    """Ordered regex → PartitionSpec rules (first match wins)."""

    rules: tuple[tuple[str, P], ...] = ()

    def match(self, name: str) -> P | None:
        for pattern, spec in self.rules:
            if re.search(pattern, name):
                return spec
        return None


def fsdp_spec_for(
    shape: Sequence[int],
    base: P,
    *,
    axis: str = "fsdp",
    axis_size: int,
    min_size: int,
) -> P:
    """Overlay the fsdp axis onto ``base`` for an array of ``shape``.

    Picks the largest dimension that is (a) unsharded in ``base`` and
    (b) divisible by ``axis_size``. Ties break toward the *first* such dim
    (usually the input/feature dim, giving all-gather-friendly layouts).
    """
    if axis_size <= 1 or int(np.prod(shape)) < min_size:
        return base
    entries = list(base) + [None] * (len(shape) - len(base))
    # Already sharded over this axis (e.g. ZeRO-1 overlay on FSDP params):
    # nothing to add — a mesh axis can appear at most once in a spec.
    if any(axis == e or (isinstance(e, tuple) and axis in e) for e in entries):
        return P(*entries)
    candidates = [
        i
        for i, (dim, e) in enumerate(zip(shape, entries))
        if e is None and dim % axis_size == 0 and dim >= axis_size
    ]
    if not candidates:
        return base
    best = max(candidates, key=lambda i: shape[i])
    entries[best] = axis
    return P(*entries)


def _fit_rule_to_shape(
    name: str, spec: P, shape: Sequence[int], mesh: Mesh
) -> P:
    """Drop a rule's mesh axes from every dimension they do not divide;
    that dimension is then replicated (and the fsdp overlay may still take
    another). A rule names the dimension a layer WANTS cut, but only the
    shape says whether it can be: GPT-2's published vocabulary, 50257, is
    odd, so no ``model`` axis divides the embedding's vocab dimension and
    the Megatron rule for ``wte`` cannot apply at full width."""
    entries = list(spec)
    for i, (dim, e) in enumerate(zip(shape, entries)):
        if e is None:
            continue
        axes = e if isinstance(e, tuple) else (e,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % size != 0:
            from frl_distributed_ml_scaffold_tpu.utils.logging import (
                get_logger,
            )

            get_logger().warning(
                "param %s: dim %d of shape %s is not divisible by mesh "
                "axis %r (size %d); that dimension stays replicated",
                name, i, tuple(shape), e, size,
            )
            entries[i] = None
    return P(*entries)


def param_specs(
    params: Any,
    parallel: ParallelConfig,
    mesh: Mesh,
    rules: PartitionRules | None = None,
) -> Any:
    """PartitionSpec pytree for the parameters."""
    fsdp_size = mesh.shape["fsdp"]

    def decide(name: str, leaf) -> P:
        base = (rules.match(name) if rules else None) or P()
        base = _fit_rule_to_shape(name, base, leaf.shape, mesh)
        if parallel.param_sharding == "fsdp":
            return fsdp_spec_for(
                leaf.shape,
                base,
                axis_size=fsdp_size,
                min_size=parallel.fsdp_min_size,
            )
        if parallel.param_sharding == "replicated":
            return base
        raise ValueError(f"unknown param_sharding {parallel.param_sharding!r}")

    return named_tree_map(decide, params)


def opt_state_specs(
    opt_state_shapes: Any,
    params: Any,
    p_specs: Any,
    parallel: ParallelConfig,
    mesh: Mesh,
) -> Any:
    """PartitionSpec pytree for the optimizer state.

    ``opt_state_shapes`` should come from ``jax.eval_shape(tx.init, params)``
    so no real memory is allocated. Leaves are matched to params by path
    suffix: optax embeds params-shaped trees (``mu``, ``nu``, trace, …) whose
    key paths end with the param's own path.
    """
    param_names = tree_path_names(params)
    spec_leaves = jax.tree.leaves(p_specs, is_leaf=lambda x: isinstance(x, P))
    param_shapes = [l.shape for l in jax.tree.leaves(params)]
    # Longest path first: with nested modules, "Block_0/Dense_0/kernel" must
    # win over a sibling "Dense_0/kernel" that is also a suffix. The shape
    # check rejects any remaining same-suffix/different-array collisions.
    by_name = sorted(
        zip(param_names, spec_leaves, param_shapes), key=lambda t: -len(t[0])
    )
    fsdp_size = mesh.shape["fsdp"]
    unmatched: list[str] = []

    def decide(name: str, leaf) -> P:
        if not hasattr(leaf, "shape") or leaf.shape == ():
            return P()  # step counts etc.
        matched: P | None = None
        for pname, pspec, pshape in by_name:
            if (name.endswith("/" + pname) or name == pname) and leaf.shape == pshape:
                matched = pspec
                break
        if matched is None:
            # Suffix matching relies on optax states embedding param-shaped
            # subtrees under param-suffixed paths; optimizers that don't
            # (factored states, custom wrappers) land here and stay
            # replicated. Silent replication is a ZeRO no-op — record any
            # leaf big enough that sharding it would have mattered (only
            # when the mesh could have sharded it at all: fsdp > 1).
            if fsdp_size > 1 and int(np.prod(leaf.shape)) >= parallel.fsdp_min_size:
                unmatched.append(name)
            return P()
        if parallel.opt_sharding == "zero1":
            # ZeRO-1: shard the state mirror over fsdp even though params
            # aren't. (If params are already fsdp-sharded this is a no-op
            # overlay on top of the inherited spec.)
            return fsdp_spec_for(
                leaf.shape,
                matched,
                axis_size=fsdp_size,
                min_size=parallel.fsdp_min_size,
            )
        if parallel.opt_sharding == "like_params":
            return matched
        raise ValueError(f"unknown opt_sharding {parallel.opt_sharding!r}")

    specs = named_tree_map(decide, opt_state_shapes)
    if unmatched:
        from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

        get_logger().warning(
            "opt_state_specs: %d optimizer-state leaves >= fsdp_min_size "
            "did not suffix-match any parameter and stay REPLICATED "
            "(opt_sharding=%r is a no-op for them): %s",
            len(unmatched),
            parallel.opt_sharding,
            ", ".join(unmatched[:5]) + (", ..." if len(unmatched) > 5 else ""),
        )
    return specs


def block_param_slice_shapes(params_shapes: Any, model_axis: int) -> set[tuple]:
    """Legal all_gather output shapes for a blockwise overlap schedule:
    per-block slices of the stacked ``blocks`` params (scan-sliced —
    leading layer dim dropped), or whole leaves for non-scanned families,
    with Megatron-split dims also allowed at ``1/model_axis`` (the
    per-shard view inside a composed schedule's shard_map regions).

    This is the shape set ``analysis.pins.assert_schedule`` and the
    graft-lint runner check blockwise gathers against — kept next to the
    spec derivation so "which dims a block gather may move" has one owner.
    """
    import jax

    slices: set[tuple] = set()
    blocks = getattr(params_shapes, "get", lambda *_: None)("blocks")
    leaves = jax.tree.leaves(blocks) if blocks is not None else []
    if not leaves:  # non-scanned families: any full param leaf is a block
        leaves = jax.tree.leaves(params_shapes)
        for l in leaves:
            slices.add(tuple(l.shape))
    for l in leaves:
        s = tuple(l.shape[1:]) if blocks is not None else tuple(l.shape)
        slices.add(s)
        if model_axis > 1:
            for i, d in enumerate(s):
                if d % model_axis == 0:
                    slices.add(s[:i] + (d // model_axis,) + s[i + 1:])
    return slices


def shardings_from_specs(specs: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree → NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params_for_serving(params: Any, env: Any, rules: PartitionRules) -> Any:
    """Place a params tree onto a serving mesh per the model's TP rules
    — the one-call version of derive-specs + device_put that every
    decode consumer (serving/engine.py callers, tools/serve_bench.py,
    the sharded-decode tests) otherwise hand-rolls.

    Serving has no optimizer state and no FSDP overlay — params are
    either replicated or Megatron-sharded over ``model`` — so the overlay
    config is the default ``ParallelConfig()`` (replicated base) and only
    ``rules`` decides placement. The head-sharded KV cache then follows
    from these kernels at trace time (models/gpt.py pins the layout).

    Device-resident SHARDED trees (a live training layout — the
    train→serve handoff, ISSUE 15) route through the redistribution
    service: each leaf moves only the shard deltas the destination
    layout lacks, never a replicated host round-trip. Host (numpy)
    trees — and multi-process trees whose shards this process cannot
    address (the executor is single-controller) — keep the direct
    shard-wise ``device_put``."""
    leaves = jax.tree.leaves(params)
    if any(
        isinstance(getattr(l, "sharding", None), NamedSharding)
        for l in leaves
    ) and all(
        getattr(l, "is_fully_addressable", True) for l in leaves
    ):
        from frl_distributed_ml_scaffold_tpu.redistribute import (
            train_to_serve,
        )

        placed, _plan = train_to_serve(params, env, rules)
        return placed
    specs = param_specs(params, ParallelConfig(), env.mesh, rules)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(env.mesh, s)),
        params,
        specs,
    )
