"""Overlap-scheduled FSDP: explicit blockwise all-gather / reduce-scatter.

The plain ``param_sharding="fsdp"`` path hands parameter gathering to GSPMD:
sharded params flow into the jit program and the partitioner inserts
all-gathers wherever it likes — in practice often hoisted to the top of the
program (full params materialized up front) and serialized against compute.
SimpleFSDP (arxiv 2411.00284) shows that making the per-block collectives
explicit recovers the hidden communication time: gather block k's shards
immediately before block k's compute, free them after use, and
reduce-scatter block k's gradients straight back into shards, so the
latency-hiding scheduler can run block k+1's gather under block k's compute.

Mechanics here (``parallel.fsdp_overlap=true``):

- ``gather_leaf`` opens a one-leaf ``shard_map`` region over the current
  mesh and calls the ``dist/collectives.py`` façade's ``all_gather`` over
  the ``fsdp`` axis — an *explicit* AllGather pinned to the consuming
  block, visible in the jaxpr (the blockwise-ness test keys on this).
  JAX's transpose of a tiled ``all_gather`` is ``psum_scatter``, so the
  backward is the matching explicit ReduceScatter for free; cross-axis
  gradient reductions (the ``data`` allreduce) stay with GSPMD, which
  already inserts them for the non-overlap path.
- Gathered leaves are tagged ``checkpoint_name(..., "fsdp_gathered")`` and
  every hooked block is wrapped in ``nn.remat`` with (by default) the
  ``save_anything_except_these_names`` policy: forward residuals are kept
  as usual but the gathered full params are NOT saved — the backward
  re-gathers (standard FSDP reshard-after-forward), which is what keeps
  peak live params at ~one block instead of the whole model.
- Models expose *blockwise apply hooks* (``param_hooks`` on GPT/ResNet):
  the scanned transformer stack applies the gather per scan iteration via
  ``nn.map_variables`` (so each layer's slice is gathered inside the loop
  body — the form XLA's collective pipeliner hoists one iteration ahead,
  the ``fsdp_prefetch=1`` schedule), and the ResNet block loop creates a
  per-block hook whose gather is tied by ``optimization_barrier`` to the
  output of block ``k - 1 - prefetch`` — a structurally enforced prefetch
  window.

Everything is correctness-gated on the CPU sim (tests/test_fsdp_overlap.py:
numerics vs the GSPMD path, jaxpr blockwise-ness, mesh compositions); the
on-chip step-time A/B rides ``tools/perf_sweep.py gpt2_fsdp_overlap``
(BACKLOG).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.dist import collectives
from frl_distributed_ml_scaffold_tpu.dist.mesh import (
    current_mesh_env,
    shard_map_unchecked,
)

#: checkpoint_name tag on gathered params; the remat policy drops exactly
#: these so the backward re-gathers instead of keeping full params alive.
GATHER_NAME = "fsdp_gathered"

#: Model families with blockwise apply hooks wired up.
SUPPORTED_FAMILIES = ("gpt", "resnet")


@dataclass(frozen=True)
class OverlapHooks:
    """What a model needs to run the overlap schedule.

    ``block_hook`` — ``nn.map_variables`` trans_in_fn for a scanned block
    stack (receives ``{"params": <sliced block params>}``); built from the
    per-block (scan-sliced) PartitionSpecs.
    ``hook_factory`` — ``factory(token) -> trans_in_fn`` for Python-loop
    block stacks (ResNet): ``token`` is the activation whose completion
    gates this block's gather (the prefetch window).
    """

    prefetch: int = 1
    block_hook: Callable[[dict], dict] | None = None
    hook_factory: Callable[[Any], Callable[[dict], dict]] | None = None


def gathered_spec(spec: P, axis: str = "fsdp") -> P:
    """``spec`` with every occurrence of ``axis`` removed (gather target)."""
    out = []
    for e in spec:
        if e == axis:
            out.append(None)
        elif isinstance(e, tuple):
            rest = tuple(a for a in e if a != axis)
            out.append(rest if rest else None)
        else:
            out.append(e)
    return P(*out)


@jax.custom_vjp
def _sched_gate(x, token):
    """Scheduling-only dependence of ``x`` on ``token``: XLA may not issue
    ``x``'s consumers before ``token`` exists, but the VALUE is just ``x``
    — so the custom VJP passes the cotangent straight through (this jax's
    ``optimization_barrier`` has no differentiation rule of its own, and
    the token's true derivative is zero anyway)."""
    x2, _ = lax.optimization_barrier((x, token))
    return x2


def _sched_gate_fwd(x, token):
    return _sched_gate(x, token), token


def _sched_gate_bwd(token, dy):
    import jax.numpy as jnp

    return dy, jnp.zeros_like(token)


_sched_gate.defvjp(_sched_gate_fwd, _sched_gate_bwd)


def _axis_dim(spec: P, axis: str) -> int | None:
    for i, e in enumerate(spec):
        if e == axis or (isinstance(e, tuple) and axis in e):
            return i
    return None


def strip_scan_dim(spec: P) -> P:
    """Spec of one scan-sliced block leaf from its stacked spec (drop the
    leading layer-dim entry). If the fsdp overlay landed on the layer dim
    itself the sliced leaf is simply unsharded — the hook passes it through
    and GSPMD keeps handling it."""
    entries = list(spec)
    return P(*entries[1:]) if entries else P()


def gather_leaf(x: jax.Array, spec: P, *, axis: str = "fsdp", token=None):
    """Explicit all-gather of one sharded leaf over ``axis``.

    Identity on leaves whose spec doesn't carry ``axis``. ``token`` (an
    activation) gates when the gather may be *issued*: an
    ``optimization_barrier`` ties the shard read to the token, which is how
    the ResNet loop enforces the ``fsdp_prefetch`` window. The gathered
    value is checkpoint_name-tagged so remat policies can refuse to save it.
    """
    dim = _axis_dim(spec, axis)
    if dim is None:
        return x
    env = current_mesh_env()
    if env is None or env.axis_size(axis) == 1:
        return x
    if token is not None:
        # The gate's only job is scheduling: the shard becomes
        # data-dependent on the token, so XLA cannot issue this gather
        # before the token's producer block has finished.
        x = _sched_gate(x, token)
    out_spec = gathered_spec(spec, axis)

    def inner(shard):
        return collectives.all_gather(shard, axis, gather_axis=dim, tiled=True)

    y = shard_map_unchecked(
        inner, mesh=env.mesh, in_specs=(spec,), out_specs=out_spec
    )(x)
    return checkpoint_name(y, GATHER_NAME)


def gather_tree(tree: Any, specs: Any, *, axis: str = "fsdp", token=None):
    """``gather_leaf`` over a params subtree with a matching specs subtree."""
    return jax.tree_util.tree_map(
        lambda x, s: gather_leaf(x, s, axis=axis, token=token),
        tree,
        specs,
        is_leaf=lambda t: isinstance(t, P),
    )


def make_scan_block_hook(sliced_specs: Any, *, axis: str = "fsdp"):
    """trans_in_fn for ``nn.map_variables`` around a scanned Block.

    ``sliced_specs`` must mirror one block's param subtree (the stacked
    specs with the leading layer dim stripped — ``strip_scan_dim``).
    Running inside the scan body, this gathers exactly one layer's slice
    per iteration: the blockwise schedule.
    """

    def hook(variables: dict) -> dict:
        out = dict(variables)
        out["params"] = gather_tree(variables["params"], sliced_specs, axis=axis)
        return out

    return hook


def make_shape_hook_factory(parallel, axis_size: int, *, axis: str = "fsdp"):
    """Per-block hook factory for non-scanned block stacks (ResNet).

    ResNet has no TP rules by design, so each leaf's spec is derived from
    its shape with exactly the machinery ``param_specs`` used
    (``fsdp_spec_for`` with base=P()) — the hook's view of "which dim is
    sharded" provably matches the state shardings. ``factory(token)``
    closes over the prefetch-window token for one block.
    """
    from frl_distributed_ml_scaffold_tpu.parallel.partition import fsdp_spec_for

    def leaf_spec(leaf) -> P:
        return fsdp_spec_for(
            leaf.shape,
            P(),
            axis=axis,
            axis_size=axis_size,
            min_size=parallel.fsdp_min_size,
        )

    def factory(token):
        def hook(variables: dict) -> dict:
            out = dict(variables)
            out["params"] = jax.tree_util.tree_map(
                lambda x: gather_leaf(x, leaf_spec(x), axis=axis, token=token),
                variables["params"],
            )
            return out

        return hook

    return factory


def overlap_remat_policy(block_remat: str = "none"):
    """Checkpoint policy for a hooked block: whatever the configured
    per-block remat mode saves, gathered params are never among it.

    - "none"      — save every intermediate EXCEPT the gathered params
                    (memory profile of the un-rematted block, minus the
                    full-params residency; backward re-gathers).
    - "full"      — save nothing (model.block_remat=full semantics; the
                    gathered params are recomputed along with the rest).
    - "save_attn" — save only the attention-sublayer outputs (gathers
                    excluded by construction).
    """
    if block_remat == "none":
        return jax.checkpoint_policies.save_anything_except_these_names(
            GATHER_NAME
        )
    if block_remat == "full":
        return None
    if block_remat == "save_attn":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    raise KeyError(
        f"unknown block_remat {block_remat!r} for the overlap path "
        "(none | full | save_attn)"
    )


def validate_block_schedule(cfg, *, prefetch: int) -> None:
    """Fail fast on configs a blockwise gather rule cannot honor (a silent
    fallback to the GSPMD schedule would invalidate any A/B built on it).
    Called by the schedule layer (parallel/schedule.py
    ``validate_schedule_config``) for every ``granularity="block"``
    gather; the legacy knob path reaches it through
    ``validate_overlap_config``."""
    family = getattr(cfg.model, "family", None)
    if cfg.parallel.param_sharding != "fsdp":
        raise ValueError(
            "parallel.fsdp_overlap=true requires param_sharding='fsdp' "
            f"(got {cfg.parallel.param_sharding!r}): the overlap schedule "
            "is a rewrite of how fsdp-sharded params are gathered, not a "
            "sharding strategy of its own"
        )
    if family not in SUPPORTED_FAMILIES:
        raise ValueError(
            f"parallel.fsdp_overlap=true: model family {family!r} has no "
            f"blockwise apply hooks (supported: {SUPPORTED_FAMILIES})"
        )
    if (
        getattr(cfg.model, "pipeline_stages", 1) > 1
        and getattr(cfg.model, "pipeline_impl", "spmd") != "mpmd"
    ):
        # The SPMD stage-vmap path owns its own block schedule; the MPMD
        # backend (ISSUE 14) lowers the blockwise gathers INSIDE each
        # per-stage program, where they compose as in the plain stack.
        raise ValueError(
            "parallel.fsdp_overlap composes with dp/fsdp/tp meshes but not "
            "with the SPMD pipeline backend (the stage-vmap path owns its "
            "own block schedule); set model.pipeline_stages=1 or "
            "model.pipeline_impl='mpmd'"
        )
    if prefetch < 0:
        raise ValueError(
            f"parallel.fsdp_prefetch must be >= 0, got {prefetch}"
        )


def validate_overlap_config(cfg) -> None:
    """Legacy-knob adapter: validate ``parallel.fsdp_overlap=true`` by
    deriving its schedule declaration and running the schedule layer's
    checks (parallel/schedule.py owns the full contradiction set — e.g.
    the prefetch-vs-block-count window bound)."""
    from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
        OverlapSchedule,
        gather,
        scatter,
        validate_schedule_config,
    )

    sched = OverlapSchedule.build(
        gather("fsdp", granularity="block",
               prefetch=cfg.parallel.fsdp_prefetch),
        scatter("fsdp"),
    )
    validate_schedule_config(sched, cfg)
