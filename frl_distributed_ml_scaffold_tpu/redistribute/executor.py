"""Redistribution plan EXECUTOR (ISSUE 15): run LeafPlans donated-in-place.

Two lowerings behind one ``execute`` entry:

- ``collective`` plans become ONE jitted shard_map program per (mesh,
  specs, shape, dtype) class — slice / all_to_all / all_gather steps in
  add→move→drop order (shrink first, grow last), input donated. These
  are the programs graft-lint's ``reshard:*`` family pins: every
  intermediate fits the plan's scratch budget (one source shard + one
  destination shard per device), and a naive gather-then-scatter —
  materialize the full logical array on every device, re-slice — trips
  the materialization pin. ``_NAIVE_GATHER_SCATTER`` switches the body
  to exactly that naive reference: the mutation gate's mutant AND the
  bit-exactness oracle the tests compare the real program against.

- ``chunked`` plans run host-orchestrated: per destination shard,
  assemble from bounded source-shard slices (device-to-device when one
  chunk covers the shard; a host window otherwise) and build the
  destination array from its per-device shards. Peak transient = one
  destination shard + one chunk — measured and stamped back onto the
  plan (``executed_scratch_bytes``) so tests pin measured <= planned.

Donation: ``donate=True`` deletes each source leaf's buffers as soon as
its destination array is materialized, so peak tree memory is ONE leaf's
(src + dst), not two full trees.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from frl_distributed_ml_scaffold_tpu.redistribute.plan import (
    LeafPlan,
    RedistributionPlan,
    Transition,
    _region_size,
)

#: Mutation switch for the graft-lint gate (tests/test_graft_lint.py)
#: and the reference oracle for the equivalence tests: True lowers every
#: collective plan to gather-everything-then-slice — the replicated
#: staging the real program is pinned never to do.
_NAIVE_GATHER_SCATTER = False

#: (mesh ids, src spec, dst spec, shape, dtype, naive) -> jitted program.
_PROGRAM_CACHE: dict[tuple, Any] = {}


def _flat_axis_index(names: tuple[str, ...], sizes: dict[str, int]):
    """Flattened (major-to-minor) index of this device within a
    multi-name atom's group — the P(('a','b')) nesting order."""
    from jax import lax

    idx = None
    for n in names:
        i = lax.axis_index(n)
        idx = i if idx is None else idx * sizes[n] + i
    return idx


def _axis_arg(names: tuple[str, ...]):
    return names[0] if len(names) == 1 else names


def _collective_body(tr: Transition):
    """The minimal redistribution body for an atom-clean transition:
    adds (local slice — shrink) first, moves (all_to_all — constant
    size), drops (tiled all_gather — grow) last, each on its own dim."""
    from jax import lax

    def body(x):
        for names, dim in tr.adds:
            size = tr.atom_size(names)
            idx = _flat_axis_index(names, tr.axis_sizes)
            piece = x.shape[dim] // size
            x = lax.dynamic_slice_in_dim(x, idx * piece, piece, axis=dim)
        for names, src_dim, dst_dim in tr.moves:
            x = lax.all_to_all(
                x, _axis_arg(names), split_axis=dst_dim,
                concat_axis=src_dim, tiled=True,
            )
        for names, dim in tr.drops:
            x = lax.all_gather(x, _axis_arg(names), axis=dim, tiled=True)
        return x

    return body


def _naive_body(tr: Transition):
    """The replicated-staging reference: gather EVERY source atom (the
    full logical array lands on every device), then slice every
    destination atom back out. Correct, and exactly what the
    materialization pin exists to forbid."""
    from jax import lax

    def body(x):
        for names, dim in tr.src_atoms:
            x = lax.all_gather(x, _axis_arg(names), axis=dim, tiled=True)
        for names, dim in tr.dst_atoms:
            size = tr.atom_size(names)
            idx = _flat_axis_index(names, tr.axis_sizes)
            piece = x.shape[dim] // size
            x = lax.dynamic_slice_in_dim(x, idx * piece, piece, axis=dim)
        return x

    return body


def collective_callable(plan: LeafPlan):
    """The UN-jitted same-mesh reshard program for a collective
    LeafPlan: shard_map(in=src spec, out=dst spec) around the
    transition body. One artifact for the executor (jitted below) and
    for graft-lint's ``reshard:*`` family (traced via make_jaxpr) —
    they cannot drift. ``_NAIVE_GATHER_SCATTER`` swaps in the
    replicated-staging reference, which is both the mutation gate's
    mutant and the tests' equivalence oracle."""
    from frl_distributed_ml_scaffold_tpu.dist.mesh import shard_map_unchecked

    body = (
        _naive_body(plan.transition)
        if _NAIVE_GATHER_SCATTER
        else _collective_body(plan.transition)
    )
    return shard_map_unchecked(
        body, mesh=plan.dst_sharding.mesh,
        in_specs=(plan.src_sharding.spec,),
        out_specs=plan.dst_sharding.spec,
    )


def collective_program(plan: LeafPlan, *, donate: bool = True):
    """THE jitted same-mesh reshard program for a collective LeafPlan —
    ``collective_callable`` under jit, source donated when ``donate``
    (the executor default — graft-lint audits the donated form). Cached
    per program class."""
    import jax

    mesh = plan.dst_sharding.mesh
    # The mesh SHAPE is part of the program identity: the same device
    # ids under mesh(data=2, model=4) vs mesh(data=4, model=2) lower
    # the same spec strings to different placements.
    key = (
        tuple(d.id for d in mesh.devices.flat),
        mesh.axis_names, mesh.devices.shape,
        str(plan.src_sharding.spec), str(plan.dst_sharding.spec),
        plan.shape, plan.dtype, donate, _NAIVE_GATHER_SCATTER,
    )
    if key not in _PROGRAM_CACHE:
        _PROGRAM_CACHE[key] = jax.jit(
            collective_callable(plan),
            donate_argnums=(0,) if donate else (),
        )
    return _PROGRAM_CACHE[key]


def _devices_by_id(*shardings) -> dict[int, Any]:
    out = {}
    for sh in shardings:
        for d in getattr(sh, "device_set", ()) or ():
            out[d.id] = d
    return out


def _rel(index, base):
    """Global region -> slices relative to ``base``'s origin."""
    return tuple(
        slice(a - b0, b - b0) for (a, b), (b0, _) in zip(index, base)
    )


def _execute_chunked(plan: LeafPlan, x, track) -> Any:
    """Host-orchestrated chunk assembly (cross-mesh / unclean
    transitions): per destination shard, either one device-to-device
    slice transfer or a host window filled chunk-by-chunk. Never holds
    more than one destination shard + one chunk."""
    import jax

    devs = _devices_by_id(plan.src_sharding, plan.dst_sharding)
    shards = {s.device.id: s for s in x.addressable_shards}
    missing = [
        c.src_device for c in plan.chunks if c.src_device not in shards
    ]
    if missing:
        raise RuntimeError(
            "chunked redistribution needs every source shard addressable "
            f"(single-process); missing device ids {sorted(set(missing))}. "
            "Multi-host cross-mesh moves must route through a same-mesh "
            "collective plan or a checkpoint round-trip."
        )
    per_dst: dict[int, list] = {}
    for c in plan.chunks:
        per_dst.setdefault(c.dst_device, []).append(c)
    dst_map = {
        d.id: idx
        for d, idx in plan.dst_sharding.devices_indices_map(
            plan.shape
        ).items()
    }
    from frl_distributed_ml_scaffold_tpu.redistribute.plan import (
        _resolve_index,
    )

    out_shards = []
    itemsize = np.dtype(plan.dtype).itemsize
    # Replicated (or partially replicated) destinations repeat regions
    # across devices: assemble each unique region's host window ONCE
    # and device_put per consumer, instead of re-pulling the same
    # source slices R times. The window is dropped after its LAST
    # consumer (refcounted below) — distinct regions are never live
    # together, so the host transient stays at one shard + one chunk,
    # which is what the plan's peak_scratch_bytes promises and
    # track() reports.
    regions = {
        dst_id: _resolve_index(dst_map[dst_id], plan.shape)
        for dst_id in per_dst
    }
    consumers: dict[tuple, int] = {}
    for r in regions.values():
        consumers[r] = consumers.get(r, 0) + 1
    buf_cache: dict[tuple, np.ndarray] = {}
    for dst_id in sorted(per_dst):
        region = regions[dst_id]
        cs = per_dst[dst_id]
        if len(cs) == 1 and cs[0].index == region:
            c = cs[0]
            src = shards[c.src_device]
            src_region = _resolve_index(
                src.index if src.index else (), plan.shape
            )
            piece = src.data[_rel(c.index, src_region)]
            track(c.nbytes)
            piece = jax.device_put(piece, devs[dst_id])
        else:
            buf = buf_cache.get(region)
            if buf is None:
                buf = np.empty(
                    tuple(b - a for a, b in region), np.dtype(plan.dtype)
                )
                shard_bytes = buf.size * itemsize
                for c in cs:
                    src = shards[c.src_device]
                    src_region = _resolve_index(
                        src.index if src.index else (), plan.shape
                    )
                    track(shard_bytes + c.nbytes)
                    buf[_rel(c.index, region)] = np.asarray(
                        src.data[_rel(c.index, src_region)]
                    )
                buf_cache[region] = buf
            piece = jax.device_put(buf, devs[dst_id])
            # device_put copies host->device synchronously enough to
            # release the window once its last consumer has a piece.
            consumers[region] -= 1
            if consumers[region] == 0:
                buf_cache.pop(region, None)
        out_shards.append(piece)
    return jax.make_array_from_single_device_arrays(
        plan.shape, plan.dst_sharding, out_shards
    )


def execute_leaf(plan: LeafPlan, x, *, donate: bool = True, track=None):
    """Run one LeafPlan. ``track(nbytes)`` observes transient peaks."""
    import jax

    track = track or (lambda _n: None)
    if plan.kind == "identity":
        return x
    if plan.kind == "host":
        track(plan.peak_scratch_bytes)
        return jax.device_put(np.asarray(x), plan.dst_sharding)
    if plan.kind == "collective":
        track(plan.peak_scratch_bytes)
        # Donation rides the program (donate_argnums): in-place at the
        # buffer level, which is what keeps an N-device reshard at
        # ~2 shards/device instead of 2 full arrays.
        return collective_program(plan, donate=donate)(x)
    out = _execute_chunked(plan, x, track)
    if donate and isinstance(x, jax.Array) and not x.is_deleted():
        # The chunk transfers above are enqueued; make sure they landed
        # before the source buffers go away.
        jax.block_until_ready(out)
        if not _shares_buffers(x, out):
            x.delete()
    return out


def _shares_buffers(x, out) -> bool:
    """True when any output shard aliases a source buffer — a full-cover
    same-device chunk is a zero-copy re-own (slicing a whole shard
    returns the shard and ``device_put`` onto its own device is a
    no-op), and deleting the source would tear the output. Nothing to
    free in that case anyway: the memory IS shared."""
    try:
        src = {s.data.unsafe_buffer_pointer() for s in x.addressable_shards}
        dst = {
            s.data.unsafe_buffer_pointer() for s in out.addressable_shards
        }
    except Exception:  # backends without the pointer API: be safe
        return True
    return bool(src & dst)


def execute(
    plan: RedistributionPlan, tree: Any, *, donate: bool = True
) -> Any:
    """Run a tree plan leaf-by-leaf (donated: each source leaf is freed
    as soon as its destination exists). Stamps the MEASURED transient
    peak back onto ``plan.executed_scratch_bytes``."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten(tree)
    if len(flat) != len(plan.leaves):
        raise ValueError(
            f"plan has {len(plan.leaves)} leaves but tree has {len(flat)}"
        )
    peak = 0

    def track(n: int) -> None:
        nonlocal peak
        peak = max(peak, int(n))

    out = [
        execute_leaf(lp, leaf, donate=donate, track=track)
        for lp, leaf in zip(plan.leaves, flat)
    ]
    plan.executed_scratch_bytes = peak
    return jax.tree_util.tree_unflatten(treedef, out)
