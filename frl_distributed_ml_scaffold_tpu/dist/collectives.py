"""Collective façade (SURVEY C2): the ``dist/`` wrapper API, TPU-native.

Two tiers, mirroring how the reference is used:

**Device tier** — inside a compiled program under ``shard_map`` over a mesh
axis. These lower to XLA collectives on ICI/DCN (the NCCL equivalents):
``all_reduce``/``all_mean`` → AllReduce, ``all_gather`` → AllGather,
``reduce_scatter`` → ReduceScatter, ``permute`` → CollectivePermute,
``all_to_all`` → AllToAll, ``broadcast`` → source-select + AllReduce.
Under plain GSPMD (no shard_map) you normally never call these — the compiler
inserts them from sharding annotations; they exist for the manual-parallelism
paths (pipeline, ring attention, MoE dispatch) and for parity with the
reference's explicit-collective API.

**Host tier** — outside jit, process-level coordination:
``host_all_gather``, ``host_broadcast``, ``barrier``.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import multihost_utils

AxisName = str | tuple[str, ...]

# ----------------------------- device tier --------------------------------


def all_reduce(x: Any, axis: AxisName) -> Any:
    """Sum-allreduce a pytree over mesh axis/axes (NCCL allreduce parity)."""
    return jax.tree.map(lambda a: lax.psum(a, axis), x)


def all_mean(x: Any, axis: AxisName) -> Any:
    """Mean-allreduce (the DDP gradient-averaging semantic)."""
    return jax.tree.map(lambda a: lax.pmean(a, axis), x)


def all_gather(x: Any, axis: AxisName, *, gather_axis: int = 0, tiled: bool = True) -> Any:
    """Gather shards along ``gather_axis`` from every member of ``axis``."""
    return jax.tree.map(
        lambda a: lax.all_gather(a, axis, axis=gather_axis, tiled=tiled), x
    )


def reduce_scatter(x: Any, axis: AxisName, *, scatter_axis: int = 0) -> Any:
    """Sum-reduce then scatter shards along ``scatter_axis``."""
    return jax.tree.map(
        lambda a: lax.psum_scatter(a, axis, scatter_dimension=scatter_axis, tiled=True),
        x,
    )


def broadcast(x: Any, axis: str, *, source: int = 0) -> Any:
    """Broadcast ``source``'s value to all members of ``axis``.

    SPMD has no asymmetric send; the idiom is mask-then-allreduce (one
    AllReduce, same cost class as NCCL broadcast on a ring).
    """
    idx = lax.axis_index(axis)

    def _bcast(a):
        masked = jnp.where(idx == source, a, jnp.zeros_like(a))
        return lax.psum(masked, axis)

    return jax.tree.map(_bcast, x)


def permute(x: Any, axis: str, perm: Sequence[tuple[int, int]]) -> Any:
    """Point-to-point shift over ``axis``: ``perm`` is (src, dst) pairs.

    The primitive under ring attention and pipeline stage hand-off.
    """
    return jax.tree.map(lambda a: lax.ppermute(a, axis, perm), x)


def ring_shift(x: Any, axis: str, *, shift: int = 1) -> Any:
    """Rotate shards around the axis ring by ``shift`` (ring-attention step)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return permute(x, axis, perm)


def all_to_all(
    x: Any, axis: str, *, split_axis: int, concat_axis: int, tiled: bool = True
) -> Any:
    """AllToAll resharding (Ulysses head<->seq exchange, MoE dispatch)."""
    return jax.tree.map(
        lambda a: lax.all_to_all(
            a, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
        ),
        x,
    )


def axis_index(axis: str):
    """This shard's coordinate along ``axis`` (reference: group rank)."""
    return lax.axis_index(axis)


def axis_size(axis: str):
    """Size of the mesh axis (reference: group world size) — a
    compile-time constant, so ring/Ulysses hop counts stay static."""
    return lax.axis_size(axis)


# ------------------------------ host tier ---------------------------------


def host_all_gather(x: Any) -> Any:
    """Gather per-process values to every process (outside jit)."""
    return multihost_utils.process_allgather(x)


def host_broadcast(x: Any, *, is_source: bool | None = None) -> Any:
    """Broadcast process 0's pytree to all processes (outside jit)."""
    if is_source is None:
        is_source = jax.process_index() == 0
    return multihost_utils.broadcast_one_to_all(x, is_source=is_source)


def barrier(name: str = "barrier") -> None:
    """Block until every process reaches this point (reference: dist.barrier)."""
    multihost_utils.sync_global_devices(name)
