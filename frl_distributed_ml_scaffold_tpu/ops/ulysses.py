"""Ulysses-style sequence parallelism (SURVEY C8): all_to_all resharding.

The alternative long-context scheme: instead of rotating K/V (ring), one
``all_to_all`` over the ``seq`` axis converts sequence-sharded activations
into head-sharded ones — each shard then holds the FULL sequence for a
subset of heads, runs ordinary dense attention locally, and a second
``all_to_all`` converts back. Two collectives per attention call vs. the
ring's n-1 hops: cheaper at moderate sequence lengths, but requires
num_heads % seq_axis == 0 and O(T²/n) score memory per shard.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.dist.mesh import BATCH_AXES, current_mesh_env
from frl_distributed_ml_scaffold_tpu.ops.ring_attention import dense_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "seq",
    causal: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, T, H, D) attention, T sharded over ``axis_name`` (SP-Ulysses).

    The local full-sequence attention after the all_to_all runs through the
    fused flash kernel on TPU (dense on untileable shapes / other backends),
    so Ulysses' per-shard memory is O(block), not O(T²/n).
    """
    env = current_mesh_env()
    if env is None or env.axis_size(axis_name) == 1:
        return dense_attention(q, k, v, causal=causal)

    n = env.axis_size(axis_name)
    tp = env.axis_size("model")
    # The shard_map spec below shards heads over "model" too, so the
    # divisibility that matters is of the *local* (per-TP-shard) head count.
    if q.shape[2] % tp != 0 or (q.shape[2] // tp) % n != 0:
        raise ValueError(
            f"ulysses needs num_heads/model_axis ({q.shape[2]}/{tp}) "
            f"divisible by seq axis ({n}); use ring attention instead"
        )

    spec = P(BATCH_AXES, axis_name, "model", None)
    inner = partial(
        _ulysses_shard_fn, axis_name=axis_name, causal=causal, interpret=interpret
    )
    from frl_distributed_ml_scaffold_tpu.dist.mesh import shard_map_unchecked

    return shard_map_unchecked(
        inner,
        mesh=env.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)


def _ulysses_shard_fn(q, k, v, *, axis_name: str, causal: bool, interpret):
    from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
        local_flash_attention,
    )

    # seq-sharded (B, T/n, H, D) -> head-sharded (B, T, H/n, D)
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = local_flash_attention(qh, kh, vh, causal=causal, interpret=interpret)
    return to_seq(out)
