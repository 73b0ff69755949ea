"""Expert feed-forwards computed GROUPED BY EXPERT, for top-k routing without
drops (models/moe.py ``dropless``).

The token-expert pairs are sorted by expert and laid out in row TILES of
``tm`` rows, each expert's rows starting on a tile boundary: a tile then
belongs to exactly one expert, and the whole layer is one pass over the
tiles in which tile ``i`` multiplies its rows by the three matrices of expert
``tile_expert[i]`` (gate, up, down: the gated feed-forward
``(silu(x W1) * (x W3)) W2``). Only experts that received a pair are ever
read — at a decode batch the layer is bound by those reads, a few megabytes
an expert touched — and no pair is dropped: the layout has room for every
pair whatever the routing (``ceil(pairs / tm) + experts`` tiles; each group
wastes less than one).

``grouped_layout`` builds the layout from the routing (plain ``jax.numpy``:
one sort, two cumulative sums); ``expert_ffn_grouped`` runs the tiles: the
Pallas kernel ``moe_expert_ffn`` on the TPU (the tile's expert id rides the
scalar-prefetch channel, so the weight DMA addresses the stacked ``[E, D, F]``
gate and up arrays and the flat ``[E * F, D]`` down array where they lie; tiles past the last used one repeat its block indices
and skip their body, so they move nothing), and off the TPU the same product
as a tile-batched einsum (identical layout, no interpreter slowdown — the
contract of ``ops/decode_attention.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Test hook (the ``decode_attention.FORCE_INTERPRET`` pattern).
FORCE_INTERPRET: bool | None = None

#: The kernel holds one expert's three matrices double-buffered beside its
#: row tile: 12 MB at 2048 x 512 in bf16, over the compiler's default scoped
#: limit (16 MB) once the tiles and the float32 intermediates are counted.
_VMEM_LIMIT = 64 * 1024 * 1024


def tile_rows(pairs: int) -> int:
    """Rows a tile: the bf16 sublane tile (16) while the layer is bound by
    reading the experts it touches (a decode batch: hundreds of pairs over
    as many experts), the MXU's 128 once every expert gets rows enough
    (a prefill)."""
    return 16 if pairs <= 2048 else 128


def grouped_layout(expert_ids, num_experts: int, tm: int):
    """Where each token-expert pair goes. ``expert_ids`` ``[P]`` int32 holds
    the pair's expert, or ``num_experts`` for a pair that is not to be
    computed (a padding column, a dead slot row). Returns ``dest [P]`` (the
    pair's row in the sorted layout; 0 for a pair left out), ``src [M]``
    (the pair that sits in each row, ``P`` where none does), ``tile_expert
    [n_tiles]``, ``n_used [1]`` (tiles that hold a pair) and ``counts [E]``
    (pairs an expert)."""
    p = expert_ids.shape[0]
    e = num_experts
    n_tiles = -(-p // tm) + e
    counts = jnp.zeros((e + 1,), jnp.int32).at[expert_ids].add(1)[:e]
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)  # a group's end in the layout, by expert
    starts = ends - padded
    order = jnp.argsort(expert_ids, stable=True)
    first = jnp.cumsum(counts) - counts  # a group's start in sorted order
    safe = jnp.minimum(expert_ids, e - 1)
    rank = jnp.zeros((p,), jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32)
    ) - first[safe]
    live = expert_ids < e
    dest = jnp.where(live, starts[safe] + rank, 0).astype(jnp.int32)
    src = jnp.full((n_tiles * tm,), p, jnp.int32).at[
        jnp.where(live, dest, n_tiles * tm)
    ].set(jnp.arange(p, dtype=jnp.int32), mode="drop")
    n_used = (ends[-1] // tm).astype(jnp.int32)
    # Tile i belongs to the first expert whose group ends past its first row;
    # tiles past the last used one repeat the last used tile's expert.
    at = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                     jnp.maximum(n_used - 1, 0)) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, at, side="right"), e - 1
    ).astype(jnp.int32)
    return dest, src, tile_expert, n_used.reshape(1), counts


def _expert_ffn_kernel(te_ref, nu_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref):
    @pl.when(pl.program_id(0) < nu_ref[0])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, w2_ref[...], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def _kernel_call(x, w1, w3, w2, tile_expert, n_used, *, tm, interpret):
    m, d = x.shape
    f = w1.shape[-1]

    def rows(i, te, nu):  # an unused tile re-references the last used one
        return (jnp.minimum(i, jnp.maximum(nu[0] - 1, 0)), 0)

    def expert(i, te, nu):
        return (te[i], 0, 0)

    def expert_rows(i, te, nu):  # expert e's F rows of the flat down array
        return (te[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, d), rows),
            pl.BlockSpec((None, d, f), expert),
            pl.BlockSpec((None, d, f), expert),
            pl.BlockSpec((f, d), expert_rows),
        ],
        out_specs=pl.BlockSpec((tm, d), rows),
    )
    return pl.pallas_call(
        _expert_ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_expert_ffn",
    )(tile_expert, n_used, x, w1, w3, w2)


def expert_ffn_grouped(x, w1, w3, w2, tile_expert, n_used, *, tm: int,
                       interpret: bool | None = None):
    """``x [M, D]`` in the layout of ``grouped_layout`` (``M`` a whole
    number of tiles), stacked experts ``w1, w3 [E, D, F]``, their
    down-projections flat ``w2 [E * F, D]`` (expert after expert)
    -> ``[M, D]``: row r through the gated feed-forward of its tile's
    expert. Rows of tiles past ``n_used`` are not computed (their content is
    unspecified; the caller reads rows of real pairs only)."""
    if interpret is None:
        interpret = FORCE_INTERPRET
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _tiles_einsum(x, w1, w3, w2, tile_expert, tm)
        interpret = False
    return _kernel_call(
        x, w1, w3, w2, tile_expert, n_used, tm=tm, interpret=interpret
    )


def _tiles_einsum(x, w1, w3, w2, tile_expert, tm):
    """The same product with no kernel: each tile against its expert's
    matrices, gathered (off the TPU only: the gather copies a matrix a
    tile)."""
    m, d = x.shape
    xt = x.reshape(m // tm, tm, d)
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    gate = mm("ntd,ndf->ntf", xt, w1[tile_expert])
    up = mm("ntd,ndf->ntf", xt, w3[tile_expert])
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    w2 = w2.reshape(w1.shape[0], w1.shape[2], d)
    return mm("ntf,nfd->ntd", h, w2[tile_expert]).astype(x.dtype).reshape(m, d)
