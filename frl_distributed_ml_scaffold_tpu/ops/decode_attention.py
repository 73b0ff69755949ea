"""Decode attention on the TPU: one query token (or a small tile) a row
against a KV cache, as Pallas kernels with plain twins. Forward only.

Two caches, one kernel each, every kernel beside a ``dense_*`` twin of the
same numerics (fp32 online softmax, bf16 multiply / fp32 accumulate) that
tests hold it to and that runs wherever the kernel does not.

CONTIGUOUS (``decode_attention``: ``generate``, ``beam_search``, the
engine's bucketed cache). q ``[B, H, D]``, k/v ``[B, S, H, D]``, ``kv_len
[B]``. Split-KV: the cache is walked in chunks with the running max /
denominator / accumulator in VMEM; ``kv_len`` rides the scalar-prefetch
channel, so chunks past a row's length are neither copied nor computed. A
quantized cache (1-byte k/v, ``[B, S, H]`` scales) is dequantized a chunk
at a time in VMEM; the twin streams bounded chunks through a ``lax.scan``
for the same reason: no full-precision copy of the cache ever exists.

PAGED (``paged_verify_attention`` and its T = 1 name
``paged_decode_attention``: the serving engine). The contract:

- Pools. K and V of every layer that shares a pool are ONE leaf each,
  lane-dense ``[rows, N, bs, Hkv*D]``: a token's K row is ``Hkv*D``
  contiguous values, heads major (models/gpt.py ``paged_cache_leaves``
  says why). ``layer`` picks the row; the kernel's copies address the leaf
  where it lies (``memory_space=pl.ANY``), so no layer's slice is cut out.
  Query head i reads KV head ``i // (Hq / Hkv)``. A quantized pool adds
  ``[rows, N, Hkv*bs]`` scales, a block's scales one row.
- Tables. ``block_tables [B, places]`` maps a row's logical block j to a
  pool block. ``window`` 0: place j holds block j and every position ``<
  kv_len`` is attended. ``window`` W: positions ``kv_len - W <= pos <
  kv_len``, and the table is a RING, block j at place ``j % places``.
- The walk. One program a row reads the blocks from the first one the
  window still reaches to the last one under ``kv_len`` and no other, a
  lane tile's worth a step, the next step's copies in flight under this
  step's products: a step costs what is live, not the table's width.
- The dead row. ``kv_len[b] == 0`` says the row holds no request: nothing
  of it is read, whatever its table says, and its output is zeros. Death is
  the length alone (models/gpt.py ``live_rows`` decides it).
- The tile. q ``[B, T, Hq, D]``: the K/V of all T positions are already in
  the pool and ``kv_len`` counts them; query t attends positions ``<
  kv_len - T + 1 + t``, so query 0 is exactly a single-token step and
  greedy acceptance of speculated tokens is exact.
- What falls back, with one warning on a TPU: a block size that is no
  power of two >= 8, a pool row that is not whole 128-lane tiles, grouped
  heads whose head size is not whole lane tiles, a quantized pool of fewer
  than ``_SCALE_ROWS`` blocks. Off the TPU the twin runs unless
  ``interpret`` is forced (tests).

Under a live ``model`` mesh axis both entries run head-sharded through
``shard_map`` with no collective: the one psum a block happens in the
row-sharded ``out`` projection that consumes the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
    _pick_block,
    _warn_fallback,
)

_NEG_INF = -1.0e30

#: Test hook (the ``fused_bn.FORCE_INTERPRET`` pattern): set to True to
#: force the Pallas interpreter through model-level entry points that do
#: not expose an ``interpret`` argument.
FORCE_INTERPRET: bool | None = None


def dense_decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, kv_len: jax.Array
) -> jax.Array:
    """Reference decode attention: q ``[B, H, D]`` against the cache
    ``[B, S, H, D]``, keys at positions >= ``kv_len[b]`` masked out. fp32
    softmax, bf16-multiply/fp32-accumulate — the numerics contract the
    kernel is gated against (and the same contract as
    ``_masked_dense_attention`` in models/gpt.py)."""
    d = q.shape[-1]
    s = jnp.einsum("bhd,bshd->bhs", q, k, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    kpos = jnp.arange(k.shape[1])
    mask = kpos[None, :] < kv_len[:, None]  # [B, S]
    s = jnp.where(mask[:, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhs,bshd->bhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.astype(q.dtype)


def dense_decode_attention_quant(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    *,
    block: int | None = None,
) -> jax.Array:
    """Reference decode attention over a QUANTIZED cache: k/v are 1-byte
    ``[B, S, H, D]`` payloads with ``[B, S, H]`` scales.

    Deliberately NOT "dequantize the cache, call the dense reference":
    that materializes a full-precision cache-sized tensor every decode
    step — exactly the allocation the quantized cache exists to avoid
    (and the graft-lint mutation gate for it). Instead the cache streams
    through an online-softmax ``lax.scan`` in chunks of ``block``
    positions: each iteration dequantizes one bounded ``[B, block, H, D]``
    chunk, folds the per-position scales into the score strip / the
    probability row, and merges with the standard log-sum-exp rescale —
    the same merge the Pallas kernel and the flash kernels use, in plain
    XLA. fp32 softmax throughout (the decode numerics contract).
    """
    b, s, h, d = k.shape
    if block is None:
        # Largest power-of-two divisor of S capped at min(64, S/2): the
        # cap at S/2 keeps the dequantized chunk STRICTLY smaller than
        # the bucket at every size, so the "no wide cache-geometry
        # intermediate" pin holds even for the smallest buckets.
        cap = min(64, max(1, s // 2))
        block = next(
            c for c in (64, 32, 16, 8, 4, 2, 1) if c <= cap and s % c == 0
        )
    n = s // block
    q32 = q.astype(jnp.float32)
    inv = 1.0 / np.sqrt(d)
    # [n, B, block, H, ...] chunk stacks (1-byte reshapes — no widening).
    kc = jnp.moveaxis(k.reshape(b, n, block, h, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n, block, h, d), 1, 0)
    ksc = jnp.moveaxis(
        k_scale.astype(jnp.float32).reshape(b, n, block, h), 1, 0
    )
    vsc = jnp.moveaxis(
        v_scale.astype(jnp.float32).reshape(b, n, block, h), 1, 0
    )

    def step(carry, xs):
        m, l, acc, j = carry
        k_q, k_s, v_q, v_s = xs
        k_f = k_q.astype(jnp.float32)  # [B, block, H, D] — bounded
        sc = jnp.einsum("bhd,bchd->bhc", q32, k_f)
        sc = sc * jnp.moveaxis(k_s, 1, 2) * inv  # scale per (b, h, pos)
        kpos = j * block + jnp.arange(block)
        mask = kpos[None, None, :] < kv_len[:, None, None]
        sc = jnp.where(mask, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = p * jnp.moveaxis(v_s, 1, 2)  # fold v scales into the probs
        acc = acc * alpha + jnp.einsum(
            "bhc,bchd->bhd", pv, v_q.astype(jnp.float32)
        )
        return (m_new, l, acc, j + 1), None

    carry0 = (
        jnp.full((b, h, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, 1), jnp.float32),
        jnp.zeros((b, h, d), jnp.float32),
        jnp.int32(0),
    )
    (m, l, acc, _), _ = jax.lax.scan(step, carry0, (kc, ksc, vc, vsc))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def dense_paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    layer,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    *,
    window: int = 0,
) -> jax.Array:
    """Reference attention over a PAGED cache, for a small query tile:
    q ``[B, T, Hq, D]`` — the row's last accepted token plus T-1 draft
    tokens, whose K/V have already been written into the pool at logical
    positions ``kv_len - T .. kv_len - 1`` — against row ``layer`` of the
    stacked lane-dense pools ``[rows, N, bs, Hkv*D]``, addressed through
    per-row block tables ``[B, places]``; query head i reads KV head
    ``i // (Hq / Hkv)``. CAUSAL inside the tile: query t attends logical
    positions ``< kv_len - T + 1 + t`` — and, with a ``window``, no
    further back than ``window`` positions — so position 0 scores exactly
    like a single-token decode step and each draft position additionally
    sees the drafts before it; a row with ``kv_len`` 0 sees nothing and
    reads zeros. ``window`` 0: logical block j sits at table place j;
    otherwise the table is a ring, block j at place ``j % places``. With
    ``k_scale``/``v_scale`` (``[rows, N, Hkv*bs]``: a block's scales are
    one row, heads major) the pool is quantized and the scales fold into
    the score strip / probability rows per chunk.

    Deliberately NOT "gather the logical cache, call the contiguous
    reference": that materializes a ``places*bs >= seq_len``-wide tensor
    every decode step — exactly the full-context array the block pool
    exists to avoid (and the graft-lint mutation gate for the paged
    program). Instead the blocks stream through an online-softmax
    ``lax.scan``, as many steps as the table has places, from the first
    block a row's window reaches (block 0 without one): each step gathers
    ONE bounded ``[B, bs, Hkv*D]`` block per row straight out of the stack
    (``pool[layer, phys]``; the layer's slice of the pool is never cut
    out) and merges with the standard log-sum-exp rescale; the tile only
    widens the score strip to ``[B, Hkv, Hq/Hkv, T, bs]``. fp32 softmax
    throughout (the decode numerics contract)."""
    bs, places = k_pool.shape[2], block_tables.shape[1]
    b, t, h, d = q.shape
    h_kv = k_pool.shape[3] // d
    quant = k_scale is not None
    qg = q.astype(jnp.float32).reshape(b, t, h_kv, h // h_kv, d)
    inv = 1.0 / np.sqrt(d)
    layer = jnp.asarray(layer, jnp.int32)
    tables = block_tables.astype(jnp.int32)
    # Per-(row, query) occupancy: query t of row b covers base[b] + t.
    base = kv_len.astype(jnp.int32) - (t - 1)  # length at query 0
    qlen = (base[:, None] + jnp.arange(t)[None, :])[:, None, None, :, None]
    first = jnp.maximum(base - window, 0) // bs if window else jnp.zeros_like(base)

    def step(carry, i):
        m, l, acc = carry
        j = first + i  # [B]: the row's logical block of this step
        phys = jnp.take_along_axis(tables, (j % places)[:, None], axis=1)[:, 0]
        # [B, bs, Hkv*D] -> [B, bs, Hkv, D]: bounded, and a free reshape.
        k_c = k_pool[layer, phys].reshape(b, bs, h_kv, d)
        v_c = v_pool[layer, phys].reshape(b, bs, h_kv, d)
        sc = jnp.einsum(
            "btkgd,bckd->bkgtc", qg, k_c.astype(jnp.float32)
        )  # [B, Hkv, Hq/Hkv, T, bs]
        if quant:
            k_s = k_scale[layer, phys].astype(jnp.float32)  # [B, Hkv*bs]
            sc = sc * k_s.reshape(b, h_kv, 1, 1, bs)
        sc = sc * inv
        kpos = (j[:, None] * bs + jnp.arange(bs))[:, None, None, None, :]
        mask = kpos < qlen
        if window:
            mask &= kpos >= qlen - window
        sc = jnp.where(mask, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        if quant:
            v_s = v_scale[layer, phys].astype(jnp.float32)
            p = p * v_s.reshape(b, h_kv, 1, 1, bs)
        acc = acc * alpha + jnp.einsum(
            "bkgtc,bckd->bkgtd", p, v_c.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    rows = (b, h_kv, h // h_kv, t)
    carry0 = (
        jnp.full(rows + (1,), _NEG_INF, jnp.float32),
        jnp.zeros(rows + (1,), jnp.float32),
        jnp.zeros(rows + (d,), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        step, carry0, jnp.arange(places, dtype=jnp.int32)
    )
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return jnp.moveaxis(out, 3, 1).reshape(b, t, h, d)


def dense_paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    layer,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    **kw,
) -> jax.Array:
    """Reference single-token decode over a paged cache: q ``[B, H, D]``,
    keys at logical positions >= ``kv_len[b]`` masked out — the T=1 tile
    of ``dense_paged_verify_attention``, as the kernel's decode step is
    the T=1 tile of the verify kernel."""
    return dense_paged_verify_attention(
        q[:, None], k_pool, v_pool, kv_len, block_tables, layer,
        k_scale, v_scale, **kw,
    )[:, 0]


# ------------------------------------------------------------------ kernel


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_k, scale):
    """One (batch row, KV chunk) program: all H heads at once. The query
    keeps its row dimension of size 1 (scores are [H, 1, block_k]): Mosaic
    refuses a batched mat-vec whose left operand has no free dimension
    (``lhs_non_contracting_dims`` empty), so the dots are batched
    [1, D] x [D, block_k] matmuls, as in the verify tile."""
    b_, j = pl.program_id(0), pl.program_id(1)
    n_k = pl.num_programs(1)
    length = len_ref[b_]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Chunks entirely past this row's occupancy contribute nothing (their
    # DMA is clamped to the last live chunk by the index map below).
    @pl.when(j * block_k < length)
    def _step():
        q = q_ref[0]  # (H, 1, D)
        k_blk = k_ref[0]  # (H, Bk, D)
        v_blk = v_ref[0]
        # (H, 1, D) x (H, Bk, D) -> (H, 1, Bk): batch over H, contract D.
        s = lax.dot_general(
            q, k_blk,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        kpos = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < length, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        # (H, 1, Bk) x (H, Bk, D) -> (H, 1, D): batch over H, contract Bk.
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _decode_kernel_quant(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, block_k, scale):
    """Quantized-cache sibling of ``_decode_kernel``: k/v arrive as 1-byte
    payloads with per-(head, position) scales. The chunk dequantizes IN
    VMEM — the payload upcasts in-register for the dot and the scale
    folds into the score strip / probability row afterwards (it factors
    out of the head_dim contraction), so no full-precision cache chunk
    ever round-trips through HBM."""
    b_, j = pl.program_id(0), pl.program_id(1)
    n_k = pl.num_programs(1)
    length = len_ref[b_]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_k < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (H, 1, D)
        k_blk = k_ref[0].astype(jnp.float32)  # (H, Bk, D) — VMEM upcast
        v_blk = v_ref[0].astype(jnp.float32)
        k_s = ks_ref[0][:, None, :]  # (H, 1, Bk) fp32 scales
        v_s = vs_ref[0][:, None, :]
        s = lax.dot_general(
            q, k_blk,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * k_s * scale
        kpos = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < length, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p * v_s, v_blk,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _paged_verify_kernel(len_ref, tbl_ref, layer_ref, q_ref, *refs,
                         block_k, group, q_len, heads, kv_heads, window,
                         scale, quant):
    """THE paged kernel — one program a batch ROW, which walks the row's
    LIVE blocks and nothing else; single-token decode is the T=1 tile (a
    dedicated q_len=1 kernel would be a batched mat-vec whose left
    operand has no free dimension, which Mosaic refuses).

    The walk. The pools stay in HBM, whole (``memory_space=pl.ANY``):
    the kernel reads the blocks of row b under its length ``len_ref[b]``
    — ``window`` 0: from block 0, never more than the table holds;
    otherwise from the first block the tile's window still reaches, the
    table a ring (block j at place ``j % places``) — in steps of
    ``group`` blocks (``_blocks_per_step``), each block copied from where
    it lies, row ``layer_ref[0]`` of the pool, into one of two VMEM
    buffers: step i + 1 is in flight while step i is computed. A step's
    ``group`` blocks are ONE ``[group * bs, Hkv*D]`` tile, so the score
    strip fills whole lanes where a single small block would fill
    ``bs`` of them. A row of length 0 is DEAD: no copy starts, the loop
    runs no step, and its output is zeros. Places of the table that hold
    no block of the walk are never looked at, so the time follows what
    is live and not the table's width.

    Everything is LANE-DENSE: a pool block arrives as it is stored,
    ``(bs, Hkv*D)`` — a token's K row is Hkv*D contiguous values — and
    Mosaic refuses to split that minor dimension into ``(Hkv, D)`` inside
    a kernel. So the heads are separated by a 0/1 mask instead of a
    reshape: row ``t*Hq + i`` of ``qh`` holds query t's head i in the
    lanes of its KV head (``i // (Hq / Hkv)``) and zeros elsewhere, and
    ONE ``[T*Hq, Hkv*D] x [group*bs, Hkv*D]^T`` product gives all heads'
    scores (the zeros take the other heads' lanes out of the
    contraction); ``p @ v`` then gives ``[T*Hq, Hkv*D]``, of which a row
    is wanted in its own KV head's lanes only, and the same mask picks
    those at the end. How the query tile comes in and the output goes out
    is the one thing the geometry decides. ``Hkv == Hq``: each head owns
    its lanes, so a query is one lane-dense row ``[T, H*D]`` broadcast
    over the heads, and the masked rows of a query sum to its output row
    (a head of 64 is half a lane tile, which Mosaic will not slice).
    Heads grouped: several rows share a KV head's lanes, so a head is a
    row, ``[T*Hq, D]``, laid beside itself Hkv times, and the output is
    the sum of the masked rows' lane slices (D is whole lane tiles). The
    causal mask is applied INSIDE the walk — query t of a row at total
    occupancy ``len_ref[b]`` admits keys at logical positions ``< len -
    (T-1) + t`` (for T=1: ``< len``) and, with a window, ``>=`` that less
    ``window``. Running max / denominator / accumulator live in VMEM
    scratch, fp32.

    ``quant``: the pool is 1-byte; a step is one block, upcast in VMEM,
    and the per-(position, head) scales — a block's are ONE lane-dense
    row ``(1, Hkv*bs)``, heads major, which arrives inside its aligned
    group of ``_SCALE_ROWS`` pool rows and is spread to ``[Hq, bs]`` by a
    mask and a 0/1 product for the same reason (a pool whose block count
    is no multiple of the group hands its last rows over as a block of
    their own, ``ks_tail`` / ``vs_tail``: a copy cannot take part of a
    group) — fold into the score strip / probability rows after the
    dots: the same per-chunk dequantize contract as
    ``_decode_kernel_quant``."""
    k_hbm, v_hbm, *refs = refs
    if quant:
        ks_hbm, vs_hbm, ks_tail, vs_tail, *refs = refs
    o_ref, k_buf, v_buf, *refs = refs
    if quant:
        ks_buf, vs_buf, *refs = refs
    sems, qh_ref, m_ref, l_ref, acc_ref = refs
    b_ = pl.program_id(0)
    length, layer = len_ref[b_], layer_ref[0]
    f = qh_ref.shape[1]  # Hkv*D
    hd = f // kv_heads
    shared = heads // kv_heads  # query heads to a KV head
    places = tbl_ref.shape[1]
    span = group * block_k  # positions a step covers
    # The walk takes the row's blocks ``first .. first + n_blocks - 1``;
    # ``place`` is where the table keeps the n-th of them.
    n_blocks = (length + block_k - 1) // block_k
    if window:
        first = jnp.maximum(length - (q_len - 1) - window, 0) // block_k
        n_blocks = n_blocks - first

        def place(n):
            return lax.rem(first + n, places)
    else:
        n_blocks = jnp.minimum(n_blocks, places)

        def place(n):
            return n
    n_steps = (n_blocks + group - 1) // group

    def tile(piece):  # [Hq, ...] per query position -> [T*Hq, ...]
        return jnp.concatenate([piece(t) for t in range(q_len)], axis=0)

    def own_lanes(width):  # [Hq, Hkv*width]: the row's KV head has the lane
        shape = (heads, kv_heads * width)
        head = lax.broadcasted_iota(jnp.int32, shape, 0)
        # (nothing is traced for the uniform stack that it does not need:
        # its program is what it was before the geometry came in)
        lo = (head if shared == 1 else head // shared) * width
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        return (lane >= lo) & (lane < lo + width)

    def head_mask():  # [T*Hq, Hkv*D]
        own = own_lanes(hd)
        return tile(lambda t: own)

    def scale_group(blk):
        """First row of the WHOLE aligned group of scale rows that a copy
        brings for block ``blk``: its own group, or the pool's last whole
        one (the rows after that come in ``ks_tail`` / ``vs_tail``)."""
        whole = (ks_hbm.shape[1] // _SCALE_ROWS - 1) * _SCALE_ROWS
        own = blk // _SCALE_ROWS * _SCALE_ROWS
        return pl.multiple_of(jnp.minimum(own, whole), _SCALE_ROWS)

    def copies(n, slot, at):
        """The copies that bring the n-th block of the walk into place
        ``at`` of buffer ``slot``."""
        blk = tbl_ref[b_, place(n)]
        dst = pl.ds(at * block_k, block_k)
        out = [
            pltpu.make_async_copy(
                k_hbm.at[layer, blk], k_buf.at[slot, dst], sems.at[0, slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, blk], v_buf.at[slot, dst], sems.at[1, slot]
            ),
        ]
        if quant:
            src = pl.ds(scale_group(blk), _SCALE_ROWS)
            out += [
                pltpu.make_async_copy(
                    ks_hbm.at[layer, src], ks_buf.at[slot], sems.at[2, slot]
                ),
                pltpu.make_async_copy(
                    vs_hbm.at[layer, src], vs_buf.at[slot], sems.at[3, slot]
                ),
            ]
        return out

    def transfer(step, slot, arrive):
        """Start (``arrive`` False) or await the copies of ``step``'s
        live blocks into buffer ``slot``."""
        for at in range(group):
            n = step * group + at

            @pl.when(n < n_blocks)
            def _():
                for copy in copies(n, slot, at):
                    copy.wait() if arrive else copy.start()

            if not arrive:
                continue

            # A place of the last step that no live block fills holds
            # whatever the buffer held: its scores are masked, and zeros
            # in V keep 0 x (stale bits) out of the accumulator.
            @pl.when(n >= n_blocks)
            def _():
                v_buf[slot, pl.ds(at * block_k, block_k)] = jnp.zeros(
                    (block_k, f), v_buf.dtype
                )

    def scales(buf, tail_ref, step, slot):  # the block's row -> [T*Hq, bs]
        blk = tbl_ref[b_, place(step)]  # quant: a step is one block
        # The pool's last group of rows (whole or not) is ``tail_ref``.
        tail = (ks_hbm.shape[1] - 1) // _SCALE_ROWS * _SCALE_ROWS
        in_tail = blk >= tail
        mine = blk - jnp.where(in_tail, tail, scale_group(blk))
        got = jnp.where(in_tail, tail_ref[:], buf[slot]).astype(jnp.float32)
        at = lax.broadcasted_iota(jnp.int32, got.shape, 0)
        row = jnp.where(at == mine, got, 0.0).sum(axis=0, keepdims=True)
        row = jnp.where(own_lanes(block_k), row, 0.0)  # (Hq, Hkv*bs)
        x = lax.broadcasted_iota(jnp.int32, (kv_heads * block_k, block_k), 0)
        c = lax.broadcasted_iota(jnp.int32, (kv_heads * block_k, block_k), 1)
        # one nonzero term a sum: exact at any matmul precision (the
        # scales are stored in bf16).
        per_head = jnp.dot(
            row, ((x & (block_k - 1)) == c).astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # [Hq, bs]
        return tile(lambda t: per_head)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32)
    if shared == 1:  # (T, H*D): a query's heads side by side
        q_rows = tile(lambda t: jnp.broadcast_to(q[t:t + 1], (heads, f)))
    else:  # (T*Hq, D): a head a row
        q_rows = jnp.concatenate([q] * kv_heads, axis=1)
    qh_ref[:] = jnp.where(head_mask(), q_rows, 0.0).astype(qh_ref.dtype)
    transfer(0, 0, arrive=False)

    def step_body(i, carry):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_steps)
        def _():
            transfer(i + 1, 1 - slot, arrive=False)

        transfer(i, slot, arrive=True)
        k_blk, v_blk = k_buf[slot], v_buf[slot]  # (span, Hkv*D), as stored
        if quant:
            k_blk = k_blk.astype(jnp.float32)  # VMEM upcast
            v_blk = v_blk.astype(jnp.float32)
        # (T*Hq, Hkv*D) x (span, Hkv*D)^T -> (T*Hq, span)
        s = lax.dot_general(
            qh_ref[:], k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if quant:
            s = s * scales(ks_buf, ks_tail, i, slot)
        kpos = i * span + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        tpos = tile(lambda t: jnp.full((heads, 1), t, jnp.int32))
        qlen = length - (q_len - 1) + tpos  # what query t has before it
        if window:
            kpos = kpos + first * block_k
            seen = (kpos < qlen) & (kpos >= qlen - window)
        else:
            seen = kpos < qlen
        s = jnp.where(seen, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        if quant:
            p = p * scales(vs_buf, vs_tail, i, slot)
        # (T*Hq, span) x (span, Hkv*D) -> (T*Hq, Hkv*D)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return carry

    lax.fori_loop(0, n_steps, step_body, 0)
    own = jnp.where(
        head_mask(), acc_ref[:] / jnp.maximum(l_ref[:], 1e-30), 0.0
    )
    if shared == 1:
        out = jnp.concatenate(
            [
                own[t * heads:(t + 1) * heads].sum(axis=0, keepdims=True)
                for t in range(q_len)
            ],
            axis=0,
        )
    else:
        out = sum(own[:, g * hd:(g + 1) * hd] for g in range(kv_heads))
    o_ref[0] = out.astype(o_ref.dtype)


def _kv_index_map(block_k):
    """Clamp the chunk index to the row's last OCCUPIED chunk: programs
    past the occupancy re-reference the chunk already resident, so no DMA
    fires for dead cache rows (their compute is skipped by ``pl.when``).
    The scalar-prefetch channel is what makes the length visible here,
    before the kernel body runs."""

    def index_map(b_, j, len_ref):
        last = jnp.maximum((len_ref[b_] - 1) // block_k, 0)
        return (b_, 0, jnp.minimum(j, last), 0)

    return index_map


def _kv_scale_index_map(block_k):
    """The scale arrays' ([B, H, S]-layout) twin of ``_kv_index_map``."""

    def index_map(b_, j, len_ref):
        last = jnp.maximum((len_ref[b_] - 1) // block_k, 0)
        return (b_, 0, jnp.minimum(j, last))

    return index_map


def _flash_decode(q, k, v, kv_len, *, block_k, interpret):
    """q ``[B, H, 1, D]``, k/v ``[B, H, S, D]`` (kernel layout), kv_len
    ``[B]`` int32 -> ``[B, H, 1, D]``."""
    b, h, s, d = k.shape
    n_k = s // block_k
    q_spec = pl.BlockSpec((1, h, 1, d), lambda b_, j, len_ref: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, h, block_k, d), _kv_index_map(block_k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1, 1), jnp.float32),  # running max
            pltpu.VMEM((h, 1, 1), jnp.float32),  # running denom
            pltpu.VMEM((h, 1, d), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, block_k=block_k, scale=1.0 / np.sqrt(d)
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="attn_decode",
    )(kv_len, q, k, v)


def _flash_decode_quant(q, k, k_scale, v, v_scale, kv_len, *, block_k,
                        interpret):
    """Quantized-cache split-KV decode: q ``[B, H, 1, D]`` float, k/v
    ``[B, H, S, D]`` 1-byte payloads, scales ``[B, H, S]`` fp32."""
    b, h, s, d = k.shape
    n_k = s // block_k
    q_spec = pl.BlockSpec((1, h, 1, d), lambda b_, j, len_ref: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, h, block_k, d), _kv_index_map(block_k))
    sc_spec = pl.BlockSpec((1, h, block_k), _kv_scale_index_map(block_k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_k),
        in_specs=[q_spec, kv_spec, sc_spec, kv_spec, sc_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1, 1), jnp.float32),  # running max
            pltpu.VMEM((h, 1, 1), jnp.float32),  # running denom
            pltpu.VMEM((h, 1, d), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel_quant, block_k=block_k, scale=1.0 / np.sqrt(d)
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="attn_decode_quant",
    )(kv_len, q, k, k_scale, v, v_scale)


#: A block's scales are ONE row of the ``[L, N, H*bs]`` scale pool, and
#: Mosaic moves rows in aligned groups of eight: the copy fetches the group
#: and the kernel picks its row.
_SCALE_ROWS = 8

#: Lanes of a vector register: the paged kernel takes as many blocks a step
#: as make a score strip one register wide.
_LANES = 128


def _blocks_per_step(block_k, quant):
    """Blocks the paged kernel takes in one step of its walk: as many as
    fill the lanes of a score strip (8 blocks of 16, 2 of 64, 1 of 128 or
    more). A quantized block is spread by its own scale row, so it is a
    step by itself."""
    return 1 if quant else max(1, _LANES // block_k)


# ------------------------------------------------------------------ router


#: Preferred KV chunk, in positions of a 2-byte (or narrower) cache: decode
#: is HBM-bandwidth-bound, so the chunk only has to be big enough to
#: amortize the revolving-buffer DMA; 512 matches the short-T training
#: block. A 4-byte cache takes half as many positions (``_local_decode``):
#: at H=16, D=64 the double-buffered fp32 K and V chunks of 512 positions
#: overflow the v5e's scoped VMEM and Mosaic refuses the kernel. The
#: on-chip ladder is queued (BACKLOG R8-1).
_PREFERRED_BLOCK_K = 512


def _local_decode(q, k, v, kv_len, *, impl, interpret, k_scale=None,
                  v_scale=None):
    """Decode attention on LOCAL (already per-shard) arrays; with
    ``k_scale``/``v_scale`` present the cache is quantized and every
    branch takes its chunk-dequantizing twin."""
    quant = k_scale is not None

    def dense():
        if quant:
            return dense_decode_attention_quant(
                q, k, v, kv_len, k_scale, v_scale
            )
        return dense_decode_attention(q, k, v, kv_len)

    if impl == "dense":
        return dense()
    if impl != "flash":
        raise KeyError(
            f"unknown decode_attention impl {impl!r} (dense | flash)"
        )
    if interpret is None:
        interpret = FORCE_INTERPRET
    s, d = k.shape[1], q.shape[-1]
    pref = _PREFERRED_BLOCK_K * 2 // max(2, k.dtype.itemsize)
    block_k = _pick_block(s, min(pref, s))
    if block_k is None or d % 32 != 0:
        if jax.default_backend() == "tpu":
            _warn_fallback(
                "flash-decode falling back to dense: cache shape "
                f"(S={s}, head_dim={d}) is not tileable (need a "
                "power-of-two divisor of S and head_dim % 32 == 0)"
            )
        return dense()
    if interpret is None:
        if jax.default_backend() != "tpu":
            # Identical numerics, no interpreter slowdown — the same
            # silent off-TPU contract as flash_attention.
            return dense()
        interpret = False
    qT = q[:, :, None, :]  # [B, H, 1, D]
    kT = k.transpose(0, 2, 1, 3)  # [B, H, S, D]
    vT = v.transpose(0, 2, 1, 3)
    lens = jnp.maximum(kv_len.astype(jnp.int32), 1)
    if quant:
        o = _flash_decode_quant(
            qT, kT, k_scale.astype(jnp.float32).transpose(0, 2, 1),
            vT, v_scale.astype(jnp.float32).transpose(0, 2, 1),
            lens, block_k=block_k, interpret=interpret,
        )
    else:
        o = _flash_decode(
            qT, kT, vT, lens, block_k=block_k, interpret=interpret
        )
    return o[:, :, 0, :]


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str = "flash",
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token decode attention over a KV cache — the ONE entry point
    every decode consumer (generate, beam_search, serving/engine.py) routes
    through.

    q ``[B, H, D]``, k/v ``[B, S, H, D]`` (cache layout), ``kv_len [B]``
    int32 occupancy per row. With ``k_scale``/``v_scale`` (``[B, S, H]``,
    both or neither) the cache is QUANTIZED (1-byte k/v payloads,
    ``model.kv_cache_quant``) and every branch dequantizes per chunk —
    module docstring. Under a mesh whose ``model`` axis is live the
    call runs head-sharded via shard_map (GSPMD cannot partition an opaque
    pallas_call, and even the dense path benefits from a pinned layout):
    each shard attends its local heads against its local cache shard —
    zero collectives here; the one psum per block happens where Megatron
    puts it, in the row-sharded ``out`` projection that consumes this
    output. The batch dimension shards over the batch axes exactly when
    the cache constraint does (``_constrain_kv_cache``): the two MUST
    agree, or entering this region would all-gather the cache's batch
    shards — the monolithic reshard the handoff pin forbids. The scale
    arrays shard like the cache (heads over ``model``) for the same
    reason.
    """
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        BATCH_AXES,
        current_mesh_env,
        shard_map_unchecked,
    )

    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "k_scale and v_scale must be passed together (a quantized "
            "cache quantizes both of its halves)"
        )
    env = current_mesh_env()
    m = env.axis_size("model") if env is not None else 1
    h = q.shape[1]
    if env is None or m <= 1 or h % m != 0:
        return _local_decode(
            q, k, v, kv_len, impl=impl, interpret=interpret,
            k_scale=k_scale, v_scale=v_scale,
        )
    batch = BATCH_AXES if q.shape[0] % env.batch_axis_size == 0 else None
    q_spec = P(batch, "model", None)
    kv_spec = P(batch, None, "model", None)
    if k_scale is None:
        fn = shard_map_unchecked(
            functools.partial(_local_decode, impl=impl, interpret=interpret),
            mesh=env.mesh,
            in_specs=(q_spec, kv_spec, kv_spec, P(batch)),
            out_specs=q_spec,
        )
        return fn(q, k, v, kv_len)
    sc_spec = P(batch, None, "model")
    fn = shard_map_unchecked(
        lambda q_, k_, v_, l_, ks_, vs_: _local_decode(
            q_, k_, v_, l_, impl=impl, interpret=interpret,
            k_scale=ks_, v_scale=vs_,
        ),
        mesh=env.mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(batch), sc_spec, sc_spec),
        out_specs=q_spec,
    )
    return fn(q, k, v, kv_len, k_scale, v_scale)


def _flash_paged_verify(q, k_pool, v_pool, kv_len, tables, layer, *,
                        window, interpret, name, k_scale=None, v_scale=None):
    """q ``[B, T, Hq, D]``, stacked pools ``[rows, N, bs, Hkv*D]`` (+
    optional ``[rows, N, Hkv*bs]`` scales), tables ``[B, places]`` int32,
    ``layer`` int32 ``[1]`` -> ``[B, T, Hq, D]``. Grid is the rows; the
    pools are handed over where they lie and the kernel copies a row's live
    blocks itself (``_paged_verify_kernel``, which also says why the query
    tile goes in as ``[T, H*D]`` or, heads grouped, as ``[T*Hq, D]``); the
    scratch accumulators carry the T dim. The kernel serves a decode step
    (T=1) and a verify tile, of the uniform stack and of each layer kind:
    its caller names it (``attn_paged_decode`` / ``attn_paged_verify``,
    ``attn_mixed_decode_<kind>``; the quantized pool's kernel adds
    ``_quant``), and that is what a device trace calls it."""
    b, t, h, d = q.shape
    bs, f = k_pool.shape[2], k_pool.shape[3]
    h_kv = f // d
    quant = k_scale is not None
    group = _blocks_per_step(bs, quant)
    q_tile = (t, f) if h == h_kv else (t * h, d)
    q_spec = pl.BlockSpec((1,) + q_tile, lambda b_, *_refs: (b_, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, group * bs, f), k_pool.dtype)
    pools, bufs = (k_pool, v_pool), [kv_buf, kv_buf]
    in_specs = [q_spec, hbm, hbm]
    if quant:
        sc_buf = pltpu.VMEM((2, _SCALE_ROWS, h_kv * bs), k_scale.dtype)
        tail = pl.BlockSpec(
            (None, _SCALE_ROWS, h_kv * bs),
            lambda b_, len_ref, tbl_ref, layer_ref: (
                layer_ref[0], (k_scale.shape[1] - 1) // _SCALE_ROWS, 0
            ),
        )
        pools += (k_scale, v_scale, k_scale, v_scale)
        bufs += [sc_buf, sc_buf]
        in_specs += [hbm, hbm, tail, tail]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((4 if quant else 2, 2)),  # pool x buffer
            # the query tile spread over heads; fp32 against a 1-byte pool
            pltpu.VMEM((t * h, f), jnp.float32 if quant else q.dtype),
            pltpu.VMEM((t * h, 1), jnp.float32),  # running max
            pltpu.VMEM((t * h, 1), jnp.float32),  # running denom
            pltpu.VMEM((t * h, f), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_verify_kernel, block_k=bs, group=group, q_len=t, heads=h,
            kv_heads=h_kv, window=window, scale=1.0 / np.sqrt(d),
            quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,) + q_tile, q.dtype),
        interpret=interpret,
        name=name + ("_quant" if quant else ""),
    )(kv_len, tables, layer, q.reshape((b,) + q_tile), *pools)
    return out.reshape(b, t, h, d)


def _local_paged_verify(q, k_pool, v_pool, kv_len, tables, layer,
                        k_scale=None, v_scale=None, *, window, impl,
                        interpret, name):
    """Paged tile attention on LOCAL (already per-shard) arrays; the
    paged twin of ``_local_decode`` with the same impl routing and
    fallback contract."""

    def dense():
        return dense_paged_verify_attention(
            q, k_pool, v_pool, kv_len, tables, layer, k_scale, v_scale,
            window=window,
        )

    if impl == "dense":
        return dense()
    if impl != "flash":
        raise KeyError(
            f"unknown decode_attention impl {impl!r} (dense | flash)"
        )
    if interpret is None:
        interpret = FORCE_INTERPRET
    bs, f, (h, d) = k_pool.shape[2], k_pool.shape[3], q.shape[2:]
    # The pool block IS the kernel chunk: it must be a tileable size on
    # its own (the contiguous kernel gets to pick a divisor; a paged
    # kernel cannot re-chunk across physical blocks), and a token's row
    # must fill whole 128-lane tiles — as must a head, where query heads
    # share a KV head's lanes and the output is cut out in lane slices.
    # (A quantized pool's scale rows are copied in groups of _SCALE_ROWS.)
    tileable = bs >= 8 and (bs & (bs - 1)) == 0 and f % _LANES == 0 and (
        h * d == f or d % _LANES == 0
    ) and (k_scale is None or k_pool.shape[1] >= _SCALE_ROWS)
    if not tileable:
        if jax.default_backend() == "tpu":
            _warn_fallback(
                "paged flash-decode falling back to dense: block geometry "
                f"(bs={bs}, kv_heads*head_dim={f}, head_dim={d}) is not "
                "tileable (need a power-of-two block size >= 8, "
                "kv_heads*head_dim % 128 == 0, head_dim % 128 == 0 where "
                f"query heads share a KV head and, quantized, {_SCALE_ROWS} "
                "pool blocks or more)"
            )
        return dense()
    if interpret is None:
        if jax.default_backend() != "tpu":
            # Identical numerics, no interpreter slowdown — the same
            # silent off-TPU contract as flash_attention.
            return dense()
        interpret = False
    return _flash_paged_verify(
        q, k_pool, v_pool, kv_len.astype(jnp.int32),
        tables.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        window=window, interpret=interpret, name=name, k_scale=k_scale,
        v_scale=v_scale,
    )


def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    layer,
    *,
    window: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str = "flash",
    interpret: bool | None = None,
    name: str = "attn_paged_verify",
) -> jax.Array:
    """Attention of a small query TILE over the PAGED (block-pool) KV
    cache — the one entry point of every block-table decode path
    (models/gpt.py ``paged_attend``, the serving engine): the speculative
    verify step with T = k+1, and through ``paged_decode_attention`` every
    plain decode step as its T=1 tile; the uniform stack and the pools of
    each layer kind alike.

    q ``[B, T, Hq, D]`` — T positions per row (last accepted token + T-1
    drafts), whose K/V have already been scattered into the pool at
    logical positions ``kv_len - T .. kv_len - 1``; ``kv_len [B]`` is
    each row's TOTAL occupancy including the tile; ``block_tables
    [B, places]`` int32 maps a logical block of row b to a physical pool
    block. ``kv_len[b] == 0`` says the row is DEAD (a serving slot with
    no request): no block of it is read — whatever its table says — and
    its output is zeros, finite, in the kernel and in the plain twin
    alike. Death is the length alone: the kernel never reads it from the
    table (physical block 0 is a block like any other here; the MODEL
    knows that the engine hands it to no request, models/gpt.py). A live
    row costs the blocks it attends and a dead one an empty grid step,
    whatever the table's width.
    The pools are the model's cache leaves AS THEY ARE STORED: the layers
    that share them stacked, lane-dense, ``[rows, N, bs, Hkv*D]`` (a
    token's K row is Hkv*D contiguous values, heads major), and ``layer``
    (an int32 scalar, traced inside the layer loop) says which row's
    blocks to read — the kernel's copies address the stack directly, so
    no layer's slice of the pool is ever cut out or copied. ``Hkv`` is
    the pool row over D: query head i reads KV head ``i // (Hq / Hkv)``.
    With ``k_scale``/``v_scale`` (``[rows, N, Hkv*bs]``: a block's
    per-(position, head) scales as one row, heads major; both or neither)
    the pool is quantized and every branch dequantizes per block.

    ``window`` 0: every position under the length is attended and table
    place j holds logical block j. ``window`` W (a sliding layer): a query
    attends the W positions up to its own, and the table is a RING —
    logical block j at place ``j % places`` — which has to hold the blocks
    a tile reaches, ``(W + T - 2) // bs + 2`` places
    (models/gpt.py ``window_table_blocks`` for T = 1).

    Causality is per query position inside the tile: query t attends
    logical positions ``< kv_len - T + 1 + t``, so query 0 computes
    exactly what a single-token decode step would and every draft
    position additionally sees the drafts before it — which is what
    makes greedy acceptance exact (token-identity with ``generate()``).

    Sharding: the pool carries NO batch axis — blocks are shared across
    rows (that is the whole point), so under a live ``model`` axis the
    pool shards over KV HEADS only — the major part of its last dimension,
    ``P(None, None, None, 'model')``, the paged analog of the
    ``_constrain_kv_cache`` layout — and is replicated over the batch
    axes, while q / lengths / tables shard over batch when divisible.
    Each shard then attends its local heads of its local rows against
    its full local-head pool — zero collectives here, same as the
    contiguous path."""
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        BATCH_AXES,
        current_mesh_env,
        shard_map_unchecked,
    )

    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "k_scale and v_scale must be passed together (a quantized "
            "pool quantizes both of its halves)"
        )
    (t, h, d), bs, f = q.shape[1:], k_pool.shape[2], k_pool.shape[3]
    if f % d or h % (f // d):
        raise ValueError(
            f"{h} query heads of {d} do not group over a pool row of {f}"
        )
    if window and block_tables.shape[1] < (window + t - 2) // bs + 2:
        raise ValueError(
            f"a ring of {block_tables.shape[1]} blocks of {bs} cannot hold "
            f"what {t} queries with a window of {window} reach"
        )
    local = functools.partial(
        _local_paged_verify, window=window, impl=impl, interpret=interpret,
        name=name,
    )
    args = (q, k_pool, v_pool, kv_len, block_tables,
            jnp.asarray(layer, jnp.int32))
    if k_scale is not None:
        args += (k_scale, v_scale)
    env = current_mesh_env()
    m = env.axis_size("model") if env is not None else 1
    if env is None or m <= 1 or (f // d) % m != 0:
        return local(*args)
    batch = BATCH_AXES if q.shape[0] % env.batch_axis_size == 0 else None
    q_spec = P(batch, None, "model", None)
    # Heads are the major part of every pool leaf's last dimension.
    pool_spec, sc_spec = P(None, None, None, "model"), P(None, None, "model")
    in_specs = (q_spec, pool_spec, pool_spec, P(batch), P(batch, None), P())
    return shard_map_unchecked(
        local,
        mesh=env.mesh,
        in_specs=in_specs + (sc_spec,) * (len(args) - len(in_specs)),
        out_specs=q_spec,
    )(*args)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    layer,
    **kw,
) -> jax.Array:
    """Single-token decode attention over the paged KV cache: q
    ``[B, H, D]`` against every key at a logical position ``< kv_len[b]``
    — the T=1 tile of ``paged_verify_attention`` (same pools, tables,
    layer index, window, quantization, sharding and fallback contract),
    under the kernel name ``attn_paged_decode``."""
    return paged_verify_attention(
        q[:, None], k_pool, v_pool, kv_len, block_tables, layer,
        name="attn_paged_decode", **kw,
    )[:, 0]
