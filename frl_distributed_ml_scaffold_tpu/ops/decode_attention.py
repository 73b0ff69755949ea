"""Pallas TPU flash-decode attention: fused split-KV single-token decode.

The serving-side sibling of ``ops/flash_attention.py``. Training attention
streams K/V blocks under a [T, T] score tile; at decode the query is ONE
token per sequence, so the kernel shape flips: scores are a [H, S] strip
and the win is (i) never materializing the [B, H, S] probability tensor in
HBM and (ii) never *reading* cache rows past the occupied prefix. The
kernel is a split-KV partial-softmax: the cache length S is tiled into
``block_k`` chunks walked by the inner grid dimension (TPU grids iterate
sequentially, so the running max / denominator / accumulator live in VMEM
scratch and the chunk merge is the standard online-softmax log-sum-exp
rescale — numerically the same merge the flash kernel and the ring hops
use).

Length masking is first-class, not an afterthought: the per-row occupancy
``kv_len`` rides the scalar-prefetch channel (``PrefetchScalarGridSpec``),
so it is available to the *index maps* — chunks entirely past a row's
occupancy clamp their DMA to the last live chunk and skip their compute via
``pl.when``. A bucketed cache (serving/engine.py) bounds the worst case;
the length clamp means a request at occupancy 70 in a 512-bucket reads ~70
rows of cache, not 512 and not ``config.seq_len``.

Decode is inference-only, so there is no VJP — the kernel is forward-only,
which also keeps the router trivially compatible with ``lax.scan`` decode
loops.

Layout: public API is cache layout — q ``[B, H, D]`` (the single token's
heads), k/v ``[B, S, H, D]`` (exactly how models/gpt.py stores the cache),
``kv_len [B]`` int32. The kernel internally runs ``[B, H, S, D]`` like its
training sibling.

On non-TPU backends the kernel runs under the Pallas interpreter when
``interpret=True`` is forced (tests); the default off-TPU path is the
identical-numerics ``dense_decode_attention`` — the same silent-fallback
contract as ``flash_attention`` / ``fused_bn``.

Quantized KV cache (``model.kv_cache_quant``, ROADMAP item 5): decode is
HBM-bandwidth-bound and the cache is what it reads, so K/V may arrive
here quantized — 1-byte elements (int8 / fp8, ops/quantization.py) plus
per-(row, position, head) scales. The kernel dequantizes PER SPLIT-KV
CHUNK in VMEM: the int8 chunk is upcast in-register and the scale folds
into the score strip after the dot (scale-per-position factors out of
the contraction over head_dim), so the full-precision cache never exists
in HBM — not at ``[B, S, H, D]``, not per step. The dense fallback keeps
the same property by streaming bounded chunks through an online-softmax
``lax.scan`` (``dense_decode_attention_quant``); graft-lint pins that no
wide-dtype cache-shaped intermediate materializes in a quantized decode
step.

Paged KV cache (ISSUE 10, ROADMAP item 1): the serving engine stores K/V
in a fixed POOL of fixed-size blocks shared by every slot, with a per-row
block table ``[B, M]`` mapping each row's logical block j to a physical
pool block (serving/engine.py owns allocation, refcounts, and
shared-prefix reuse). The pool is ONE leaf for all layers and LANE-DENSE,
``[L, N, bs, H*D]``: a token's K row is H*D contiguous values, so the
device keeps the leaf row-major and the kernel reads it where it lies
(models/gpt.py ``paged_cache_leaves`` has the why). ``paged_decode_attention``
takes the split-KV merge through the SAME scalar-prefetch channel: the
block table and the layer index ride it next to the per-row lengths, and
the kernel — one program a row, the pools left in HBM — copies block
``table[b, j]`` of the layer for the ``ceil(kv_len[b] / bs)`` blocks
under the row's length and for no other, a lane tile's worth a step,
the next step's copies in flight under this step's products. A row of
length 0 is dead: nothing is read and its output is zeros. So a step
costs what is live, not the table's width (ISSUE 32).
Nothing is ever gathered into a contiguous logical view and no layer's
slice of the pool is cut out: the dense fallback streams bounded
``[B, bs, H*D]`` chunks (one gather per table column) through the same
online-softmax ``lax.scan``, so no full-``seq_len`` array — and no
pool-sized copy — materializes per step (graft-lint's paged decode
program pins both in the jaxpr, tests/test_chip_compile.py in the
compiled HLO).

Speculative verify tile (ISSUE 11): speculative decoding proposes k
draft tokens per row and the TARGET model scores all k+1 positions in
one batched forward — the whole point is that the pool read (the
bandwidth bill decode pays) is amortized over k+1 query positions
instead of one. ``paged_verify_attention`` runs THE paged kernel over a
small q TILE ``[B, T, H, D]`` (single-token paged decode is its T=1
tile) with causal masking
inside the chunk loop: query position t of a row whose total occupancy
(tile included) is ``kv_len`` attends logical positions
``< kv_len - T + 1 + t`` — position 0 sees exactly what a single-token
decode step would, each later draft position additionally sees the
drafts before it. Same scalar-prefetch block-table gather, same
online-softmax merge, same streamed-bounded-chunk dense fallback
(``dense_paged_verify_attention``) — contract-identical off-TPU.
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
) -> jax.Array:
    """Reference attention over a PAGED cache, for a small query tile
    (ISSUE 11): q ``[B, T, H, D]`` — the row's last accepted token plus
    T-1 draft tokens, whose K/V have already been written into the pool
    at logical positions ``kv_len - T .. kv_len - 1`` — against layer
    ``layer`` of the stacked lane-dense pools ``[L, N, bs, H*D]``,
    addressed through per-row block tables ``[B, M]`` (row b's logical
    positions ``[j*bs, (j+1)*bs)`` live in pool block
    ``block_tables[b, j]``). CAUSAL inside the tile: query t attends
    logical positions ``< kv_len - T + 1 + t``, so position 0 scores
    exactly like a single-token decode step and each draft position
    additionally sees the drafts before it; a row with ``kv_len`` 0 sees
    nothing and reads zeros. With ``k_scale``/``v_scale``
    (``[L, N, H*bs]``: a block's scales are one row, heads major) the
    pool is quantized and the scales fold into the score strip /
    probability rows per chunk.

    Deliberately NOT "gather the logical cache, call the contiguous
    reference": that materializes an ``M*bs >= seq_len``-wide tensor
    every decode step — exactly the full-context array the block pool
    exists to avoid (and the graft-lint mutation gate for the paged
    program). Instead the table columns stream through an online-softmax
    ``lax.scan``: each iteration gathers ONE bounded ``[B, bs, H*D]``
    block per row straight out of the stack (``pool[layer, phys]`` —
    gather at the boundary, the arXiv 2112.01075 discipline; the layer's
    slice of the pool is never cut out) and merges with the standard
    log-sum-exp rescale; the tile only widens the score strip to
    ``[B, H, T, bs]``. fp32 softmax throughout (the decode numerics
    contract)."""
    bs = k_pool.shape[2]
    b, t, h, d = q.shape
    quant = k_scale is not None
    q32 = q.astype(jnp.float32)
    inv = 1.0 / np.sqrt(d)
    layer = jnp.asarray(layer, jnp.int32)
    cols = block_tables.astype(jnp.int32).T  # [M, B] physical ids per step
    # Per-(row, query) occupancy: query t of row b covers base[b] + t.
    base = kv_len.astype(jnp.int32) - (t - 1)  # length at query 0
    qlen = base[:, None] + jnp.arange(t)[None, :]  # [B, T]

    def step(carry, phys):
        m, l, acc, j = carry
        # [B, bs, H*D] -> [B, bs, H, D]: bounded, and a free reshape.
        k_c = k_pool[layer, phys].reshape(b, bs, h, d)
        v_c = v_pool[layer, phys].reshape(b, bs, h, d)
        sc = jnp.einsum(
            "bthd,bchd->bhtc", q32, k_c.astype(jnp.float32)
        )  # [B, H, T, bs]
        if quant:
            k_s = k_scale[layer, phys].astype(jnp.float32)  # [B, H*bs]
            sc = sc * k_s.reshape(b, h, 1, bs)
        sc = sc * inv
        kpos = j * bs + jnp.arange(bs)
        mask = kpos[None, None, None, :] < qlen[:, None, :, None]
        sc = jnp.where(mask, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        if quant:
            v_s = v_scale[layer, phys].astype(jnp.float32)
            p = p * v_s.reshape(b, h, 1, bs)
        acc = acc * alpha + jnp.einsum(
            "bhtc,bchd->bhtd", p, v_c.astype(jnp.float32)
        )
        return (m_new, l, acc, j + 1), None

    carry0 = (
        jnp.full((b, h, t, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, t, 1), jnp.float32),
        jnp.zeros((b, h, t, d), jnp.float32),
        jnp.int32(0),
    )
    (m, l, acc, _), _ = jax.lax.scan(step, carry0, cols)
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)  # [B, H, T, D]
    return jnp.swapaxes(out, 1, 2)  # [B, T, H, D]


def dense_paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    layer,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Reference single-token decode over a paged cache: q ``[B, H, D]``,
    keys at logical positions >= ``kv_len[b]`` masked out — the T=1 tile
    of ``dense_paged_verify_attention``, as the kernel's decode step is
    the T=1 tile of the verify kernel."""
    return dense_paged_verify_attention(
        q[:, None], k_pool, v_pool, kv_len, block_tables, layer,
        k_scale, v_scale,
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
                         block_k, group, q_len, heads, scale, quant):
    """THE paged kernel — one program a batch ROW, which walks the row's
    LIVE blocks and nothing else; single-token decode is the T=1 tile (a
    dedicated q_len=1 kernel would be a batched mat-vec whose left
    operand has no free dimension, which Mosaic refuses).

    The walk. The pools stay in HBM, whole (``memory_space=pl.ANY``):
    the kernel reads ``ceil(len_ref[b] / bs)`` blocks of row b — never
    more than the table holds — in steps of ``group`` blocks
    (``_blocks_per_step``), each block copied from where it lies, block
    ``tbl_ref[b, j]`` of layer ``layer_ref[0]``, into one of two VMEM
    buffers: step i + 1 is in flight while step i is computed. A step's
    ``group`` blocks are ONE ``[group * bs, H*D]`` tile, so the score
    strip fills whole lanes where a single small block would fill
    ``bs`` of them. A row of length 0 is DEAD: no copy starts, the loop
    runs no step, and its output is zeros. Places of the table past the
    row's length are never looked at, so the time follows what is live
    and not the table's width.

    Everything is LANE-DENSE: a pool block arrives as it is stored,
    ``(bs, H*D)`` — a token's K row is H*D contiguous values — and Mosaic
    refuses to split that minor dimension into ``(H, D)`` inside a
    kernel. So the heads are separated by a 0/1 mask instead of a
    reshape: row ``t*H + h`` of ``qh`` holds query t with every lane
    outside head h zeroed, and ONE ``[T*H, H*D] x [group*bs, H*D]^T``
    product gives all heads' scores (the zeros take the other heads'
    lanes out of the contraction); ``p @ v`` then gives ``[T*H, H*D]``,
    of which row ``t*H + h`` is wanted in head h's lanes only, and the
    same mask picks those at the end. The causal mask is applied INSIDE
    the walk — query t of a row at total occupancy ``len_ref[b]`` admits
    keys at logical positions ``< len - (T-1) + t`` (for T=1: ``< len``).
    Running max / denominator / accumulator live in VMEM scratch, fp32.

    ``quant``: the pool is 1-byte; a step is one block, upcast in VMEM,
    and the per-(position, head) scales — a block's are ONE lane-dense
    row ``(1, H*bs)``, heads major, which arrives inside its aligned
    group of ``_SCALE_ROWS`` pool rows and is spread to ``[H, bs]`` by a
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
    f = qh_ref.shape[1]  # H*D
    hd = f // heads
    span = group * block_k  # positions a step covers
    n_blocks = jnp.minimum(
        (length + block_k - 1) // block_k, tbl_ref.shape[1]
    )
    n_steps = (n_blocks + group - 1) // group

    def tile(piece):  # [H, ...] per query position -> [T*H, ...]
        return jnp.concatenate([piece(t) for t in range(q_len)], axis=0)

    def own_lanes(width):  # [H, H*width]: lane belongs to the row's head
        lo = lax.broadcasted_iota(jnp.int32, (heads, heads * width), 0) * width
        lane = lax.broadcasted_iota(jnp.int32, (heads, heads * width), 1)
        return (lane >= lo) & (lane < lo + width)

    def head_mask():  # [T*H, H*D]
        own = own_lanes(hd)
        return tile(lambda t: own)

    def scale_group(blk):
        """First row of the WHOLE aligned group of scale rows that a copy
        brings for block ``blk``: its own group, or the pool's last whole
        one (the rows after that come in ``ks_tail`` / ``vs_tail``)."""
        whole = (ks_hbm.shape[1] // _SCALE_ROWS - 1) * _SCALE_ROWS
        own = blk // _SCALE_ROWS * _SCALE_ROWS
        return pl.multiple_of(jnp.minimum(own, whole), _SCALE_ROWS)

    def copies(j, slot, at):
        """The copies that bring logical block j of the row into place
        ``at`` of buffer ``slot``."""
        blk = tbl_ref[b_, j]
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
            j = step * group + at

            @pl.when(j < n_blocks)
            def _():
                for copy in copies(j, slot, at):
                    copy.wait() if arrive else copy.start()

            if not arrive:
                continue

            # A place of the last step that no live block fills holds
            # whatever the buffer held: its scores are masked, and zeros
            # in V keep 0 x (stale bits) out of the accumulator.
            @pl.when(j >= n_blocks)
            def _():
                v_buf[slot, pl.ds(at * block_k, block_k)] = jnp.zeros(
                    (block_k, f), v_buf.dtype
                )

    def scales(buf, tail_ref, step, slot):  # the block's row -> [T*H, bs]
        blk = tbl_ref[b_, step]  # quant: a step is one block
        # The pool's last group of rows (whole or not) is ``tail_ref``.
        tail = (ks_hbm.shape[1] - 1) // _SCALE_ROWS * _SCALE_ROWS
        in_tail = blk >= tail
        mine = blk - jnp.where(in_tail, tail, scale_group(blk))
        got = jnp.where(in_tail, tail_ref[:], buf[slot]).astype(jnp.float32)
        at = lax.broadcasted_iota(jnp.int32, got.shape, 0)
        row = jnp.where(at == mine, got, 0.0).sum(axis=0, keepdims=True)
        row = jnp.where(own_lanes(block_k), row, 0.0)  # (H, H*bs)
        x = lax.broadcasted_iota(jnp.int32, (heads * block_k, block_k), 0)
        c = lax.broadcasted_iota(jnp.int32, (heads * block_k, block_k), 1)
        # one nonzero term a sum: exact at any matmul precision (the
        # scales are stored in bf16).
        per_head = jnp.dot(
            row, ((x & (block_k - 1)) == c).astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # [H, bs]
        return tile(lambda t: per_head)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32)  # (T, H*D)
    q_rows = tile(lambda t: jnp.broadcast_to(q[t:t + 1], (heads, f)))
    qh_ref[:] = jnp.where(head_mask(), q_rows, 0.0).astype(qh_ref.dtype)
    transfer(0, 0, arrive=False)

    def step_body(i, carry):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_steps)
        def _():
            transfer(i + 1, 1 - slot, arrive=False)

        transfer(i, slot, arrive=True)
        k_blk, v_blk = k_buf[slot], v_buf[slot]  # (span, H*D), as stored
        if quant:
            k_blk = k_blk.astype(jnp.float32)  # VMEM upcast
            v_blk = v_blk.astype(jnp.float32)
        # (T*H, H*D) x (span, H*D)^T -> (T*H, span)
        s = lax.dot_general(
            qh_ref[:], k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if quant:
            s = s * scales(ks_buf, ks_tail, i, slot)
        kpos = i * span + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        tpos = tile(lambda t: jnp.full((heads, 1), t, jnp.int32))
        seen = kpos < length - (q_len - 1) + tpos
        s = jnp.where(seen, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        if quant:
            p = p * scales(vs_buf, vs_tail, i, slot)
        # (T*H, span) x (span, H*D) -> (T*H, H*D)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return carry

    lax.fori_loop(0, n_steps, step_body, 0)
    own = jnp.where(
        head_mask(), acc_ref[:] / jnp.maximum(l_ref[:], 1e-30), 0.0
    )
    o_ref[0] = jnp.concatenate(
        [
            own[t * heads:(t + 1) * heads].sum(axis=0, keepdims=True)
            for t in range(q_len)
        ],
        axis=0,
    ).astype(o_ref.dtype)


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
                        interpret, name, k_scale=None, v_scale=None):
    """q ``[B, T, H, D]``, stacked pools ``[L, N, bs, H*D]`` (+ optional
    ``[L, N, H*bs]`` scales), tables ``[B, M]`` int32, ``layer`` int32
    ``[1]`` -> ``[B, T, H, D]``. Grid is the rows; the pools are handed
    over where they lie and the kernel copies a row's live blocks itself
    (``_paged_verify_kernel``); the scratch accumulators carry the T dim.
    The kernel serves a decode step (T=1) and a verify tile: its caller
    names it (``attn_paged_decode`` / ``attn_paged_verify``; the
    quantized pool's kernel adds ``_quant``), and that is what a device
    trace calls it."""
    b, t, h, d = q.shape
    bs, f = k_pool.shape[2], h * d
    quant = k_scale is not None
    group = _blocks_per_step(bs, quant)
    q_spec = pl.BlockSpec((1, t, f), lambda b_, *_refs: (b_, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, group * bs, f), k_pool.dtype)
    pools, bufs = (k_pool, v_pool), [kv_buf, kv_buf]
    in_specs = [q_spec, hbm, hbm]
    if quant:
        sc_buf = pltpu.VMEM((2, _SCALE_ROWS, h * bs), k_scale.dtype)
        tail = pl.BlockSpec(
            (None, _SCALE_ROWS, h * bs),
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
            scale=1.0 / np.sqrt(d), quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, f), q.dtype),
        interpret=interpret,
        name=name + ("_quant" if quant else ""),
    )(kv_len, tables, layer, q.reshape(b, t, f), *pools)
    return out.reshape(b, t, h, d)


def _local_paged_verify(q, k_pool, v_pool, kv_len, tables, layer,
                        k_scale=None, v_scale=None, *, impl, interpret,
                        name):
    """Paged tile attention on LOCAL (already per-shard) arrays; the
    paged twin of ``_local_decode`` with the same impl routing and
    fallback contract."""

    def dense():
        return dense_paged_verify_attention(
            q, k_pool, v_pool, kv_len, tables, layer, k_scale, v_scale
        )

    if impl == "dense":
        return dense()
    if impl != "flash":
        raise KeyError(
            f"unknown decode_attention impl {impl!r} (dense | flash)"
        )
    if interpret is None:
        interpret = FORCE_INTERPRET
    bs, f = k_pool.shape[2], k_pool.shape[3]
    # The pool block IS the kernel chunk: it must be a tileable size on
    # its own (the contiguous kernel gets to pick a divisor; a paged
    # kernel cannot re-chunk across physical blocks), and a token's row
    # must fill whole 128-lane tiles.
    # (A quantized pool's scale rows are copied in groups of _SCALE_ROWS.)
    tileable = bs >= 8 and (bs & (bs - 1)) == 0 and f % 128 == 0 and (
        k_scale is None or k_pool.shape[1] >= _SCALE_ROWS
    )
    if not tileable:
        if jax.default_backend() == "tpu":
            _warn_fallback(
                "paged flash-decode falling back to dense: block geometry "
                f"(bs={bs}, heads*head_dim={f}) is not tileable (need a "
                "power-of-two block size >= 8, heads*head_dim % 128 == 0 "
                f"and, quantized, {_SCALE_ROWS} pool blocks or more)"
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
        interpret=interpret, name=name, k_scale=k_scale, v_scale=v_scale,
    )


def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    layer,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str = "flash",
    interpret: bool | None = None,
    name: str = "attn_paged_verify",
) -> jax.Array:
    """Attention of a small query TILE over the PAGED (block-pool) KV
    cache — the one entry point the block-table decode path (models/gpt.py
    paged branch, serving engine) routes through: the speculative verify
    step (ISSUE 11) with T = k+1, and through ``paged_decode_attention``
    every plain decode step as its T=1 tile.

    q ``[B, T, H, D]`` — T positions per row (last accepted token + T-1
    drafts), whose K/V have already been scattered into the pool at
    logical positions ``kv_len - T .. kv_len - 1``; ``kv_len [B]`` is
    each row's TOTAL occupancy including the tile; ``block_tables
    [B, M]`` int32 maps logical block j of row b to a physical pool
    block. ``kv_len[b] == 0`` says the row is DEAD (a serving slot with
    no request): no block of it is read — whatever its table says — and
    its output is zeros, finite, in the kernel and in the plain twin
    alike. Death is the length alone: the kernel never reads it from the
    table (physical block 0 is a block like any other here; the MODEL
    knows that the engine hands it to no request, models/gpt.py). A live
    row costs its ``ceil(kv_len / bs)`` blocks and a dead one an empty
    grid step, whatever the table's width.
    The pools are the model's cache leaves AS THEY ARE STORED:
    all layers stacked, lane-dense, ``[L, N, bs, H*D]`` (a token's K row
    is H*D contiguous values, heads major), and ``layer`` (an int32
    scalar, traced inside the layer loop) says which layer's blocks to
    read — the kernel's copies address the stack directly, so no
    layer's slice of the pool is ever cut out or copied. With
    ``k_scale``/``v_scale`` (``[L, N, H*bs]``: a block's per-(position,
    head) scales as one row, heads major; both or neither) the pool is
    quantized and every branch dequantizes per block.

    Causality is per query position inside the tile: query t attends
    logical positions ``< kv_len - T + 1 + t``, so query 0 computes
    exactly what a single-token decode step would and every draft
    position additionally sees the drafts before it — which is what
    makes greedy acceptance exact (token-identity with ``generate()``).

    Sharding: the pool carries NO batch axis — blocks are shared across
    rows (that is the whole point), so under a live ``model`` axis the
    pool shards over HEADS only — the major part of its last dimension,
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
    local = functools.partial(
        _local_paged_verify, impl=impl, interpret=interpret, name=name
    )
    args = (q, k_pool, v_pool, kv_len, block_tables,
            jnp.asarray(layer, jnp.int32))
    if k_scale is not None:
        args += (k_scale, v_scale)
    env = current_mesh_env()
    m = env.axis_size("model") if env is not None else 1
    if env is None or m <= 1 or q.shape[2] % m != 0:
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
    layer index, quantization, sharding and fallback contract), under
    the kernel name ``attn_paged_decode``."""
    return paged_verify_attention(
        q[:, None], k_pool, v_pool, kv_len, block_tables, layer,
        name="attn_paged_decode", **kw,
    )[:, 0]


# ------------------------------------------- pools by layer kind (grouped)
#
# A model with ``layer_types`` (models/gpt.py) keeps a pool for each layer
# kind. Its decode step differs from the uniform stack's in three ways: the
# query heads of a layer are GROUPED over fewer KV heads (a pool row is
# ``Hkv*D`` lanes, a query tile ``[Hq, D]``); a sliding layer attends the
# last ``window`` positions only; and a sliding layer's block table is a
# RING of ``ceil(window / bs) + 1`` places (logical block j at place
# ``j % places``), so the grid covers the window's blocks whatever the
# context. Single-token steps only.


def _ring_block(jj, last, places):
    """The logical block that sits at ring place ``jj`` when the newest
    block is ``last``: the one in ``(last - places, last]`` congruent to
    ``jj`` (negative: the place is empty)."""
    return last - lax.rem(last - jj + places, places)


def dense_paged_grouped_decode_attention(
    q, k_pool, v_pool, kv_len, block_tables, row, *, window: int = 0
):
    """Reference for ``paged_grouped_decode_attention``: q ``[B, Hq, D]``
    against row ``row`` of a kind's pools ``[Lk, N, bs, Hkv*D]`` through
    that kind's tables ``[B, M]``, keys at positions ``kv_len - window <=
    j < kv_len`` (``window`` 0: every ``j < kv_len``; a windowed table is a
    ring). Streams one bounded block a table place through an online
    softmax, like ``dense_paged_verify_attention``."""
    bs, places = k_pool.shape[2], block_tables.shape[1]
    b, hq, d = q.shape
    hkv = k_pool.shape[3] // d
    g = hq // hkv
    qg = q.astype(jnp.float32).reshape(b, hkv, g, d)
    inv = 1.0 / np.sqrt(d)
    length = kv_len.astype(jnp.int32)
    last = jnp.maximum(length - 1, 0) // bs
    first_pos = jnp.maximum(length - window, 0) if window else jnp.zeros_like(length)

    def step(carry, xs):
        m, l, acc = carry
        jj, phys = xs
        j = _ring_block(jj, last, places) if window else jnp.full_like(last, jj)
        k_c = k_pool[row, phys].reshape(b, bs, hkv, d).astype(jnp.float32)
        v_c = v_pool[row, phys].reshape(b, bs, hkv, d).astype(jnp.float32)
        sc = jnp.einsum("bkgd,bckd->bkgc", qg, k_c) * inv  # [B, Hkv, G, bs]
        kpos = j[:, None] * bs + jnp.arange(bs)[None, :]  # [B, bs]
        mask = (kpos < length[:, None]) & (kpos >= first_pos[:, None]) & (
            j[:, None] >= 0)
        mask = mask[:, None, None, :]
        sc = jnp.where(mask, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgc,bckd->bkgd", p, v_c)
        return (m_new, l, acc), None

    carry0 = (
        jnp.full((b, hkv, g, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, 1), jnp.float32),
        jnp.zeros((b, hkv, g, d), jnp.float32),
    )
    cols = block_tables.astype(jnp.int32).T  # [M, B]
    (m, l, acc), _ = lax.scan(
        step, carry0, (jnp.arange(places, dtype=jnp.int32), cols))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype).reshape(b, hq, d)


def _paged_grouped_kernel(len_ref, tbl_ref, row_ref, q_ref, k_ref, v_ref,
                          o_ref, qh_ref, m_ref, l_ref, acc_ref, *, block_k,
                          heads, kv_heads, head_dim, scale, window):
    """One (slot row, table place) program. Lane-dense like
    ``_paged_verify_kernel``: a pool block arrives as stored, ``(bs,
    Hkv*D)``, and the query heads are spread over the lanes of their KV
    heads by a 0/1 mask — row i of ``qh`` holds query head i in the lanes of
    KV head ``i // (Hq / Hkv)`` and zeros elsewhere — so ONE ``[Hq, Hkv*D]
    x [bs, Hkv*D]^T`` product gives every head's scores, and ``p @ v_blk``
    gives ``[Hq, Hkv*D]`` of which row i is wanted in its own KV head's
    lanes. With a window, place ``jj`` holds the ring's logical block
    ``_ring_block`` and positions behind ``len - window`` are masked."""
    b_, jj = pl.program_id(0), pl.program_id(1)
    places = pl.num_programs(1)
    length = len_ref[b_]
    last = jnp.maximum(length - 1, 0) // block_k
    j = _ring_block(jj, last, places) if window else jj
    first_pos = jnp.maximum(length - window, 0) if window else 0
    group = heads // kv_heads

    def own(g):  # [Hq, 1]: the query head reads KV head g
        at = lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
        return (at >= g * group) & (at < (g + 1) * group)

    @pl.when(jj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        q = q_ref[0]  # (Hq, D)
        qh_ref[:] = jnp.concatenate(
            [jnp.where(own(g), q, jnp.zeros_like(q)) for g in range(kv_heads)],
            axis=1,
        )

    @pl.when((j >= 0) & (j * block_k < length)
             & ((j + 1) * block_k > first_pos))
    def _step():
        k_blk, v_blk = k_ref[0], v_ref[0]  # (Bk, Hkv*D), as stored
        s = lax.dot_general(
            qh_ref[:], k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (Hq, Bk)
        kpos = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kpos < length) & (kpos >= first_pos), s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)

    @pl.when(jj == places - 1)
    def _finish():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)  # (Hq, Hkv*D)
        mine = jnp.zeros((heads, head_dim), jnp.float32)
        for g in range(kv_heads):
            mine = mine + jnp.where(
                own(g), out[:, g * head_dim:(g + 1) * head_dim], 0.0)
        o_ref[0] = mine.astype(o_ref.dtype)


def _flash_paged_grouped(q, k_pool, v_pool, kv_len, tables, row, *, window,
                         interpret, name):
    b, hq, d = q.shape
    bs, f = k_pool.shape[2], k_pool.shape[3]
    places = tables.shape[1]

    def block_at(b_, jj, len_ref, tbl_ref, row_ref):
        last = jnp.maximum(len_ref[b_] - 1, 0) // bs
        if window:
            # An empty place (or one wholly behind the window) re-references
            # the newest block: its body is skipped.
            j = _ring_block(jj, last, places)
            live = (j >= 0) & ((j + 1) * bs > len_ref[b_] - window)
            place = jnp.where(live, jj, lax.rem(last, places))
        else:
            place = jnp.minimum(jj, last)
        return (row_ref[0], tbl_ref[b_, place], 0, 0)

    q_spec = pl.BlockSpec((1, hq, d), lambda b_, jj, *_refs: (b_, 0, 0))
    kv_spec = pl.BlockSpec((None, 1, bs, f), block_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, places),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hq, f), q.dtype),  # the query heads spread over lanes
            pltpu.VMEM((hq, 1), jnp.float32),  # running max
            pltpu.VMEM((hq, 1), jnp.float32),  # running denom
            pltpu.VMEM((hq, f), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_grouped_kernel, block_k=bs, heads=hq, kv_heads=f // d,
            head_dim=d, scale=1.0 / np.sqrt(d), window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=interpret,
        name=name,
    )(kv_len, tables, row, q, k_pool, v_pool)


def paged_grouped_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    kv_len: jax.Array,
    block_tables: jax.Array,
    row: int,
    *,
    window: int = 0,
    impl: str = "flash",
    interpret: bool | None = None,
    name: str = "attn_mixed_decode",
) -> jax.Array:
    """Single-token decode attention over the pools of ONE LAYER KIND: q
    ``[B, Hq, D]`` (the step's K/V already written at position ``kv_len -
    1``) against row ``row`` of the kind's lane-dense pools ``[Lk, N, bs,
    Hkv*D]`` through the kind's block tables ``[B, M]``; query head i reads
    KV head ``i // (Hq / Hkv)``. ``window`` 0: a full layer, every position
    ``< kv_len``, table place j holds logical block j. ``window`` W: a
    sliding layer, positions ``kv_len - W <= j < kv_len``, the table a ring
    of ``M = ceil(W / bs) + 1`` places — the kernel's grid is those places,
    so a step costs a window's blocks whatever the context. Same impl
    routing and fallback contract as ``paged_verify_attention`` (one chip:
    a mesh with a live ``model`` axis is refused by the engine for such a
    model)."""
    def dense():
        return dense_paged_grouped_decode_attention(
            q, k_pool, v_pool, kv_len, block_tables, row, window=window)

    if impl == "dense":
        return dense()
    if impl != "flash":
        raise KeyError(
            f"unknown decode_attention impl {impl!r} (dense | flash)"
        )
    if interpret is None:
        interpret = FORCE_INTERPRET
    bs, f, d = k_pool.shape[2], k_pool.shape[3], q.shape[-1]
    # The lane slices that pick a KV head's output are whole lane tiles
    # only when a head is one or more tiles wide.
    tileable = bs >= 8 and (bs & (bs - 1)) == 0 and d % 128 == 0
    if not tileable and not interpret:
        if jax.default_backend() == "tpu":
            _warn_fallback(
                "grouped paged decode falling back to dense: geometry "
                f"(bs={bs}, head_dim={d}) is not tileable (need a "
                "power-of-two block size >= 8 and head_dim % 128 == 0)"
            )
        return dense()
    if interpret is None:
        if jax.default_backend() != "tpu":
            return dense()
        interpret = False
    return _flash_paged_grouped(
        q, k_pool, v_pool, jnp.maximum(kv_len.astype(jnp.int32), 1),
        block_tables.astype(jnp.int32), jnp.asarray([row], jnp.int32),
        window=window, interpret=interpret, name=name,
    )
