"""Pallas TPU flash attention: fused blockwise causal attention kernel.

SURVEY §7 names the custom-kernel tier as the framework's "native" layer on
TPU (the CUDA-kernel equivalent). This is that tier's centerpiece: a
flash-attention forward + backward written directly against the Mosaic/TPU
pipeline via ``pl.pallas_call``:

- **Forward**: online-softmax with K/V streamed block-by-block through an
  inner grid dimension — VMEM residency is O(block·D), independent of T, so
  context length is bounded by HBM, not VMEM. The per-row logsumexp (a
  lane-1 (B, H, T, 1) array — the only extra HBM traffic) is saved for the
  backward. Running max/denominator/accumulator live in VMEM scratch that
  persists across the inner grid steps (TPU grids iterate sequentially).
- **Backward**: custom VJP with two kernels — one producing dQ (inner grid
  over K/V blocks), one producing dK/dV (inner grid over Q/dO blocks) — the
  flash-attention-2 split so each output block has a single writer. The row
  term ``delta = rowsum(dO·O)`` is computed in-VMEM from tiles already
  resident instead of being broadcast through HBM.
- **Causality**: blocks strictly above the diagonal skip their compute via
  ``pl.when`` (the MXU work — the dominant cost — is elided; only the
  block DMA is not).

Layout: kernels run in (B, H, T, D) — Mosaic requires the (sublane, lane)
pair to be the (T-block, D) tile — with the public API staying (B, T, H, D);
the wrapper's transposes fuse into the surrounding projection matmuls. All
matmuls run bf16-multiply/fp32-accumulate (``preferred_element_type``),
softmax math in fp32 — the same numerics contract as ``dense_attention``,
which the tests assert equivalence against.

On non-TPU backends the kernels run in Pallas interpreter mode so the CPU
test suite exercises the exact same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1.0e30


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


_warned: set[str] = set()


def _warn_fallback(msg: str) -> None:
    """Log each distinct fallback reason once — silent perf cliffs are the
    review-flagged failure mode; a log line per step would be the other."""
    if msg not in _warned:
        _warned.add(msg)
        from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

        get_logger().warning(msg)


#: T at and above which the auto block size steps up to 1024x1024
#: (tools/flash_sweep.py on-chip ladder, 2026-07-30: +21%/+37%/+39% over
#: 512x512 at T=16k/32k/64k).
_LONG_T_BLOCKS = 16384


def _pick_block(t: int, preferred: int) -> int | None:
    """Largest power-of-two block <= preferred that divides t.

    Only power-of-two candidates: anything else risks a sublane-misaligned
    tile that Mosaic rejects at compile time — untileable T falls back to
    dense attention instead.
    """
    for b in (1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= preferred and t % b == 0:
            return b
    return None


def _causal_mask(s, i, j, block_q, block_k):
    qpos = i * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, _NEG_INF)


def _dot(a, b, *, trans_b=False, trans_a=False):
    """MXU matmul, fp32 accumulate."""
    dims = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)), ((), ()))
    return lax.dot_general(a, b, dimension_numbers=dims,
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------- fwd


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, block_q, block_k, causal, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Blocks strictly above the causal diagonal contribute nothing.
    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, 0, :, :]  # (Bq, D)
        k_blk = k_ref[0, 0, :, :]  # (Bk, D)
        v_blk = v_ref[0, 0, :, :]
        s = _dot(q, k_blk, trans_b=True) * scale  # (Bq, Bk)
        if causal:
            s = _causal_mask(s, i, j, block_q, block_k)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _dot(p.astype(v_blk.dtype), v_blk)

    @pl.when(j == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_ref[:] + jnp.log(l_safe)


def _clamp_j(causal, block_q, block_k):
    """KV index map for causal grids: clamp j to the diagonal block so
    programs above the diagonal reference the block already resident —
    their compute is skipped by ``pl.when`` and no DMA fires."""
    if not causal:
        return lambda b_, h_, i, j: (b_, h_, j, 0)
    return lambda b_, h_, i, j: (
        b_, h_, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0
    )


def _clamp_i(causal, block_q, block_k):
    """Q-side index map for the dkv grid (outer j over K blocks): clamp i
    up to the first Q block that reaches the diagonal."""
    if not causal:
        return lambda b_, h_, j, i: (b_, h_, i, 0)
    return lambda b_, h_, j, i: (
        b_, h_, jnp.maximum(i, (j * block_k) // block_q), 0
    )


def _fwd(q, k, v, *, causal, block_q, block_k, interpret):
    """q, k, v in kernel layout (B, H, T, D)."""
    b, h, t, d = q.shape
    scale = 1.0 / np.sqrt(d)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d), _clamp_j(causal, block_q, block_k))
    lse_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale),
        grid=(b, h, t // block_q, t // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="attn_flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------- bwd


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   dq_acc_ref, delta_ref, *, block_q, block_k, causal, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        o = o_ref[0, 0, :, :].astype(jnp.float32)
        delta_ref[:] = (do * o).sum(axis=-1, keepdims=True)  # (Bq, 1)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, 0, :, :]
        k_blk = k_ref[0, 0, :, :]
        v_blk = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]  # (Bq, 1)
        s = _dot(q, k_blk, trans_b=True) * scale
        if causal:
            s = _causal_mask(s, i, j, block_q, block_k)
        p = jnp.exp(s - lse)  # exact probabilities — no rescaling needed
        dp = _dot(do, v_blk.astype(jnp.float32), trans_b=True)
        ds = p * (dp - delta_ref[:]) * scale
        dq_acc_ref[:] += _dot(ds.astype(k_blk.dtype), k_blk)

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[0, 0, :, :] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, block_q, block_k, causal, scale):
    j, i = pl.program_id(2), pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    live = ((i + 1) * block_q - 1 >= j * block_k) if causal else True

    @pl.when(live)
    def _step():
        k_blk = k_ref[0, 0, :, :]  # (Bk, D)
        v_blk = v_ref[0, 0, :, :]
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        o = o_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]
        delta = (do * o).sum(axis=-1, keepdims=True)  # (Bq, 1)
        s = _dot(q, k_blk, trans_b=True) * scale
        if causal:
            s = _causal_mask(s, i, j, block_q, block_k)
        p = jnp.exp(s - lse)  # (Bq, Bk)
        dv_acc_ref[:] += _dot(p, do, trans_a=True)
        dp = _dot(do, v_blk.astype(jnp.float32), trans_b=True)
        ds = p * (dp - delta) * scale  # (Bq, Bk)
        dk_acc_ref[:] += _dot(ds, q.astype(jnp.float32), trans_a=True)

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd(causal, block_q, block_k, interpret, residuals, dout):
    q, k, v, o, lse = residuals
    b, h, t, d = q.shape
    scale = 1.0 / np.sqrt(d)
    n_q, n_k = t // block_q, t // block_k

    # dq: outer grid over Q blocks, inner over K/V blocks.
    qi_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kj_spec = pl.BlockSpec((1, 1, block_k, d), _clamp_j(causal, block_q, block_k))
    lse_i = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale),
        grid=(b, h, n_q, n_k),
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, qi_spec, lse_i],
        out_specs=qi_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),  # dq accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),  # delta row term
        ],
        interpret=interpret,
        name="attn_flash_dq",
    )(q, k, v, o, dout, lse)

    # dk/dv: outer grid over K blocks, inner over Q/dO blocks.
    qi2 = pl.BlockSpec((1, 1, block_q, d), _clamp_i(causal, block_q, block_k))
    kj2 = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j, i: (b_, h_, j, 0))
    _ci = _clamp_i(causal, block_q, block_k)
    lse_i2 = pl.BlockSpec((1, 1, block_q, 1), _ci)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale),
        grid=(b, h, n_k, n_q),
        in_specs=[qi2, kj2, kj2, qi2, qi2, lse_i2],
        out_specs=[kj2, kj2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),  # dk accumulator
            pltpu.VMEM((block_k, d), jnp.float32),  # dv accumulator
        ],
        interpret=interpret,
        name="attn_flash_dkv",
    )(q, k, v, o, dout, lse)
    return dq, dk, dv


# ----------------------------------------------------- block-level (ring)
#
# Mid-level API used by ring attention (ops/ring_attention.py): attention of
# a local Q block against ONE K/V block, exposing the per-row logsumexp so
# the caller can merge blocks (ring hops) exactly. The pallas kernels above
# already have precisely these semantics — ``_fwd`` returns (o, lse) and
# ``_bwd`` consumes the *global* lse (p = exp(s - lse) yields the exact
# probabilities for any sub-block once lse covers the full row) — so the
# ring's per-hop compute is the same fused kernel as single-device flash.
# Dense fallbacks (identical numerics, with lse) cover untileable shapes and
# non-TPU backends.


def _dense_fwd_lse(q, k, v, *, causal):
    """(B, H, Tq, D) x (B, H, Tk, D) -> (o, lse[B, H, Tq, 1]); fp32 softmax,
    bf16-multiply/fp32-accumulate matmuls — the kernel's numerics contract."""
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        qpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (acc / l).astype(q.dtype), m + jnp.log(l)


def _dense_bwd_lse(q, k, v, o, lse, do, *, causal):
    """Dense mirror of the pallas backward: exact p from the global lse."""
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        qpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG_INF)
    p = jnp.exp(s - lse)  # masked entries: exp(-inf - lse) == 0
    do32 = do.astype(jnp.float32)
    delta = (do32 * o.astype(jnp.float32)).sum(axis=-1, keepdims=True)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do32, v.astype(jnp.float32))
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _auto_block(t: int) -> int:
    """Length-adaptive preferred block (the measured v5e optimum): 512 at
    short T, 1024 from ``_LONG_T_BLOCKS`` up — shared by the public entry
    AND the ring/Ulysses per-hop kernels, whose local T is exactly the
    long-context regime the sweep measured."""
    return 1024 if t >= _LONG_T_BLOCKS else 512


def _block_tileable(q, k) -> tuple[int, int] | None:
    tq, tk, d = q.shape[2], k.shape[2], q.shape[3]
    if tq != tk or d % 32 != 0:
        return None
    bq = _pick_block(tq, min(_auto_block(tq), tq))
    bk = _pick_block(tk, min(_auto_block(tk), tk))
    return (bq, bk) if bq and bk else None


def _block_route(q, k, interpret):
    """(blocks, interpret) — blocks=None means take the dense path."""
    blocks = _block_tileable(q, k)
    if interpret is None:
        # Pallas interpreter mode is far slower than the identical-numerics
        # dense math — off-TPU it is opt-in (tests force interpret=True).
        if _interpret_default():
            return None, None
        interpret = False
    return blocks, interpret


def local_flash_attention(q, k, v, *, causal, interpret=None):
    """Differentiable fused attention on LOCAL (B, T, H, D) arrays — for
    callers already inside a shard_map region (Ulysses), where the public
    ``flash_attention`` wrapper's own shard_map must not re-wrap. Falls back
    to dense on untileable shapes / non-TPU, like the public entry point.
    """
    from frl_distributed_ml_scaffold_tpu.ops.ring_attention import dense_attention

    qT = q.transpose(0, 2, 1, 3)
    kT, vT = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    blocks, interpret = _block_route(qT, kT, interpret)
    if blocks is None:
        if not _interpret_default():
            # On TPU this is a real perf/memory cliff (O(T_local²) dense
            # instead of the fused kernel) — same warn-once contract as the
            # public wrapper. Off-TPU dense is the intended default.
            _warn_fallback(
                "local_flash_attention falling back to dense: shape "
                f"(T={q.shape[1]}, head_dim={q.shape[3]}) is not tileable"
            )
        return dense_attention(q, k, v, causal=causal)
    bq, bk = blocks
    return _flash(qT, kT, vT, causal, bq, bk, interpret).transpose(0, 2, 1, 3)


def block_attention_fwd(q, k, v, *, causal, interpret=None):
    """One-block attention in kernel layout (B, H, T, D) -> (o, lse).

    ``causal`` here means Q and K share a position origin (the ring's
    diagonal hop); off-diagonal hops pass ``causal=False``. Routes to the
    pallas kernel when the shapes tile (and the backend is TPU or
    ``interpret`` is forced), else to the identical-numerics dense path.
    """
    blocks, interpret = _block_route(q, k, interpret)
    if blocks is None:
        return _dense_fwd_lse(q, k, v, causal=causal)
    bq, bk = blocks
    return _fwd(q, k, v, causal=causal, block_q=bq, block_k=bk,
                interpret=interpret)


def block_attention_bwd(q, k, v, o, lse, do, *, causal, interpret=None):
    """Per-block gradients given the GLOBAL per-row lse -> (dq, dk, dv).

    Because ``p = exp(s - lse)`` with the row's full-sequence lse gives the
    exact attention probabilities restricted to this block, summing these
    per-block grads over all visible blocks reproduces the full-attention
    gradient — the identity the ring backward is built on.
    """
    blocks, interpret = _block_route(q, k, interpret)
    if blocks is None:
        return _dense_bwd_lse(q, k, v, o, lse, do, causal=causal)
    bq, bk = blocks
    return _bwd(causal, bq, bk, interpret, (q, k, v, o, lse), do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, T, H, D) fused flash attention; drop-in for ``dense_attention``.

    Default blocks are the measured v5e optimum at LM shapes, and they are
    length-adaptive (``None`` = auto). At T=1024 ([4,1024,16,64] sweeps,
    2026-07-30): (512, 512) runs the fwd+bwd call ~20% faster than the
    previous (256, 256) — larger blocks amortize the VMEM revolving and
    keep the MXU fed — and (1024, 1024) measures equal within noise, so
    the smaller VMEM footprint wins at short T. At long T the balance
    flips: the on-chip ladder (tools/flash_sweep.py, 64k, 2026-07-30)
    measures (1024, 1024) at +21%/+37%/+39% over (512, 512) at
    T=16k/32k/64k (59.4 vs 42.6 TFLOPs at 64k), so auto selects
    1024x1024 from T>=16k. ``_pick_block`` clamps both to the sequence
    length so shorter/odd shapes still tile.

    Falls back to ``dense_attention`` when T doesn't tile (no power-of-two
    block divides it) or the head dim isn't sublane-aligned — the numerics
    contract is identical, so the fallback is silent by design.
    """
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        BATCH_AXES,
        current_mesh_env,
    )
    from frl_distributed_ml_scaffold_tpu.ops.ring_attention import dense_attention

    # Seq-axis routing first, before any backend/tileability fallback, so
    # the behavior is identical on CPU simulation and real TPU: a flash call
    # under a sequence-sharded mesh delegates to ring attention, whose
    # per-hop compute is this very kernel (block_attention_fwd/_bwd below) —
    # flash + SP compose rather than conflict.
    from frl_distributed_ml_scaffold_tpu.ops.ring_attention import ring_attention

    env = current_mesh_env()
    if env is not None and env.axis_size("seq") > 1:
        return ring_attention(
            q, k, v, axis_name="seq", causal=causal, interpret=interpret
        )

    t, d = q.shape[1], q.shape[3]
    if block_q is None:
        block_q = _auto_block(t)
    if block_k is None:
        block_k = _auto_block(t)
    bq = _pick_block(t, min(block_q, t))
    bk = _pick_block(t, min(block_k, t))
    if bq is None or bk is None or d % 32 != 0:
        _warn_fallback(
            f"flash_attention falling back to dense: shape (T={t}, head_dim="
            f"{d}) is not tileable (need a power-of-two divisor of T and "
            f"head_dim % 32 == 0)"
        )
        return dense_attention(q, k, v, causal=causal)
    if interpret is None:
        if _interpret_default():
            # Pallas interpreter mode is orders of magnitude slower than the
            # identical-numerics dense path — only tests (which pass
            # interpret=True explicitly) should ever run it.
            _warn_fallback(
                "flash_attention falling back to dense on non-TPU backend "
                f"({jax.default_backend()}); pass interpret=True to force "
                "the Pallas interpreter"
            )
            return dense_attention(q, k, v, causal=causal)
        interpret = False

    def _call(q, k, v):
        # Kernel layout is (B, H, T, D); these transposes sit against the
        # QKV projection reshapes and fuse in XLA.
        qT, kT, vT = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        o = _flash(qT, kT, vT, causal, bq, bk, interpret)
        return o.transpose(0, 2, 1, 3)

    if env is None:
        return _call(q, k, v)
    # Under a mesh, GSPMD cannot partition an opaque pallas_call — an
    # unwrapped kernel would silently all-gather and run replicated. Flash
    # attention is independent per (batch, head), so shard_map over the
    # batch axes and the TP head axis keeps it fully local (same mechanism
    # as the ring/Ulysses siblings). Sequence sharding is ring attention's
    # job, not this kernel's (validated above).
    from frl_distributed_ml_scaffold_tpu.dist.mesh import shard_map_unchecked

    spec = jax.sharding.PartitionSpec(BATCH_AXES, None, "model", None)
    return shard_map_unchecked(
        _call,
        mesh=env.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)
