"""Flash-decode kernel gates (ops/decode_attention.py).

The same contract every kernel in the repo is held to (fused_bn, flash
attention): interpreter-mode equivalence against the identical-numerics
dense reference — here across cache OCCUPANCY (the dimension the split-KV
kernel is built around: occupancy 1, chunk boundaries, full bucket) and
dtypes — plus the decode-path integration gates: the model's decode step
must read only the active cache bucket (jaxpr-pinned), and the
flash-routed model must reproduce the dense decode path token-for-token.
"""

from __future__ import annotations

import pytest as _pytest_mark

# Whole module is `serving`; the op-level kernel gates (sub-second,
# interpreter-mode) additionally ride `fast` per-test — the model-level
# integration gates compile multi-second decode programs and stay tier-1.
pytestmark = _pytest_mark.mark.serving

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jit import jit_init

from frl_distributed_ml_scaffold_tpu.config.schema import (
    GPTConfig,
    PrecisionConfig,
)
from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
# The submodule via importlib: the ops package re-exports the
# decode_attention FUNCTION, which shadows the submodule attribute on
# every `import ... as` form (the flash_attention naming pattern).
import importlib

da = importlib.import_module(
    "frl_distributed_ml_scaffold_tpu.ops.decode_attention"
)
from frl_distributed_ml_scaffold_tpu.precision import get_policy

FP32 = get_policy(PrecisionConfig(policy="fp32"))


def _make(b, s, h, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    return q, k, v


#: Occupancy classes per bucket S: a single row (the first decode step of
#: a fresh request), straddling the first KV-chunk boundary, a mid-bucket
#: interior point, and the full bucket — plus per-ROW variation inside
#: each case (the engine's slots never share an occupancy).
def _occupancies(s):
    return sorted({1, 2, min(8, s), min(9, s), s // 2, s - 1, s})


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.fast
@pytest.mark.parametrize("s", [8, 64, 512], ids=lambda s: f"S{s}")
def test_flash_decode_matches_dense_across_occupancies(dtype, s):
    """Interpreter-mode kernel == dense reference at every occupancy
    class of every bucket size, fp32 to fp32 tolerance and bf16 to one-ulp
    class tolerance (the repo's standard kernel gate)."""
    b, h, d = 3, 4, 64
    for occ in _occupancies(s):
        q, k, v = _make(b, s, h, d, dtype, seed=occ)
        lens = jnp.asarray(
            [occ, max(1, occ // 2), min(s, occ + 3)], jnp.int32
        )
        ref = da.dense_decode_attention(q, k, v, lens)
        out = da._local_decode(q, k, v, lens, impl="flash", interpret=True)
        ref32 = np.asarray(ref, np.float32)
        out32 = np.asarray(out, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(ref32, out32, atol=2e-6, rtol=2e-6)
        else:
            atol = 2 * float(jnp.finfo(jnp.bfloat16).eps) * max(
                1.0, float(np.abs(ref32).max())
            )
            np.testing.assert_allclose(ref32, out32, atol=atol, rtol=0.05)


@pytest.mark.fast
def test_flash_decode_occupied_prefix_only():
    """Length masking is real: cache rows at positions >= kv_len must not
    influence the output (fill them with garbage and compare against a
    clean cache)."""
    b, s, h, d = 2, 64, 4, 64
    q, k, v = _make(b, s, h, d, jnp.float32)
    lens = jnp.asarray([5, 23], jnp.int32)
    occ = np.arange(s)[None, :, None, None] < np.asarray(lens)[:, None, None, None]
    k_dirty = jnp.where(occ, k, 1e6)
    v_dirty = jnp.where(occ, v, -1e6)
    clean = da._local_decode(q, k, v, lens, impl="flash", interpret=True)
    dirty = da._local_decode(
        q, k_dirty, v_dirty, lens, impl="flash", interpret=True
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.fast
def test_flash_decode_untileable_falls_back_to_dense():
    """Shapes outside the kernel contract (head_dim not sublane-aligned,
    S with no power-of-two divisor) must take the identical-numerics dense
    path, not miscompute."""
    b, h = 2, 2
    for s, d in ((48, 16), (7, 64)):
        q, k, v = _make(b, s, h, d, jnp.float32)
        lens = jnp.asarray([3, s], jnp.int32)
        out = da._local_decode(q, k, v, lens, impl="flash", interpret=True)
        ref = da.dense_decode_attention(q, k, v, lens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.fast
def test_decode_attention_rejects_unknown_impl():
    q, k, v = _make(2, 8, 2, 32, jnp.float32)
    with pytest.raises(KeyError, match="decode_attention"):
        da._local_decode(
            q, k, v, jnp.asarray([1, 2], jnp.int32), impl="bogus",
            interpret=True,
        )


# ------------------------------------------------------- quantized cache


def _make_quant(b, s, h, d, fmt="int8", seed=0):
    from frl_distributed_ml_scaffold_tpu.ops.quantization import quantize

    q, k, v = _make(b, s, h, d, jnp.float32, seed=seed)
    kq, ks = quantize(k, fmt, channel_axes=(0, 1, 2))
    vq, vs = quantize(v, fmt, channel_axes=(0, 1, 2))
    return q, (k, v), (kq, ks[..., 0]), (vq, vs[..., 0])


@pytest.mark.fast
@pytest.mark.parametrize("s", [8, 64, 512], ids=lambda s: f"S{s}")
def test_quant_flash_decode_matches_quant_dense_across_occupancies(s):
    """The quantized-cache column of the kernel grid: interpreter-mode
    quantized kernel == the chunked quantized dense reference == the
    full-dequantize oracle, at every occupancy class (all three consume
    the SAME once-quantized values, so agreement is kernel-tolerance,
    not quantization-tolerance)."""
    from frl_distributed_ml_scaffold_tpu.ops.quantization import dequantize

    b, h, d = 3, 4, 64
    for occ in _occupancies(s):
        q, (k, v), (kq, ks), (vq, vs) = _make_quant(b, s, h, d, seed=occ)
        lens = jnp.asarray(
            [occ, max(1, occ // 2), min(s, occ + 3)], jnp.int32
        )
        ref = da.dense_decode_attention_quant(q, kq, vq, lens, ks, vs)
        out = da._local_decode(
            q, kq, vq, lens, impl="flash", interpret=True,
            k_scale=ks, v_scale=vs,
        )
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=3e-6, rtol=3e-6
        )
        # Oracle: dequantize everything, run the unquantized reference —
        # the chunked online-softmax path must agree to fp32 merge
        # tolerance (this is what makes "chunked" a pure memory property).
        kf = dequantize(kq, ks[..., None], jnp.float32)
        vf = dequantize(vq, vs[..., None], jnp.float32)
        oracle = da.dense_decode_attention(q, kf, vf, lens)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(oracle), atol=2e-6, rtol=2e-6
        )


@pytest.mark.fast
def test_quant_decode_tracks_unquantized_within_tolerance():
    """int8-cache decode vs the full-precision cache on the same values:
    the documented quantization band (per-position-per-head scales keep
    the relative error at the scaled-int grid's ~0.4%, amplified through
    the softmax to a few percent worst-case)."""
    b, s, h, d = 2, 64, 4, 64
    q, (k, v), (kq, ks), (vq, vs) = _make_quant(b, s, h, d)
    lens = jnp.asarray([17, 64], jnp.int32)
    ref = da.dense_decode_attention(q, k, v, lens)
    out = da.dense_decode_attention_quant(q, kq, vq, lens, ks, vs)
    rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
    assert rel < 0.05, rel


def _quant_cache_grid(gpt, fmt, buckets, atol_factor, steps):
    """Shared quantized-cache harness: (i) quantized-KV generation is
    token-IDENTICAL across cache buckets (each written token quantizes
    once over its own head vector — the values a position contributes
    are bucket-independent by construction); (ii) teacher-forced decode
    logits stay within ``atol_factor`` of the full-precision cache's.
    Token equality across FORMATS is not the gate — argmax on a random
    tiny model can sit on near-ties."""
    import dataclasses

    from frl_distributed_ml_scaffold_tpu.models.generation import (
        _decode_step,
        _prefill,
        generate,
    )

    model, params, tokens = gpt
    mq = GPT(dataclasses.replace(model.config, kv_cache_quant=fmt), FP32)
    outs = [
        generate(mq, params, tokens, max_new_tokens=5, temperature=0.0,
                 cache_len=cl)
        for cl in buckets
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))

    md, mqb = model.clone(cache_len=32), mq.clone(cache_len=32)
    log_d, cache_d = _prefill(md, params, tokens, None)
    log_q, cache_q = _prefill(mqb, params, tokens, None)
    scale = max(1.0, float(jnp.abs(log_d).max()))
    for _ in range(steps):
        np.testing.assert_allclose(
            np.asarray(log_d), np.asarray(log_q), atol=atol_factor * scale,
        )
        tok = jnp.argmax(log_d, -1).astype(jnp.int32)
        log_d, cache_d = _decode_step(md, params, cache_d, tok)
        log_q, cache_q = _decode_step(mqb, params, cache_q, tok)


def test_fp8_cache_generates_and_tracks(gpt):
    """The fp8_e4m3 cache flavor rides the same knob end-to-end at the
    fp8 band (looser: 3-bit mantissa; the tight grid rides the int8
    column, test_quantized_cache_bucket_invariant_and_tracks_bf16)."""
    _quant_cache_grid(gpt, "fp8_e4m3", (None, 64), 0.12, steps=4)


@pytest.mark.fast
def test_quant_dense_chunk_is_strictly_smaller_than_bucket():
    """The bounded-dequantize contract the materialization pin relies
    on: the chunked reference never widens a full-bucket cache tensor,
    at any bucket size including the smallest."""
    for s in (8, 16, 64, 512):
        q, (k, v), (kq, ks), (vq, vs) = _make_quant(2, s, 2, 32)
        lens = jnp.asarray([1, s], jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda *a: da.dense_decode_attention_quant(*a)
        )(q, kq, vq, lens, ks, vs)
        pins.assert_no_wide_dims_materialized(
            jaxpr, (s, 2, 32),
            msg=f"quant dense fallback widened the full S={s} bucket",
        )


# ------------------------------------------------------------ paged cache


#: The pools hold all layers; the fixtures put the cache in this one and
#: garbage in the others, so an entry that read another layer would show.
LAYERS, LAYER = 3, 1


def _paged_from_contiguous(k, v, bs, n_blocks, seed=0, scales=None):
    """Scatter a contiguous [B, S, H, D] cache into pool blocks through a
    random (non-trivial) block table — the layout serving/engine.py
    grafts into, built here by hand so the op gates do not depend on the
    engine: stacked lane-dense pools ``[L, N, bs, H*D]`` (a block's
    scales one row ``[L, N, H*bs]``, heads major), the cache in layer
    ``LAYER`` and garbage in every other layer."""
    rng = np.random.default_rng(seed)
    b, s, h, d = k.shape
    m_tbl = s // bs
    assert b * m_tbl <= n_blocks - 1, "pool too small for the fixture"
    perm = rng.permutation(np.arange(1, n_blocks))[: b * m_tbl]
    tables = jnp.asarray(perm.reshape(b, m_tbl), jnp.int32)
    junk = 100 if jnp.issubdtype(k.dtype, jnp.integer) else 1e6

    def pool(x, row):  # [B, S, ...] -> [L, N, *row]
        out = np.full((LAYERS, n_blocks) + row, junk, np.float32)
        out[LAYER] = 0
        blocks = np.asarray(x, np.float32).reshape((b, m_tbl, bs) + x.shape[2:])
        if x.ndim == 3:  # scales [.., bs, H] -> one row, heads major
            blocks = np.swapaxes(blocks, -1, -2)
        out[LAYER, np.asarray(tables)] = blocks.reshape((b, m_tbl) + row)
        return jnp.asarray(out, x.dtype)

    k_pool, v_pool = pool(k, (bs, h * d)), pool(v, (bs, h * d))
    sc_pools = None
    if scales is not None:
        sc_pools = tuple(pool(x, (h * bs,)) for x in scales)
    return k_pool, v_pool, tables, sc_pools


def _paged_decode_kernel(q, k_pool, v_pool, lens, tables, **kw):
    return da.paged_decode_attention(
        q, k_pool, v_pool, lens, tables, LAYER, impl="flash",
        interpret=True, **kw,
    )


def _paged_verify_kernel(q, k_pool, v_pool, lens, tables, **kw):
    return da.paged_verify_attention(
        q, k_pool, v_pool, lens, tables, LAYER, impl="flash",
        interpret=True, **kw,
    )


@pytest.mark.fast
@pytest.mark.parametrize("s", [16, 64, 512], ids=lambda s: f"S{s}")
def test_paged_decode_matches_contiguous_across_occupancies(s):
    """The paged column of the kernel grid (ISSUE 10): the streamed
    paged dense reference tracks the contiguous dense reference to fp32
    merge tolerance at every occupancy class, and the interpreter-mode
    paged kernel (block table on the scalar-prefetch channel) matches
    the streamed reference — same physical blocks, same order, same
    chunking — to kernel tolerance."""
    b, h, d, bs = 3, 4, 64, 8
    for occ in _occupancies(s):
        q, k, v = _make(b, s, h, d, jnp.float32, seed=occ)
        lens = jnp.asarray(
            [occ, max(1, occ // 2), min(s, occ + 3)], jnp.int32
        )
        k_pool, v_pool, tables, _ = _paged_from_contiguous(
            k, v, bs, b * (s // bs) + 7, seed=occ
        )
        ref = da.dense_decode_attention(q, k, v, lens)
        out = da.dense_paged_decode_attention(
            q, k_pool, v_pool, lens, tables, LAYER
        )
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=2e-6, rtol=2e-6
        )
        kern = _paged_decode_kernel(q, k_pool, v_pool, lens, tables)
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(out), atol=2e-6, rtol=2e-6
        )


@pytest.mark.fast
def test_paged_quant_decode_matches_quant_dense():
    """Quantized pools: the paged streamed reference == the contiguous
    chunked quantized reference (same once-quantized values), and the
    interpreter-mode quantized paged kernel tracks it."""
    b, s, h, d, bs = 3, 64, 4, 64, 8
    for occ in (1, 9, 32, 64):
        q, (k, v), (kq, ks), (vq, vs) = _make_quant(b, s, h, d, seed=occ)
        lens = jnp.asarray(
            [occ, max(1, occ // 2), min(s, occ + 3)], jnp.int32
        )
        kqp, vqp, tables, (ksp, vsp) = _paged_from_contiguous(
            kq, vq, bs, b * (s // bs) + 5, seed=occ,
            scales=(ks.astype(jnp.float32), vs.astype(jnp.float32)),
        )
        ref = da.dense_decode_attention_quant(q, kq, vq, lens, ks, vs)
        out = da.dense_paged_decode_attention(
            q, kqp, vqp, lens, tables, LAYER, ksp, vsp
        )
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=3e-6, rtol=3e-6
        )
        kern = _paged_decode_kernel(
            q, kqp, vqp, lens, tables, k_scale=ksp, v_scale=vsp
        )
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(out), atol=3e-6, rtol=3e-6
        )


@pytest.mark.fast
def test_paged_decode_ignores_unreferenced_and_dead_blocks():
    """Isolation, the property block sharing rests on: pool blocks not
    referenced by a row's table — and referenced blocks past the row's
    occupancy — must not influence its output (fill both with garbage
    and compare against the clean pool)."""
    b, s, h, d, bs = 2, 64, 4, 64, 8
    q, k, v = _make(b, s, h, d, jnp.float32)
    lens = jnp.asarray([5, 23], jnp.int32)
    k_pool, v_pool, tables, _ = _paged_from_contiguous(k, v, bs, 32)
    clean = da.dense_paged_decode_attention(
        q, k_pool, v_pool, lens, tables, LAYER
    )
    # Garbage in every block a row's OCCUPIED prefix does not reach:
    # row 0 occupies 5 tokens (block 0 of its table), row 1 occupies 23
    # (blocks 0..2) — everything else in the pool is fair game.
    live = set()
    for bb in range(b):
        for j in range((int(lens[bb]) - 1) // bs + 1):
            live.add(int(tables[bb, j]))
    dirty_k, dirty_v = k_pool, v_pool
    for pid in range(32):
        if pid not in live:
            dirty_k = dirty_k.at[LAYER, pid].set(1e6)
            dirty_v = dirty_v.at[LAYER, pid].set(-1e6)
    # Positions past occupancy INSIDE the last live block too.
    dirty = da.dense_paged_decode_attention(
        q, dirty_k, dirty_v, lens, tables, LAYER
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    kern_clean = _paged_decode_kernel(q, k_pool, v_pool, lens, tables)
    kern_dirty = _paged_decode_kernel(q, dirty_k, dirty_v, lens, tables)
    np.testing.assert_array_equal(
        np.asarray(kern_clean), np.asarray(kern_dirty)
    )


@pytest.mark.fast
def test_paged_untileable_block_falls_back_to_dense():
    """Block geometries outside the kernel contract (block < 8, a token's
    row of heads * head_dim values not whole 128-lane tiles) must take
    the identical-numerics streamed dense path, not miscompute — the
    ``_local_decode`` fallback contract."""
    b, h = 2, 2
    for bs, d in ((4, 64), (8, 16)):
        s = 8 * bs
        q, k, v = _make(b, s, h, d, jnp.float32)
        lens = jnp.asarray([3, s], jnp.int32)
        k_pool, v_pool, tables, _ = _paged_from_contiguous(k, v, bs, 32)
        out = _paged_decode_kernel(q, k_pool, v_pool, lens, tables)
        ref = da.dense_paged_decode_attention(
            q, k_pool, v_pool, lens, tables, LAYER
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.fast
def test_paged_dense_fallback_streams_bounded_chunks():
    """The paged no-cache-clone contract at the op level: the streamed
    reference never materializes the logical cache view (no intermediate
    carries the M*bs logical-context dim) at any block size — the same
    bounded-chunk discipline as the quantized fallback, which is what
    the graft-lint paged program pin relies on."""
    b, h, d = 2, 2, 24  # h*d is no logical-context size below
    for bs, m_tbl in ((8, 8), (16, 32)):
        s = bs * m_tbl
        n_blocks = 2 * b * m_tbl + 1
        q = jnp.zeros((b, h, d), jnp.float32)
        k_pool = jnp.zeros((LAYERS, n_blocks, bs, h * d), jnp.float32)
        tables = jnp.zeros((b, m_tbl), jnp.int32)
        lens = jnp.asarray([1, s], jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda *a: da.dense_paged_decode_attention(*a, LAYER)
        )(q, k_pool, k_pool, lens, tables)
        pins.assert_no_dim_materialized(
            jaxpr, s,
            f"paged dense fallback materialized the M*bs={s} logical view",
        )


# --------------------------------------------- speculative verify tile


@pytest.mark.fast
@pytest.mark.parametrize("t", [2, 4], ids=lambda t: f"T{t}")
def test_paged_verify_matches_per_position_decode(t):
    """ISSUE 11 op gate: the verify tile's causal contract — query j of
    a row whose TOTAL occupancy (tile included) is L scores exactly
    like a single-token decode step at occupancy L - T + 1 + j, for
    every position, at mixed occupancies — and the interpreter-mode
    verify kernel matches the streamed reference to kernel tolerance.
    This per-position equality is what makes greedy acceptance exact
    (the engine's token-identity pin rides on it)."""
    b, s, h, d, bs = 3, 64, 4, 64, 8
    rng = np.random.default_rng(7 + t)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k_pool, v_pool, tables, _ = _paged_from_contiguous(
        k, v, bs, b * (s // bs) + 5, seed=t
    )
    lens = jnp.asarray([t + 1, 29, s], jnp.int32)  # total incl. tile
    out = da.dense_paged_verify_attention(
        q, k_pool, v_pool, lens, tables, LAYER
    )
    for j in range(t):
        ref = da.dense_decode_attention(
            q[:, j], k, v, lens - (t - 1) + j
        )
        np.testing.assert_allclose(
            np.asarray(out[:, j]), np.asarray(ref), atol=2e-6, rtol=2e-6,
            err_msg=f"verify position {j} diverged from its decode step",
        )
    kern = _paged_verify_kernel(q, k_pool, v_pool, lens, tables)
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(out), atol=2e-6, rtol=2e-6
    )


@pytest.mark.fast
def test_paged_verify_quant_matches_quant_reference():
    """Quantized pools under the verify tile: the streamed reference's
    per-position slices track the contiguous quantized decode reference
    (same once-quantized values), and the interpreter-mode quantized
    verify kernel matches the streamed reference."""
    b, s, h, d, bs, t = 3, 64, 4, 64, 8, 3
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    from frl_distributed_ml_scaffold_tpu.ops.quantization import quantize

    kq, ks = quantize(k, "int8", channel_axes=(0, 1, 2))
    vq, vs = quantize(v, "int8", channel_axes=(0, 1, 2))
    ks, vs = ks[..., 0], vs[..., 0]
    kqp, vqp, tables, sc = _paged_from_contiguous(
        kq, vq, bs, b * (s // bs) + 5, seed=5, scales=(ks, vs)
    )
    ksp, vsp = sc
    lens = jnp.asarray([t, 21, s], jnp.int32)
    out = da.dense_paged_verify_attention(
        q, kqp, vqp, lens, tables, LAYER, ksp, vsp
    )
    for j in range(t):
        ref = da.dense_decode_attention_quant(
            q[:, j], kq, vq, lens - (t - 1) + j, ks, vs
        )
        np.testing.assert_allclose(
            np.asarray(out[:, j]), np.asarray(ref), atol=1e-5, rtol=1e-5,
        )
    kern = _paged_verify_kernel(
        q, kqp, vqp, lens, tables, k_scale=ksp, v_scale=vsp
    )
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(out), atol=1e-5, rtol=1e-5
    )


@pytest.mark.fast
@pytest.mark.parametrize("t", [1, 4], ids=lambda t: f"T{t}")
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_kernel_reads_the_stacked_pool_where_it_lies(pool, t):
    """ISSUE 28 op gate: the kernel entry as the model calls it — the
    STACKED lane-dense pool ``[L, N, bs, H*D]`` (int8: with its
    ``[L, N, H*bs]`` scale rows) plus a TRACED layer index, under jit —
    against the streamed dense reference, in the serving precisions
    (bf16 pool, int8 pool), for the decode step (T=1) and a verify tile,
    at ragged occupancies, with a retired row whose table is all trash
    block 0 and whose cursor has run on, and with a pool whose block
    count is no multiple of the scale rows' DMA group."""
    b, s, h, d, bs = 4, 64, 4, 64, 8
    rng = np.random.default_rng(11 + t)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
    sc = None
    if pool == "int8":
        from frl_distributed_ml_scaffold_tpu.ops.quantization import quantize

        k, ks = quantize(k, "int8", channel_axes=(0, 1, 2))
        v, vs = quantize(v, "int8", channel_axes=(0, 1, 2))
        sc = (ks[..., 0].astype(jnp.bfloat16), vs[..., 0].astype(jnp.bfloat16))
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    k_pool, v_pool, tables, sc = _paged_from_contiguous(
        k, v, bs, b * (s // bs) + 6, seed=t, scales=sc
    )
    assert k_pool.shape[1] % da._SCALE_ROWS != 0
    # Row 3 is RETIRED: every logical block is the trash block 0 (which
    # holds whatever was last written there) and its length ran past it.
    tables = tables.at[3].set(0)
    lens = jnp.asarray([t, 29, s, 37], jnp.int32)  # total incl. the tile
    kw = {} if sc is None else dict(k_scale=sc[0], v_scale=sc[1])

    def both(layer):
        ref = da.dense_paged_verify_attention(
            q, k_pool, v_pool, lens, tables, layer, *kw.values()
        )
        kern = da.paged_verify_attention(
            q, k_pool, v_pool, lens, tables, layer, impl="flash",
            interpret=True, **kw,
        )
        return ref, kern

    ref, kern = jax.jit(both)(jnp.int32(LAYER))
    assert np.isfinite(np.asarray(ref, np.float32)).all()
    np.testing.assert_allclose(
        np.asarray(kern, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,  # bf16 outputs: one rounding of O(1) values
    )
    if t == 1:
        one = da.paged_decode_attention(
            q[:, 0], k_pool, v_pool, lens, tables, LAYER, impl="flash",
            interpret=True, **kw,
        )
        np.testing.assert_array_equal(np.asarray(one), np.asarray(kern[:, 0]))


#: (query heads, KV heads, head size, window) of the pools the one paged
#: kernel serves: the uniform stack's (each head its own lanes, half a lane
#: tile wide), a layer kind's with the query heads grouped over fewer KV
#: heads, and a sliding kind's, whose table is a ring under a window that
#: starts on no block boundary.
GEOMETRY = {
    "uniform": (4, 4, 64, 0),
    "grouped": (8, 2, 128, 0),
    "ring": (8, 2, 128, 40),
}


def _plain_paged_attention(q, k, v, lens, window):
    """A softmax a (row, query, head), in numpy: query j of a row of total
    length L (the tile included) sees positions ``< L - T + 1 + j``, the
    last ``window`` of them under a window; query head i reads KV head
    ``i // (Hq / Hkv)``; a row of length 0 reads zeros."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    (b, t, h, d), h_kv = q.shape, k.shape[2]
    out = np.zeros(q.shape, np.float32)
    for r in range(b):
        for j in range(t):
            n = int(lens[r]) - (t - 1) + j
            lo = max(n - window, 0) if window else 0
            for i in range(h if n > lo else 0):
                kv = i // (h // h_kv)
                sc = k[r, lo:n, kv] @ q[r, j, i] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                out[r, j, i] = (p / p.sum()) @ v[r, lo:n, kv]
    return out


@pytest.mark.fast
@pytest.mark.parametrize("t", [1, 4], ids=lambda t: f"T{t}")
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", list(GEOMETRY))
def test_paged_kernel_walks_live_blocks_only(geometry, pool, t):
    """ISSUE 32 op gate, for every geometry the ONE paged kernel serves
    (ISSUE 33): the kernel's walk follows ``kv_len`` — and the window —
    and nothing else. Rows at the walk's edges — length 0 (DEAD: no block
    read, zeros out), the shortest live row, one short of and exactly on a
    block boundary, on and just past a multiple of the blocks a step takes,
    and the whole table (whose width is no multiple of a step); under a
    window, a context shorter than it, exactly it, one past it, and several
    windows long (the ring has wrapped many times) — agree with the plain
    twin, in layer ``LAYER`` of the stack, and the twin with a softmax a
    position; and a pool POISONED with NaN everywhere a block of the walk
    is not (every block of the dead row among them, though its table names
    real blocks: death is the length, never the table — a live row here
    owns block 0; and the blocks a ring still names that the window has
    left) reads the same, bit for bit."""
    h, h_kv, d, window = GEOMETRY[geometry]
    b, bs, m_tbl = 8, 16, 20
    s = m_tbl * bs
    step = da._blocks_per_step(bs, pool == "int8") * bs  # positions a step
    assert (m_tbl * bs) % (8 * bs) != 0 and (window % bs or not window)
    lens = (
        [0, t, 23, window, window + 1, 5 * window + 7, 10 * bs, s] if window
        else [0, t, bs - 1, bs, 8 * bs, 8 * bs + 1, 2 * step + 3, s]
    )  # total incl. the tile
    rng = np.random.default_rng(32 + t)
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
    sc = None
    if pool == "int8":
        from frl_distributed_ml_scaffold_tpu.ops.quantization import quantize

        k, ks = quantize(k, "int8", channel_axes=(0, 1, 2))
        v, vs = quantize(v, "int8", channel_axes=(0, 1, 2))
        sc = (ks[..., 0].astype(jnp.bfloat16), vs[..., 0].astype(jnp.bfloat16))
        plain_kv = [x.astype(jnp.float32) * y.astype(jnp.float32)[..., None]
                    for x, y in ((k, sc[0]), (v, sc[1]))]
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        plain_kv = [k, v]
    pools = list(_paged_from_contiguous(k, v, bs, b * m_tbl + 6, seed=t,
                                        scales=sc))
    tables = pools[2]
    leaves = [pools[0], pools[1]] + list(pools[3] or ())
    # A LIVE row's first block is physical block 0.
    moved = int(tables[3, 0])
    leaves = [x.at[LAYER, 0].set(x[LAYER, moved]) for x in leaves]
    tables = np.asarray(tables.at[3, 0].set(0))
    # The blocks a row's walk reads: those under its length, from the first
    # one the tile's window reaches.
    end = [-(-n // bs) for n in lens]
    first = [max(n - (t - 1) - window, 0) // bs if window else 0 for n in lens]
    live = {
        int(tables[r, j]) for r in range(b) for j in range(first[r], end[r])
    }
    if window:
        # The ring keeps a row's newest blocks, block j at place j % places.
        places = (window + t - 2) // bs + 2
        ring = np.zeros((b, places), np.int32)
        for r in range(b):
            for j in range(max(end[r] - places, 0), end[r]):
                ring[r, j % places] = tables[r, j]
        tables = ring
    tables, lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    dead = np.asarray([i for i in range(leaves[0].shape[1]) if i not in live])

    def poisoned(x):
        bad = 127 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.nan
        return x.at[:, dead].set(bad)

    def run(fn, xs, tables=tables):
        kw = {} if sc is None else dict(k_scale=xs[2], v_scale=xs[3])
        return np.asarray(
            fn(q, xs[0], xs[1], lens, tables, LAYER, window=window, **kw),
            np.float32,
        )

    kernel = functools.partial(
        da.paged_verify_attention, impl="flash", interpret=True
    )
    dense = functools.partial(da.paged_verify_attention, impl="dense")
    ref = run(dense, leaves)
    np.testing.assert_allclose(
        ref, _plain_paged_attention(q, *plain_kv, lens, window),
        atol=2e-2, rtol=2e-2,
    )
    clean = run(kernel, leaves)
    np.testing.assert_allclose(clean, ref, atol=2e-2, rtol=2e-2)
    assert not ref[0].any() and not clean[0].any(), "a dead row reads zeros"
    dirty = run(kernel, [poisoned(x) for x in leaves])
    np.testing.assert_array_equal(dirty, clean)
    if window:
        for fn in (kernel, dense):
            with pytest.raises(ValueError, match="a ring of 3 blocks"):
                run(fn, leaves, tables[:, :3])


@pytest.mark.fast
def test_paged_verify_dense_fallback_streams_bounded_chunks():
    """The no-logical-view contract holds at tile width: k+1 query
    positions make the gather temptation bigger, not smaller — the
    verify fallback still streams one bounded block per table column
    (no intermediate carries the M*bs logical-context dim), which is
    what the graft-lint serving:verify_step_paged pin relies on."""
    b, h, d, t = 2, 2, 24, 3
    for bs, m_tbl in ((8, 8), (16, 32)):
        s = bs * m_tbl
        n_blocks = 2 * b * m_tbl + 1
        q = jnp.zeros((b, t, h, d), jnp.float32)
        k_pool = jnp.zeros((LAYERS, n_blocks, bs, h * d), jnp.float32)
        tables = jnp.zeros((b, m_tbl), jnp.int32)
        lens = jnp.asarray([t, s], jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda *a: da.dense_paged_verify_attention(*a, LAYER)
        )(q, k_pool, k_pool, lens, tables)
        pins.assert_no_dim_materialized(
            jaxpr, s,
            f"verify fallback materialized the M*bs={s} logical view",
        )


# --------------------------------------------------------- model decode


TINY = dict(
    vocab_size=64, num_layers=2, num_heads=2, hidden_dim=64, seq_len=96,
    dropout=0.0,
)


@pytest.fixture(scope="module")
def gpt():
    model = GPT(GPTConfig(**TINY), FP32)
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, 64)
    params = jit_init(model, tokens, train=False)["params"]
    return model, params, tokens


@pytest.mark.parametrize("policy", ["fp32", "bf16_mixed"])
def test_model_flash_decode_matches_dense_decode(policy):
    """The integration gate, across a bucket boundary (prompt in bucket
    16, generation crossing into 32): under fp32, generate() with
    decode_attention=flash (kernel forced through the interpreter) must
    reproduce the dense decode path's greedy tokens at every step. Under
    bf16 the online-softmax merge legitimately rounds once where the
    dense softmax rounds per op, so the gate is per-step LOGITS within
    the bf16 ulp class on the teacher-forced dense trajectory (greedy
    argmax on a random tiny model sits on bf16-scale ties)."""
    import dataclasses

    pol = get_policy(PrecisionConfig(policy=policy))
    cfg = GPTConfig(**TINY)
    tokens = jax.random.randint(jax.random.key(3), (2, 10), 0, 64)
    model_d = GPT(dataclasses.replace(cfg, decode_attention="dense"), pol)
    params = jit_init(model_d, tokens, train=False)["params"]
    from frl_distributed_ml_scaffold_tpu.models.generation import generate

    ref = generate(model_d, params, tokens, max_new_tokens=12,
                   temperature=0.0)
    model_f = GPT(dataclasses.replace(cfg, decode_attention="flash"), pol)
    da.FORCE_INTERPRET = True
    try:
        if policy == "fp32":
            out = generate(model_f, params, tokens, max_new_tokens=12,
                           temperature=0.0)
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
            return
        # bf16: teacher-force the dense trajectory through both paths and
        # compare the logits stepwise.
        from frl_distributed_ml_scaffold_tpu.models.generation import (
            _decode_step,
            _prefill,
        )

        ref_np = np.asarray(ref)
        md, mf = (m.clone(cache_len=32) for m in (model_d, model_f))
        log_d, cache_d = _prefill(md, params, tokens, None)
        log_f, cache_f = _prefill(mf, params, tokens, None)
        atol = 8 * float(jnp.finfo(jnp.bfloat16).eps) * max(
            1.0, float(np.abs(np.asarray(log_d, np.float32)).max())
        )
        for i in range(10, ref_np.shape[1]):
            np.testing.assert_allclose(
                np.asarray(log_d, np.float32), np.asarray(log_f, np.float32),
                atol=atol, rtol=0.05,
            )
            tok = jnp.asarray(ref_np[:, i], jnp.int32)
            log_d, cache_d = _decode_step(md, params, cache_d, tok)
            log_f, cache_f = _decode_step(mf, params, cache_f, tok)
    finally:
        da.FORCE_INTERPRET = None


def test_bucketed_cache_matches_full_cache(gpt):
    """Numerics across cache buckets: the same generation run in the
    smallest covering bucket, an oversized bucket, and the legacy
    full-seq_len cache must agree token-for-token."""
    from frl_distributed_ml_scaffold_tpu.models.generation import generate

    model, params, tokens = gpt
    outs = [
        generate(model, params, tokens, max_new_tokens=6, temperature=0.0,
                 cache_len=cl)
        for cl in (None, 32, model.config.seq_len)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


def test_quantized_cache_bucket_invariant_and_tracks_bf16(gpt):
    """The int8 column of the bucket/dtype grid at the documented
    quantization band (~0.4% per-tensor noise through the softmax),
    including the legacy full-seq_len bucket."""
    model, _, _ = gpt
    _quant_cache_grid(
        gpt, "int8", (None, 32, model.config.seq_len), 0.03, steps=6
    )


def _decode_step_jaxpr(model, params, cache_len):
    """Jaxpr of one single-token decode step at the given cache bucket."""
    m = model.clone(cache_len=cache_len)
    tokens = jnp.zeros((2, 1), jnp.int32)
    # Build a cache of the right structure via a 1-token prefill.
    _, vars_out = m.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"]
    )
    cache = vars_out["cache"]

    def step(params, cache, tok):
        logits, vo = m.apply(
            {"params": params, "cache": cache}, tok, decode=True,
            mutable=["cache"],
        )
        return logits, vo["cache"]

    return jax.make_jaxpr(step)(params, cache, tokens)


# The eqn-shape walker this file used to carry lives in
# analysis/jaxpr_utils.py; the pin itself rides analysis.pins.
from frl_distributed_ml_scaffold_tpu.analysis import pins


@pytest.mark.fast
def test_quantized_decode_step_never_dequantizes_whole_cache(gpt):
    """ISSUE 6's decode pin: the int8-KV decode step at a 16-bucket
    carries (i) no full-seq_len intermediate (the PR 4 pin still holds)
    and (ii) no WIDE-float intermediate with the cache's (S, H, hd)
    geometry — the cache dequantizes per chunk, never wholesale. The
    deliberately-broken wholesale variant is the graft-lint mutation
    gate (tests/test_graft_lint.py)."""
    import dataclasses

    model, params, _ = gpt
    mq = GPT(
        dataclasses.replace(model.config, kv_cache_quant="int8"), FP32
    )
    seq_len, bucket = model.config.seq_len, 16
    jaxpr = _decode_step_jaxpr(mq, params, bucket)
    pins.assert_no_dim_materialized(
        jaxpr, seq_len,
        "quantized decode step materializes full-context arrays",
    )
    h = model.config.num_heads
    hd = model.config.hidden_dim // h
    pins.assert_no_wide_dims_materialized(
        jaxpr, (bucket, h, hd),
        msg="quantized decode step dequantized the whole cache",
    )
    # The 1-byte cache updates ARE there (the pin isn't passing vacuously).
    shapes = pins.eqn_output_shapes(jaxpr)
    assert any(s[-3:] == (bucket, h, hd) for s in shapes), (
        "no bucket-sized cache arrays found — is decode even caching?"
    )


@pytest.mark.fast
def test_decode_step_reads_only_active_bucket(gpt):
    """The jaxpr pin of the acceptance gate: with the cache bucketed to 16
    of a seq_len=96 model, the decode step must carry NO intermediate
    sized to the full context — every cache-derived array (the cache
    update, the [B, H, 1, S] score strip, the attention output chain) is
    bucket-sized. seq_len appears only in the wpe PARAM (an invar, never
    materialized per step: the position embedding is gathered per row)."""
    model, params, _ = gpt
    seq_len, bucket = model.config.seq_len, 16
    jaxpr = _decode_step_jaxpr(model, params, bucket)
    pins.assert_no_dim_materialized(
        jaxpr, seq_len,
        f"decode step materializes full-context ({seq_len}) arrays with a "
        f"{bucket}-bucket cache",
    )
    shapes = pins.eqn_output_shapes(jaxpr)
    h, hd = model.config.num_heads, model.config.hidden_dim // model.config.num_heads
    assert any(
        s[-3:] == (bucket, h, hd) or (bucket in s and h in s)
        for s in shapes
    ), "no bucket-sized cache arrays found — is decode even caching?"
