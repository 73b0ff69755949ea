"""Pallas flash attention vs. the dense reference (SURVEY §4 unit tier).

Runs the real kernel code path in Pallas interpreter mode on CPU (same
kernels the TPU compiles) and asserts forward and gradient equivalence with
``dense_attention`` — the numerics contract shared by every attention mode.
"""

from __future__ import annotations
import pytest as _pytest_mark  # noqa: E402

# Sub-2-minute smoke tier (COVERAGE.md "Test tiers"): this module's
# measured wall time keeps `pytest -m fast` under the tier budget.
pytestmark = _pytest_mark.mark.fast


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from frl_distributed_ml_scaffold_tpu.ops.flash_attention import flash_attention
from frl_distributed_ml_scaffold_tpu.ops.ring_attention import dense_attention


def _qkv(b=2, t=256, h=2, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_uneven_blocks():
    # block_q != block_k and blocks that don't divide evenly into each other
    q, k, v = _qkv(t=512)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64,
                          interpret=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_dense(causal):
    q, k, v = _qkv(t=128)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                            interpret=True)
        return (o * jnp.sin(jnp.arange(o.size).reshape(o.shape))).sum()

    def loss_dense(q, k, v):
        o = dense_attention(q, k, v, causal=causal)
        return (o * jnp.sin(jnp.arange(o.size).reshape(o.shape))).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            gf, gd, atol=5e-5, rtol=5e-4,
            err_msg=f"grad mismatch for {name}",
        )


def test_bf16_forward_close():
    q, k, v = _qkv(dtype=jnp.bfloat16, t=128)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_fallback_on_untileable_shapes():
    # T=100 has no power-of-two block divisor; the fallback must actually be
    # taken (a 100-row tile would fail Mosaic's sublane alignment on TPU).
    import importlib

    fa_mod = importlib.import_module(
        "frl_distributed_ml_scaffold_tpu.ops.flash_attention"
    )

    assert fa_mod._pick_block(100, 100) is None  # 100 = 4·25: no p2 divisor
    assert fa_mod._pick_block(24, 24) == 8  # sublane-aligned 3×8 tiling
    assert fa_mod._pick_block(1024, 256) == 256
    assert fa_mod._pick_block(96, 256) == 32

    q, k, v = _qkv(t=100, d=32)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_length_adaptive_block_ladder():
    """Pin the auto block selection the on-chip sweep tuned
    (2026-07-30 → BASELINE.md long-context table):
    512 below 16k, 1024 from 16k up — at 16k/32k/64k the 1024×1024
    blocks measured +21%/+37%/+39% over 512×512 on v5e. A regression
    here silently costs a third of long-context throughput."""
    import importlib

    fa_mod = importlib.import_module(
        "frl_distributed_ml_scaffold_tpu.ops.flash_attention"
    )
    for t, want in [
        (1024, 512), (8192, 512),
        (16384, 1024), (32768, 1024), (65536, 1024),
    ]:
        assert fa_mod._auto_block(t) == want, (t, fa_mod._auto_block(t))
        # And the tileability snap keeps the preferred size whole at
        # power-of-two T (these lengths never fall down the ladder).
        assert fa_mod._pick_block(t, want) == want


def test_sharded_flash_matches_dense():
    """Under a live mesh the wrapper runs the kernel inside shard_map over
    the batch + TP-head axes — per-(b,h) local, no gather (the review-flagged
    multi-device cliff). Verified against dense on the 8-device CPU mesh."""
    import jax

    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh, mesh_context

    env = build_mesh(MeshConfig(data=4, model=2))
    q, k, v = _qkv(b=4, t=128, h=2, d=32)
    ref = dense_attention(q, k, v, causal=True)
    with mesh_context(env):
        out = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64, interpret=True
            )
        )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sharded_flash_delegates_to_ring_on_seq_axis():
    """attention='flash' under a sequence-sharded mesh routes through ring
    attention (whose hops ARE the flash kernel) instead of raising — the
    round-1 flash/SP exclusion, lifted. Must match dense numerics."""
    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh, mesh_context

    env = build_mesh(MeshConfig(data=2, seq=4))
    q, k, v = _qkv(b=4, t=128, h=2, d=32)
    ref = dense_attention(q, k, v, causal=True)
    with mesh_context(env):
        out = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True)
        )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_gpt_model_flash_attention_path(tmp_path):
    """attention='flash' trains end-to-end (tiny GPT).

    On the CPU test backend this exercises the config wiring plus the
    documented non-TPU dense fallback; the kernel numerics themselves are
    covered by the interpret=True tests above.
    """
    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(
        get_config("gpt2_medium_zero1"),
        [
            "model.num_layers=2",
            "model.hidden_dim=64",
            "model.num_heads=2",
            "model.vocab_size=256",
            "model.seq_len=64",
            "model.attention=flash",
            "data.seq_len=64",
            "data.vocab_size=256",
            "data.global_batch_size=8",
            "trainer.grad_accum=1",
            "trainer.log_every=10",
            "checkpoint.enabled=false",
            f"workdir={tmp_path}",
        ],
    )
    trainer = Trainer(cfg)
    state = trainer.init_state()
    batch = trainer.pipeline.global_batch(0)
    losses = []
    for step in range(8):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
