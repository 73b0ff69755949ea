"""Low-precision fast path gates (parallel.low_precision +
ops/collective_matmul.py ``lowp``): the quantized collective-matmul rings
must (i) track the full-precision rings numerically at the DOCUMENTED
tolerances (int8 per-tensor quantization is ~0.4% relative noise per
tensor; after 3 adamw steps on the tiny grid the observed param drift is
~1e-3, loss drift ~3e-5 — gated at 1e-2 / 5e-3 with margin, see
docs/perf_playbook.md "Low-precision fast path"), (ii) actually shrink
the wire — every chunk-sized ppermute payload is 1-byte, pinned through
the per-dtype collective census at >= 3x lower collective-permute bytes
than the full-precision schedule — and (iii) refuse configs where the
knob would silently change nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.analysis import pins
from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
    census_by_dtype,
    census_diff,
    collective_census,
)
from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
from frl_distributed_ml_scaffold_tpu.dist.mesh import (
    build_mesh,
    mesh_context,
    shard_map_unchecked,
)
from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

GPT_TINY = [
    "model.num_layers=2", "model.num_heads=4", "model.hidden_dim=64",
    "model.seq_len=64", "model.vocab_size=256",
    "data.seq_len=64", "data.vocab_size=256",
    "data.global_batch_size=16",
    "trainer.grad_accum=1", "trainer.remat=none",
    "trainer.log_every=1000000",
    "precision.policy=fp32",
    "checkpoint.enabled=false",
    "optimizer.warmup_steps=0",
]


def make_trainer(name, overrides, tmp_path):
    cfg = apply_overrides(
        get_config(name), GPT_TINY + [f"workdir={tmp_path}"] + list(overrides)
    )
    return Trainer(cfg, mesh_env=build_mesh(cfg.mesh))


def run_steps(trainer, n=3):
    state = trainer.init_state()
    for step in range(n):
        state, metrics = trainer.train_step(
            state, trainer.pipeline.global_batch(step)
        )
    return jax.device_get(state), jax.device_get(metrics)


def assert_close_at_lowp_tolerance(ref, lp, ref_m=None, lp_m=None):
    """THE documented int8-vs-full-precision band: params within 1e-2
    absolute (quantization noise x adamw's lr-scale amplification of
    sign flips, ~8x margin over the observed ~1.2e-3), losses within
    5e-3 relative."""
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-2, rtol=1e-3),
        ref.params,
        lp.params,
    )
    if ref_m is not None:
        l_ref, l_lp = float(ref_m["loss"]), float(lp_m["loss"])
        assert abs(l_ref - l_lp) <= 5e-3 * max(1.0, abs(l_ref)), (
            l_ref, l_lp,
        )


# ------------------------------------------------------------- ring level


def _ring_pair(lowp, grad=False):
    """agm -> mrs on a data=2 x model=4 mesh, JITTED (eager shard_map
    dispatch of the unrolled rings costs minutes of per-op compiles on
    the sim; one jitted program is sub-second)."""
    from functools import partial

    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.ops.collective_matmul import (
        all_gather_matmul,
        matmul_reduce_scatter,
    )

    env = build_mesh(MeshConfig(data=2, model=4))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 32, 16)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32) * 0.2
    w2 = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32) * 0.2

    def fwd(x, w1, w2):
        agm = shard_map_unchecked(
            partial(all_gather_matmul, axis_name="model", chunk_axis=1,
                    return_full=False, precision=None, lowp=lowp),
            mesh=env.mesh,
            in_specs=(P(None, "model", None), P(None, "model")),
            out_specs=P(None, None, "model"),
        )
        mrs = shard_map_unchecked(
            partial(matmul_reduce_scatter, axis_name="model", chunk_axis=1,
                    precision=None, lowp=lowp),
            mesh=env.mesh,
            in_specs=(P(None, None, "model"), P("model", None)),
            out_specs=P(None, "model", None),
        )
        return mrs(agm(x, w1), w2)

    with mesh_context(env):
        if grad:
            return jax.jit(
                jax.grad(lambda *a: (fwd(*a) ** 2).sum(), argnums=(0, 1, 2))
            )(x, w1, w2)
        return jax.jit(fwd)(x, w1, w2)


@pytest.mark.fast
@pytest.mark.parametrize("lowp", ["int8", "fp8_e4m3"])
def test_ring_pair_forward_tracks_full_precision(lowp):
    """agm -> mrs (the Megatron column->row pair) quantized vs full
    precision, per-shard: the op-level tolerance band (int8 ~1%, fp8_e4m3
    ~4% — one fewer mantissa bit than the scaled-int grid)."""
    ref = _ring_pair(None)
    out = _ring_pair(lowp)
    rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
    assert rel < (0.03 if lowp == "int8" else 0.10), (lowp, rel)


@pytest.mark.fast
def test_ring_grads_track_full_precision_straight_through():
    """The backward rings quantize their own transfers but differentiate
    straight-through — gradients stay within the same relative band."""
    ref = _ring_pair(None, grad=True)
    out = _ring_pair("int8", grad=True)
    for a, b in zip(ref, out):
        rel = float(jnp.abs(a - b).max() / jnp.abs(a).max())
        assert rel < 0.05, rel


# ---------------------------------------------------------- trainer grids


def int8_pair(tmp_path, mesh, extra=()):
    """(full-precision tp_overlap, int8 tp_overlap) after 3 steps on the
    same mesh — the quantization-noise-only A/B (both sides run the ring
    schedule, so the delta IS the low-precision path)."""
    ref = make_trainer(
        "gpt2_medium_tp_overlap", mesh + list(extra), tmp_path / "ref"
    )
    lp = make_trainer(
        "gpt2_medium_tp_overlap_int8", mesh + list(extra), tmp_path / "lp"
    )
    return run_steps(ref), run_steps(lp)


def test_int8_rings_match_model_only_mesh(tmp_path):
    """model=8: the pure-TP mesh of the acceptance grid, plus the
    sharding sanity check (a silently replicated run would also
    'match')."""
    (ref, ref_m), (lp, lp_m) = int8_pair(
        tmp_path, ["mesh.data=1", "mesh.model=8"]
    )
    assert_close_at_lowp_tolerance(ref, lp, ref_m, lp_m)
    t = make_trainer(
        "gpt2_medium_tp_overlap_int8", ["mesh.data=1", "mesh.model=8"],
        tmp_path / "shard",
    )
    state = t.init_state()
    qk = state.params["blocks"]["attn"]["query"]["kernel"]
    assert any(
        e == "model" or (isinstance(e, tuple) and "model" in e)
        for e in qk.sharding.spec
    ), qk.sharding.spec


@pytest.mark.slow
def test_int8_rings_match_fsdp_x_model(tmp_path):
    """data=2 x fsdp=2 x model=2 with fsdp-sharded params: the quantized
    rings must compose with GSPMD's fsdp gathers of the weight shards.
    (slow tier: each trainer pair costs ~60 s of XLA compiles — the
    model-only pair plus the op-level band tests carry tier-1.)"""
    extra = [
        "parallel.param_sharding=fsdp", "parallel.opt_sharding=like_params",
        "parallel.fsdp_min_size=16",
    ]
    (ref, _), (lp, _) = int8_pair(
        tmp_path, ["mesh.data=2", "mesh.fsdp=2", "mesh.model=2"], extra
    )
    assert_close_at_lowp_tolerance(ref, lp)


@pytest.mark.slow
def test_int8_rings_grad_accum_matches(tmp_path):
    """grad_accum=4: the quantized rings run inside the microbatch scan
    body (the acceptance grid's accumulation cell; slow tier — see
    test_int8_rings_match_fsdp_x_model)."""
    (ref, _), (lp, _) = int8_pair(
        tmp_path, ["mesh.data=2", "mesh.model=4"],
        extra=["trainer.grad_accum=4"],
    )
    assert_close_at_lowp_tolerance(ref, lp)


@pytest.mark.slow
@pytest.mark.parametrize("block_remat", ["full", "save_attn"])
def test_int8_rings_block_remat_interaction(tmp_path, block_remat):
    """Remat cells: the quantized rings sit inside the remat region, so
    the backward re-runs them (re-quantizing the SAME values — the
    deterministic quantizer makes recompute reproduce the forward)."""
    (ref, _), (lp, _) = int8_pair(
        tmp_path, ["mesh.data=2", "mesh.model=4"],
        extra=[f"model.block_remat={block_remat}"],
    )
    assert_close_at_lowp_tolerance(ref, lp)


# ----------------------------------------------------------- bytes pins


def _step_census(t):
    state = t.init_state()
    batch = t.pipeline.global_batch(0)
    with mesh_context(t.env):
        jaxpr = jax.make_jaxpr(t._train_step_fn)(state, batch)
    return collective_census(jaxpr)


@pytest.mark.fast
def test_int8_ring_collective_bytes_pinned_3x_lower(tmp_path):
    """THE comm pin of the acceptance gate (ISSUE 6): on the same mesh,
    the int8 recipe's collective-permute bytes are >= 3x lower than the
    full-precision rings' (4x at the fp32 sim policy minus scale
    traffic), every chunk-sized ppermute payload is 1-byte
    (assert_collective_bytes_within on the wide dtypes: only scalar
    scales remain), and census_diff against the full-precision census
    shows the f32 chunk traffic REMOVED and int8 traffic ADDED — the
    promoted, diffable form of 'the rings actually shrank'."""
    mesh = ["mesh.data=1", "mesh.model=8"]
    ref = make_trainer("gpt2_medium_tp_overlap", mesh, tmp_path / "ref")
    lp = make_trainer("gpt2_medium_tp_overlap_int8", mesh, tmp_path / "lp")
    c_ref = _step_census(ref)
    c_lp = _step_census(lp)

    ref_bytes = pins.collective_bytes(c_ref, "ppermute", axes=("model",))
    lp_bytes = pins.collective_bytes(c_lp, "ppermute", axes=("model",))
    assert ref_bytes > 0 and lp_bytes > 0
    assert ref_bytes >= 3 * lp_bytes, (ref_bytes, lp_bytes)

    # Wide dtypes may carry only the scalar scales: budget = the scale
    # traffic itself (one f32 per chunk transfer) with 2x headroom.
    by_dtype = census_by_dtype(c_lp)
    scale_bytes = by_dtype.get(("ppermute", "float32"), {}).get(
        "total_bytes", 0
    )
    pins.assert_collective_bytes_within(
        c_lp, "ppermute", max(2 * scale_bytes, 1),
        dtypes=("float32", "bfloat16", "float16"),
        msg="int8 recipe moves chunk-sized wide-float ppermute traffic",
    )
    assert by_dtype[("ppermute", "int8")]["total_bytes"] > 0

    # The diffable artifact: f32 chunk records removed, int8 added.
    diff = census_diff(c_ref, c_lp)
    assert any(d["dtype"] == "int8" for d in diff["added"]), diff["added"]
    assert any(
        d["dtype"] == "float32" and d["primitive"] == "ppermute"
        for d in diff["removed"]
    ), diff["removed"]


@pytest.mark.fast
def test_fp8_knob_traces_fp8_rings(tmp_path):
    """The fp8 flavors ride the same knob: parallel.low_precision=
    fp8_e4m3 produces float8 ppermute payloads (smoke — the deep numerics
    grid rides int8, the serving default)."""
    t = make_trainer(
        "gpt2_medium_tp_overlap",
        ["mesh.data=1", "mesh.model=8", "parallel.low_precision=fp8_e4m3"],
        tmp_path,
    )
    by_dtype = census_by_dtype(_step_census(t))
    assert by_dtype.get(("ppermute", "float8_e4m3fn"), {}).get(
        "total_bytes", 0
    ) > 0, sorted(by_dtype)


# ------------------------------------------------------------- validation


@pytest.mark.fast
def test_low_precision_requires_tp_overlap(tmp_path):
    """The knob quantizes the rings; without them it must refuse, not
    silently change nothing (the no-silent-fallback contract)."""
    with pytest.raises(ValueError, match="tp_overlap"):
        make_trainer(
            "gpt2_medium_zero1",
            ["mesh.fsdp=8", "parallel.low_precision=int8"],
            tmp_path,
        )


@pytest.mark.fast
def test_low_precision_unknown_format_refuses(tmp_path):
    with pytest.raises(KeyError, match="fp8_e4m3"):
        make_trainer(
            "gpt2_medium_tp_overlap",
            ["mesh.data=1", "mesh.model=8", "parallel.low_precision=int4"],
            tmp_path,
        )
