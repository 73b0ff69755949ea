"""Pipeline parallelism (SURVEY C7): GPipe-in-GSPMD must (i) match the plain
layer-stacked model exactly, (ii) actually shard stages over ``pipe``, and
(iii) train end-to-end composed with DP."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jit import jit_apply, jit_init

from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
from frl_distributed_ml_scaffold_tpu.config.schema import GPTConfig, PrecisionConfig
from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
from frl_distributed_ml_scaffold_tpu.precision import get_policy
from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

FP32 = get_policy(PrecisionConfig(policy="fp32"))

TINY = dict(
    vocab_size=128, num_layers=4, num_heads=2, hidden_dim=32, seq_len=16, dropout=0.0
)


def plain_to_pipelined(params, num_stages):
    """Map plain GPT params -> pipelined structure: the ``blocks`` leaves
    reshape [L, ...] -> [S, L/S, ...] and move under pipeline/ticks/blocks."""
    blocks = jax.tree.map(
        lambda x: x.reshape((num_stages, x.shape[0] // num_stages) + x.shape[1:]),
        params["blocks"],
    )
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["pipeline"] = {"ticks": {"blocks": blocks}}
    return out


def plain_to_circular(params, num_stages, repeat):
    """Plain GPT params -> circular structure: ``blocks`` leaves reshape
    [L, ...] -> [repeat, S, L/(S*repeat), ...] (virtual stage r*S+j holds
    layer group r*S+j) and move under pipeline/blocks."""
    blocks = jax.tree.map(
        lambda x: x.reshape(
            (repeat, num_stages, x.shape[0] // (repeat * num_stages)) + x.shape[1:]
        ),
        params["blocks"],
    )
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["pipeline"] = {"blocks": blocks}
    return out


@pytest.mark.parametrize(
    "stages,repeat,micro",
    [(2, 2, 2), (2, 2, 4)],  # M == S (no parking) and M > S (parking FIFO)
)
def test_circular_pp_matches_plain(stages, repeat, micro):
    """The circular (interleaved) schedule — dynamic per-tick virtual-stage
    param selection + parking FIFO — must match the plain stack exactly,
    forward and backward."""
    base = GPTConfig(**TINY)
    cc = dataclasses.replace(
        base,
        pipeline_stages=stages,
        pipeline_microbatches=micro,
        pipeline_circular_repeat=repeat,
    )
    tokens = jax.random.randint(jax.random.key(8), (8, 16), 0, 128)
    m_plain, m_c = GPT(base, FP32), GPT(cc, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]
    cp = plain_to_circular(params, stages, repeat)
    out_plain = jit_apply(m_plain, train=False)({"params": params}, tokens)
    out_c = jit_apply(m_c, train=False)({"params": cp}, tokens)
    np.testing.assert_allclose(out_plain, out_c, atol=1e-5, rtol=1e-5)

    def loss_plain(p):
        return jnp.mean(m_plain.apply({"params": p}, tokens, train=False) ** 2)

    def loss_c(p):
        return jnp.mean(m_c.apply({"params": p}, tokens, train=False) ** 2)

    g_plain = plain_to_circular(
        jax.jit(jax.grad(loss_plain))(params), stages, repeat
    )
    g_c = jax.jit(jax.grad(loss_c))(cp)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4),
        g_plain,
        g_c,
    )


def test_circular_pp_requires_enough_microbatches():
    """M < S would make a re-entering microbatch collide with a fresh
    injection — the model must refuse, not silently corrupt the schedule."""
    cc = dataclasses.replace(
        GPTConfig(**TINY),
        pipeline_stages=2,
        pipeline_microbatches=1,
        pipeline_circular_repeat=2,
    )
    tokens = jax.random.randint(jax.random.key(9), (8, 16), 0, 128)
    with pytest.raises(ValueError, match="microbatches >= stages"):
        GPT(cc, FP32).init({"params": jax.random.key(0)}, tokens, train=False)


def test_circular_pp_e2e_trains_and_shards(tmp_path):
    """Circular PP=2 x repeat=2 trains end-to-end on the mesh, block params
    carry [repeat, stage, ...] with the stage dim actually sharded over
    ``pipe``, and the logged bubble fraction reflects the v* amortization."""
    from frl_distributed_ml_scaffold_tpu.parallel.pipeline import pipeline_summary

    trainer = make_gpt_trainer(
        tmp_path,
        [
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=4",
            "model.pipeline_circular_repeat=2",
            "mesh.pipe=2",
            "mesh.data=4",
        ],
    )
    summary = pipeline_summary(trainer.cfg.model)
    assert "circular(x2)" in summary and "0.111" in summary  # 1/(2*4+1)
    state = trainer.init_state()
    leaf = state.params["pipeline"]["blocks"]["attn"]["query"]["kernel"]
    assert leaf.shape[:2] == (2, 2)  # [repeat, stage, ...]
    spec = leaf.sharding.spec
    assert spec[1] == "pipe" and spec[0] is None, spec
    state, metrics = run_steps(trainer, state, steps=3)
    assert np.isfinite(float(metrics["loss"]))


def test_pp_forward_matches_plain():
    base = GPTConfig(**TINY)
    pp = dataclasses.replace(base, pipeline_stages=2, pipeline_microbatches=2)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 128)
    m_plain, m_pp = GPT(base, FP32), GPT(pp, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]
    out_plain = jit_apply(m_plain, train=False)({"params": params}, tokens)
    out_pp = jit_apply(m_pp, train=False)(
        {"params": plain_to_pipelined(params, 2)}, tokens
    )
    np.testing.assert_allclose(out_plain, out_pp, atol=1e-5, rtol=1e-5)


def test_pp_grads_match_plain():
    """Autodiff through the rolling-buffer schedule == plain backprop."""
    base = GPTConfig(**TINY)
    pp = dataclasses.replace(base, pipeline_stages=2, pipeline_microbatches=2)
    tokens = jax.random.randint(jax.random.key(2), (4, 16), 0, 128)
    m_plain, m_pp = GPT(base, FP32), GPT(pp, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]

    def loss_plain(p):
        return jnp.mean(m_plain.apply({"params": p}, tokens, train=False) ** 2)

    def loss_pp(p):
        return jnp.mean(m_pp.apply({"params": p}, tokens, train=False) ** 2)

    g_plain = jax.jit(jax.grad(loss_plain))(params)
    g_pp = jax.jit(jax.grad(loss_pp))(plain_to_pipelined(params, 2))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4),
        plain_to_pipelined(g_plain, 2),
        g_pp,
    )


def test_pp_moe_aux_loss_batch_invariant():
    """The MoE router aux loss must not scale with num_microbatches."""
    from frl_distributed_ml_scaffold_tpu.config.schema import MoEConfig

    base = GPTConfig(**TINY, moe=MoEConfig(num_experts=4, top_k=2))
    pp = dataclasses.replace(base, pipeline_stages=2, pipeline_microbatches=4)
    tokens = jax.random.randint(jax.random.key(3), (8, 16), 0, 128)
    m_plain, m_pp = GPT(base, FP32), GPT(pp, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]
    _, aux_plain = jit_apply(m_plain, train=False)({"params": params}, tokens)
    _, aux_pp = jit_apply(m_pp, train=False)(
        {"params": plain_to_pipelined(params, 2)}, tokens
    )
    # Microbatch router stats are means over different token subsets, so
    # the two aux values agree only in expectation — assert same scale.
    assert float(aux_plain) > 0
    ratio = float(aux_pp) / float(aux_plain)
    assert 0.5 < ratio < 2.0, f"aux scales with microbatch count: {ratio}"


def test_pp_composes_with_ring_attention():
    """Round-1 exclusion, lifted: ring attention's shard_map (ppermute over
    ``seq``) nests inside the pipeline's stage vmap via spmd_axis_name.
    PP=2 x SP=2 forward must match the plain dense-attention model."""
    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh, mesh_context

    base = GPTConfig(**TINY)
    pp_ring = dataclasses.replace(
        base, pipeline_stages=2, pipeline_microbatches=2, attention="ring"
    )
    tokens = jax.random.randint(jax.random.key(4), (4, 16), 0, 128)
    m_plain, m_pp = GPT(base, FP32), GPT(pp_ring, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]
    out_plain = jit_apply(m_plain, train=False)({"params": params}, tokens)

    env = build_mesh(MeshConfig(pipe=2, data=2, seq=2))
    with mesh_context(env):
        out_pp = jax.jit(
            lambda p, t: m_pp.apply({"params": p}, t, train=False)
        )(plain_to_pipelined(params, 2), tokens)
    np.testing.assert_allclose(out_plain, out_pp, atol=2e-5, rtol=1e-5)


def test_pp_composes_with_ring_attention_grads(tmp_path):
    """The same composition must hold through the backward (custom-VJP ring
    inside the vmapped/scanned pipeline): train a PP=2 x SP=2 x DP=2 GPT
    end-to-end and check the loss moves."""
    trainer = make_gpt_trainer(
        tmp_path,
        [
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=2",
            "model.attention=ring",
            "mesh.pipe=2",
            "mesh.data=2",
            "mesh.seq=2",
        ],
    )
    state = trainer.init_state()
    _, metrics = run_steps(trainer, state, steps=4)
    assert np.isfinite(float(metrics["loss"]))


def test_pp_composes_with_remat(tmp_path):
    """PP x activation checkpointing: rematerializing through the rolling-
    buffer schedule must not change the math (it is the lever that keeps
    GPipe's saved-per-tick activations from bounding pipeline depth)."""
    ref = make_gpt_trainer(
        tmp_path / "ref",
        ["model.pipeline_stages=2", "model.pipeline_microbatches=2",
         "mesh.pipe=2", "mesh.data=4", "trainer.remat=none"],
    )
    ref_state, _ = run_steps(ref, ref.init_state(), steps=3)
    for mode in ("full", "dots"):
        tr = make_gpt_trainer(
            tmp_path / mode,
            ["model.pipeline_stages=2", "model.pipeline_microbatches=2",
             "mesh.pipe=2", "mesh.data=4", f"trainer.remat={mode}"],
        )
        state, _ = run_steps(tr, tr.init_state(), steps=3)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
            ),
            jax.device_get(ref_state.params),
            jax.device_get(state.params),
        )


def test_pp_composes_with_ulysses_attention():
    """Ulysses' all_to_all shard_map also batches over the stage vmap."""
    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh, mesh_context

    base = GPTConfig(**TINY)
    pp_uly = dataclasses.replace(
        base, pipeline_stages=2, pipeline_microbatches=2, attention="ulysses"
    )
    tokens = jax.random.randint(jax.random.key(5), (4, 16), 0, 128)
    m_plain, m_pp = GPT(base, FP32), GPT(pp_uly, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]
    out_plain = jit_apply(m_plain, train=False)({"params": params}, tokens)

    env = build_mesh(MeshConfig(pipe=2, data=2, seq=2))
    with mesh_context(env):
        out_pp = jax.jit(
            lambda p, t: m_pp.apply({"params": p}, t, train=False)
        )(plain_to_pipelined(params, 2), tokens)
    np.testing.assert_allclose(out_plain, out_pp, atol=2e-5, rtol=1e-5)


def test_pp_composes_with_ring_attention_mpmd(tmp_path):
    """The MPMD twin of test_pp_composes_with_ring_attention: the per-stage
    programs have no vmap(spmd_axis_name), so ring attention's shard_map
    (ppermute over ``seq``) opens directly inside each stage program —
    the pipe2 x data2 x seq2 composition must pass
    here, forward AND through two finite training steps."""
    import dataclasses as _dc

    trainer = make_gpt_trainer(
        tmp_path,
        [
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=2",
            "model.pipeline_impl=mpmd",
            "model.attention=ring",
            "mesh.pipe=2",
            "mesh.data=2",
            "mesh.seq=2",
        ],
    )
    plain = GPT(
        _dc.replace(
            trainer.cfg.model, pipeline_stages=1, attention="dense"
        ),
        trainer.policy,
    )
    tokens = jax.random.randint(jax.random.key(4), (8, 32), 0, 128)
    params = jit_init(plain, tokens, train=False)["params"]
    out_plain = jit_apply(plain, train=False)({"params": params}, tokens)
    mp_params = trainer._mpmd.place_plain_params(jax.device_get(params))
    out_mpmd = trainer._mpmd.apply_logits(mp_params, tokens)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(out_mpmd)),
        np.asarray(jax.device_get(out_plain)),
        atol=2e-5, rtol=1e-5,
    )
    state = trainer.init_state().replace(params=mp_params)
    state, metrics = run_steps(trainer, state, steps=2)
    assert np.isfinite(float(metrics["loss"]))


def test_pp_composes_with_ulysses_attention_mpmd(tmp_path):
    """Ulysses' all_to_all shard_map through the MPMD per-stage programs:
    the MPMD twin of test_pp_composes_with_ulysses_attention."""
    import dataclasses as _dc

    trainer = make_gpt_trainer(
        tmp_path,
        [
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=2",
            "model.pipeline_impl=mpmd",
            "model.attention=ulysses",
            "mesh.pipe=2",
            "mesh.data=2",
            "mesh.seq=2",
        ],
    )
    plain = GPT(
        _dc.replace(
            trainer.cfg.model, pipeline_stages=1, attention="dense"
        ),
        trainer.policy,
    )
    tokens = jax.random.randint(jax.random.key(5), (8, 32), 0, 128)
    params = jit_init(plain, tokens, train=False)["params"]
    out_plain = jit_apply(plain, train=False)({"params": params}, tokens)
    mp_params = trainer._mpmd.place_plain_params(jax.device_get(params))
    out_mpmd = trainer._mpmd.apply_logits(mp_params, tokens)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(out_mpmd)),
        np.asarray(jax.device_get(out_plain)),
        atol=2e-5, rtol=1e-5,
    )


def test_pp_composes_with_flash_attention_pallas(monkeypatch):
    """flash's pallas_call-in-shard_map also nests under the stage vmap.
    On CPU flash normally falls back to dense before reaching its shard_map,
    so force interpreter mode through the model's call site to exercise the
    real composition the TPU path uses."""
    import functools

    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh, mesh_context
    import importlib

    # The ops package re-exports the flash_attention FUNCTION under the same
    # name, shadowing the submodule on attribute import.
    fa_mod = importlib.import_module(
        "frl_distributed_ml_scaffold_tpu.ops.flash_attention"
    )

    monkeypatch.setattr(
        fa_mod,
        "flash_attention",
        functools.partial(fa_mod.flash_attention, interpret=True),
    )
    base = GPTConfig(**TINY)
    pp_flash = dataclasses.replace(
        base, pipeline_stages=2, pipeline_microbatches=2, attention="flash"
    )
    tokens = jax.random.randint(jax.random.key(6), (4, 16), 0, 128)
    m_plain, m_pp = GPT(base, FP32), GPT(pp_flash, FP32)
    params = jit_init(m_plain, tokens, train=False)["params"]
    out_plain = jit_apply(m_plain, train=False)({"params": params}, tokens)

    env = build_mesh(MeshConfig(pipe=2, data=2, model=2))
    with mesh_context(env):
        out_pp = jax.jit(
            lambda p, t: m_pp.apply({"params": p}, t, train=False)
        )(plain_to_pipelined(params, 2), tokens)
    np.testing.assert_allclose(out_plain, out_pp, atol=2e-5, rtol=1e-5)


GPT_TINY_OVERRIDES = [
    "model.vocab_size=128",
    "model.num_layers=4",
    "model.num_heads=2",
    "model.hidden_dim=32",
    "model.seq_len=32",
    "data.vocab_size=128",
    "data.seq_len=32",
    "data.global_batch_size=16",
    "trainer.grad_accum=1",
    "optimizer.warmup_steps=0",
    "precision.policy=fp32",
    "trainer.log_every=1000",
]


def make_gpt_trainer(tmp_path, overrides):
    cfg = apply_overrides(
        get_config("gpt2_medium_zero1"),
        GPT_TINY_OVERRIDES + [f"workdir={tmp_path}"] + overrides,
    )
    return Trainer(cfg)


def run_steps(trainer, state, steps=6):
    for step in range(steps):
        batch = trainer.pipeline.global_batch(step)
        state, metrics = trainer.train_step(state, batch)
    return state, metrics


def test_pp_e2e_matches_dp(tmp_path):
    """PP=2 x DP=4 training == pure DP=8 training, step for step.

    The two init RNG layouts differ (vmap-over-stages splits differently
    than the plain layer scan), so the PP run starts from the DP run's
    init mapped into the stage-stacked structure.
    """
    dp = make_gpt_trainer(tmp_path / "dp", ["mesh.data=8"])
    pp = make_gpt_trainer(
        tmp_path / "pp",
        [
            "mesh.data=4",
            "mesh.pipe=2",
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=4",
        ],
    )
    dp_state = dp.init_state()
    shared = plain_to_pipelined(jax.device_get(dp_state.params), 2)
    pp_state = pp.init_state().replace(params=shared)

    dp_state, _ = run_steps(dp, dp_state)
    pp_state, pp_metrics = run_steps(pp, pp_state)
    assert np.isfinite(float(pp_metrics["loss"]))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-4),
        plain_to_pipelined(jax.device_get(dp_state.params), 2),
        jax.device_get(pp_state.params),
    )


def test_pp_actually_shards_stages(tmp_path):
    """Stage dim of every block param must shard over ``pipe``; training
    must reduce the loss."""
    cfg = apply_overrides(
        get_config("gpt2_pp"),
        GPT_TINY_OVERRIDES
        + [
            f"workdir={tmp_path}",
            "mesh.data=4",
            "mesh.pipe=2",
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=4",
        ],
    )
    trainer = Trainer(cfg)
    state = trainer.init_state()
    blocks = state.params["pipeline"]["ticks"]["blocks"]
    for leaf in jax.tree.leaves(blocks):
        assert tuple(leaf.sharding.spec)[:1] == ("pipe",), leaf.sharding.spec
    losses = []
    for step in range(8):
        batch = trainer.pipeline.global_batch(step)
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_pp_composes_with_tp(tmp_path):
    """PP x TP: stage dim on ``pipe`` AND kernel dim on ``model`` at once."""
    cfg = apply_overrides(
        get_config("gpt2_pp"),
        GPT_TINY_OVERRIDES
        + [
            f"workdir={tmp_path}",
            "mesh.data=2",
            "mesh.pipe=2",
            "mesh.model=2",
            "model.pipeline_stages=2",
            "model.pipeline_microbatches=2",
        ],
    )
    trainer = Trainer(cfg)
    state = trainer.init_state()
    qk = state.params["pipeline"]["ticks"]["blocks"]["attn"]["query"]["kernel"]
    spec = tuple(qk.sharding.spec)
    assert spec[0] == "pipe" and "model" in spec, spec
    batch = trainer.pipeline.global_batch(0)
    state, metrics = trainer.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("circular", [False, True], ids=["gpipe", "circular"])
def test_pp_stage_remat_grads_match(circular):
    """pipeline_stage_remat is pure rematerialization: gradients must be
    identical (fp32, same contractions) to the non-remat schedule while the
    backward saves only stage-boundary activations per tick (residency
    measured by tools/pp_memory_audit.py)."""
    base = GPTConfig(**TINY)
    kw = dict(pipeline_stages=2, pipeline_microbatches=2)
    if circular:
        kw["pipeline_circular_repeat"] = 2
        base = dataclasses.replace(base, num_layers=4)
        to_pp = lambda p: plain_to_circular(p, 2, 2)
    else:
        to_pp = lambda p: plain_to_pipelined(p, 2)
    pp = dataclasses.replace(base, **kw)
    pp_sr = dataclasses.replace(pp, pipeline_stage_remat=True)
    tokens = jax.random.randint(jax.random.key(3), (4, 16), 0, 128)
    params = jit_init(GPT(base, FP32), tokens, train=False)["params"]

    def grads(model):
        def loss(p):
            return jnp.mean(
                model.apply({"params": p}, tokens, train=False) ** 2
            )

        return jax.jit(jax.grad(loss))(to_pp(params))

    g, g_sr = grads(GPT(pp, FP32)), grads(GPT(pp_sr, FP32))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6),
        g,
        g_sr,
    )

    # Composes with the trainer-level remat wrap (nested jax.checkpoint —
    # trainer.remat=full around a stage-remat pipeline).
    m_sr = GPT(pp_sr, FP32)

    def loss_sr(p):
        return jnp.mean(m_sr.apply({"params": p}, tokens, train=False) ** 2)

    g_nested = jax.jit(jax.grad(jax.checkpoint(loss_sr)))(to_pp(params))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6),
        g,
        g_nested,
    )


def _residual_bytes_of(loss, params):
    from jax._src.ad_checkpoint import saved_residuals

    total = 0
    for aval, _ in saved_residuals(loss, params):
        if hasattr(aval, "shape"):
            total += int(aval.size) * aval.dtype.itemsize
    return total


def test_pp_residual_ordering_pinned():
    """CI-light version of the tools/pp_memory_audit.py conclusion (VERDICT
    r3 next-round #8), pinned so the docs' qualitative ordering can't rot:
    saved fwd→bwd residuals must satisfy stage_remat < plain < gpipe
    (the raw scan-autodiff pipeline saves every tick's stage activations —
    MORE than plain DP — and stage remat collapses it to boundaries)."""
    base = GPTConfig(**TINY)
    pp = dataclasses.replace(
        base, pipeline_stages=2, pipeline_microbatches=4
    )
    pp_sr = dataclasses.replace(pp, pipeline_stage_remat=True)
    tokens = jax.random.randint(jax.random.key(5), (8, 16), 0, 128)
    params = jit_init(GPT(base, FP32), tokens, train=False)["params"]

    def bytes_for(model, to_params):
        def loss(p):
            return jnp.mean(
                model.apply({"params": p}, tokens, train=False) ** 2
            )

        return _residual_bytes_of(loss, to_params(params))

    plain = bytes_for(GPT(base, FP32), lambda p: p)
    gpipe = bytes_for(GPT(pp, FP32), lambda p: plain_to_pipelined(p, 2))
    sr = bytes_for(GPT(pp_sr, FP32), lambda p: plain_to_pipelined(p, 2))
    assert sr < plain < gpipe, (sr, plain, gpipe)
