"""graft-lint (frl_distributed_ml_scaffold_tpu/analysis/): each analyzer
pass on small synthetic programs — one positive and one negative case per
pass — plus the mutation gates the ISSUE names: re-enable plain GSPMD TP
and the exposed-collective detector fires; drop a donation and the audit
fires; oversize a decode intermediate and the materialization budget
fires.  The CLI itself runs over every registered recipe as the `lint`
tier's integration gate."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.analysis import pins
from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
    census_diff,
    collective_census,
    hlo_collective_census,
)
from frl_distributed_ml_scaffold_tpu.analysis.donation import (
    args_info_donations,
    compiled_aliases,
    lowered_donations,
)
from frl_distributed_ml_scaffold_tpu.analysis.hygiene import lint_source
from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
    max_materialized_bytes,
    oversized_intermediates,
)
from frl_distributed_ml_scaffold_tpu.analysis.reshard import (
    exposed_collectives,
    monolithic_gathers,
)
from frl_distributed_ml_scaffold_tpu.dist.mesh import (
    build_mesh,
    mesh_context,
    shard_map_unchecked,
)
from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig

pytestmark = pytest.mark.lint


# ------------------------------------------------------ collective census


@pytest.mark.fast
def test_census_counts_collectives_with_axes_and_scan_trips():
    """Positive: a psum + ppermute inside a 3-trip scan is recorded with
    its axis name and a trip_count of 3; negative: a collective-free
    program yields an empty census."""
    env = build_mesh(MeshConfig(data=8))

    def inner(x):
        def body(c, _):
            c = jax.lax.psum(c, "data")
            c = jax.lax.ppermute(
                c, "data", [(i, (i + 1) % 8) for i in range(8)]
            )
            return c, ()

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    f = shard_map_unchecked(
        inner, mesh=env.mesh, in_specs=P("data"), out_specs=P("data")
    )
    with mesh_context(env):
        jaxpr = jax.make_jaxpr(f)(jnp.ones((8, 4)))
    census = collective_census(jaxpr)
    by_prim = {r.primitive: r for r in census}
    assert set(by_prim) == {"psum", "ppermute"}, census
    assert by_prim["psum"].axes == ("data",)
    assert by_prim["psum"].trip_count == 3
    assert by_prim["ppermute"].trip_count == 3
    # bytes: per-shard [1, 4] fp32 = 16 bytes per call (8-way split of 8).
    assert by_prim["psum"].bytes_per_call == 1 * 4 * 4
    assert by_prim["psum"].total_bytes == 3 * 1 * 4 * 4

    empty = collective_census(jax.make_jaxpr(lambda x: x * 2)(jnp.ones(3)))
    assert empty == []


@pytest.mark.fast
def test_census_diff_reports_added_and_removed():
    env = build_mesh(MeshConfig(data=8))

    def with_psum(x):
        return jax.lax.psum(x, "data")

    def with_two(x):
        return jax.lax.psum(jax.lax.psum(x, "data"), "data")

    def mk(fn):
        f = shard_map_unchecked(
            fn, mesh=env.mesh, in_specs=P("data"), out_specs=P()
        )
        with mesh_context(env):
            return collective_census(jax.make_jaxpr(f)(jnp.ones((8,))))

    one, two = mk(with_psum), mk(with_two)
    d = census_diff(one, two)
    assert len(d["added"]) == 1 and d["added"][0]["count"] == 1
    assert d["removed"] == []
    d_rev = census_diff(two, one)
    assert len(d_rev["removed"]) == 1 and d_rev["added"] == []
    assert census_diff(one, one) == {"added": [], "removed": []}


@pytest.mark.fast
def test_census_diff_sees_scan_trip_count_drift():
    """Same eqn, longer scan (12x the wire bytes) must register as drift
    — trip_count is part of the record identity."""
    env = build_mesh(MeshConfig(data=8))

    def mk(length):
        def inner(x):
            def body(c, _):
                return jax.lax.psum(c, "data"), ()

            return jax.lax.scan(body, x, None, length=length)[0]

        f = shard_map_unchecked(
            inner, mesh=env.mesh, in_specs=P("data"), out_specs=P("data")
        )
        with mesh_context(env):
            return collective_census(jax.make_jaxpr(f)(jnp.ones((8, 4))))

    d = census_diff(mk(2), mk(24))
    assert d["added"] and d["removed"], d
    assert d["added"][0]["trip_count"] == 24
    assert d["removed"][0]["trip_count"] == 2


# --------------------------------------- exposed collectives / reshard


def _tp_matmul_compiled(constrain_out: bool):
    """A Megatron-ish sharded matmul pair; GSPMD must insert an all-reduce
    (row-split contraction) when the output is pinned replicated-on-model."""
    env = build_mesh(MeshConfig(data=2, model=4))
    mesh = env.mesh
    x = jax.ShapeDtypeStruct(
        (16, 32), jnp.float32, sharding=NamedSharding(mesh, P("data", None))
    )
    w1 = jax.ShapeDtypeStruct(
        (32, 32), jnp.float32, sharding=NamedSharding(mesh, P(None, "model"))
    )
    w2 = jax.ShapeDtypeStruct(
        (32, 32), jnp.float32, sharding=NamedSharding(mesh, P("model", None))
    )

    def f(x, w1, w2):
        y = (x @ w1) @ w2
        if constrain_out:
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P("data", None))
            )
        return y

    with mesh_context(env):
        return jax.jit(f).lower(x, w1, w2).compile()


@pytest.mark.fast
def test_mutation_gspmd_tp_trips_exposed_collective_detector():
    """THE mutation gate: on plain GSPMD TP the partitioner inserts an
    all-reduce for the row-split contraction — the detector must fire on
    the compiled HLO (it cannot fire on the jaxpr: GSPMD collectives
    don't exist there, which is why the detector reads HLO)."""
    compiled = _tp_matmul_compiled(constrain_out=True)
    assert collective_census(
        jax.make_jaxpr(lambda x: x + 1)(jnp.ones(3))
    ) == []  # jaxpr level blind to GSPMD, as documented
    hits = exposed_collectives(
        compiled.as_text(), ops=("all-reduce", "all-gather")
    )
    assert hits, "GSPMD TP produced no exposed collective?!"
    with pytest.raises(AssertionError, match="all-reduce"):
        pins.assert_no_collective_hlo(compiled, "all-reduce")


@pytest.mark.fast
def test_negative_unsharded_program_has_no_exposed_collectives():
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    assert exposed_collectives(compiled.as_text()) == []
    pins.assert_no_collective_hlo(compiled, "all-reduce")
    pins.assert_no_collective_hlo(compiled, "all-gather")


@pytest.mark.fast
def test_monolithic_gather_detector_on_synthetic_gathers():
    """Positive/negative for the jaxpr-level reshard pass: a gather of an
    allowed per-block slice passes; a gather of a full stacked tensor is
    flagged."""
    env = build_mesh(MeshConfig(fsdp=8))

    def gather(x):
        return jax.lax.all_gather(x, "fsdp", tiled=True)

    f = shard_map_unchecked(
        gather, mesh=env.mesh, in_specs=P("fsdp"), out_specs=P()
    )
    with mesh_context(env):
        jaxpr = jax.make_jaxpr(f)(jnp.ones((8, 16)))
    assert monolithic_gathers(jaxpr, allowed_shapes={(8, 16)}) == []
    bad = monolithic_gathers(jaxpr, allowed_shapes={(2, 16)})
    assert bad == [(8, 16)]
    pins.assert_all_gather_outputs_within(jaxpr, {(8, 16)})
    with pytest.raises(AssertionError, match="monolithic"):
        pins.assert_all_gather_outputs_within(jaxpr, {(2, 16)})


@pytest.mark.fast
def test_reshard_pin_matches_shape_signatures_in_hlo():
    """assert_reshard_free flags only collectives carrying the pinned
    signatures (the serving handoff pin's contract)."""
    compiled = _tp_matmul_compiled(constrain_out=True)
    txt = compiled.as_text()
    hits = hlo_collective_census(txt)
    assert hits
    shapes = {tuple(s) for r in hits for s in r.shapes}
    some_shape = next(iter(shapes))
    with pytest.raises(AssertionError, match="reshard"):
        pins.assert_reshard_free(
            txt, [some_shape],
            ops=("all-reduce", "all-gather", "all-to-all"),
        )
    # A signature that matches nothing passes.
    pins.assert_reshard_free(txt, [(99, 99, 99)])


# ------------------------------------------------------- materialization


@pytest.mark.fast
def test_materialization_budget_positive_and_negative():
    def f(x):
        big = jnp.einsum("i,j->ij", x, x)  # [256, 256] fp32 = 256 KiB
        return big.sum()

    jaxpr = jax.make_jaxpr(f)(jnp.ones((256,)))
    assert max_materialized_bytes(jaxpr) == 256 * 256 * 4
    assert oversized_intermediates(jaxpr, 300 * 1024) == []
    over = oversized_intermediates(jaxpr, 100 * 1024)
    assert [tuple(i.shape) for i in over] == [(256, 256)]
    pins.assert_max_materialized_bytes(jaxpr, 300 * 1024)
    with pytest.raises(AssertionError, match="budget"):
        pins.assert_max_materialized_bytes(jaxpr, 100 * 1024)


@pytest.mark.fast
def test_mutation_oversized_decode_intermediate_is_caught(gpt_tiny):
    """THE decode mutation gate: the bucketed decode step passes the
    no-full-seq_len pin; the legacy full-context cache (the 'oversized
    intermediate' mutation — cache_len=seq_len) trips the same analyzer."""
    model, params = gpt_tiny
    seq_len = model.config.seq_len

    def step_jaxpr(cache_len):
        m = model.clone(cache_len=cache_len)
        tokens = jnp.zeros((2, 1), jnp.int32)
        _, vo = jax.eval_shape(
            lambda p, t: m.apply(
                {"params": p}, t, decode=True, mutable=["cache"]
            ),
            params, tokens,
        )
        return jax.make_jaxpr(
            lambda p, c, t: m.apply(
                {"params": p, "cache": c}, t, decode=True,
                mutable=["cache"],
            )
        )(params, vo["cache"], tokens)

    pins.assert_no_dim_materialized(step_jaxpr(16), seq_len)
    with pytest.raises(AssertionError, match=str(seq_len)):
        pins.assert_no_dim_materialized(step_jaxpr(seq_len), seq_len)


@pytest.fixture(scope="module")
def gpt_tiny():
    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=64, num_layers=2, num_heads=2, hidden_dim=32,
            seq_len=96, dropout=0.0,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((2, 4), jnp.int32),
            train=False,
        )["params"]
    )
    return model, params


# --------------------------------------------------------------- donation


# -------------------------------------------------- low-precision pins


@pytest.mark.fast
def test_collective_bytes_pin_positive_and_negative():
    """assert_collective_bytes_within sums (dtype-/axis-filtered) wire
    bytes: a budget above the measured traffic passes, below fires with
    the measured total; dtype filtering separates payload from scale
    traffic."""
    env = build_mesh(MeshConfig(data=8))
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def inner(x):
        q = x.astype(jnp.int8)
        s = jnp.max(jnp.abs(x))[None]
        q = jax.lax.ppermute(q, "data", perm)
        s = jax.lax.ppermute(s, "data", perm)
        return q.astype(jnp.float32) * s

    f = shard_map_unchecked(
        inner, mesh=env.mesh, in_specs=P("data"), out_specs=P("data")
    )
    with mesh_context(env):
        jaxpr = jax.make_jaxpr(f)(jnp.ones((8, 64)))
    # Payload: [1, 64] int8 = 64 bytes; scale: [1] f32 = 4 bytes.
    assert pins.collective_bytes(jaxpr, "ppermute") == 68
    assert pins.collective_bytes(jaxpr, "ppermute", dtypes=("int8",)) == 64
    pins.assert_collective_bytes_within(
        jaxpr, "ppermute", 8, dtypes=("float32",)
    )
    with pytest.raises(AssertionError, match="bytes"):
        pins.assert_collective_bytes_within(
            jaxpr, "ppermute", 32, dtypes=("int8",)
        )


@pytest.mark.fast
def test_mutation_bf16_ring_under_int8_recipe_trips_bytes_pin(monkeypatch):
    """THE low-precision mutation gate (ISSUE 6): strip the quantization
    off the rings while the recipe says low_precision=int8 — the runner's
    per-dtype census check must flag the wide ppermute payloads (and the
    missing int8 traffic) as errors. At HEAD the same recipe lints
    clean (test_lint_train_step_overlap_recipes_enforce_their_pins
    covers the tp_overlap family positive)."""
    import dataclasses

    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_train_step,
    )
    from frl_distributed_ml_scaffold_tpu.parallel import tp_overlap as tpo

    # (The positive — the int8 recipe linting clean at HEAD — rides
    # test_cli_all_recipes_runs_clean_and_emits_json, which lints every
    # registered recipe; no need to pay a second trainer build here.)
    real = tpo.make_tp_hooks

    def sabotaged(cfg, env):
        return dataclasses.replace(real(cfg, env), lowp=None)

    monkeypatch.setattr(tpo, "make_tp_hooks", sabotaged)
    rep = lint_train_step(
        "gpt2_medium_tp_overlap_int8", workdir="/tmp/graft_lint_test"
    )
    codes = {f.code for f in rep.errors()}
    assert "wide-ppermute" in codes and "missing-lowp-rings" in codes, (
        codes, [f.message for f in rep.errors()][:3],
    )


@pytest.mark.fast
def test_mutation_wholesale_cache_dequantize_trips_materialization(gpt_tiny):
    """THE quantized-decode mutation gate: the shipped int8-KV decode
    step passes the no-wide-cache-geometry pin (it dequantizes per
    chunk); a deliberately-broken step that dequantizes the WHOLE cache
    before attending trips the same analyzer."""
    import dataclasses

    from frl_distributed_ml_scaffold_tpu.models.gpt import (
        GPT,
        _masked_dense_attention,
    )
    from frl_distributed_ml_scaffold_tpu.ops.quantization import (
        dequantize,
        quantize,
    )

    model, _ = gpt_tiny
    bucket, h = 16, model.config.num_heads
    hd = model.config.hidden_dim // h
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 1, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, bucket, h, hd)), jnp.float32)
    kq, ks = quantize(k, "int8", channel_axes=(0, 1, 2))

    def broken(q, kq, ks):
        # The mutation: wholesale dequantize, then dense-attend.
        kf = dequantize(kq, ks, jnp.float32)  # [B, S, H, hd] fp32
        mask = jnp.ones((2, 1, bucket), bool)
        return _masked_dense_attention(q, kf, kf, mask)

    jaxpr = jax.make_jaxpr(broken)(q, kq, ks)
    with pytest.raises(AssertionError, match="geometry"):
        pins.assert_no_wide_dims_materialized(jaxpr, (bucket, h, hd))

    def broken_transposed(q, kq, ks):
        # Same mutation behind a layout transpose ([B, S, H, hd] ->
        # [B, H, S, hd], the kernel layout): the pin matches the cache
        # geometry as a dim multiset, so reordering can't dodge it.
        kf = dequantize(
            jnp.transpose(kq, (0, 2, 1, 3)),
            jnp.transpose(ks, (0, 2, 1))[..., None],
            jnp.float32,
        )
        return (q[:, 0, :, None, :] * kf).sum()

    jaxpr_t = jax.make_jaxpr(broken_transposed)(
        q, kq, jnp.squeeze(ks, -1) if ks.ndim == 4 else ks
    )
    with pytest.raises(AssertionError, match="geometry"):
        pins.assert_no_wide_dims_materialized(jaxpr_t, (bucket, h, hd))

    # The shipped quantized decode step passes (positive gate, runner-
    # level: same analyzer the CLI arms for serving:decode_step_int8kv).
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_decode_step,
    )

    rep = lint_decode_step(kv_cache_quant="int8")
    assert rep.program == "serving:decode_step_int8kv"
    assert rep.ok, [f.message for f in rep.errors()]


@pytest.mark.fast
def test_paged_decode_step_lint_clean_and_mutations_trip():
    """ISSUE 10's no-cache-clone gates on the block-table serving
    program: the shipped paged decode step passes both teeth (no
    full-seq_len materialization, nothing bigger than one pool leaf —
    the donated in-place update); the two canonical regressions trip —
    (a) clone-per-grow: padding the pool one block wider is a
    bigger-than-pool copy, exactly the bucketed ``_grow_fn`` clone the
    paged engine exists to delete; (b) gather-the-logical-view:
    ``pool[tables]`` reshaped contiguous materializes the full logical
    context the table indirection exists to avoid."""
    from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
        oversized_intermediates,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        _max_pool_leaf_bytes,
        build_paged_decode_step_program,
        lint_paged_decode_step,
    )

    # Positive gates, runner-level: the same analyzers the CLI arms for
    # serving:decode_step_paged[_int8kv].
    for quant in ("none", "int8"):
        rep = lint_paged_decode_step(kv_cache_quant=quant)
        assert rep.ok, [f.message for f in rep.errors()]
        assert rep.meta["pool_leaf_bytes"] > 0

    model, params, cache, tok, jaxpr = build_paged_decode_step_program()
    seq_len = model.config.seq_len
    budget = _max_pool_leaf_bytes(cache)
    pins.assert_no_dim_materialized(jaxpr, seq_len)
    pins.assert_max_materialized_bytes(jaxpr, budget)

    # Mutation (a): clone-per-grow — pad the pool one block wider.
    def clone_per_grow(c):
        kp = c["blocks"]["attn"]["key_pool"]  # [L, N, bs, H*hd]
        pad = [(0, 0)] * kp.ndim
        pad[1] = (0, 1)
        return jnp.pad(kp, pad)

    grow_jaxpr = jax.make_jaxpr(clone_per_grow)(cache)
    assert oversized_intermediates(grow_jaxpr, budget), (
        "a padded-pool clone fits under the pool-leaf budget — the "
        "no-cache-clone pin has no teeth"
    )
    with pytest.raises(AssertionError, match="budget"):
        pins.assert_max_materialized_bytes(grow_jaxpr, budget)

    # Mutation (b): gather the logical cache view out of the pool.
    def gather_logical(c):
        kp = c["blocks"]["attn"]["key_pool"]  # [L, N, bs, H*hd]
        tbl = c["block_tables"]  # [B, M]
        g = jnp.take(kp, tbl, axis=1)  # [L, B, M, bs, H*hd]
        l, _, bs, f = kp.shape
        b, m = tbl.shape
        return g.reshape(l, b, m * bs, f)  # full context

    gather_jaxpr = jax.make_jaxpr(gather_logical)(cache)
    with pytest.raises(AssertionError, match=str(seq_len)):
        pins.assert_no_dim_materialized(gather_jaxpr, seq_len)


def test_verify_step_lint_clean_and_mutations_trip():
    """ISSUE 11's gates on the speculative verify program: the shipped
    [B, k+1] verify step passes the paged pins at tile width (no
    full-seq_len materialization, nothing bigger than one pool leaf,
    every cache leaf donated on the engine's ONE compiled verify
    program); the canonical regressions trip — (a) scoring the tile
    against a GATHERED logical cache view (the k+1 queries make the
    gather temptation bigger, and it materializes the full context the
    table indirection exists to avoid), and (b) dropping the verify
    program's cache donation (two pools live per verify)."""
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        _max_pool_leaf_bytes,
        build_verify_step_program,
        lint_verify_step,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        _verify_step,
    )

    # Positive gates: both pool flavors, same analyzers the CLI arms
    # for serving:verify_step_paged.
    for quant in ("none", "int8"):
        rep = lint_verify_step(kv_cache_quant=quant)
        assert rep.ok, [f.message for f in rep.errors()]
        assert rep.meta["verify_positions"] == 3
        assert rep.meta["pool_leaf_bytes"] > 0

    model, params, cache, tile, jaxpr = build_verify_step_program()
    seq_len = model.config.seq_len
    budget = _max_pool_leaf_bytes(cache)
    pins.assert_no_dim_materialized(jaxpr, seq_len)
    pins.assert_max_materialized_bytes(jaxpr, budget)

    # Mutation (a): verify the tile against the gathered logical view —
    # a [B, T, M*bs]-scored step materializes the full context.
    def gathered_scores(c, t):
        kp = c["blocks"]["attn"]["key_pool"]  # [L, N, bs, H*hd]
        tbl = c["block_tables"]  # [B, M]
        g = jnp.take(kp[0], tbl, axis=0)  # [B, M, bs, H*hd]
        b, m = tbl.shape
        logical = g.reshape(b, m * kp.shape[2], -1)  # full context
        q = jnp.zeros((b, t.shape[1], logical.shape[-1]), jnp.float32)
        return jnp.einsum("btd,bsd->bts", q, logical.astype(jnp.float32))

    mut_jaxpr = jax.make_jaxpr(gathered_scores)(cache, tile)
    with pytest.raises(AssertionError, match=str(seq_len)):
        pins.assert_no_dim_materialized(mut_jaxpr, seq_len)

    # Mutation (b): dropped donation on the verify program — the audit
    # fires at the args_info level exactly like the decode programs.
    m = model.clone(kv_block_size=16, kv_pool_blocks=9)

    def fn(p, c, t):
        logits, c = _verify_step(m, p, c, t)
        return jnp.argmax(logits, -1), c

    donated = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, tile)
    dropped = jax.jit(fn).lower(params, cache, tile)
    n_cache = len(jax.tree.leaves(cache))
    pins.assert_donated(donated, min_donated=n_cache)
    with pytest.raises(AssertionError, match="donated"):
        pins.assert_donated(dropped, min_donated=1)
    d_pairs = args_info_donations(dropped)
    assert not any(d for _, d in d_pairs), "dropped donation still marked"


def test_handoff_lint_clean_and_gather_mutation_trips():
    """ISSUE 12's gates on the prefill→decode handoff splice: the
    shipped splice (``generation.splice_pool_blocks`` — the exact
    function the engine jits for both colocated grafts and
    disaggregated handoffs) passes all three teeth (ZERO collectives,
    no full-seq_len materialization, nothing bigger than one pool leaf,
    pool donated), and the canonical regression trips — a GATHER-BASED
    handoff that materializes the logical cache view (``pool[tables]``
    contiguous) and rewrites the pool is exactly the cache copy the
    block-table splice exists to delete."""
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.analysis.materialization import (
        oversized_intermediates,
    )
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        _max_pool_leaf_bytes,
        build_handoff_program,
        lint_handoff,
    )

    rep = lint_handoff()
    assert rep.ok, [f.message for f in rep.errors()]
    assert rep.meta["collective_census"] == [], "splice grew a collective"
    assert rep.meta["pool_leaf_bytes"] > 0
    # The ledger's table-bytes claim: splice ownership cost is the int32
    # table row, orders of magnitude under the pool.
    assert rep.meta["splice_table_bytes"] * 100 < rep.meta["pool_leaf_bytes"]

    model, pool_cache, slot_cache, blk_ids, jaxpr = build_handoff_program()
    seq_len = model.config.seq_len
    budget = _max_pool_leaf_bytes(pool_cache)
    pins.assert_no_dim_materialized(jaxpr, seq_len)
    pins.assert_max_materialized_bytes(jaxpr, budget)

    # Mutation: the gather-based handoff — materialize the logical view,
    # splice the slot cache into it, scatter the WHOLE pool back.
    def gather_handoff(c, sc):
        kp = c["blocks"]["attn"]["key_pool"]  # [L, N, bs, H*hd]
        tbl = c["block_tables"]  # [B, M]
        g = jnp.take(kp, tbl, axis=1)  # [L, B, M, bs, H*hd]
        l, _, bs, f = kp.shape
        b, m = tbl.shape
        logical = g.reshape(l, b, m * bs, f)  # the full-context copy
        sk = sc["blocks"]["attn"]["cached_key"]  # [L, 1, s_c, H, hd]
        logical = logical.at[:, 0, : sk.shape[2]].set(
            sk[:, 0].reshape(l, sk.shape[2], f)
        )
        return logical

    mut_jaxpr = jax.make_jaxpr(gather_handoff)(pool_cache, slot_cache)
    assert oversized_intermediates(mut_jaxpr, budget), (
        "a gather-based handoff fits under the pool-leaf budget — the "
        "cache-copy pin has no teeth"
    )
    with pytest.raises(AssertionError, match=str(seq_len)):
        pins.assert_no_dim_materialized(mut_jaxpr, seq_len)


def test_reshard_lint_clean_and_naive_mutation_trips(monkeypatch):
    """ISSUE 15's gates on the redistribution executor's same-mesh
    program classes: at HEAD every ``reshard:*`` program passes (every
    per-device intermediate inside the plan's scratch budget, the pure
    axis-move all_gather-free, source donated), and the canonical
    regression — the NAIVE gather-then-scatter executor, which stages
    the full logical array on every device before re-slicing — trips
    the replicated-staging materialization pin on every program (plus
    the gather-on-move pin on the pure all_to_all class)."""
    import frl_distributed_ml_scaffold_tpu.redistribute.executor as rd_exec
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        RESHARD_PROGRAMS,
        build_reshard_program,
        lint_reshard,
        lint_reshard_programs,
    )

    reports = lint_reshard_programs()
    assert {r.program for r in reports} == set(RESHARD_PROGRAMS)
    for rep in reports:
        assert rep.ok, (rep.program, [f.message for f in rep.errors()])
        assert rep.meta["plan"]["bytes_moved"] == (
            rep.meta["plan"]["bytes_lower_bound"]
        ), rep.program
    # The pure-move program really is ONE all_to_all on the wire.
    plan, jaxpr, _ = build_reshard_program("reshard:tp_row_to_col")
    pins.assert_collective_present(jaxpr, "all_to_all")
    pins.assert_no_collective(jaxpr, "all_gather")

    monkeypatch.setattr(rd_exec, "_NAIVE_GATHER_SCATTER", True)
    for name in RESHARD_PROGRAMS:
        rep = lint_reshard(name)
        codes = {f.code for f in rep.errors()}
        assert "replicated-staging" in codes, (name, codes)
        if RESHARD_PROGRAMS[name].get("no_gather"):
            assert "gather-on-move" in codes, (name, codes)


@pytest.mark.fast
def test_mutation_dropped_donation_is_caught():
    """THE donation mutation gate: the same program jitted with and
    without donate_argnums — the audit passes the donated one and fires
    on the dropped one, at both the lowered and args_info levels."""
    s = {"mu": jnp.ones((64, 64)), "nu": jnp.ones((64, 64))}
    g = jnp.ones((64, 64))

    def update(s, g):
        return {"mu": s["mu"] * 0.9 + g, "nu": s["nu"] * 0.99 + g * g}

    donated = jax.jit(update, donate_argnums=(0,)).lower(s, g)
    dropped = jax.jit(update).lower(s, g)

    pins.assert_donated(donated, min_donated=2)
    with pytest.raises(AssertionError, match="donated"):
        pins.assert_donated(dropped, min_donated=1)

    d_pairs = dict(args_info_donations(donated))
    assert all(d for p, d in d_pairs.items() if "mu" in p or "nu" in p)
    assert not any(d for p, d in dict(args_info_donations(dropped)).items())

    # Lowered-marker level agrees.
    assert sum(1 for d in lowered_donations(donated.as_text()) if d.donated) == 2
    assert sum(1 for d in lowered_donations(dropped.as_text()) if d.donated) == 0


@pytest.mark.fast
def test_compiled_alias_table_positive_and_negative():
    """Compiled-executable ground truth: donation shows up in
    input_output_alias; without donation the table is empty."""
    f = lambda x: x + 1.0
    x = jnp.ones((32, 32))
    comp_d = jax.jit(f, donate_argnums=(0,)).lower(x).compile()
    comp_n = jax.jit(f).lower(x).compile()
    aliases = pins.assert_aliased(comp_d, min_aliases=1)
    assert aliases[0]["param"] == 0
    assert compiled_aliases(comp_n) == []
    with pytest.raises(AssertionError, match="alias"):
        pins.assert_aliased(comp_n)


# ---------------------------------------------------------------- hygiene


@pytest.mark.fast
def test_hygiene_flags_host_sync_rng_and_axis_typo():
    src = '''
import random
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

def traced_bad(x):
    noise = random.random()
    y = jnp.sum(x) * noise
    z = float(np.median(y))
    zz = float(jnp.mean(y))
    v = y.item()
    w = lax.psum(y, "modle")
    i = lax.axis_index("daat")
    return jax.device_get(w) + i

def host_ok(batch):
    import numpy as np
    return np.asarray(batch["x"]).mean()
'''
    findings = lint_source(src, "synthetic.py")
    codes = sorted({(f.code, f.severity) for f in findings})
    assert ("python-rng", "error") in codes, codes
    assert ("host-sync", "error") in codes, codes
    assert ("axis-typo", "error") in codes, codes
    # Both positions: psum's arg 1 ("modle") AND axis_index's arg 0
    # ("daat") — the typo detector knows each collective's axis slot.
    typos = {f.context["axis"] for f in findings if f.code == "axis-typo"}
    assert typos == {"modle", "daat"}, typos
    assert ("host-sync-cast", "warning") in codes, codes  # float(np.median)
    assert ("numpy-in-traced", "warning") in codes, codes
    # The host-side function (no jnp/lax in body) is exempt.
    assert not any(
        f.context.get("function") == "host_ok" for f in findings
    ), findings


@pytest.mark.fast
def test_hygiene_clean_traced_source_passes():
    src = '''
import jax
import jax.numpy as jnp
from jax import lax

def traced_ok(x):
    y = jnp.sum(x)
    return lax.psum(y, "model")
'''
    assert lint_source(src, "clean.py") == []


@pytest.mark.fast
@pytest.mark.obs
def test_hygiene_metrics_in_traced_mutation_gate():
    """ISSUE 7 mutation gate: a telemetry mutation inside traced code is
    an ERROR (trace-time freeze or per-step host sync), while the legal
    look-alikes — jnp's functional ``x.at[i].set(v)`` in traced code and
    metric writes on the host side of the jitted call — stay clean."""
    bad = '''
import jax.numpy as jnp

def traced_decode(x, m_tpot, engine, reg):
    y = jnp.sum(x)
    m_tpot.observe(0.001)
    engine.telemetry.counter("decode_steps_total").inc()
    reg.gauge("occupancy").set(0.5)
    return y
'''
    findings = [
        f for f in lint_source(bad, "bad.py") if f.code == "metrics-in-traced"
    ]
    # Every metric statement flagged (chained factory+mutator may each
    # report, so pin the flagged LINES): observe / telemetry chain / set.
    assert {f.context["line"] for f in findings} == {6, 7, 8}, findings
    assert all(f.severity == "error" for f in findings)
    assert {f.context["function"] for f in findings} == {"traced_decode"}
    calls = {f.context["call"] for f in findings}
    assert "m_tpot.observe" in calls and "reg.gauge" in calls, calls

    clean = '''
import jax.numpy as jnp
import numpy as np

def traced_update(cache, idx, v, done):
    out = cache.at[idx].set(v)      # functional update, not a gauge
    done.set()                      # threading.Event.set(): zero args
    counts, edges = jnp.histogram(out, bins=8)   # array op, not a factory
    np.histogram(np.ones(4), bins=2)             # ditto at shape time
    return out * jnp.ones(())

def host_step(engine, fn, x):
    t0 = perf_counter()
    y = fn(x)                       # the jitted call
    engine.m_step.observe(perf_counter() - t0)
    engine.telemetry.counter("steps_total").inc()
    return y
'''
    assert [
        f for f in lint_source(clean, "clean.py")
        if f.code == "metrics-in-traced"
    ] == []


@pytest.mark.fast
def test_hygiene_span_tracing_in_traced_mutation_gate():
    """ISSUE 8 mutation gate: the hygiene ERROR extends to the span API —
    ``.span(...)`` starts and any ``tracing``/``tracer`` attribute chain
    inside traced code are flagged (a span inside a trace freezes at
    trace time or drags a per-step clock read + sync in); the host-side
    loop around the jitted call stays clean."""
    bad = '''
import jax.numpy as jnp

def traced_block(x, engine, tracer):
    with engine.tracing.span("block"):
        y = jnp.sum(x)
    tracer.emit("phase", t0=0.0, dur_s=0.1)
    sp = tracer.begin("p")
    return y
'''
    findings = [
        f for f in lint_source(bad, "bad.py") if f.code == "metrics-in-traced"
    ]
    assert {f.context["line"] for f in findings} == {5, 7, 8}, findings
    assert all(f.severity == "error" for f in findings)

    clean = '''
import jax.numpy as jnp

def traced_fn(x):
    return jnp.sum(x) * 2

def host_loop(tracer, fn, x, trace):
    with tracer.span("dispatch", trace=trace):
        y = fn(x)                      # the jitted call
    return y
'''
    assert [
        f for f in lint_source(clean, "clean.py")
        if f.code == "metrics-in-traced"
    ] == []


@pytest.mark.fast
def test_hygiene_repo_traced_modules_are_clean():
    """The repo's own traced modules carry no hygiene errors (warnings
    allowed: shape-time numpy is legal)."""
    from frl_distributed_ml_scaffold_tpu.analysis.runner import lint_hygiene

    report = lint_hygiene()
    assert report.errors() == [], [f.message for f in report.errors()]


# ------------------------------------------------------------- robustness


@pytest.mark.fast
@pytest.mark.chaos
def test_robustness_flags_swallowed_exceptions_and_unbounded_retry():
    """ISSUE 9 mutation gate: a pass-only wide except is an ERROR (the
    fault vanishes — no log, no counter, no typed resolution) in HOST
    code too, and a while-True retry loop with no backoff call and a
    never-escalating handler is a WARNING."""
    from frl_distributed_ml_scaffold_tpu.analysis.hygiene import (
        lint_robustness_source,
    )

    bad = '''
import os, time

def swallow_everything(path):
    try:
        os.remove(path)
    except Exception:
        pass

def swallow_bare(path):
    try:
        os.remove(path)
    except:
        ...

def swallow_in_tuple(path):
    try:
        os.remove(path)
    except (OSError, Exception):
        pass

def spin_forever(fn):
    while True:
        try:
            return fn()
        except OSError:
            continue

def spin_with_str_join(fn, log):
    while True:
        try:
            return fn()
        except OSError as e:
            log(", ".join([str(e)]))  # join != backoff: still a busy-spin
            continue
'''
    findings = lint_robustness_source(bad, "bad.py")
    swallowed = [f for f in findings if f.code == "swallowed-exception"]
    assert len(swallowed) == 3, findings
    assert all(f.severity == "error" for f in swallowed)
    spins = [f for f in findings if f.code == "unbounded-retry"]
    assert len(spins) == 2 and all(
        f.severity == "warning" for f in spins
    ), findings

    clean = '''
import os, time

def narrow_swallow(path):
    try:
        os.remove(path)
    except OSError:
        pass  # best-effort unlink: narrow type is legal

def logged_swallow(path, logger):
    try:
        os.remove(path)
    except Exception as e:
        logger.warning("cleanup failed: %s", e)

def retry_with_backoff(fn, policy):
    while True:
        try:
            return fn()
        except OSError:
            time.sleep(policy.backoff_s)

def retry_that_escalates(fn):
    while True:
        try:
            return fn()
        except OSError:
            raise
'''
    assert lint_robustness_source(clean, "clean.py") == [], (
        lint_robustness_source(clean, "clean.py")
    )


@pytest.mark.fast
@pytest.mark.chaos
def test_robustness_repo_package_is_clean():
    """The whole package (host orchestration included — engine,
    supervisor, checkpointer) carries no robustness errors: every wide
    except either handles, logs, or narrows."""
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_robustness,
    )

    report = lint_robustness()
    assert report.meta["files"] > 50  # the glob really covers the package
    assert report.errors() == [], [f.message for f in report.errors()]


# ----------------------------------------------------------- concurrency


@pytest.mark.fast
@pytest.mark.chaos
def test_concurrency_unguarded_shared_write_mutation_gate():
    """ISSUE 20 mutation gate (a): an attribute written under
    ``self._lock`` in one method is GUARDED; a read-modify-write of it
    outside that lock, on a class that spawns threads, is an ERROR
    (lost-update race). The properly-locked twin lints clean."""
    from frl_distributed_ml_scaffold_tpu.analysis.concurrency import (
        lint_concurrency_source,
    )

    bad = '''
import threading

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def add(self, n):
        with self._lock:
            self._count += n

    def _run(self):
        self._count += 1  # RMW of a guarded attr, no lock held
'''
    findings = lint_concurrency_source(bad, "bad.py")
    races = [f for f in findings if f.code == "unguarded-shared-write"]
    assert len(races) == 1, findings
    assert races[0].severity == "error"
    assert "_count" in races[0].message
    assert "Pool._lock" in races[0].message

    clean = bad.replace(
        "        self._count += 1  # RMW of a guarded attr, no lock held",
        "        with self._lock:\n            self._count += 1",
    )
    assert lint_concurrency_source(clean, "clean.py") == [], (
        lint_concurrency_source(clean, "clean.py")
    )


@pytest.mark.fast
@pytest.mark.chaos
def test_concurrency_lock_order_inversion_mutation_gate():
    """ISSUE 20 mutation gate (b): both inversion shapes are caught —
    a direct nested-``with`` A→B/B→A in one module, and the
    interprocedural form where each class takes its own lock then calls
    into the other (edges discovered through annotated constructor
    params). The one-direction variant lints clean."""
    from frl_distributed_ml_scaffold_tpu.analysis.concurrency import (
        lint_concurrency_source,
    )

    direct = '''
import threading

a = threading.Lock()
b = threading.Lock()

def fwd():
    with a:
        with b:
            pass

def rev():
    with b:
        with a:
            pass
'''
    findings = lint_concurrency_source(direct, "direct.py")
    cycles = [f for f in findings if f.code == "lock-order-inversion"]
    assert len(cycles) == 1, findings
    assert cycles[0].severity == "error"
    assert "direct.py" in cycles[0].message  # edge sites are named

    interproc = '''
import threading

class Right:
    def __init__(self, left: "Left"):
        self._lock = threading.Lock()
        self._left = left

    def bump(self):
        with self._lock:
            pass

    def rev(self):
        with self._lock:
            self._left.poke()

class Left:
    def __init__(self, right: "Right"):
        self._lock = threading.Lock()
        self._right = right

    def poke(self):
        with self._lock:
            pass

    def fwd(self):
        with self._lock:
            self._right.bump()
'''
    findings = lint_concurrency_source(interproc, "interproc.py")
    cycles = [f for f in findings if f.code == "lock-order-inversion"]
    assert len(cycles) == 1, findings
    assert "Left._lock" in cycles[0].message
    assert "Right._lock" in cycles[0].message

    # Drop one direction and the cycle disappears.
    one_way = interproc.replace(
        "    def rev(self):\n        with self._lock:\n"
        "            self._left.poke()\n",
        "",
    )
    assert one_way != interproc
    assert lint_concurrency_source(one_way, "one_way.py") == [], (
        lint_concurrency_source(one_way, "one_way.py")
    )


@pytest.mark.fast
@pytest.mark.chaos
def test_concurrency_blocking_under_lock_mutation_gate():
    """ISSUE 20 mutation gate (c): text-surgery on the REAL
    ``telemetry/metrics.py`` source — inserting ``jax.block_until_ready``
    inside ``Counter.inc``'s locked region — trips ``blocking-under-lock``
    (error), while the committed source stays clean.  Also the
    interprocedural shape: a helper that sleeps, called under a lock."""
    from frl_distributed_ml_scaffold_tpu.analysis.concurrency import (
        lint_concurrency_source,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = open(
        os.path.join(
            repo, "frl_distributed_ml_scaffold_tpu", "telemetry",
            "metrics.py",
        )
    ).read()
    assert lint_concurrency_source(src, "metrics.py") == [], (
        lint_concurrency_source(src, "metrics.py")
    )
    anchor = "        with self._reg._lock:\n            self._value += n"
    assert anchor in src
    mutated = src.replace(
        anchor,
        "        with self._reg._lock:\n"
        "            jax.block_until_ready(n)\n"
        "            self._value += n",
    )
    findings = lint_concurrency_source(mutated, "metrics.py")
    blocked = [f for f in findings if f.code == "blocking-under-lock"]
    assert len(blocked) == 1, findings
    assert blocked[0].severity == "error"
    assert "block_until_ready" in blocked[0].message

    indirect = '''
import time
import threading

class Poller:
    def __init__(self):
        self._lock = threading.Lock()

    def _backoff(self):
        time.sleep(0.5)

    def step(self):
        with self._lock:
            self._backoff()
'''
    findings = lint_concurrency_source(indirect, "indirect.py")
    blocked = [f for f in findings if f.code == "blocking-under-lock"]
    assert blocked and all(f.severity == "error" for f in blocked), findings
    assert any("time.sleep" in f.message for f in blocked)


@pytest.mark.fast
@pytest.mark.chaos
def test_concurrency_repo_package_is_clean():
    """The whole package (serving engine, elastic launcher, telemetry,
    native loader) carries no lock-discipline errors: every guarded
    attribute is written under its lock, the acquisition-order graph is
    acyclic, and nothing blocks while holding a lock."""
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_concurrency,
    )

    report = lint_concurrency()
    assert report.meta["files"] > 50  # the glob really covers the package
    assert report.errors() == [], [f.message for f in report.errors()]


# ------------------------------------------------------------ runner/CLI


@pytest.mark.fast
def test_lint_train_step_overlap_recipes_enforce_their_pins():
    """The runner applies the right invariant per recipe class: both
    overlap recipes lint clean at HEAD (their schedules intact)."""
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_train_step,
    )

    for name in ("gpt2_medium_tp_overlap", "gpt2_medium_fsdp_overlap"):
        rep = lint_train_step(name, workdir="/tmp/graft_lint_test")
        assert rep.ok, [f.message for f in rep.errors()]
        census = rep.meta["collective_census"]
        assert census, "overlap recipe census is empty?!"
        prims = {r["primitive"] for r in census}
        if name == "gpt2_medium_tp_overlap":
            assert "ppermute" in prims, prims
            assert "all_gather" not in prims, prims
        else:
            assert "all_gather" in prims and "reduce_scatter" in prims, prims


@pytest.mark.fast
def test_stage_program_lint_clean_and_mutations_trip(monkeypatch):
    """THE pipeline:stage_program family gates (ISSUE 14). Positive: the
    MPMD recipe's per-stage programs lint clean at HEAD (free of
    cross-stage collectives, stage state donated). Mutations: (a) drop
    the stage update donation — the audit fires `stage-not-donated`;
    (b) sneak a pipe-axis psum into a stage program — the census check
    fires `cross-stage-collective` (boundary traffic must be the
    driver's explicit transfers only)."""
    from frl_distributed_ml_scaffold_tpu import parallel
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_stage_programs,
    )
    from frl_distributed_ml_scaffold_tpu.parallel import (
        mpmd_pipeline as mpp,
    )

    rep = lint_stage_programs(workdir="/tmp/graft_lint_test")
    assert rep.ok, [f.message for f in rep.errors()]
    assert rep.meta["pipeline"]["impl"] == "mpmd"
    assert rep.meta["stages"] == rep.meta["pipeline"]["stages"]

    # (a) dropped stage-state donation.
    monkeypatch.setattr(mpp, "_DONATE_STAGE_STATE", False)
    rep_d = lint_stage_programs(workdir="/tmp/graft_lint_test")
    codes = {f.code for f in rep_d.errors()}
    assert "stage-not-donated" in codes, codes
    monkeypatch.setattr(mpp, "_DONATE_STAGE_STATE", True)

    # (b) a collective over the pipe axis inside a stage program.
    real = mpp._stage_forward

    def sabotaged(module, policy, params_c, x, rng, train):
        from frl_distributed_ml_scaffold_tpu.dist.mesh import (
            current_mesh_env,
        )

        y = real(module, policy, params_c, x, rng, train)
        env = current_mesh_env()
        return shard_map_unchecked(
            lambda t: jax.lax.psum(t, "pipe"),
            mesh=env.mesh, in_specs=P(), out_specs=P(),
        )(y)

    monkeypatch.setattr(mpp, "_stage_forward", sabotaged)
    rep_c = lint_stage_programs(workdir="/tmp/graft_lint_test")
    codes = {f.code for f in rep_c.errors()}
    assert "cross-stage-collective" in codes, codes


@pytest.mark.fast
def test_lint_runner_unknown_recipe_refuses():
    from frl_distributed_ml_scaffold_tpu.analysis.runner import (
        lint_train_step,
    )

    with pytest.raises(KeyError, match="RECIPE_OVERRIDES"):
        lint_train_step("no_such_recipe", workdir="/tmp/graft_lint_test")


@pytest.mark.fast
def test_cli_all_recipes_runs_clean_and_emits_json(tmp_path):
    """The acceptance gate: `python tools/graft_lint.py --all-recipes`
    exits 0 on HEAD under JAX_PLATFORMS=cpu and the JSON report covers
    every registered recipe + the serving decode step + hygiene."""
    from frl_distributed_ml_scaffold_tpu.config import list_configs

    out = tmp_path / "report.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
         "--all-recipes", "--json", str(out), "-q",
         "--workdir", str(tmp_path / "wd")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=540,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    reports = json.loads(out.read_text())
    programs = {r["program"] for r in reports}
    for name in list_configs():
        assert f"recipe:{name}" in programs, programs
    assert "serving:decode_step" in programs
    assert "serving:decode_step_int8kv" in programs
    assert "serving:handoff" in programs
    assert "pipeline:stage_program" in programs
    assert "reshard:fsdp_to_tp" in programs
    assert "reshard:tp_row_to_col" in programs
    assert "reshard:restore_even_to_fsdp" in programs
    assert "hygiene:traced-modules" in programs
    assert "robustness:package" in programs
    assert "concurrency:package" in programs
    assert all(r["ok"] for r in reports), [
        r["program"] for r in reports if not r["ok"]
    ]


@pytest.mark.fast
def test_cli_only_selects_named_pass_families(tmp_path):
    """ISSUE 20 satellite: ``--only concurrency`` runs exactly that pass
    (no recipe tracing — fast), exits 0 on HEAD, and stacking ``--only``
    flags unions the families."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = tmp_path / "only.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
         "--only", "concurrency", "--json", str(out), "-q"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=540,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    reports = json.loads(out.read_text())
    assert {r["program"] for r in reports} == {"concurrency:package"}

    out3 = tmp_path / "only3.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
         "--only", "concurrency", "--only", "robustness",
         "--only", "hygiene", "--json", str(out3), "-q"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=540,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    programs = {r["program"] for r in json.loads(out3.read_text())}
    assert programs == {
        "concurrency:package", "robustness:package",
        "hygiene:traced-modules",
    }


@pytest.mark.fast
def test_cli_only_unknown_pass_refused(tmp_path):
    """A typo'd pass name must fail loudly (argparse choices), not lint
    nothing and exit 0; --only also refuses to combine with --no-*."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
         "--only", "concurency", "-q"],  # sic: typo'd
        capture_output=True, text=True, env=env, cwd=repo, timeout=120,
    )
    assert proc.returncode != 0
    assert "invalid choice" in proc.stderr, proc.stderr

    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
         "--only", "hygiene", "--no-serving", "-q"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=120,
    )
    assert proc.returncode != 0
    assert "--no-" in proc.stderr, proc.stderr


@pytest.mark.fast
def test_cli_exits_nonzero_on_error_finding(tmp_path, monkeypatch):
    """severity:error ⇒ non-zero exit: lint a recipe subset with an
    absurd materialization budget (1 byte) — every recipe trips it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
         "--recipe", "mnist_mlp", "--no-serving", "--no-hygiene",
         "--budget-mb", "0.000001", "-q",
         "--workdir", str(tmp_path / "wd")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "over-budget" in proc.stdout


def test_cli_census_baseline_roundtrip_and_diff(tmp_path):
    """--save-census then --against: identical program ⇒ no census
    warnings; a doctored baseline (one ring removed) ⇒ census-added."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    census_path = tmp_path / "census.json"
    base_cmd = [
        sys.executable, os.path.join(repo, "tools", "graft_lint.py"),
        "--recipe", "gpt2_medium_tp_overlap", "--no-serving",
        "--no-hygiene", "-q", "--workdir", str(tmp_path / "wd"),
    ]
    proc = subprocess.run(
        base_cmd + ["--save-census", str(census_path)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    baseline = json.loads(census_path.read_text())
    assert baseline["recipe:gpt2_medium_tp_overlap"]

    proc2 = subprocess.run(
        base_cmd + ["--against", str(census_path), "--json",
                    str(tmp_path / "r.json")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300,
    )
    assert proc2.returncode == 0
    reports = json.loads((tmp_path / "r.json").read_text())
    assert not any(
        f["code"].startswith("census-")
        for r in reports for f in r["findings"]
    )

    # Doctor the baseline: drop one record — the diff must flag it added.
    key = "recipe:gpt2_medium_tp_overlap"
    baseline[key] = baseline[key][1:]
    census_path.write_text(json.dumps(baseline))
    proc3 = subprocess.run(
        base_cmd + ["--against", str(census_path), "--json",
                    str(tmp_path / "r3.json")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300,
    )
    assert proc3.returncode == 0  # census drift is a warning, not an error
    reports3 = json.loads((tmp_path / "r3.json").read_text())
    assert any(
        f["code"] == "census-added"
        for r in reports3 for f in r["findings"]
    ), reports3


# ------------------------------------------------------------ perf ledger


def test_perf_ledger_check_matches_committed_baseline(tmp_path):
    """ISSUE 8 acceptance gate: `python tools/perf_ledger.py --check`
    round-trips green against the committed PERF_LEDGER.json — the
    analytic census/FLOPs of the baseline recipes are bit-deterministic
    on the CPU sim, so this is the census regression gate (counts that
    repeat exactly; never a speed claim)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "perf_ledger.py"),
         "--check", "--workdir", str(tmp_path / "wd")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "rows match" in proc.stdout
    # The committed baseline carries both sides of the join: analytic
    # census/flops AND the measured provenance columns.
    baseline = json.loads(
        open(os.path.join(repo, "PERF_LEDGER.json")).read()
    )
    rows = baseline["rows"]
    assert "serving:decode_step" in rows
    tp = rows["recipe:gpt2_medium_tp_overlap"]
    assert tp["collectives"]["ppermute"]["total_bytes"] > 0  # the rings
    assert tp["flops_per_step"] > 0
    assert tp["measured"]["step_time_p50_s"] > 0
    # A CPU-sim wall time is provenance, never a utilization: the ledger
    # derives no MFU / achieved FLOP/s from it.
    assert "attribution" not in tp
    assert rows["serving:decode_step"]["measured"]["tpot_p50_s"] > 0


def test_perf_ledger_check_exits_nonzero_on_mutation(tmp_path):
    """The mutation gate: doctor the committed baseline (census bytes and
    FLOPs) — --check must report the drift per field and exit 1."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    baseline = json.loads(
        open(os.path.join(repo, "PERF_LEDGER.json")).read()
    )
    tp = baseline["rows"]["recipe:gpt2_medium_tp_overlap"]
    tp["flops_per_step"] += 1
    tp["collectives"]["ppermute"]["total_bytes"] //= 2
    doctored = tmp_path / "doctored.json"
    doctored.write_text(json.dumps(baseline))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "perf_ledger.py"),
         "--check", "--baseline", str(doctored),
         "--workdir", str(tmp_path / "wd")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "flops_per_step drifted" in proc.stdout
    assert "collectives drifted" in proc.stdout
