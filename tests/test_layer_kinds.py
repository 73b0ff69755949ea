"""A model with two kinds of layer (window and full attention mixed, unlike head
counts, a dense feed-forward before sparse ones with a shared expert), served
through the pools by layer kind, against its plain reference
(benchmarks/reference/laguna.py) on seeded random weights at a small size.

Tolerances. The tests run the fp32 policy on the CPU, where a float32 matrix
product is exact to rounding: the program and the reference then differ by the
order of their sums only, a few 1e-5 on logits of size 4. LOGIT_TOL = 2e-4
leaves that a factor of ten and is a thousandth of what the same model reads
under the bf16 policy (0.1 and more: ``test_the_tolerance_fails_bf16`` pins
it), so computing in bf16 where float32 is stated fails every comparison here.
"""

from __future__ import annotations

import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from frl_distributed_ml_scaffold_tpu.config import (
    ExperimentConfig,
    config_from_dict,
)
from frl_distributed_ml_scaffold_tpu.config.schema import RopeConfig
from frl_distributed_ml_scaffold_tpu.models import create_model
from frl_distributed_ml_scaffold_tpu.models.generation import (
    _decode_step,
    _prefill,
    generate,
    splice_kind_pools,
)
from frl_distributed_ml_scaffold_tpu.models.gpt import (
    apply_rope,
    init_paged_cache,
    rope_inv_freq,
    window_table_blocks,
)
from frl_distributed_ml_scaffold_tpu.precision import get_policy
from frl_distributed_ml_scaffold_tpu.serving import (
    DisaggServingEngine,
    ServingEngine,
)
from frl_distributed_ml_scaffold_tpu.telemetry import MetricsRegistry, Tracer

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"
)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from lib.weights import flat, make_params  # noqa: E402
from reference import laguna  # noqa: E402

da = importlib.import_module(
    "frl_distributed_ml_scaffold_tpu.ops.decode_attention")
ge = importlib.import_module(
    "frl_distributed_ml_scaffold_tpu.ops.grouped_experts")

LOGIT_TOL = 2e-4
VOCAB, WINDOW, BLOCK = 128, 16, 8

#: Both layer types, 6 against 8 query heads over 2 KV heads, a window shorter
#: than the contexts, a dense first layer, 8 experts with 2 a token and a
#: shared one, yarn over half of a head's dimensions in the full layers.
SIZES = dict(
    vocab_size=VOCAB, num_layers=5, num_heads=6, num_heads_sliding=8,
    num_kv_heads=2, head_dim=16, hidden_dim=32, seq_len=128,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"],
    sliding_window=WINDOW, norm="rmsnorm", layer_norm_epsilon=1e-6,
    position="rope",
    rope=dict(rope_type="yarn", rope_theta=500000.0, partial_rotary_factor=0.5,
              factor=4.0, original_max_position_embeddings=32, beta_fast=8.0,
              beta_slow=1.0, attention_factor=0.0),
    rope_sliding=dict(rope_type="default", rope_theta=10000.0,
                      partial_rotary_factor=1.0),
    bias=False, attention_gate=True, mlp="swiglu", mlp_dim=64,
    dense_layers=[0], tie_embeddings=False,
    moe=dict(num_experts=8, top_k=2, routing="dropless", expert_dim=16,
             num_shared_experts=1, shared_expert_dim=16, score_func="sigmoid",
             norm_topk_prob=True, routed_scaling_factor=2.5),
)


def build(policy="fp32", seed=7, **over):
    sizes = dict(SIZES, **over)
    cfg = config_from_dict(
        ExperimentConfig, {"model": dict(sizes, family="gpt")}).model
    model = create_model(cfg, get_policy(policy))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False)["params"])
    params = make_params(shapes, seed, dtype=get_policy(policy).param_dtype)
    return model, params, sizes


def reference_logits(params, tokens, sizes):
    """The reference's logits [B, T, V], one compiled program a shape."""
    @jax.jit
    def run(pflat, tokens):
        feats = laguna.features(pflat, tokens, sizes)
        return laguna.head(pflat, feats, 0, sizes["vocab_size"], sizes)

    with jax.default_matmul_precision("highest"):
        return np.asarray(run(flat(params), jnp.asarray(tokens)))


def forward(model, params, tokens):
    return np.asarray(jax.jit(
        lambda p, t: model.apply({"params": p}, t)[0])(params, jnp.asarray(tokens)),
        np.float32)


@pytest.fixture(scope="module")
def fp32():
    return build()


def test_parameter_tree_is_the_references_layout(fp32):
    model, params, sizes = fp32
    want = flat(laguna.param_shapes(sizes))
    have = flat(params)
    assert sorted(have) == sorted(want)
    assert all(have[k].shape == want[k].shape for k in want)
    # Layers apart: 6 query heads in a full layer, 8 in a sliding one; layer
    # 0 dense, the rest sparse with a shared expert.
    assert have["layer_0/attn/query/kernel"].shape == (32, 6 * 16)
    assert have["layer_1/attn/query/kernel"].shape == (32, 8 * 16)
    assert "layer_0/mlp/w1/kernel" in have and "layer_1/moe/w1" in have


def test_full_forward_matches_the_reference(fp32):
    model, params, sizes = fp32
    tokens = np.random.default_rng(1).integers(0, VOCAB, size=(2, 70))
    got = forward(model, params, tokens)
    assert np.abs(got - reference_logits(params, tokens, sizes)).max() < LOGIT_TOL


def test_the_tolerance_fails_bf16():
    """What LOGIT_TOL is for: the same comparison under the bf16 policy reads
    a thousand times the tolerance."""
    model, params, sizes = build("bf16")
    tokens = np.random.default_rng(1).integers(0, VOCAB, size=(1, 70))
    gap = np.abs(forward(model, params, tokens)
                 - reference_logits(params, tokens, sizes)).max()
    assert gap > 100 * LOGIT_TOL, gap


@pytest.mark.parametrize("route", ["dense", "kernel"])
def test_prefill_then_decode_through_both_pools_matches_the_reference(
    fp32, route, monkeypatch
):
    """Three rows ragged in one batch: a context that stays under the window
    (3 -> 13 of 16), one that crosses it during decode (10 -> 20) and one
    several windows long (50 -> 60). Each row's prompt is prefilled (the
    contiguous cache) and spliced into the pools — every block into the full
    kind's, the last window into the sliding kind's ring — and then every
    decode step's logits, through the paged kernel's dense route and through
    the Pallas kernels themselves (interpreted; heads of 128, because query
    heads that share a KV head's lanes come out of the kernel in whole lane
    slices, and any other head size takes the dense route), are compared
    with the reference's full forward at that position."""
    model, params, sizes = fp32 if route == "dense" else build(head_dim=128)
    cfg = model.config
    kernel_calls, kernel = [], da._flash_paged_verify

    def counted(*args, **kw):
        kernel_calls.append(kw["name"])
        return kernel(*args, **kw)

    monkeypatch.setattr(da, "_flash_paged_verify", counted)
    prompts, steps = [3, 10, 50], 10
    rng = np.random.default_rng(5)
    rows = [rng.integers(0, VOCAB, size=n + steps) for n in prompts]
    # Causal: padding on the right leaves a row's own positions as they are,
    # so the three rows go through the reference as one batch.
    padded_rows = np.zeros((3, max(prompts) + steps), np.int64)
    for b, r in enumerate(rows):
        padded_rows[b, :len(r)] = r
    want = reference_logits(params, padded_rows, sizes)

    places = window_table_blocks(cfg, BLOCK)
    assert places == 3
    paged = model.clone(kv_block_size=BLOCK, kv_pool_blocks=1 + 3 * 16)
    cache = init_paged_cache(paged, 3)
    full_tbl = np.zeros((3, 16), np.int32)
    ring_tbl = np.zeros((3, places), np.int32)
    was = da.FORCE_INTERPRET, ge.FORCE_INTERPRET
    da.FORCE_INTERPRET = ge.FORCE_INTERPRET = True if route == "kernel" else None
    try:
        for b, n in enumerate(prompts):
            full_tbl[b] = 1 + b * 16 + np.arange(16)
            ring_tbl[b] = 1 + b * places + np.arange(places)
            bucket = max(BLOCK, 1 << (n - 1).bit_length())
            padded = np.zeros((1, bucket), np.int32)
            padded[0, bucket - n:] = rows[b][:n]
            at_bucket = model.clone(cache_len=bucket)
            logits, slot_cache = jax.jit(
                lambda p, t, l, m=at_bucket: _prefill(m, p, t, l)
            )(params, jnp.asarray(padded), jnp.asarray([n], jnp.int32))
            assert np.abs(np.asarray(logits[0]) - want[b][n - 1]).max() < LOGIT_TOL
            n_g = -(-n // BLOCK)
            n_w = min(places, n_g)
            cache = jax.jit(
                lambda c, sc, f, w, slot: splice_kind_pools(
                    c, sc, f, w, slot, cfg=cfg, block_size=BLOCK)
            )(cache, slot_cache, jnp.asarray(full_tbl[b, :n_g]),
              jnp.asarray([ring_tbl[b, j % places]
                           for j in range(n_g - n_w, n_g)], jnp.int32), b)
        cache = {**cache, "block_tables": jnp.asarray(full_tbl),
                 "block_tables_sliding": jnp.asarray(ring_tbl)}
        step = jax.jit(lambda c, t: _decode_step(paged, params, c, t))
        for i in range(steps):
            tok = jnp.asarray([rows[b][n + i] for b, n in enumerate(prompts)],
                              jnp.int32)
            logits, cache = step(cache, tok)
            for b, n in enumerate(prompts):
                gap = np.abs(np.asarray(logits[b]) - want[b][n + i]).max()
                assert gap < LOGIT_TOL, (route, b, i, gap)
            # Two sparse full/sliding layers each: 3 live rows x 2 experts
            # a token in each of the 4 sparse layers.
            assert int(cache["moe_stats"][1]) == 3 * 2 * 4
    finally:
        da.FORCE_INTERPRET, ge.FORCE_INTERPRET = was
    assert sorted(kernel_calls) == (
        ["attn_mixed_decode_full"] * 2 + ["attn_mixed_decode_sliding"] * 3
        if route == "kernel" else [])


def _served(eng, requests):
    ids = [eng.submit(p, k) for p, k in requests]
    done, seen = {}, []
    while eng.pending:
        for c in eng.step():
            done[c.id] = c
        seen.append(([len(h) for h in eng._wslot_blocks], len(eng._wfree),
                     len(eng._free), eng._reserved_future))
    return [done[i] for i in ids], seen


def test_engine_tokens_equal_generate_and_the_allocator_holds_a_window(fp32):
    """Five ragged requests through three slots and a full pool small enough
    to make the queue wait. The engine's tokens are ``generate()``'s; a slot
    never holds more than ``ceil(W / block) + 1`` sliding blocks whatever its
    context; a block given back is free again in that step (held + free is
    the pool at every step, a ring for each slot, and what the full kind has
    reserved is there to be taken); and when all have finished both kinds are
    as they were found."""
    model, params, _ = fp32
    eng = ServingEngine(model, params, num_slots=3, kv_block_size=BLOCK,
                        kv_pool_blocks=25, temperature=0.0)
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, VOCAB, size=n), k)
                for n, k in [(5, 30), (20, 40), (50, 60), (9, 3), (33, 17)]]
    done, seen = _served(eng, requests)
    # generate() on the five as one left-padded batch (greedy: a request's
    # tokens are the first of its row whatever the row's budget).
    longest, most = max(len(p) for p, _ in requests), max(k for _, k in requests)
    batch = np.zeros((len(requests), longest), np.int32)
    for b, (prompt, _) in enumerate(requests):
        batch[b, longest - len(prompt):] = prompt
    want = np.asarray(jax.jit(lambda p, t, l: generate(
        model, p, t, max_new_tokens=most, temperature=0.0, prompt_lengths=l,
    ))(params, jnp.asarray(batch),
       jnp.asarray([len(p) for p, _ in requests], jnp.int32)))
    for b, (comp, (prompt, k)) in enumerate(zip(done, requests)):
        assert comp.finish_reason == "length"
        assert np.array_equal(comp.tokens[len(prompt):], want[b, longest:longest + k])
    assert eng.window_places == math.ceil(WINDOW / BLOCK) + 1
    usable = eng.window_pool_blocks - 1
    assert usable == eng.num_slots * eng.window_places
    for held, wfree, free, future in seen:
        assert max(held) <= eng.window_places
        assert sum(held) + wfree == usable
        assert 0 <= future <= free
    assert max(max(h) for h, *_ in seen) == eng.window_places
    assert eng.stats["window_blocks_released"] > 0
    assert eng.stats["admission_deferred"] > 0  # the full kind made it wait
    assert sorted(eng._wfree) == list(range(1, eng.window_pool_blocks))
    assert sorted(eng._free) == list(range(1, eng.pool_blocks))
    assert eng._reserved_future == 0
    assert not eng._wtables.any() and not eng._tables.any()


def test_a_request_the_full_kind_can_never_hold_is_refused_at_submit(fp32):
    model, params, _ = fp32
    eng = ServingEngine(model, params, num_slots=2, kv_block_size=BLOCK,
                        kv_pool_blocks=6, temperature=0.0)
    with pytest.raises(ValueError, match="could never admit"):
        eng.submit(np.arange(40) % VOCAB, 20)  # 8 blocks of 5 usable
    assert eng.submit(np.arange(20) % VOCAB, 10) == 0  # 4 blocks: fine


@pytest.mark.parametrize("what,kwargs", [
    ("the bucketed cache", dict()),
    ("the prefix cache", dict(kv_block_size=BLOCK, prefix_cache=True)),
    ("speculation", dict(kv_block_size=BLOCK, speculate="ngram", speculate_k=2)),
])
def test_what_two_kinds_cannot_do_yet_is_refused_at_construction(
    fp32, what, kwargs
):
    model, params, _ = fp32
    with pytest.raises(NotImplementedError, match=what):
        ServingEngine(model, params, num_slots=2, **kwargs)


def test_quantised_pools_and_the_disaggregated_engine_are_refused(fp32):
    model, params, sizes = fp32
    quant, qparams, _ = build(kv_cache_quant="int8")
    with pytest.raises(NotImplementedError, match="quantised pools"):
        ServingEngine(quant, qparams, num_slots=2, kv_block_size=BLOCK)
    with pytest.raises(NotImplementedError, match="disaggregated engine"):
        DisaggServingEngine(model, params, num_slots=2, kv_block_size=BLOCK)
    eng = ServingEngine(model, params, num_slots=2, kv_block_size=BLOCK)
    assert eng.prefix_cache_enabled is False  # not asked for: off, not refused
    for call, args in ((eng.park_slot, (0,)), (eng.resume_parked, ({}, 0)),
                       (eng.respread_pool, (None,))):
        with pytest.raises(NotImplementedError, match="sliding-window layers"):
            call(*args)


def test_spans_and_gauges_of_the_two_kinds(fp32):
    """The `decode` span's counts: positions one full and one sliding layer
    attend over, experts touched and pairs (back with the tokens); `admit`'s
    blocks reserved of each kind; the gauges and the counter."""
    model, params, _ = fp32
    registry, tracer = MetricsRegistry(), Tracer(capacity=100_000)
    eng = ServingEngine(model, params, num_slots=2, kv_block_size=BLOCK,
                        temperature=0.0, telemetry=registry, tracer=tracer)
    eng.submit(np.arange(30) % VOCAB, 12)
    eng.submit(np.arange(7) % VOCAB, 12)
    eng.run()
    spans = tracer.spans()
    decodes = [s for s in spans if s["name"] == "decode"]
    first = decodes[0]
    # Both rows live: they write positions 30 and 7 and attend 31 and 8.
    assert first["kv_tokens_full"] == 31 + 8
    assert first["kv_tokens_window"] == WINDOW + 8
    assert first["expert_pairs"] == 2 * 2 * 4  # rows x experts a token x layers
    assert 4 <= first["experts_touched"] <= first["expert_pairs"]
    assert first["window_pool_blocks"] == eng.window_pool_blocks - 1
    admits = [s for s in spans if s["name"] == "admit" and s["admitted"]]
    # 30 + 12 tokens: 6 blocks of the full kind, a window's 3 of the sliding;
    # 7 + 12: 3 blocks of each.
    assert sum(s["reserved_full"] for s in admits) == 6 + 3
    assert sum(s["reserved_sliding"] for s in admits) == 3 + 3
    snap = registry.snapshot()
    # All finished: nothing of either kind in use, and blocks were given back.
    assert snap["serve_pool_blocks_in_use_full"] == 0
    assert snap["serve_pool_blocks_in_use_sliding"] == 0
    assert snap["serve_window_blocks_released_total"] == eng.stats[
        "window_blocks_released"]
    assert eng.stats["window_blocks_released"] >= 1


def test_pools_by_layer_kind_land_every_step_for_now(fp32):
    """Over pools kept by layer kind the engine does not look ahead yet
    (``ServingEngine._looks_ahead``; PERF.md section 7 says what waits for
    it): every step starts from the host's tokens, so no `decode` span is
    `ahead`, nothing is ever in flight between two steps and no row is
    computed in vain."""
    model, params, _ = fp32
    registry, tracer = MetricsRegistry(), Tracer(capacity=100_000)
    eng = ServingEngine(model, params, num_slots=2, kv_block_size=BLOCK,
                        temperature=0.0, telemetry=registry, tracer=tracer)
    assert not eng._looks_ahead
    eng.submit(np.arange(30) % VOCAB, 9)
    eng.submit(np.arange(7) % VOCAB, 6)
    while eng.pending:
        eng.step()
        assert eng._inflight is None
    decodes = [s for s in tracer.spans() if s["name"] == "decode"]
    assert len(decodes) == eng.stats["decode_steps"] == 8
    assert [s["ahead"] for s in decodes] == [0] * 8
    assert sum(s["active"] for s in decodes) == 8 + 5
    snap = registry.snapshot()
    assert snap.get("serve_decode_ahead_total", 0) == 0
    assert snap.get("serve_decode_wasted_rows_total", 0) == 0


# ------------------------------------------------------------ expert layer


def _moe_layer(top_k=2):
    from frl_distributed_ml_scaffold_tpu.models.moe import MoEMlp

    model, params, sizes = build(moe=dict(SIZES["moe"], top_k=top_k))
    layer = MoEMlp(model.config, jnp.float32)
    return layer, jax.tree.map(np.asarray, params["layer_1"]["moe"]), sizes


def _per_token_sum(p, y, chosen, weights):
    """The plain sum: each token through each of its experts in turn."""
    silu = lambda v: v / (1.0 + np.exp(-v))  # noqa: E731
    out = np.zeros_like(y)
    f = p["w1"].shape[-1]  # the down-projections lie flat, expert after expert
    for t in range(y.shape[0]):
        for e, w in zip(chosen[t], weights[t]):
            h = silu(y[t] @ p["w1"][e]) * (y[t] @ p["w3"][e])
            out[t] += w * (h @ p["w2"][e * f:(e + 1) * f])
    sh = {k: p["shared"][k]["kernel"] for k in ("w1", "w3", "w2")}
    return out + (silu(y @ sh["w1"]) * (y @ sh["w3"])) @ sh["w2"]


@pytest.mark.parametrize("route", ["einsum", "kernel"])
def test_grouped_product_equals_the_per_token_sum(route):
    layer, p, sizes = _moe_layer()
    y = np.random.default_rng(3).normal(size=(2, 9, 32)).astype(np.float32)
    was = ge.FORCE_INTERPRET
    ge.FORCE_INTERPRET = True if route == "kernel" else None
    try:
        got, stats = layer.apply({"params": p}, jnp.asarray(y), train=False)
    finally:
        ge.FORCE_INTERPRET = was
    flat_y = y.reshape(-1, 32).astype(np.float64)
    scores = 1.0 / (1.0 + np.exp(-(flat_y @ p["router"]["kernel"])))
    chosen = np.argsort(-scores, axis=1)[:, :2]
    top = np.take_along_axis(scores, chosen, 1)
    weights = top / top.sum(1, keepdims=True) * 2.5
    p64 = jax.tree.map(lambda a: a.astype(np.float64), p)
    want = _per_token_sum(p64, flat_y, chosen, weights)
    assert np.abs(np.asarray(got).reshape(-1, 32) - want).max() < 1e-4
    assert int(stats[1]) == 18 * 2 and int(stats[0]) == len(set(chosen.ravel()))


@pytest.mark.parametrize("pairs,experts", [(40, 8), (3000, 16)])
def test_grouped_layout_seats_every_pair_on_a_tile_of_its_expert(pairs, experts):
    """Each expert's rows start on a tile boundary, so a tile has one expert;
    every pair that is to be computed has a row of its own on such a tile;
    the tiles used are what the groups need and no more. Both tile sizes
    (a decode batch's 16 rows, a prefill's 128)."""
    tm = ge.tile_rows(pairs)
    assert tm == (16 if pairs <= 2048 else 128)
    rng = np.random.default_rng(pairs)
    ids = rng.integers(0, experts, size=pairs)
    ids[::5] = experts  # a padding column, a dead slot row: not computed
    ids[ids == 3] = 4  # and an expert that gets nothing
    dest, src, tile_expert, n_used, counts = (
        np.asarray(a) for a in ge.grouped_layout(jnp.asarray(ids, jnp.int32),
                                                 experts, tm))
    live = ids < experts
    assert np.array_equal(counts, np.bincount(ids[live], minlength=experts))
    assert len(set(dest[live])) == live.sum() and not dest[~live].any()
    assert np.array_equal(src[dest[live]], np.flatnonzero(live))
    assert np.array_equal(tile_expert[dest[live] // tm], ids[live])
    assert int(n_used[0]) == sum(-(-c // tm) for c in counts)
    assert dest[live].max() < int(n_used[0]) * tm
    assert len(tile_expert) == -(-pairs // tm) + experts  # room whatever the routing


def test_one_expert_gets_every_token_and_none_is_dropped():
    """Routing so skewed that expert 5 is every token's first choice and
    expert 2 every token's second: 64 tokens on two experts of eight, far
    past any capacity. The layer still computes every pair (the reference's
    sum over experts, no drop), and says it touched two experts."""
    layer, p, sizes = _moe_layer()
    router = np.full((32, 8), -4.0, np.float32)
    router[:, 5], router[:, 2] = 4.0, 2.0
    p = dict(p, router={"kernel": router})
    # Positive inputs: every token scores expert 5, then 2, above the rest.
    y = np.abs(np.random.default_rng(4).normal(size=(1, 64, 32))).astype(np.float32)
    got, stats = layer.apply({"params": p}, jnp.asarray(y), train=False)
    assert [int(s) for s in stats] == [2, 128]
    ref_p = jax.tree.map(jnp.asarray, {
        "ln2/scale": np.ones((32,), np.float32), "moe/router/kernel": router,
        "moe/w1": p["w1"], "moe/w3": p["w3"], "moe/w2": p["w2"],
        **{f"moe/shared/{k}/kernel": p["shared"][k]["kernel"]
           for k in ("w1", "w3", "w2")}})
    # The reference's layer is x + f(RMSNorm(x)): hand it rows of unit RMS.
    x = jnp.asarray(y[0] / np.sqrt((y[0] ** 2).mean(-1, keepdims=True) + 1e-6))
    with jax.default_matmul_precision("highest"):
        want = laguna._feed_forward(x, ref_p, sizes, 1, False) - x
        got_x, _ = layer.apply({"params": p}, x[None], train=False)
    assert np.abs(np.asarray(got_x[0]) - np.asarray(want)).max() < LOGIT_TOL
    # A token the mask leaves out goes to no expert.
    mask = jnp.arange(64)[None, :] >= 60
    _, stats = layer.apply({"params": p}, jnp.asarray(y), train=False,
                           token_mask=mask)
    assert [int(s) for s in stats] == [2, 8]


# ------------------------------------------------------------------ rotary


def test_plain_and_partial_rotary_against_their_closed_form():
    """Dimension i of the rotating part pairs with i + rot/2 and turns by
    position * theta ** (-2i / rot); what lies past ``rot`` passes."""
    rope = RopeConfig(rope_theta=10000.0, partial_rotary_factor=0.5)
    inv, mscale, rot = rope_inv_freq(rope, 16)
    assert rot == 8 and mscale == 1.0
    assert np.allclose(inv, [10000.0 ** (-2 * i / 8) for i in range(4)])
    x = np.zeros((1, 3, 1, 16), np.float32)
    x[..., 1] = 1.0  # unit vector on rotating dimension 1
    x[..., 12] = 7.0  # and a dimension that does not rotate
    pos = np.array([[0, 5, 11]])
    out = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos), rope, 16))
    ang = pos[0] * 10000.0 ** (-2 / 8)
    assert np.allclose(out[0, :, 0, 1], np.cos(ang), atol=1e-6)
    assert np.allclose(out[0, :, 0, 5], np.sin(ang), atol=1e-6)
    assert np.allclose(out[..., 12], 7.0)
    rest = np.delete(out, [1, 5, 12], axis=-1)
    assert np.allclose(rest, 0.0, atol=1e-7)
    # The reference's own tables say the same.
    cos, sin, r = laguna.rotary_tables(
        dict(rope_theta=10000.0, partial_rotary_factor=0.5), 16, jnp.asarray(pos[0]))
    assert r == 8 and np.allclose(cos[:, 1], np.cos(ang), atol=1e-6)


def test_yarn_against_its_closed_form():
    """The published full-attention parameters: rot = 64 of 128 dimensions,
    theta 5e5, factor 64 over an original context of 4096. A dimension that
    turns more than beta_fast = 64 times over the original context keeps its
    frequency, one under beta_slow = 1 turn is divided by the factor, linear
    between; cos and sin carry 0.1 ln(64) + 1 = 1.41588..., which is the
    published attention_factor."""
    rope = RopeConfig(rope_type="yarn", rope_theta=500000.0,
                      partial_rotary_factor=0.5, factor=64.0,
                      original_max_position_embeddings=4096, beta_fast=64.0,
                      beta_slow=1.0, attention_factor=1.4158883083359672)
    inv, mscale, rot = rope_inv_freq(rope, 128)
    assert rot == 64
    assert mscale == pytest.approx(0.1 * math.log(64.0) + 1.0, rel=1e-12)
    plain = np.array([500000.0 ** (-2 * i / 64) for i in range(32)])
    turns = 4096 * plain / (2 * math.pi)  # over the original context
    dim_of = lambda n: 64 * math.log(4096 / (n * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(500000.0))
    low, high = math.floor(dim_of(64.0)), math.ceil(dim_of(1.0))
    assert (low, high) == (5, 16)
    assert np.all(turns[:low] > 64.0) and np.all(turns[high + 1:] < 1.0)
    assert np.allclose(inv[:low + 1], plain[:low + 1], rtol=1e-12)
    assert np.allclose(inv[high:], plain[high:] / 64.0, rtol=1e-12)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    assert inv[mid] == pytest.approx(
        plain[mid] * (1 - ramp) + plain[mid] / 64.0 * ramp, rel=1e-12)
    zero = RopeConfig(rope_type="yarn", factor=64.0, rope_theta=500000.0,
                      original_max_position_embeddings=4096, beta_fast=64.0)
    assert rope_inv_freq(zero, 128)[1] == pytest.approx(mscale, rel=1e-12)
    cos, _, _ = laguna.rotary_tables(
        dict(rope_type="yarn", rope_theta=500000.0, partial_rotary_factor=0.5,
             factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
             beta_slow=1.0, attention_factor=1.4158883083359672),
        128, jnp.asarray([0, 3]))
    assert np.allclose(cos[1], mscale * np.cos(3 * inv), rtol=1e-5)


def test_gpt2_defaults_keep_their_parameter_tree():
    """The fields that describe another architecture leave GPT-2's tree as it
    was: one scanned `blocks` stack with biases, learned positions and no
    head of its own."""
    from frl_distributed_ml_scaffold_tpu.config.schema import GPTConfig
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT

    model = GPT(GPTConfig(num_layers=2, hidden_dim=32, num_heads=4, seq_len=16,
                          vocab_size=64), get_policy("fp32"))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False)["params"])
    assert sorted(flat(shapes)) == sorted(
        ["wte/embedding", "wpe", "ln_f/scale", "ln_f/bias"]
        + [f"blocks/{m}/{p}" for m in ("ln1", "ln2") for p in ("scale", "bias")]
        + [f"blocks/attn/{m}/{p}" for m in ("query", "key", "value", "out")
           for p in ("kernel", "bias")]
        + [f"blocks/mlp/{m}/{p}" for m in ("fc_in", "fc_out")
           for p in ("kernel", "bias")])
