"""Autoregressive generation for the GPT family: prefill + KV-cache decode.

The training scaffold's inference story (call stack (e) in SURVEY.md §3 is
eval-forward; this extends it to sampling). TPU-idiomatic shape: one
compiled **prefill** over the whole prompt writes every layer's K/V cache,
then one compiled **decode step** inside ``lax.scan`` appends a token per
iteration — static shapes throughout (the cache is pre-sized to
``config.seq_len``), so the entire generate call is two XLA programs no
matter how many tokens are produced.

Sampling: greedy (``temperature=0``), temperature, top-k, and nucleus
(top-p) — all pure functions of the passed rng key, so generation is
reproducible.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def _sample(
    logits: jax.Array, rng, *, temperature: float, top_k: int,
    top_p: float = 0.0,
):
    """[B, V] logits -> [B] sampled token ids (fp32 for stable softmax)."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0 and top_k < logits.shape[-1]:  # k >= V keeps everything
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]  # O(V) threshold
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)
    if 0.0 < top_p < 1.0:
        # Nucleus: keep the smallest prefix of the sorted distribution
        # whose mass reaches p (the token crossing the threshold is kept —
        # the standard inclusive nucleus). The keep mask is scattered back
        # by POSITION, not compared by logit value: value thresholding
        # would keep every token tied with the boundary logit, silently
        # disabling the filter on uniform/tied distributions.
        b = logits.shape[0]
        # Negate for a genuinely stable descending order (reversing an
        # ascending stable sort would invert tie order at the boundary).
        order = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = mass_before < top_p  # always keeps the top token
        keep = jnp.zeros(logits.shape, bool).at[
            jnp.arange(b)[:, None], order
        ].set(keep_sorted)
        logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def next_cache_bucket(seq_len: int, needed: int, floor: int = 8) -> int:
    """The serving bucket policy: smallest power of two >= ``needed``
    (and >= ``floor``), with ``seq_len`` itself as the terminal bucket.
    Powers of two keep the number of distinct compiled decode programs at
    log2(seq_len) while short requests stop paying full-context cache
    traffic."""
    if needed > seq_len:
        raise ValueError(f"needed cache {needed} exceeds seq_len {seq_len}")
    b = 1 << max(needed, floor, 1).bit_length()
    if b // 2 >= max(needed, floor, 1):
        b //= 2
    return min(b, seq_len)


def _bucketed(model: Any, cache_len: int | None, needed: int) -> Any:
    """Clone the model with its decode cache sized to the active bucket
    (``cache_len=None`` = auto policy; pass ``model.config.seq_len`` for
    the legacy full-context cache)."""
    if cache_len is None:
        cache_len = next_cache_bucket(model.config.seq_len, needed)
    if cache_len < needed:
        raise ValueError(
            f"cache_len={cache_len} cannot hold prompt+new={needed} tokens"
        )
    return model.clone(cache_len=int(cache_len))


def _take_logits(out):
    """MoE models return (logits, aux) tuples from apply."""
    return out[0] if isinstance(out, tuple) else out


def _prefill(model: Any, params: Any, prompt: jax.Array,
             lengths: jax.Array | None, cache: Any = None):
    """One pass over the (possibly left-padded ragged) prompt creates +
    fills every layer's KV cache; returns (last-position logits [B, V],
    cache). Prompts are right-aligned, so logits[:, -1] is every row's
    real last token regardless of raggedness. The SHARED decode entry:
    generate and beam_search both start here, so they cannot drift.

    ``cache`` seeds the cache collection instead of the lazy zero init:
    the serving engine's shared-prefix path prefills only a prompt's
    SUFFIX against an initial cache whose leading positions hold the
    shared prefix's K/V (gathered block-wise from the pool) and whose
    ``cache_index``/``pos_index`` start at the prefix length — the
    attention math is then identical to a full-prompt prefill, minus
    the prefix tokens' projection/score work."""
    variables = {"params": params}
    if cache is not None:
        variables["cache"] = cache
    logits, vars_out = model.apply(
        variables, prompt, decode=True, lengths=lengths,
        mutable=["cache"],
    )
    return _take_logits(logits)[:, -1], vars_out["cache"]


def _decode_step(model: Any, params: Any, cache: Any, tok: jax.Array):
    """One single-token decode step for every row: returns (logits [B, V],
    updated cache). The SHARED step generate and beam_search scan over —
    both therefore route through the same ops/decode_attention entry
    point (flash-decode kernel or dense, per config.decode_attention)."""
    logits, vars_out = model.apply(
        {"params": params, "cache": cache},
        tok[:, None],
        decode=True,
        mutable=["cache"],
    )
    return _take_logits(logits)[:, 0], vars_out["cache"]


def _verify_step(model: Any, params: Any, cache: Any, toks: jax.Array):
    """One batched speculative-VERIFY forward (ISSUE 11): ``toks [B, T]``
    is each row's last accepted token followed by T-1 draft tokens;
    returns (logits ``[B, T, V]`` — ALL positions, unlike ``_prefill`` —
    and the updated cache). On a paged-cache model this is the verify
    tile: all T K/V are scattered into the pool and every position
    scores causally against the cache in one pass
    (ops/decode_attention.paged_verify_attention), so position 0's
    logits equal what ``_decode_step`` would produce and greedy
    acceptance against them is EXACT — which is the bit-exact contract
    speculative decoding rides. The cache indices advance by T
    unconditionally; rejected positions are rolled back afterwards via
    ``rewind_cache_indices`` (lengths are pointers in a paged cache, so
    rollback is a pointer move, never cache surgery)."""
    logits, vars_out = model.apply(
        {"params": params, "cache": cache},
        toks,
        decode=True,
        mutable=["cache"],
    )
    return _take_logits(logits), vars_out["cache"]


def rewind_cache_indices(cache: Any, new_idx: jax.Array) -> Any:
    """Speculative-decode ROLLBACK (ISSUE 11): set every row's cache
    write cursor — the per-layer ``cache_index`` rows ``[L, B]`` and the
    model-level ``pos_index`` ``[B]`` — to ``new_idx [B]``. A verify
    step advances every cursor by k+1; after host-side acceptance the
    true occupancy is ``len + accepted + 1``, so rejected draft
    positions are abandoned by rewinding the cursors (their K/V stay in
    the pool past the cursor, masked out of every later read and
    overwritten by later writes — the same discipline as the bucketed
    path's wrapped-pad garbage). Name-keyed like the pool taxonomy
    (``POOL_LEAF_OF``): every other leaf passes through untouched, so
    the engine can jit this with the cache donated and rollback is pure
    pointer bookkeeping."""
    from flax.traverse_util import flatten_dict, unflatten_dict

    flat = flatten_dict(cache)
    out = {}
    for kp, leaf in flat.items():
        name = kp[-1]
        if name == "cache_index":
            out[kp] = jnp.broadcast_to(
                new_idx.astype(leaf.dtype)[None, :], leaf.shape
            )
        elif name == "pos_index":
            out[kp] = new_idx.astype(leaf.dtype)
        else:
            out[kp] = leaf
    return unflatten_dict(out)


def _plain_stack(model: Any, params: Any) -> tuple[Any, Any]:
    """Decode always runs on the plain layer stack: a pipeline-trained
    model (``pipeline_stages > 1``) is swapped for its ``stages=1`` twin
    and the stage-stacked weights are restacked to ``[L, ...]`` (a pure
    reshape — models/gpt.py ``unstack_pipeline_params``). Weights are
    layout-compatible by construction, so PP checkpoints generate without
    any config surgery. The restack runs per call (free under jit after
    trace); an eager sampling loop over a large PP checkpoint should call
    ``unstack_pipeline_params`` once and pass the plain-stack pair."""
    cfg = getattr(model, "config", None)
    if cfg is None or getattr(cfg, "pipeline_stages", 1) <= 1:
        return model, params
    import dataclasses

    from frl_distributed_ml_scaffold_tpu.models.gpt import (
        unstack_pipeline_params,
    )

    plain = type(model)(
        config=dataclasses.replace(cfg, pipeline_stages=1),
        policy=model.policy,
    )
    return plain, unstack_pipeline_params(cfg, params)


def generate(
    model: Any,
    params: Any,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_id: int | None = None,
    rng: jax.Array | None = None,
    prompt_lengths: jax.Array | None = None,
    cache_len: int | None = None,
) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` ([B, Tp] int).

    Returns [B, Tp + max_new_tokens]; positions after an ``eos_id`` emission
    (when given) are padded with ``eos_id``. Jit-compatible as long as
    ``max_new_tokens``/``temperature``/``top_k``/``top_p`` stay static — wrap with
    ``jax.jit(partial(generate, model, ...), static_argnames=...)`` or just
    call it; the two inner ``apply`` calls are where the time goes.

    Ragged batches: pass LEFT-padded prompts (real tokens right-aligned)
    plus ``prompt_lengths`` [B] — prefill then neither attends over nor
    caches the pad columns, so mixed-length batches are first-class.

    The KV cache is bucketed (``next_cache_bucket``) to the smallest
    power of two covering prompt+budget rather than pre-sized to
    ``config.seq_len``; pass ``cache_len=config.seq_len`` to force the
    legacy full-context cache.
    """
    model, params = _plain_stack(model, params)
    cfg = model.config
    b, tp = prompt.shape
    if tp + max_new_tokens > cfg.seq_len:
        raise ValueError(
            f"prompt ({tp}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model context ({cfg.seq_len}) — the KV cache is sized to it"
        )
    # Prefill writes [0, Tp) and rows extend to at most len+new-1 < Tp+new.
    model = _bucketed(model, cache_len, tp + max_new_tokens)
    rng = jax.random.key(0) if rng is None else rng
    prompt = prompt.astype(jnp.int32)

    logits_last, cache = _prefill(model, params, prompt, prompt_lengths)
    rng, sub = jax.random.split(rng)
    tok = _sample(logits_last, sub, temperature=temperature,
                  top_k=top_k, top_p=top_p)
    done = jnp.zeros((b,), bool) if eos_id is None else tok == eos_id

    def step(carry, _):
        cache, tok, done, rng = carry
        logits, cache = _decode_step(model, params, cache, tok)
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits, sub, temperature=temperature,
                      top_k=top_k, top_p=top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, done, rng), tok

    (_, last, _, _), toks = jax.lax.scan(
        step, (cache, tok, done, rng), None, length=max_new_tokens - 1
    ) if max_new_tokens > 1 else ((cache, tok, done, rng), jnp.zeros((0, b), jnp.int32))
    new = jnp.concatenate([toks.T, last[:, None]], axis=1)  # [B, max_new]
    return jnp.concatenate([prompt, new], axis=1)


def cache_batch_axis(leaf, batch_rows: int) -> int | None:
    """THE decode-cache leaf taxonomy, in one place: which axis of a
    cache leaf carries the request/beam rows. Per-layer K/V stacks
    ``[L, B, S, H, hd]``, their quantization-scale stacks
    ``[L, B, S, H]`` (``kv_cache_quant``), and ``cache_index``
    ``[L, B]`` carry them on axis 1; the model-level ``pos_index``
    ``[B]`` leads with them; other leaves (none today) carry no rows.
    Every per-row cache transform — beam gather/repeat here, the serving
    engine's slot grafts — must agree with this classification, so route
    through it."""
    if leaf.ndim >= 2 and leaf.shape[1] == batch_rows:
        return 1
    if leaf.ndim == 1 and leaf.shape[0] == batch_rows:
        return 0
    return None


def cache_capacity_axis(leaf, cache_len: int) -> int | None:
    """The taxonomy's second question: which axis carries the cache
    CAPACITY (the bucketed S dim the engine grows). K/V stacks
    ``[L, B, S, H, hd]`` and scale stacks ``[L, B, S, H]`` both carry it
    on axis 2; index/position bookkeeping carries none. The engine's
    bucket growth and empty-cache widening route through this (the same
    lockstep contract as ``cache_batch_axis``) — a new capacity-bearing
    leaf class added to the model extends serving by extending THIS
    function, not three ad-hoc ``ndim == 5`` checks."""
    if leaf.ndim >= 4 and leaf.shape[2] == cache_len:
        return 2
    return None


def cache_bytes_per_slot(cache, num_slots: int) -> int:
    """Per-slot HBM bytes of a decode cache tree, from the ACTUAL leaves
    — quantization scale tensors and bookkeeping included, which is what
    keeps bucket HBM estimates (engine slot accounting,
    tools/serve_bench.py bytes-per-slot) honest: an int8 cache is
    ``(hd + 2·scale_bytes/…)`` per element-row, not a free 4x."""
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(cache):
        ax = cache_batch_axis(leaf, num_slots)
        if ax is None:
            continue
        per_row = int(np.prod(leaf.shape, dtype=np.int64)) // leaf.shape[ax]
        total += per_row * jnp.dtype(leaf.dtype).itemsize
    return int(total)


def estimate_cache_bytes_per_slot(
    cfg: Any, cache_len: int, *, kv_dtype_bytes: int = 2
) -> int:
    """Analytic twin of ``cache_bytes_per_slot`` for capacity planning
    BEFORE a cache exists: per decode slot at bucket ``cache_len``, a
    GPT config costs ``L x (K + V (+ scales) + cache_index) +
    pos_index`` bytes. ``kv_dtype_bytes`` is the UNQUANTIZED element
    width (2 for bf16 serving, 4 for the fp32 sim); with
    ``cfg.kv_cache_quant`` set, K/V cost 1 byte and the per-(position,
    head) bf16 scales ride alongside. Pinned equal to the actual cache
    tree in tests/test_serving.py — if the model grows a cache leaf this
    estimate doesn't know, that regression test is what catches the
    drift."""
    h = cfg.num_heads
    hd = cfg.hidden_dim // h
    quant = getattr(cfg, "kv_cache_quant", "none") != "none"
    elem = 1 if quant else kv_dtype_bytes
    per_layer = 2 * cache_len * h * hd * elem  # K + V payloads
    if quant:
        per_layer += 2 * cache_len * h * 2  # bf16 scale per (pos, head)
    per_layer += 4  # cache_index int32
    return cfg.num_layers * per_layer + 4  # + pos_index int32


# --------------------------------------------------------- paged (block) pool
#
# The PAGED decode cache (ISSUE 10) replaces per-slot [B, S, ...] stacks
# with a shared pool of fixed-size blocks plus per-row block tables.  The
# taxonomy below is the paged extension of cache_batch_axis /
# cache_capacity_axis: pool leaves carry NO row axis (blocks are shared —
# that is the whole point) and are classified by NAME, not by shape. A
# pool leaf holds all layers and is LANE-DENSE (models/gpt.py
# ``paged_cache_leaves``): K/V pools ``[L, N, bs, H*hd]`` — a token's row
# is H*hd contiguous values — and scale pools ``[L, N, H*bs]`` — a
# block's scales are one row — heads major in both. Every block-wise
# cache transform — the engine's block grafts, the prefix-seed gather,
# capacity accounting — routes through these names and the two
# conversions below, the same lockstep contract as the shape taxonomy.

#: Slot-cache leaf name -> its pool counterpart (the contiguous prefill
#: cache's leaves map onto pool blocks through this; the scale leaves are
#: the PR 6 format vocabulary, preserved block-wise).
POOL_LEAF_OF: dict[str, str] = {
    "cached_key": "key_pool",
    "cached_value": "value_pool",
    "key_scale": "key_pool_scale",
    "value_scale": "value_pool_scale",
}

#: Pool leaf name -> slot-cache leaf name (the reverse direction: the
#: prefix-seed gather reconstructs a contiguous prefix from pool blocks).
SLOT_LEAF_OF: dict[str, str] = {v: k for k, v in POOL_LEAF_OF.items()}


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` cache positions (ceil)."""
    if tokens <= 0:
        return 0
    return -(-int(tokens) // int(block_size))


def pool_heads_axis(name: str, leaf) -> int | None:
    """The taxonomy's third question (ISSUE 15): which axis of a POOL
    leaf carries the attention heads — the only axis a serving re-spread
    may shard. Name-keyed like ``POOL_LEAF_OF``: heads are the MAJOR
    part of every pool leaf's last axis (K/V rows ``H*hd``, scale rows
    ``H*bs``), so splitting that axis splits whole heads; every other
    leaf (tables, cursors) carries none. The engine's ``respread_pool``
    derives its destination layouts through this — the same lockstep
    contract as the shape taxonomy: a new pool leaf class extends THIS
    function, not an ad-hoc ndim check."""
    return leaf.ndim - 1 if name in SLOT_LEAF_OF else None


def _is_scale(name: str) -> bool:
    return name.endswith("scale")


def slot_blocks_to_pool(name: str, blocks):
    """Slot-cache positions cut into blocks — ``[..., n, bs, H, hd]`` K/V
    or ``[..., n, bs, H]`` scales (``name``: either side's leaf name) —
    as the pool stores them: ``[..., n, bs, H*hd]`` (a free reshape) or
    ``[..., n, H*bs]`` (a block's scales as one row, heads major)."""
    if _is_scale(name):
        blocks = jnp.swapaxes(blocks, -1, -2)
    return blocks.reshape(blocks.shape[:-2] + (-1,))


def pool_to_slot_blocks(name: str, rows, heads: int):
    """The inverse of ``slot_blocks_to_pool``: pool blocks
    ``[..., n, bs, H*hd]`` / ``[..., n, H*bs]`` back to
    ``[..., n, bs, H, hd]`` / ``[..., n, bs, H]``."""
    blocks = rows.reshape(rows.shape[:-1] + (heads, -1))
    return jnp.swapaxes(blocks, -1, -2) if _is_scale(name) else blocks


def pool_leaf_spec(name: str, leaf):
    """Destination PartitionSpec for one paged-cache leaf under a model
    axis (the ``models/gpt.py _constrain_kv_pool`` layout, derived from
    the name taxonomy): pool leaves shard heads over ``model`` and are
    REPLICATED over every batch axis (blocks are shared across slot
    rows); bookkeeping leaves replicate. ``None`` = no opinion (carry
    the leaf's current spec)."""
    from jax.sharding import PartitionSpec as P

    ax = pool_heads_axis(name, leaf)
    if ax is None:
        return None
    entries = [None] * leaf.ndim
    entries[ax] = "model"
    return P(*entries)


def splice_pool_blocks(cache, slot_cache, blk_ids, m0, slot, *,
                       block_size: int):
    """The prefill→decode HANDOFF SPLICE (ISSUE 12), over the block-pool
    taxonomy: write one prefilled (contiguous, bucketed) slot cache's
    PRIVATE blocks into their physical pool homes and set the slot's
    cursor rows. The slot cache is ``[L, 1, S, H, hd]`` (scales
    ``[L, 1, S, H]``), the pool ``[L, N, bs, H*hd]`` (``[L, N, H*bs]``):
    the slot's blocks are reshaped to pool rows
    (``slot_blocks_to_pool``). ``blk_ids [n_priv]`` are the destination
    physical block ids for the logical blocks starting at ``m0`` (shared
    prefix blocks below ``m0`` are already in the pool and are NOT
    touched — only the blocks that change owner move, the arXiv
    2112.01075 discipline), and ``slot`` is the decode-side row whose
    ``cache_index``/``pos_index`` the splice seeds.

    This is the ONLY device work in a prefill→decode handoff: ownership
    itself moves as a host-side block-table row write (a re-own, priced
    in table bytes — the perf-ledger ``serving:handoff`` row), so the
    logical cache is never copied and nothing here can reshard. The
    serving engine jits this with the pool donated (``_paged_graft_fn``);
    graft-lint's ``serving:handoff`` program lints this exact function
    (a gather-based handoff materializing the logical cache view trips
    its cache-copy budget)."""
    from flax.traverse_util import flatten_dict, unflatten_dict

    bs = block_size
    n_priv = blk_ids.shape[0]
    flat = flatten_dict(cache)
    out = dict(flat)
    sflat = flatten_dict(slot_cache)
    for kp, leaf in sflat.items():
        name = kp[-1]
        if name in POOL_LEAF_OF:
            pool_path = kp[:-1] + (POOL_LEAF_OF[name],)
            pool = out[pool_path]
            n_blk = leaf.shape[2] // bs
            chunks = leaf[:, 0].reshape(
                (leaf.shape[0], n_blk, bs) + leaf.shape[3:]
            )
            sl = slot_blocks_to_pool(
                name, jax.lax.dynamic_slice_in_dim(chunks, m0, n_priv, axis=1)
            )
            # In place on the donated pool: the leaf's rows are whole
            # lane tiles, so the device scatters the n_priv blocks and
            # touches nothing else (pinned on the compiled HLO in
            # tests/test_chip_compile.py).
            out[pool_path] = pool.at[:, blk_ids].set(sl.astype(pool.dtype))
        elif name == "cache_index":
            out[kp] = out[kp].at[:, slot].set(leaf[:, 0])
        elif name == "pos_index":
            out[kp] = out[kp].at[slot].set(leaf[0])
    return unflatten_dict(out)


def splice_kind_pools(cache, slot_cache, ids_full, ids_window, slot, *,
                      cfg: Any, block_size: int):
    """``splice_pool_blocks`` for a model with ``layer_types`` (pools by
    layer kind: models/gpt.py ``kind_pool_leaves``): write one prefilled
    contiguous slot cache (``layer_<i>/attn/cached_key`` ``[1, S, Hkv,
    hd]``) into the pools and set the slot's cursor. The FULL kind takes the
    prompt's ``n_g = len(ids_full)`` blocks, logical block j to physical
    ``ids_full[j]``. The SLIDING kind takes the prompt's last ``len(
    ids_window)`` blocks only — a window's worth, ``min(ring places, n_g)``
    — logical block ``n_g - n_w + j`` to ``ids_window[j]``; a block already
    wholly behind the window has no home and goes to the trash block 0.
    In place on the donated pools, like the uniform stack's splice."""
    from frl_distributed_ml_scaffold_tpu.models.gpt import kind_layers

    bs, n_g = block_size, ids_full.shape[0]
    out = dict(cache)
    for kind, layers in kind_layers(cfg).items():
        ids = ids_full if kind == "full" else ids_window
        n, first = ids.shape[0], (0 if kind == "full" else n_g - ids_window.shape[0])
        for slot_name, pool_name in POOL_LEAF_OF.items():
            pool_name = f"{pool_name}_{kind}"
            if pool_name not in cache:
                continue  # the scale leaves: these pools are not quantized
            rows = jnp.stack([
                slot_cache[f"layer_{i}"]["attn"][slot_name][0] for i in layers
            ])  # [Lk, S, Hkv, hd]
            blocks = rows[:, first * bs:(first + n) * bs].reshape(
                len(layers), n, bs, -1)
            out[pool_name] = out[pool_name].at[:, ids].set(
                blocks.astype(out[pool_name].dtype))
    out["pos_index"] = cache["pos_index"].at[slot].set(
        slot_cache["pos_index"][0])
    return out


def pool_block_bytes(cache) -> int:
    """HBM bytes of ONE pool block across all layers — K/V payloads AND
    quantization-scale blocks, from the ACTUAL pool leaves (the paged
    analog of ``cache_bytes_per_slot``: the unit the engine's
    pool-utilization accounting and serve_bench's paged capacity columns
    price admissions in)."""
    import numpy as np

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = getattr(path[-1], "key", None)
        if name in SLOT_LEAF_OF:
            # [L, N, ...] stacked pool leaf: bytes per (all-layers) block.
            n = leaf.shape[1]
            total += (
                int(np.prod(leaf.shape, dtype=np.int64)) // n
            ) * jnp.dtype(leaf.dtype).itemsize
    return int(total)


def estimate_pool_block_bytes(
    cfg: Any, block_size: int, *, kv_dtype_bytes: int = 2
) -> int:
    """Analytic twin of ``pool_block_bytes`` for capacity planning BEFORE
    a pool exists: one block of ``block_size`` positions costs
    ``L x 2 x bs x H x hd`` payload bytes (+ the bf16 scale blocks under
    ``cfg.kv_cache_quant``). Pinned equal to the actual pool tree in
    tests/test_serving.py, like ``estimate_cache_bytes_per_slot``."""
    h = cfg.num_heads
    hd = cfg.hidden_dim // h
    quant = getattr(cfg, "kv_cache_quant", "none") != "none"
    elem = 1 if quant else kv_dtype_bytes
    per_layer = 2 * block_size * h * hd * elem
    if quant:
        per_layer += 2 * block_size * h * 2  # bf16 scale per (pos, head)
    return cfg.num_layers * per_layer


def _gather_cache_rows(cache, rows, batch_rows: int):
    """Reorder the per-beam KV rows of a decode cache. The per-row
    bookkeeping (``cache_index``, ``pos_index``) MUST follow its beam:
    under ragged prompts beams of different rows sit at different
    positions."""

    def leaf(x):
        ax = cache_batch_axis(x, batch_rows)
        return x if ax is None else jnp.take(x, rows, axis=ax)

    return jax.tree.map(leaf, cache)


def _repeat_cache_rows(cache, w: int, batch_rows: int):
    """Row-repeat a [B]-batch cache to [B*W] beams."""

    def leaf(x):
        ax = cache_batch_axis(x, batch_rows)
        return x if ax is None else jnp.repeat(x, w, axis=ax)

    return jax.tree.map(leaf, cache)


def beam_search(
    model: Any,
    params: Any,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    num_beams: int = 4,
    eos_id: int | None = None,
    length_penalty: float = 0.0,
    prompt_lengths: jax.Array | None = None,
    cache_len: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Deterministic beam decode; returns ``([B, Tp+new] best tokens,
    [B] scores)``.

    Same two-XLA-program shape as ``generate``: one prefill over the [B]
    prompt (the cache is then row-repeated to [B*W] — cheaper than
    prefilling W copies), one scanned decode step over all beams. Each
    step extends every beam over the full vocab, keeps the top W of W*V
    by accumulated log-prob, and reorders the KV cache rows by the
    surviving beams' parents. Finished beams (``eos_id``) are frozen:
    their only continuation is eos at zero additional log-prob.

    Scoring: beams are SEARCHED by raw summed log-prob; with
    ``length_penalty`` alpha > 0, the FINAL ranking divides each beam's
    sum by ``len_emitted**alpha`` (GNMT-style, where len counts tokens up
    to and including the first eos) — countering raw-sum's short-sequence
    bias. The returned score is the ranked quantity (raw sum when
    alpha=0).
    """
    model, params = _plain_stack(model, params)
    cfg = model.config
    b, tp = prompt.shape
    w = num_beams
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens={max_new_tokens} < 1: the returned score is "
            "the sum log-prob of the emitted tokens, so at least one must "
            "be emitted"
        )
    if tp + max_new_tokens > cfg.seq_len:
        raise ValueError(
            f"prompt ({tp}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"model context ({cfg.seq_len}) — the KV cache is sized to it"
        )
    if w < 1 or w > cfg.vocab_size:
        raise ValueError(f"num_beams={w} not in [1, vocab={cfg.vocab_size}]")
    model = _bucketed(model, cache_len, tp + max_new_tokens)
    prompt = prompt.astype(jnp.int32)

    # Same shared prefill + decode-step entry as generate(): the beam path
    # cannot drift from the greedy path's attention numerics.
    logits_last, cache0 = _prefill(model, params, prompt, prompt_lengths)
    lp0 = jax.nn.log_softmax(logits_last.astype(jnp.float32))  # [B, V]
    scores, tok = jax.lax.top_k(lp0, w)  # [B, W] each
    cache = _repeat_cache_rows(cache0, w, b)
    finished = (
        jnp.zeros((b, w), bool) if eos_id is None else tok == eos_id
    )
    buf = jnp.zeros((b, w, max_new_tokens), jnp.int32)
    buf = buf.at[:, :, 0].set(tok)
    batch_idx = jnp.arange(b)[:, None]

    def step(carry, t):
        cache, tok, scores, finished, buf = carry
        logits, new_cache = _decode_step(
            model, params, cache, tok.reshape(b * w)
        )
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        lp = lp.reshape(b, w, -1)  # [B, W, V]
        if eos_id is not None:
            # Frozen beams may only repeat eos, for free — their score
            # stays comparable while live beams keep extending.
            eos_only = jnp.full_like(lp, jnp.finfo(jnp.float32).min)
            eos_only = eos_only.at[..., eos_id].set(0.0)
            lp = jnp.where(finished[..., None], eos_only, lp)
        total = scores[..., None] + lp  # [B, W, V]
        v = total.shape[-1]
        new_scores, flat_idx = jax.lax.top_k(total.reshape(b, w * v), w)
        src = flat_idx // v  # parent beam per survivor [B, W]
        new_tok = (flat_idx % v).astype(jnp.int32)
        rows = (batch_idx * w + src).reshape(-1)
        cache = _gather_cache_rows(new_cache, rows, b * w)
        buf = buf[batch_idx, src]  # reorder histories to surviving beams
        buf = buf.at[:, :, t].set(new_tok)
        finished = finished[batch_idx, src]
        if eos_id is not None:
            finished = finished | (new_tok == eos_id)
        return (cache, new_tok, new_scores, finished, buf), None

    if max_new_tokens > 1:
        (cache, tok, scores, finished, buf), _ = jax.lax.scan(
            step,
            (cache, tok, scores, finished, buf),
            jnp.arange(1, max_new_tokens),
        )
    if length_penalty > 0.0:
        # Re-rank by length-normalized score (search stays raw-sum: the
        # normalization is not monotone across different-length prefixes,
        # so applying it per-step would break the beam invariant).
        if eos_id is None:
            # Every beam has the same length: a constant division — no
            # reordering can occur, so don't sort (an unstable reorder on
            # f32 ties would needlessly swap equal-scored beams).
            scores = scores / float(max_new_tokens) ** length_penalty
        else:
            is_eos = buf == eos_id
            first = jnp.argmax(is_eos, axis=-1)
            lens = jnp.where(
                is_eos.any(-1), first + 1, max_new_tokens
            ).astype(jnp.float32)
            ranked = scores / lens**length_penalty
            # argsort(-x) is stable-descending: ties keep the raw-score
            # beam order instead of flipping to the worst tied beam.
            order = jnp.argsort(-ranked, axis=1)
            buf = jnp.take_along_axis(buf, order[..., None], axis=1)
            scores = jnp.take_along_axis(ranked, order, axis=1)
    # Beams are sorted by (possibly re-ranked) score: beam 0 is the argmax.
    return jnp.concatenate([prompt, buf[:, 0]], axis=1), scores[:, 0]
