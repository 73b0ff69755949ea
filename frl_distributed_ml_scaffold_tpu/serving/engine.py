"""Host-side continuous-batching decode engine over a fixed slot array.

TPU serving wants the same static-shape discipline as TPU training: every
device program the engine runs is one of a SMALL closed set of compiled
shapes — a prefill per prompt bucket, a decode step per cache bucket, a
cache graft per (prompt bucket, cache bucket) pair — all powers of two up
to ``config.seq_len`` (``models/generation.next_cache_bucket``). Requests
of any length mix freely inside those shapes:

- **Slots**: the decode batch is a fixed ``[num_slots]`` row array. Each
  row is an independent request; per-row cache indices/positions
  (models/gpt.py decode path) mean rows at different occupancies decode
  together in one program.
- **Continuous batching**: when a row emits eos (or exhausts its budget)
  it RETIRES — the completion is returned and the slot is freed — and the
  next queued request is prefilled into the freed row while the other
  rows keep decoding. Admission never stalls the running rows: a prompt
  is prefilled as a [1, prompt_bucket] program and its cache rows are
  grafted into the engine cache at the slot index (a dynamic-update-slice,
  not a reshard).
- **Cache buckets**: the engine cache starts at the smallest bucket that
  covers the live requests and GROWS bucket-by-bucket (a pad along the
  cache axis) only when an active slot actually needs the room. Short
  requests therefore never pay full-context cache traffic — and the
  decode kernel additionally reads only each row's occupied prefix within
  the bucket.
- **Paged cache** (``kv_block_size > 0``, ISSUE 10): the bucketed
  per-slot cache is replaced by a fixed POOL of fixed-size KV blocks
  plus per-slot block tables (ops/decode_attention.py paged kernel; the
  tables ride the scalar-prefetch channel next to the per-row lengths).
  Growth becomes appending one block to a table — no cache clone, no
  bucket ladder, ONE compiled decode shape — and admission is priced in
  pool headroom: a request reserves its worst-case block count up front,
  so mid-decode appends can never fail, and a full pool makes the queue
  head WAIT (backpressure that composes with ``max_queue_depth``'s shed
  bound: pool exhaustion -> queue growth -> typed sheds). Prefill stays
  contiguous; the graft scatters exactly the blocks that change owner
  into the pool (the arXiv 2112.01075 gather-at-the-boundary
  discipline). Refcounted SHARED-PREFIX caching rides the same
  allocator: a prompt whose leading full blocks match a cached chain
  reuses those physical blocks (prefill runs only on the suffix, seeded
  with the shared prefix gathered block-wise) with copy-on-write at the
  first divergent/partial block — a common system prompt prefills
  exactly once, and prefill work scales with UNIQUE prefixes, not
  requests.

- **One step of lookahead**: a plain decode step is enqueued BEFORE the
  tokens of the step before it are fetched. The last sampled tokens are
  a device value handed from one call of the decode program to the next
  (an admission's first token is scattered into it on the device), so
  nothing a step needs waits on the host, and the device computes step
  n+1 while the host fetches, emits and books step n. The host books a
  step in two halves: what is known at dispatch (rows live, lengths,
  death by length, blocks, tables) then, what only the tokens say (the
  token, eos, deadline) at the fetch. At most ONE step is ahead, and
  whatever reads or rewrites slot state outside ``step`` first lands it
  (``_drain_inflight``). A speculating engine proposes from host tokens
  and is the one engine that does not look ahead (``_looks_ahead``).

Everything here is host logic around jitted pure functions; under a live
mesh (captured at construction) the same loop serves model-sharded caches
— the jitted programs trace under ``mesh_context`` so the decode
attention runs head-sharded (ops/decode_attention.py router).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from frl_distributed_ml_scaffold_tpu import faults
from frl_distributed_ml_scaffold_tpu.config.schema import ServingConfig
from frl_distributed_ml_scaffold_tpu.models.generation import (
    POOL_LEAF_OF,
    SLOT_LEAF_OF,
    _decode_step,
    _plain_stack,
    _prefill,
    _sample,
    _verify_step,
    blocks_for_tokens,
    cache_batch_axis,
    cache_bytes_per_slot,
    cache_capacity_axis,
    generate,
    next_cache_bucket,
    pool_block_bytes,
    pool_to_slot_blocks,
    rewind_cache_indices,
    splice_kind_pools,
    splice_pool_blocks,
)
from frl_distributed_ml_scaffold_tpu.models.gpt import (
    init_paged_cache,
    kind_layers,
    window_pool_blocks,
    window_table_blocks,
)
from frl_distributed_ml_scaffold_tpu.telemetry import (
    Histogram,
    MetricsRegistry,
    StallWatchdog,
    Timeline,
    Tracer,
)


def ngram_propose(
    history: np.ndarray, k: int, max_ngram: int = 3
) -> np.ndarray:
    """Tier-A draft proposer (ISSUE 11): prompt-lookup / n-gram
    self-speculation. Find the most recent EARLIER occurrence of the
    history's trailing n-gram (longest n first, n = max_ngram..1) and
    propose the up-to-``k`` tokens that followed it — on repetitive or
    structured text (code, templated prose, the model's own greedy
    cycles) the continuation of a repeated n-gram is usually the same
    tokens again, so the target model accepts most of the draft and
    each verify step retires several tokens for one pool read.

    Pure host-side numpy over the slot's own token history (prompt +
    emitted) — no second model, no device work, deterministic. Returns
    an empty array when nothing matches (the slot then single-steps
    inside the shared verify program). Drafting is ADVISORY: a bad
    draft costs only its rejected verify position, never correctness.
    """
    h = np.asarray(history).reshape(-1)
    n_h = int(h.size)
    if k < 1 or n_h < 2:
        return h[:0]
    for n in range(min(max_ngram, n_h - 1), 0, -1):
        suffix = h[n_h - n :]
        # Most recent earlier occurrence WITH a full-k continuation,
        # else the most recent match at all. Overlapping matches are
        # deliberately allowed — a period-p cycle matches at n_h-n-p
        # and proposes the periodic continuation, the whole tier-A win
        # — but a match butting against the end of history truncates
        # its continuation (the period-1 extreme yields ONE token), so
        # when a slightly older occurrence can fill the whole draft
        # budget with the same pattern, prefer it. One vectorized pass
        # (this runs per active slot per verify step — an interpreted
        # backward scan would be O(len^2) host work per request, more
        # than the batched verify forward it gates).
        wins = np.lib.stride_tricks.sliding_window_view(h[:-1], n)
        hits = np.flatnonzero((wins == suffix).all(axis=1))
        if hits.size == 0:
            continue
        full = hits[hits <= n_h - n - k]
        i = int(full[-1]) if full.size else int(hits[-1])
        return h[i + n : i + n + k].copy()
    return h[:0]


def make_prefill_program(model, sample_kw: dict):
    """Build THE compiled prefill program for one prompt bucket (the
    model is already cloned to it): prefill + first-token sample. One
    builder for both admission paths (engine jit caches and the
    disaggregated ``PrefillWorker``'s), like ``prefill_request`` — a
    change here (donation, sampling) lands on both or neither."""
    kw = dict(sample_kw)

    def serve_prefill(params, prompt, lengths, rng):
        logits, cache = _prefill(model, params, prompt, lengths)
        return _sample(logits, rng, **kw), cache

    return jax.jit(serve_prefill)


def make_seeded_prefill_program(model, sample_kw: dict):
    """The shared-prefix variant: suffix prefill against a seeded slot
    cache (donated — the seed is single-use by construction)."""
    kw = dict(sample_kw)

    def serve_prefill_seeded(params, prompt, lengths, rng, cache0):
        logits, cache = _prefill(model, params, prompt, lengths, cache=cache0)
        return _sample(logits, rng, **kw), cache

    return jax.jit(serve_prefill_seeded, donate_argnums=(4,))


def prefill_request(
    req, res, rng, *, block_size: int, bucket_for, params,
    prefill_fn, seeded_fn, seed_cache=None,
):
    """THE admission prefill recipe, in one place (ISSUE 12): bucket the
    (possibly prefix-stripped) prompt, left-pad the suffix, and run the
    seeded or plain prefill program. Shared by the colocated engine
    (``_prefill_package``) and the disaggregated ``PrefillWorker`` —
    same recipe, different params/jit-caches/partition — so the two
    admission paths cannot drift. Returns the un-fetched package
    ``(tok, slot_cache, s_p, s_c, m, l_suf)``; ``l_suf >= 1`` by the
    ``_match_prefix`` cap (at least one token always prefills)."""
    l = int(req.prompt.size)
    m = res["m"] if res is not None else 0
    l_suf = l - m * block_size
    s_p = bucket_for(l_suf)
    s_c = bucket_for(l) if block_size else s_p
    prompt = np.zeros((1, s_p), np.int32)
    prompt[0, s_p - l_suf :] = req.prompt[m * block_size :]  # left-pad
    if m > 0:
        tok, slot_cache = seeded_fn(s_p, s_c)(
            params,
            jnp.asarray(prompt),
            jnp.asarray([l_suf], jnp.int32),
            rng,
            seed_cache,
        )
    else:
        tok, slot_cache = prefill_fn(s_p)(
            params,
            jnp.asarray(prompt),
            jnp.asarray([l], jnp.int32),
            rng,
        )
    return tok, slot_cache, s_p, s_c, m, l_suf


class CacheGrowError(RuntimeError):
    """Growing the KV cache to the next bucket failed (allocation failure
    at high occupancy, or the ``serve.grow`` fault site). The engine
    degrades instead of dying: requests that NEED the larger bucket are
    retired with ``finish_reason="error"``; requests that still fit keep
    decoding (see ``ServingEngine.step``)."""


@dataclasses.dataclass
class ServeRequest:
    """One queued generation request (prompt is an unpadded 1-D int array).

    ``trace``/``span``/``t_submit`` are the tracing handles (ISSUE 8):
    every request gets its own trace id at enqueue, and the root
    ``request`` span stays open from submit to retire so the exported
    trace reads as one connected tree per request. ``deadline_s`` is the
    submit-relative deadline (0 = none; ISSUE 9)."""

    id: int
    prompt: np.ndarray
    max_new_tokens: int
    trace: int = 0
    t_submit: float = 0.0
    span: Any = None
    deadline_s: float = 0.0


@dataclasses.dataclass
class Completion:
    """A finished request: prompt + generated tokens and per-token wall
    latencies (the decode steps this request was live for), plus the
    serving-SLO summary of those latencies: ``ttft_s`` (time to first
    token — the prefill) and p50/p99 time-per-output-token over the
    decode steps, computed through the telemetry histogram's log2-bucket
    quantile estimator so per-request numbers and the engine's aggregate
    ``serve_tpot_seconds`` histogram read on the same scale.

    ``finish_reason`` is the TYPED failure contract (ISSUE 9): every
    submitted request resolves to exactly one completion —
    ``"eos"``/``"length"`` (served in full), ``"shed"`` (load-shed at
    admission: queue bound hit, no tokens generated), ``"deadline"``
    (deadline passed — queued requests shed before prefill, mid-decode
    requests are cancelled carrying the tokens generated so far), or
    ``"error"`` (poison request quarantined / cache growth failed; any
    tokens generated before the fault are carried). A caller therefore
    never hangs on a faulted request and can always tell a served answer
    from a degraded one."""

    id: int
    tokens: np.ndarray  # [prompt_len + n_generated]
    prompt_len: int
    finish_reason: str  # "eos" | "length" | "shed" | "deadline" | "error"
    token_latencies_s: list[float]
    ttft_s: float = 0.0
    tpot_p50_s: float = 0.0
    tpot_p99_s: float = 0.0
    # Shared-prefix accounting (ISSUE 10), PER REQUEST — the paged
    # engine's prefix win measured where SLOs live, not just as an
    # aggregate gauge: did this request's prompt reuse cached prefix
    # blocks, and how many prompt tokens were never prefilled because
    # of it (serve_bench aggregates these into its SLO columns).
    prefix_cache_hit: bool = False
    prefill_tokens_saved: int = 0
    # Speculative-decode accounting (ISSUE 11), PER REQUEST — accepted
    # draft tokens / proposed draft tokens over this request's verify
    # steps (0.0 when nothing was proposed, e.g. speculate=off or a
    # degraded slot). The per-request SLO face of the aggregate
    # serve_spec_{proposed,accepted}_total counters, the same path as
    # prefix_cache_hit above.
    spec_accept_rate: float = 0.0
    # Token ARRIVAL times (ISSUE 12), seconds from submit, one per
    # generated token: the honest inter-token-gap record — unlike
    # ``token_latencies_s`` (the decode PROGRAM's wall time), gaps
    # between consecutive arrivals include everything the engine did in
    # between (inline prefills, grafts, handoffs), which is exactly the
    # decode-TPOT-under-prefill-burst number the disaggregation A/B
    # measures and the scheduler's per-tenant TPOT histograms observe.
    token_times_s: list[float] = dataclasses.field(default_factory=list)
    # Multi-tenant attribution (ISSUE 12): the tenant the request was
    # submitted under ("" on a plain single-tenant engine).
    tenant: str = ""

    @property
    def ok(self) -> bool:
        """Served in full (not shed / expired / quarantined)."""
        return self.finish_reason in ("eos", "length")


def _log2_quantiles(vals, qs) -> list[float]:
    """Quantiles of ``vals`` through a detached log2-bucket Histogram —
    the same estimator (and thus the same 2x-granularity scale) as the
    engine's aggregate latency histograms."""
    h = Histogram(MetricsRegistry(), "q", help="")
    for v in vals:
        h.observe(v)
    return [h.quantile(q) for q in qs]


def _hbm_gib() -> dict[str, float]:
    """In-use/peak HBM GiB (empty on backends without memory stats)."""
    from frl_distributed_ml_scaffold_tpu.utils.profiling import (
        device_memory_stats,
    )

    stats = device_memory_stats()
    return {
        k: v for k, v in stats.items()
        if k in ("hbm_in_use_gib", "hbm_peak_gib")
    }


@dataclasses.dataclass
class _StepAhead:
    """A decode step that is on the device while its tokens are not yet on
    the host: what was known when it was enqueued, kept until the `decode`
    span that fetches it. ``rows`` remembers slot -> request, so that a
    token is handed only to the request it was computed for (a row that
    ended by eos, deadline or cancel while this step was in flight has run
    one step too many: its token here is dropped)."""

    out: Any  # device: the tokens, the expert layers' counts behind them
    rows: list[tuple[int, ServeRequest]]
    attrs: dict[str, Any]  # what its `decode` span says, counted at dispatch


class _Phase:
    """``ServingEngine._span`` under a tracer that does not tee into the
    engine's timeline: the tracer's scoped span, plus the bare timeline
    event ``_phase`` falls back to."""

    __slots__ = ("_timeline", "_name", "_span", "_attrs", "_t0")

    def __init__(self, timeline, name, span, attrs):
        self._timeline, self._name, self._span = timeline, name, span
        self._attrs = attrs

    # What the tracer asks of a parent.
    span_id = property(lambda self: self._span.span_id)
    trace = property(lambda self: self._span.trace)

    def set(self, **attrs: Any) -> None:
        self._span.set(**attrs)
        self._attrs.update(attrs)

    def __enter__(self) -> "_Phase":
        self._t0 = time.perf_counter()
        self._span.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._span.__exit__(*exc)
        self._timeline.event(
            self._name, dur_s=round(time.perf_counter() - self._t0, 9),
            **self._attrs,
        )


class ServingEngine:
    """Continuous-batching engine; see the module docstring.

    Usage::

        eng = ServingEngine(model, params, num_slots=4, eos_id=50256)
        eng.submit([5, 3, 8], max_new_tokens=32)
        done = eng.run()          # or step() for one decode iteration
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        num_slots: int = 4,
        eos_id: int | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        rng: jax.Array | None = None,
        min_bucket: int = 8,
        serving: ServingConfig | None = None,
        max_queue_depth: int = 0,
        default_deadline_s: float = 0.0,
        kv_block_size: int = 0,
        kv_pool_blocks: int = 0,
        prefix_cache: bool | None = None,
        speculate: str | None = None,
        speculate_k: int = 0,
        speculate_ngram_max: int = 3,
        speculate_window: int = 32,
        draft_model: Any = None,
        draft_params: Any = None,
        telemetry: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
        stall_timeout_s: float = 0.0,
        stall_dump_path: str | None = None,
        stall_first_beat_scale: float = 5.0,
    ):
        model, params = _plain_stack(model, params)
        self.model, self.params = model, params
        if num_slots < 1:
            raise ValueError(
                f"num_slots={num_slots} < 1: zero slots can never admit, "
                "so run() would spin on a non-empty queue forever"
            )
        self.num_slots = int(num_slots)
        self.eos_id = eos_id
        self._sample_kw = dict(
            temperature=temperature, top_k=top_k, top_p=top_p
        )
        self._rng = jax.random.key(0) if rng is None else rng
        self.min_bucket = int(min_bucket)
        self.seq_len = model.config.seq_len
        # Graceful degradation (ISSUE 9): `serving=` takes the whole
        # ServingConfig (the `serving.*` section of an ExperimentConfig)
        # — THE config-driven path; the scalar kwargs remain for callers
        # without a config. Passing both is a caller bug, refused.
        if serving is not None:
            if (max_queue_depth or default_deadline_s or kv_block_size
                    or kv_pool_blocks
                    or prefix_cache is not None
                    or speculate is not None or speculate_k):
                raise ValueError(
                    "pass either serving=ServingConfig(...) or the "
                    "max_queue_depth/default_deadline_s/kv_block_size/"
                    "kv_pool_blocks/prefix_cache/speculate/speculate_k "
                    "scalars, not both"
                )
            max_queue_depth = serving.max_queue_depth
            default_deadline_s = serving.default_deadline_s
            kv_block_size = serving.kv_block_size
            kv_pool_blocks = serving.kv_pool_blocks
            prefix_cache = serving.prefix_cache
            speculate = serving.speculate
            speculate_k = serving.speculate_k
        if max_queue_depth < 0:
            raise ValueError(f"max_queue_depth={max_queue_depth} < 0")
        self.max_queue_depth = int(max_queue_depth)
        self.default_deadline_s = float(default_deadline_s)
        # A model with ``layer_types`` (full and sliding-window layers
        # mixed) keeps a pool for each layer kind. What the engine cannot
        # do over two kinds yet is refused HERE, by name: nothing falls
        # back to keeping every position in a sliding layer.
        self.mixed = bool(getattr(model.config, "layer_types", ()))
        if self.mixed:
            refused = {
                "the bucketed cache (kv_block_size=0)": kv_block_size <= 0,
                "the prefix cache (prefix_cache=True)": bool(prefix_cache),
                "speculation (speculate)": speculate not in (None, "off"),
                "the quantised pools (model.kv_cache_quant)":
                    model.config.kv_cache_quant != "none",
            }
            self._refuse_for_layer_kinds(
                *[what for what, asked in refused.items() if asked])
            prefix_cache = False
        # Paged-cache knobs (ISSUE 10). Block sizes are powers of two so
        # every prompt bucket is a whole number of blocks (the graft's
        # reshape-to-blocks relies on it) and the paged kernel's chunk is
        # tileable.
        self.paged = kv_block_size > 0
        if self.paged:
            bs = int(kv_block_size)
            if bs & (bs - 1) or bs > self.seq_len:
                raise ValueError(
                    f"kv_block_size={bs} must be a power of two "
                    f"<= seq_len={self.seq_len}"
                )
            self.block_size = bs
            self.table_blocks = blocks_for_tokens(self.seq_len, bs)
            if kv_pool_blocks == 0:
                # Auto: the never-blocks-admission worst case (+1 trash).
                kv_pool_blocks = self.num_slots * self.table_blocks + 1
            if kv_pool_blocks < 2:
                raise ValueError(
                    f"kv_pool_blocks={kv_pool_blocks} < 2: block 0 is the "
                    "reserved trash block, so a usable pool needs >= 2"
                )
            self.pool_blocks = int(kv_pool_blocks)
            # Prompt buckets must stay whole numbers of blocks.
            self.min_bucket = max(self.min_bucket, bs)
            self.prefix_cache_enabled = (
                True if prefix_cache is None else bool(prefix_cache)
            )
            # Allocator state: block 0 is TRASH (retired slots' tables
            # point at it, so the shared decode program's writes for
            # inactive rows land somewhere harmless instead of a freed —
            # possibly reallocated — block).
            self._free: list[int] = list(range(self.pool_blocks - 1, 0, -1))
            self._ref = np.zeros(self.pool_blocks, np.int64)
            self._reserved_future = 0
            self._slot_blocks: list[list[int]] = [
                [] for _ in range(self.num_slots)
            ]
            self._slot_future = np.zeros(self.num_slots, np.int64)
            # Blocks owned by PARKED requests (ISSUE 12), keyed by
            # request id: out of any slot but still refcounted — the
            # pool-demand accounting must keep seeing them.
            self._parked_held: dict[int, list[int]] = {}
            self._slot_prefix_hit = np.zeros(self.num_slots, bool)
            self._slot_tokens_saved = np.zeros(self.num_slots, np.int64)
            self._tables = np.zeros(
                (self.num_slots, self.table_blocks), np.int32
            )
            self._tables_dirty = True
            # prompt-prefix bytes -> tuple of physical block ids, LRU
            # order (move_to_end on hit, popitem(last=False) on evict).
            self._prefix_cache: collections.OrderedDict[
                bytes, tuple[int, ...]
            ] = collections.OrderedDict()
            # The SLIDING kind's half of the allocator: a free list of its
            # own pool, and per slot a ring table of ``window_places``
            # places and the blocks it holds by logical index. The pool
            # holds a ring for every slot (``window_pool_blocks``), so a
            # slot that is free finds its window's worth free with it: the
            # sliding kind never bounds admission and keeps no count of
            # blocks reserved.
            self.window = (
                model.config.sliding_window
                if self.mixed and "sliding" in kind_layers(model.config)
                else 0
            )
            if self.window:
                self.window_places = window_table_blocks(model.config, bs)
                self.window_pool_blocks = window_pool_blocks(
                    model.config, bs, self.num_slots)
                self._reset_window_pool()

        # Speculative decoding (ISSUE 11): draft-propose k tokens per
        # slot, verify all k+1 positions in ONE batched forward, accept
        # the longest matching prefix, roll the rest back (a pointer
        # move on the paged cache). Greedy only — acceptance is exact
        # argmax matching, so speculative output is TOKEN-IDENTICAL to
        # generate(); this is a pure-perf knob.
        self.spec_mode = "off" if speculate is None else str(speculate)
        if self.spec_mode not in ("off", "ngram", "draft"):
            raise ValueError(
                f"speculate={self.spec_mode!r} unknown (off | ngram | draft)"
            )
        # One step of lookahead is what a plain decode step does. A
        # speculating engine proposes from the host's tokens, so it lands
        # every step before the next.
        self._looks_ahead = self.spec_mode == "off"
        self.spec_k = int(speculate_k)
        self.spec_ngram_max = int(speculate_ngram_max)
        self.spec_window = int(speculate_window)
        self._draft = None
        if self.spec_mode != "off":
            if not self.paged:
                raise ValueError(
                    "speculative decoding runs on the PAGED engine "
                    "(serving.kv_block_size > 0): accept/rollback is "
                    "block-table pointer bookkeeping there — the "
                    "bucketed cache has no cheap rollback"
                )
            if self._sample_kw["temperature"] != 0.0:
                raise ValueError(
                    "speculate requires greedy decode (temperature=0): "
                    "acceptance is exact argmax matching; sampled "
                    "speculative decode needs rejection sampling, which "
                    "this engine does not implement"
                )
            if self.spec_k < 1:
                raise ValueError(
                    f"speculate_k={self.spec_k} < 1: a verify step must "
                    "carry at least one draft position"
                )
            if self.spec_mode == "draft":
                if draft_model is None or draft_params is None:
                    raise ValueError(
                        "speculate='draft' needs draft_model= and "
                        "draft_params= (a small GPT sharing the target's "
                        "tokenizer); use speculate='ngram' for "
                        "model-free self-speculation"
                    )
                dm, dp = _plain_stack(draft_model, draft_params)
                if dm.config.vocab_size != model.config.vocab_size:
                    raise ValueError(
                        "draft model must share the target tokenizer "
                        f"(vocab {dm.config.vocab_size} != "
                        f"{model.config.vocab_size})"
                    )
                # The draft proposes from a sliding WINDOW of each slot's
                # history (one compiled propose program: bucketed ragged
                # prefill + k greedy steps) — its cache is the window
                # bucket, so draft memory never contends with the pool.
                self.spec_window = min(
                    self.spec_window, dm.config.seq_len - self.spec_k
                )
                if self.spec_window < 1:
                    raise ValueError(
                        f"draft context ({dm.config.seq_len}) cannot fit "
                        f"a window + speculate_k={self.spec_k}"
                    )
                self._draft = (dm, dp)
        # Per-slot speculation state (reset at admission): sticky
        # degradation (draft-proposer failure -> plain decode for the
        # rest of the request) and the per-request accept accounting
        # behind Completion.spec_accept_rate.
        self._slot_spec_degraded = np.zeros(self.num_slots, bool)
        self._slot_spec_proposed = np.zeros(self.num_slots, np.int64)
        self._slot_spec_accepted = np.zeros(self.num_slots, np.int64)

        # The mesh is captured ONCE: every jitted program traces under it,
        # so replicated and sharded engines never share a trace.
        from frl_distributed_ml_scaffold_tpu.dist.mesh import current_mesh_env

        self._env = current_mesh_env()
        if self._env is not None and self._env.axis_size("model") > 1:
            self._refuse_for_layer_kinds("a mesh with a live model axis")

        self._queue: collections.deque[ServeRequest] = collections.deque()
        # Typed completions produced OUTSIDE a slot (shed at submit,
        # deadline-expired while queued, quarantined at admission) wait
        # here until the next step()/run() returns them — a faulted
        # request always resolves, never hangs.
        self._early: list[Completion] = []
        # Completions retired since the last step() drain. PERSISTENT
        # (not rebound per step): disaggregated admission (ISSUE 12,
        # admit_handoff) retires 1-token-budget requests BETWEEN steps,
        # and a per-step rebind would silently drop them — every retire
        # path appends here, step() drains.
        self._completed: list[Completion] = []
        self._next_id = 0
        self._issued_ids: set[int] = set()
        # Host-side slot state.
        self._req: list[ServeRequest | None] = [None] * self.num_slots
        self._tokens: list[list[int]] = [[] for _ in range(self.num_slots)]
        self._len = np.zeros(self.num_slots, np.int64)  # prompt+generated
        # A slot is `_active` from admission until its request's last token
        # is on the host, and `_decoding` while the NEXT step to be enqueued
        # holds its row: a row whose last step (by length) is in flight is
        # still active and no longer decoding.
        self._active = np.zeros(self.num_slots, bool)
        self._decoding = np.zeros(self.num_slots, bool)
        self._latency: list[list[float]] = [[] for _ in range(self.num_slots)]
        # Token ARRIVAL times per slot (submit-relative) — the gap record
        # behind Completion.token_times_s (ISSUE 12).
        self._tok_times: list[list[float]] = [
            [] for _ in range(self.num_slots)
        ]
        # The last sampled token of each row: `_tok_dev` is the device
        # value the decode program reads and hands on (None: the host
        # mirror `_last_tok`, written as tokens are emitted, is the truth
        # and is uploaded at the next dispatch), `_inflight` the one step
        # enqueued whose tokens are not fetched yet.
        self._last_tok = np.zeros(self.num_slots, np.int32)
        self._tok_dev: Any = None
        self._inflight: _StepAhead | None = None

        self.cache: Any = None
        self.bucket = 0
        # Jit caches keyed on the static shapes they close over.
        self._prefill_jit: dict[int, Any] = {}
        self._decode_jit: dict[int, Any] = {}
        self._graft_jit: dict[tuple[int, int], Any] = {}
        self._grow_jit: dict[tuple[int, int], Any] = {}
        # Paged-mode programs: ONE decode shape (the pool never grows),
        # seeded prefills keyed on (suffix bucket, cache bucket), prefix
        # seeds keyed on (cache bucket, shared blocks), block grafts
        # keyed on (cache bucket, private blocks written).
        self._paged_decode_jit: Any = None
        self._place_token_jit: Any = None
        self._prefill_seeded_jit: dict[tuple[int, int], Any] = {}
        self._seed_jit: dict[tuple[int, int], Any] = {}
        self._paged_graft_jit: dict[tuple[int, int], Any] = {}
        # Speculation programs: ONE verify shape for the whole engine
        # lifetime (the [B, k+1] tile is fixed at construction — no
        # per-k ladder; slots with fewer drafts pad the tile), one
        # rollback (index rewind) shape, one draft-propose shape.
        self._verify_jit: Any = None
        self._rewind_jit: Any = None
        self._draft_jit: Any = None
        # Observability: how often each compiled-shape class actually ran.
        self.stats = collections.Counter()
        # Telemetry (ISSUE 7): every metric is registered up front so both
        # exporters always carry the full serving catalog (a gauge that
        # never fired still scrapes as 0, which is itself a signal). All
        # host-side, around the jitted programs — never inside them
        # (graft-lint `metrics-in-traced` enforces this).
        self.telemetry = telemetry if telemetry is not None else MetricsRegistry()
        self.timeline = Timeline(enabled=self.telemetry.enabled)
        # Tracing (ISSUE 8): one span tree per request (trace id assigned
        # at submit), plus an "engine" lane for the slot-array-scoped
        # programs (decode steps, bucket grows). Spans tee into the
        # Timeline, so the existing drain/export path still carries the
        # phase records, while the tracer ring holds the tree for
        # export_trace(). Host-side only, same contract as the metrics.
        self.tracing = (
            tracer if tracer is not None
            else Tracer(enabled=self.telemetry.enabled, timeline=self.timeline)
        )
        # A caller-supplied tracer (its own timeline, or disabled) breaks
        # the tee into THIS engine's timeline — _phase() then falls back
        # to bare timeline events so telemetry.jsonl's phase records and
        # the watchdog's timeline tail never depend on tracing state.
        self._phases_via_tee = (
            self.tracing.enabled and self.tracing.timeline is self.timeline
        )
        self._engine_trace = self.tracing.new_trace("engine")
        # The open `step` span: parent of the engine-lane spans recorded
        # while a request-lane span (`prefill`) is the innermost one.
        self._step_span: Any = None
        # (program, key) pairs whose first call has run: `_call` records
        # every other first call as a `program_build`.
        self._ran: set[tuple[str, Any]] = set()
        self._hbm_sampled_at = float("-inf")
        t = self.telemetry
        self._m_ttft = t.histogram(
            "serve_ttft_seconds", help="time to first token (prefill+graft)"
        )
        self._m_tpot = t.histogram(
            "serve_tpot_seconds",
            help="per-output-token latency over live slots (decode steps)",
        )
        self._m_queue = t.gauge("serve_queue_depth", help="requests waiting")
        self._m_occupancy = t.gauge(
            "serve_slot_occupancy", help="active slots / num_slots"
        )
        self._m_bytes_slot = t.gauge(
            "serve_bytes_per_slot",
            help="per-slot HBM of the live cache at its current bucket",
        )
        self._m_hbm_used = t.gauge(
            "serve_hbm_in_use_gib", help="device HBM in use (0 when the "
            "backend exposes no stats, e.g. CPU sim)"
        )
        self._m_hbm_peak = t.gauge(
            "serve_hbm_peak_gib", help="device HBM high-watermark"
        )
        self._m_prefills = t.counter("serve_prefill_total", help="prefills run")
        self._m_decodes = t.counter(
            "serve_decode_steps_total", help="slot-array decode iterations"
        )
        self._m_ahead = t.counter(
            "serve_decode_ahead_total",
            help="decode steps enqueued before the step before them was "
            "fetched (one step of lookahead; the rest began at an engine "
            "that stood empty or had just been drained)",
        )
        self._m_wasted_rows = t.counter(
            "serve_decode_wasted_rows_total",
            help="rows of a step in flight computed for a request that had "
            "already ended (eos, deadline, cancel): the token is dropped",
        )
        self._m_grows = t.counter(
            "serve_bucket_grow_total", help="cache bucket growths"
        )
        self._m_grafts = t.counter(
            "serve_cache_graft_total", help="prefill-cache grafts into slots"
        )
        self._m_completed = t.counter(
            "serve_completed_total", help="requests finished"
        )
        self._m_builds = t.counter(
            "serve_program_builds_total",
            help="first calls of an engine program at a new shape "
            "(trace + compile or cache load); 0 in a warmed engine",
        )
        # Failure-semantics counters (ISSUE 9): the OBSERVED side of the
        # fault ledger — chaos drills diff these against the FaultPlan's
        # injected counts to prove detection.
        self._m_shed = t.counter(
            "serve_shed_total",
            help="requests load-shed at submit (queue bound)",
        )
        self._m_deadline = t.counter(
            "serve_deadline_miss_total",
            help="requests past deadline (shed queued / cancelled decoding)",
        )
        self._m_quarantined = t.counter(
            "serve_quarantined_total",
            help="poison requests whose prefill failed (batch kept alive)",
        )
        self._m_grow_failures = t.counter(
            "serve_grow_failures_total",
            help="cache bucket growths that failed (degraded, not fatal)",
        )
        # Paged-cache + shared-prefix observability (ISSUE 10). Always
        # registered (the full-catalog contract): 0 on a bucketed engine.
        self._m_pool_util = t.gauge(
            "serve_pool_utilization",
            help="allocated KV pool blocks / usable pool blocks "
            "(trash block excluded; 0 on a bucketed engine)",
        )
        self._m_block_appends = t.counter(
            "serve_block_append_total",
            help="mid-decode KV blocks appended to slot tables "
            "(the paged engine's 'grow': one block, never a cache clone)",
        )
        # Two kinds of layer in the pool: blocks in use of each kind
        # (beside serve_pool_utilization, which is the full kind's share)
        # and the sliding blocks given back while their requests lived.
        self._m_blocks_full = t.gauge(
            "serve_pool_blocks_in_use_full",
            help="allocated blocks of the full-attention layers' pool",
        )
        self._m_blocks_window = t.gauge(
            "serve_pool_blocks_in_use_sliding",
            help="allocated blocks of the sliding-window layers' pool "
            "(0 on a model without such layers)",
        )
        self._m_window_released = t.counter(
            "serve_window_blocks_released_total",
            help="sliding-layer blocks given back because the window of a "
            "live request slid off them",
        )
        self._m_prefix_hits = t.counter(
            "serve_prefix_hits_total",
            help="admissions that reused cached prefix blocks",
        )
        self._m_prefix_saved = t.counter(
            "serve_prefix_tokens_saved_total",
            help="prompt tokens never prefilled thanks to prefix reuse",
        )
        self._m_prefix_hit_rate = t.gauge(
            "serve_prefix_hit_rate",
            help="prefix hits / admissions since engine start",
        )
        # Live re-spread observability (ISSUE 15). Always registered
        # (the full-catalog contract): 0 until a respread_pool call.
        self._m_respread = t.counter(
            "serve_pool_respread_total",
            help="live model-axis re-spreads of the paged pool "
            "(redistribution service; in-flight slots park/resume)",
        )
        self._m_respread_bytes = t.counter(
            "serve_pool_respread_bytes_total",
            help="bytes the re-spread plans actually moved across "
            "devices (the shard delta, not the pool size)",
        )
        # Speculative-decode observability (ISSUE 11). Always registered
        # (the full-catalog contract): 0 with speculate=off.
        self._m_spec_proposed = t.counter(
            "serve_spec_proposed_total",
            help="draft tokens proposed to verify steps",
        )
        self._m_spec_accepted = t.counter(
            "serve_spec_accepted_total",
            help="draft tokens accepted by verify steps (bonus/corrected "
            "tokens not counted — they are free either way)",
        )
        self._m_spec_verifies = t.counter(
            "serve_spec_verify_total",
            help="batched verify-step program invocations",
        )
        self._m_spec_draft_failures = t.counter(
            "serve_spec_draft_failures_total",
            help="draft-proposer failures (slot degraded to plain "
            "single-token decode for the rest of its request)",
        )
        # On the shared log2 ladder like every histogram (counts, not
        # seconds: tokens emitted land in the 1/2/4/8 buckets, so
        # snapshots still merge and diff like the latency tables).
        self._m_spec_per_verify = t.histogram(
            "serve_spec_accepted_per_verify",
            help="tokens emitted per SPECULATING slot per verify step "
            "(accepted drafts + the corrected/bonus token; 1 = nothing "
            "accepted; zero-draft slots riding the tile are excluded)",
        )
        self.watchdog = StallWatchdog(
            stall_timeout_s,
            name="serve",
            registry=t,
            timeline=self.timeline,
            dump_path=stall_dump_path,
            first_beat_scale=stall_first_beat_scale,
        )

    def _refuse_for_layer_kinds(self, *what: str) -> None:
        """What the engine cannot do over pools of two layer kinds."""
        if self.mixed and what:
            raise NotImplementedError(
                "not on a model with sliding-window layers (layer_types) "
                "yet: " + "; ".join(what) + " — its pools are kept by layer "
                "kind (a sliding layer holds its last window only) and this "
                "path knows one kind"
            )

    def _reset_window_pool(self) -> None:
        self._wfree: list[int] = list(
            range(self.window_pool_blocks - 1, 0, -1))
        self._wslot_blocks: list[dict[int, int]] = [
            {} for _ in range(self.num_slots)]
        self._wtables = np.zeros(
            (self.num_slots, self.window_places), np.int32)

    def _window_first_block(self, write_pos: int) -> int:
        """The oldest logical block a step that writes ``write_pos`` still
        reads in a sliding layer: it attends positions ``> write_pos -
        window``."""
        return max(write_pos - self.window + 1, 0) // self.block_size

    def _phase(self, name, *, t0, dur_s, trace=None, parent=None, **attrs):
        """Span plus guaranteed phase record: the engine-built tracer tees
        finished spans into ``self.timeline``, which is what keeps
        ``telemetry.jsonl`` carrying the phase records; with any other
        tracer the span (if recorded at all) lands elsewhere, so emit a
        bare timeline event too."""
        self.tracing.emit(
            name, t0=t0, dur_s=dur_s, trace=trace, parent=parent,
            cat="serve", **attrs,
        )
        if not self._phases_via_tee:
            self.timeline.event(
                name, dur_s=round(max(float(dur_s), 0.0), 9), **attrs
            )

    def _span(self, name, *, trace=None, parent=None, **attrs):
        """``_phase`` for a phase that is one lexical block: a scoped span
        (``with self._span(...) as sp:``; ``sp.set(...)`` adds what is only
        known inside), so an ``annotate=True`` tracer also writes it into
        the profiler's host plane, on the device trace's own clock. Without
        ``trace``/``parent`` it nests under the innermost open span —
        ``step`` and its children ride the engine lane that way."""
        span = self.tracing.span(
            name, trace=trace, parent=parent, cat="serve", **attrs
        )
        if self._phases_via_tee:
            return span
        return _Phase(self.timeline, name, span, attrs)

    def _call(self, program: str, key: Any, fn, *args):
        """Call an engine program. The first call at a new (program, key)
        traces and compiles or loads it: that one runs inside an
        engine-lane ``program_build`` span and counts in
        ``serve_program_builds_total`` (building the jitted function
        itself is lazy and costs nothing)."""
        if (program, key) in self._ran:
            return fn(*args)
        with self._span(
            "program_build", trace=self._engine_trace,
            parent=self._step_span, program=program, key=str(key),
        ):
            out = fn(*args)
        self._ran.add((program, key))
        self._m_builds.inc()
        self.stats["program_builds"] += 1
        return out

    def _sample_hbm(self) -> None:
        """The two HBM gauges are last-written levels for a scrape, not a
        per-step series: ``memory_stats()`` is a per-device runtime call,
        so it runs at most once a second (and never with telemetry off)."""
        now = time.perf_counter()
        if not self.telemetry.enabled or now - self._hbm_sampled_at < 1.0:
            return
        self._hbm_sampled_at = now
        for k, v in _hbm_gib().items():
            (self._m_hbm_used if k == "hbm_in_use_gib"
             else self._m_hbm_peak).set(v)

    # ----------------------------------------------------------- frontend

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        request_id: int | None = None,
        *,
        deadline_s: float | None = None,
    ) -> int:
        """Enqueue a request; returns its id. ``deadline_s`` (seconds
        from now; ``None`` = the engine's ``default_deadline_s``, 0 = no
        deadline) bounds the request's total latency — see
        ``Completion.finish_reason`` for the typed outcomes. Malformed
        requests still raise here (caller bugs), but LOAD conditions
        (queue full) come back as a typed ``"shed"`` completion, so a
        client library can treat overload as data, not control flow."""
        req = self._new_request(prompt, max_new_tokens, request_id,
                                deadline_s=deadline_s)
        # Bounded admission (ISSUE 9): beyond max_queue_depth QUEUED
        # requests, shed typed instead of growing the queue without
        # bound — active slots are not counted (they already have their
        # memory), so the bound is exactly "work not yet started".
        if self.max_queue_depth and len(self._queue) >= self.max_queue_depth:
            self._m_shed.inc()
            self._complete_unadmitted(req, "shed")
            return req.id
        self._queue.append(req)
        return req.id

    def _new_request(
        self,
        prompt,
        max_new_tokens: int,
        request_id: int | None = None,
        *,
        deadline_s: float | None = None,
    ) -> ServeRequest:
        """Validate + construct a traced ``ServeRequest`` (id issued,
        trace id born, root span opened) WITHOUT enqueueing it — the
        piece of ``submit`` the disaggregated scheduler (ISSUE 12,
        serving/scheduler.py) shares: its per-tenant queues own the
        enqueue/shed policy, but the request object, the id ledger, and
        the span tree must stay THIS engine's so completions and traces
        read identically either way."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} < 1: prefill always "
                "samples the first token, so a request must want at least "
                "one (this also keeps prompt_len + 1 within the cache)"
            )
        if prompt.size + max_new_tokens > self.seq_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the model context ({self.seq_len})"
            )
        if self.paged:
            _, total = self._request_blocks(int(prompt.size), max_new_tokens)
            if total > self.pool_blocks - 1:
                raise ValueError(
                    f"request needs {total} KV blocks but the pool holds "
                    f"{self.pool_blocks - 1} usable — it could never admit "
                    "(raise serving.kv_pool_blocks or shrink the request)"
                )
        rid = self._next_id if request_id is None else request_id
        if rid in self._issued_ids:
            raise ValueError(
                f"request_id {rid} already used — completions are keyed "
                "by id, so a duplicate would silently shadow a result"
            )
        self._issued_ids.add(rid)
        self._next_id = max(self._next_id, rid) + 1
        req = ServeRequest(rid, prompt, int(max_new_tokens))
        req.deadline_s = (
            self.default_deadline_s if deadline_s is None else float(deadline_s)
        )
        # Trace-id propagation contract: the id is born HERE, at enqueue,
        # and every span this request generates (queue_wait, prefill,
        # graft, decode ticks, retire) carries it — the root "request"
        # span stays open until retirement so the tree spans
        # enqueue→retire.
        req.trace = self.tracing.new_trace(f"request {rid}")
        req.span = self.tracing.begin(
            "request", trace=req.trace, cat="serve", request=rid,
            prompt_len=int(prompt.size),
        )
        # One clock read serves both: queue_wait is emitted retroactively
        # from t_submit, so it must start exactly where the root does or
        # the tree's containment invariant breaks by a few microseconds.
        req.t_submit = getattr(req.span, "t0", None) or time.perf_counter()
        return req

    def _complete_unadmitted(self, req: ServeRequest, reason: str) -> None:
        """Resolve a request that never occupied a slot (shed / expired
        in queue / quarantined at admission) with a typed completion: the
        prompt comes back untouched, zero generated tokens, and the root
        span closes so the trace tree still reads enqueue→resolution."""
        comp = Completion(
            id=req.id,
            tokens=req.prompt.copy(),
            prompt_len=int(req.prompt.size),
            finish_reason=reason,
            token_latencies_s=[],
        )
        self._early.append(comp)
        self.stats["completed"] += 1
        self.stats[f"finish_{reason}"] += 1
        self._m_completed.inc()
        self._phase(
            "retire", t0=time.perf_counter(), dur_s=0.0,
            trace=req.trace, parent=req.span,
            request=req.id, reason=reason, n_tokens=0,
        )
        req.span.end(finish_reason=reason, n_tokens=0)

    def _expired(self, req: ServeRequest, now: float | None = None) -> bool:
        if not req.deadline_s:
            return False
        now = time.perf_counter() if now is None else now
        return now - req.t_submit > req.deadline_s

    @property
    def pending(self) -> int:
        return len(self._queue) + int(self._active.sum())

    def reset_cache(self) -> None:
        """Drop the device cache and bucket state (jit caches survive —
        they are keyed on shapes, not state). For measurement loops that
        want a cold-state pass over warm compiled programs
        (tools/serve_bench.py): the bucket trajectory replays instead of
        starting at the warm pass's terminal bucket. Refuses while
        requests are in flight."""
        self._drain_inflight()
        if self._active.any():
            raise RuntimeError("reset_cache with active slots in flight")
        self.cache = None
        self.bucket = 0
        if self.paged:
            self._free = list(range(self.pool_blocks - 1, 0, -1))
            self._ref[:] = 0
            self._reserved_future = 0
            self._slot_blocks = [[] for _ in range(self.num_slots)]
            self._slot_future[:] = 0
            self._parked_held.clear()
            self._slot_prefix_hit[:] = False
            self._slot_tokens_saved[:] = 0
            self._tables[:] = 0
            self._tables_dirty = True
            self._prefix_cache.clear()
            if self.window:
                self._reset_window_pool()
        self._slot_spec_degraded[:] = False
        self._slot_spec_proposed[:] = 0
        self._slot_spec_accepted[:] = 0
        self.stats.clear()
        # The warm pass's observations include compile time — drop them
        # so the measured pass's histograms report serving, not XLA.
        self.telemetry.reset()
        self.timeline.drain()
        self.tracing.drain()

    def bytes_per_slot(self) -> int:
        """Per-slot HBM of the LIVE engine cache at its current bucket —
        from the actual device arrays, so quantization scale tensors and
        per-slot bookkeeping are included (the accounting the bucket HBM
        estimates and serve_bench's bytes-per-slot column must agree
        with; pinned against ``generation.estimate_cache_bytes_per_slot``
        in tests/test_serving.py). 0 before the first admission.

        Paged mode: the cache is a shared pool, so "per slot" is the
        PROVISIONED share — total cache-tree bytes (pool + tables +
        bookkeeping) / num_slots. The per-REQUEST cost paged admission
        actually prices is ``block_bytes()`` x blocks reserved, which is
        what lets a deliberately small pool host more slots than the
        bucketed accounting would (serve_bench's paged capacity column)."""
        if self.cache is None:
            return 0
        if self.paged:
            total = sum(
                int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(self.cache)
            )
            return total // self.num_slots
        return cache_bytes_per_slot(self.cache, self.num_slots)

    def close(self) -> None:
        """Land the step in flight (its completions come back from the
        next ``step()`` / ``run()``), then stop the watchdog thread
        (daemon — leak-safe either way)."""
        self._drain_inflight()
        self.watchdog.stop()

    def export_trace(self, path: str) -> None:
        """Write the span ring as Chrome-trace-event JSON (Perfetto /
        chrome://tracing). One named lane per request plus the engine
        lane; non-consuming, so it can be called mid-serve."""
        self.tracing.write_chrome_trace(path)

    def run(self, max_steps: int | None = None) -> list[Completion]:
        """Drain the queue; returns completions in finish order (typed
        shed/deadline/error completions included — every submitted id
        resolves exactly once, the never-hangs contract)."""
        out: list[Completion] = []
        steps = 0
        while self.pending:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        # Requests resolved without ever entering a slot (e.g. every
        # submit shed on a full queue) never pass through step(), nor do
        # those a drain outside it resolved (close, park_slot).
        out.extend(self._drain_completed())
        out.extend(self._early)
        self._early.clear()
        return out

    # ------------------------------------------------------ jitted shapes

    def lower_decode_step(self):
        """The decode program at the engine's current cache shape, lowered
        — for callers that inspect the program (chip_smoke.py checks that
        the decode kernel is in it)."""
        self._drain_inflight()
        fn = (
            self._paged_decode_fn() if self.paged
            else self._decode_fn(self.bucket)
        )
        with self._trace_ctx():
            return fn.lower(
                self.params, self.cache, jnp.asarray(self._last_tok),
                self._rng,
            )

    def _model_at(self, cache_len: int):
        return self.model.clone(cache_len=int(cache_len))

    def _trace_ctx(self):
        from frl_distributed_ml_scaffold_tpu.dist.mesh import mesh_context

        return mesh_context(self._env)

    def _prefill_fn(self, s_p: int):
        if s_p not in self._prefill_jit:
            self._prefill_jit[s_p] = make_prefill_program(
                self._model_at(s_p), self._sample_kw
            )
        return self._prefill_jit[s_p]

    def _decode_fn(self, s: int):
        if s not in self._decode_jit:
            m = self._model_at(s)
            kw = dict(self._sample_kw)

            def serve_decode(params, cache, tok, rng):
                # The key is split in here and handed back: a dispatch
                # costs the host one call, and uploads nothing.
                rng, sub = jax.random.split(rng)
                logits, cache = _decode_step(m, params, cache, tok)
                nxt = _sample(logits, sub, **kw)
                # The tokens twice: what the next call reads on the
                # device, and what the host fetches (the paged program
                # puts its expert layers' counts behind the second).
                return nxt, nxt, cache, rng

            # Donate the cache (the PR 5 graft-lint audit's find): the
            # engine immediately rebinds self.cache to the step's output,
            # so the input cache is dead the moment the call is issued —
            # without donation every decode step transiently holds TWO
            # full KV caches live (cache-in + cache-out), exactly the
            # allocation spike continuous batching sizes its slot count
            # against. Pinned by tests/test_serving.py donation pins via
            # analysis.pins.assert_donated/assert_aliased.
            self._decode_jit[s] = jax.jit(serve_decode, donate_argnums=(1,))
        return self._decode_jit[s]

    def _graft_fn(self, s_p: int, s: int):
        """Write one prefilled request's cache rows into the engine cache
        at a (traced) slot index: a dynamic-update-slice at the leaf's
        slot-row axis (``generation.cache_batch_axis`` — THE cache-leaf
        taxonomy; the beam gather/repeat route through the same
        classifier, so new leaf classes stay in lockstep)."""
        if (s_p, s) not in self._graft_jit:
            n = self.num_slots

            def serve_graft(cache, slot_cache, slot):
                def leaf(e, p):
                    ax = cache_batch_axis(e, n)
                    assert ax is not None, (
                        f"cache leaf {e.shape} carries no slot rows"
                    )
                    idx = (0,) * ax + (slot,) + (0,) * (e.ndim - ax - 1)
                    return jax.lax.dynamic_update_slice(
                        e, p.astype(e.dtype), idx
                    )

                return jax.tree.map(leaf, cache, slot_cache)

            # The engine cache is rebound to the graft's output too —
            # donate it (same audit find as _decode_fn; the slot cache is
            # NOT donated: its rows are read strided into the update).
            self._graft_jit[(s_p, s)] = jax.jit(
                serve_graft, donate_argnums=(0,)
            )
        return self._graft_jit[(s_p, s)]

    def _grow_fn(self, s_old: int, s_new: int):
        if (s_old, s_new) not in self._grow_jit:

            def serve_grow(cache):
                def leaf(e):
                    # Pad every capacity-bearing leaf (K/V stacks AND
                    # their quantization-scale stacks) along the cache
                    # axis; bookkeeping leaves pass through.
                    ax = cache_capacity_axis(e, s_old)
                    if ax is None:
                        return e
                    pad = [(0, 0)] * e.ndim
                    pad[ax] = (0, s_new - s_old)
                    return jnp.pad(e, pad)

                return jax.tree.map(leaf, cache)

            self._grow_jit[(s_old, s_new)] = jax.jit(serve_grow)
        return self._grow_jit[(s_old, s_new)]

    # ------------------------------------------------------- paged programs

    def _paged_model(self):
        return self.model.clone(
            kv_block_size=self.block_size, kv_pool_blocks=self.pool_blocks
        )

    def _init_paged_cache(self) -> None:
        """Zero pool + tables + bookkeeping, built by the paged model's
        own ``init_paged_cache`` (the engine never hardcodes the cache
        tree; the layer loop carries the pool, so it exists before the
        first step). All-zero tables point every row at the trash
        block 0."""
        m = self._paged_model()

        def serve_init_cache():
            return init_paged_cache(m, self.num_slots)

        with self._trace_ctx():
            self.cache = self._call(
                "init_cache", None, jax.jit(serve_init_cache)
            )
        self._tables_dirty = True

    def _paged_decode_fn(self):
        """THE paged decode program — one compiled shape for the whole
        engine lifetime (the pool never grows; per-row capacity is the
        block table, which is data, not shape)."""
        if self._paged_decode_jit is None:
            m = self._paged_model()
            kw = dict(self._sample_kw)

            def serve_paged_decode(params, cache, tok, rng):
                rng, sub = jax.random.split(rng)  # as in serve_decode
                logits, cache = _decode_step(m, params, cache, tok)
                nxt = out = _sample(logits, sub, **kw)
                if self.mixed:
                    # The expert layers' counts (experts touched, pairs)
                    # come back behind the tokens, in the same array: one
                    # fetch a step, not two. The tokens alone stay on the
                    # device for the next call.
                    out = jnp.concatenate([nxt, cache["moe_stats"]])
                return nxt, out, cache, rng

            # Donate the cache (pool included) — the same two-caches-live
            # audit fix as _decode_fn, now sized at the POOL.
            self._paged_decode_jit = jax.jit(
                serve_paged_decode, donate_argnums=(1,)
            )
        return self._paged_decode_jit

    def _place_token_fn(self):
        """An admitted request's first token into the device's last
        tokens at its slot: the prefill's token is a device array before
        the host fetches it, so the step ahead reads it without a host
        upload that orders after a fetch."""
        if self._place_token_jit is None:
            def serve_place_token(toks, tok, slot):
                return toks.at[slot].set(tok[0])

            self._place_token_jit = jax.jit(serve_place_token)
        return self._place_token_jit

    def _prefill_seeded_fn(self, s_p: int, s_c: int):
        """Suffix prefill for shared-prefix admissions: the prompt SUFFIX
        (bucketed to ``s_p``) prefills against an initial slot cache of
        capacity ``s_c`` whose leading positions hold the shared prefix's
        K/V and whose indices start at the prefix length — the attention
        math is identical to a full-prompt prefill minus the prefix
        tokens' projection/score work (that is the prefill-once win)."""
        if (s_p, s_c) not in self._prefill_seeded_jit:
            self._prefill_seeded_jit[(s_p, s_c)] = (
                make_seeded_prefill_program(
                    self._model_at(s_c), self._sample_kw
                )
            )
        return self._prefill_seeded_jit[(s_p, s_c)]

    def _seed_fn(self, s_c: int, m: int):
        """Gather ``m`` shared pool blocks into the leading positions of
        a fresh slot cache at capacity ``s_c`` (indices seeded to
        ``m*block_size``): exactly the blocks that change hands move —
        never a logical-cache materialization (gather at the boundary).
        Pool leaves are ``[L, N, bs, H*hd]`` (scales ``[L, N, H*bs]``),
        slot leaves ``[L, 1, S, H, hd]`` (``[L, 1, S, H]``): the gathered
        blocks are reshaped (``generation.pool_to_slot_blocks``)."""
        if (s_c, m) not in self._seed_jit:
            bs = self.block_size
            heads = self.model.config.num_heads

            def serve_seed(cache, ids):
                from flax.traverse_util import flatten_dict, unflatten_dict

                flat = flatten_dict(cache)
                out = {}
                for kp, leaf in flat.items():
                    name = kp[-1]
                    if name in SLOT_LEAF_OF:
                        # [L, N, ...] -> [L, m, ...] gather -> [L, m, bs,
                        # H(, hd)] -> [L, 1, m*bs, ...] contiguous prefix,
                        # padded to the slot-cache capacity.
                        g = pool_to_slot_blocks(
                            name, jnp.take(leaf, ids, axis=1), heads
                        )
                        contig = g.reshape(
                            (leaf.shape[0], 1, m * bs) + g.shape[3:]
                        )
                        pad = [(0, 0)] * contig.ndim
                        pad[2] = (0, s_c - m * bs)
                        out[kp[:-1] + (SLOT_LEAF_OF[name],)] = jnp.pad(
                            contig, pad
                        )
                    elif name == "cache_index":
                        out[kp] = jnp.full(
                            (leaf.shape[0], 1), m * bs, jnp.int32
                        )
                    elif name == "pos_index":
                        out[kp] = jnp.full((1,), m * bs, jnp.int32)
                    # block_tables: slot caches carry none.
                return unflatten_dict(out)

            self._seed_jit[(s_c, m)] = jax.jit(serve_seed)
        return self._seed_jit[(s_c, m)]

    def _paged_graft_fn(self, s_c: int, n_priv: int):
        """The handoff SPLICE program (``generation.splice_pool_blocks``
        — one shared artifact: the colocated admission graft, the
        disaggregated prefill→decode handoff, and graft-lint's
        ``serving:handoff`` program are all this function): scatter the
        ``n_priv`` private blocks starting at logical block ``m0`` to
        the physical ids in ``blk_ids`` and set the slot's cache_index /
        pos_index rows — shared prefix blocks are already in the pool
        and are NOT touched (move only the blocks that change owner).
        The engine cache (pool) is donated like every program that
        rebinds it; appends and growth never clone it."""
        if (s_c, n_priv) not in self._paged_graft_jit:
            bs = self.block_size
            cfg = self.model.config

            def serve_paged_graft(cache, slot_cache, blk_ids, m0, slot):
                if self.mixed:  # m0: the sliding kind's block ids
                    return splice_kind_pools(
                        cache, slot_cache, blk_ids, m0, slot, cfg=cfg,
                        block_size=bs,
                    )
                return splice_pool_blocks(
                    cache, slot_cache, blk_ids, m0, slot, block_size=bs
                )

            self._paged_graft_jit[(s_c, n_priv)] = jax.jit(
                serve_paged_graft, donate_argnums=(0,)
            )
        return self._paged_graft_jit[(s_c, n_priv)]

    # ------------------------------------------------- speculative decoding

    def _verify_fn(self):
        """THE verify program — ONE compiled shape for the engine
        lifetime (the [B, k+1] tile is fixed at construction; no per-k
        bucket ladder — graft-lint's ``serving:verify_step_paged``
        program and the compile-once test pin this). Scores all k+1
        positions of every row against the paged cache in one forward
        and returns the greedy argmax per position; the engine accepts
        the longest draft prefix matching these predictions host-side
        — exact, which is the token-identity contract."""
        if self._verify_jit is None:
            m = self._paged_model()

            def serve_verify(params, cache, tile):
                logits, cache = _verify_step(m, params, cache, tile)
                preds = jnp.argmax(
                    logits.astype(jnp.float32), axis=-1
                ).astype(jnp.int32)
                return preds, cache

            # Donate the cache (pool included) — same two-pools-live
            # audit contract as the decode program.
            self._verify_jit = jax.jit(serve_verify, donate_argnums=(1,))
        return self._verify_jit

    def _rewind_fn(self):
        """Speculative ROLLBACK: rewind every row's cache/position
        cursor to its accepted length (``generation.rewind_cache_indices``
        — a pointer move over the donated cache; rejected positions'
        K/V are simply abandoned past the cursor). Freed tail blocks
        are returned host-side by ``step()``'s release loop."""
        if self._rewind_jit is None:
            def serve_rewind(cache, new_idx):
                return rewind_cache_indices(cache, new_idx)

            self._rewind_jit = jax.jit(serve_rewind, donate_argnums=(0,))
        return self._rewind_jit

    def _draft_fn(self):
        """Tier-B draft proposer: ONE compiled program batching every
        slot — a ragged (left-padded) prefill of each slot's trailing
        ``spec_window`` history tokens through the small draft model,
        then k greedy steps (``generation.generate`` under jit). The
        draft's cache is the window bucket, re-derived per proposal
        round: no persistent draft cache to keep consistent, nothing to
        roll back — the target pool stays the only stateful cache."""
        if self._draft_jit is None:
            dm, _ = self._draft
            k, w = self.spec_k, self.spec_window

            def serve_draft(params, windows, lengths):
                out = generate(
                    dm, params, windows, max_new_tokens=k,
                    temperature=0.0, prompt_lengths=lengths,
                )
                return out[:, w:]

            self._draft_jit = jax.jit(serve_draft)
        return self._draft_jit

    def _propose(self) -> dict[int, np.ndarray]:
        """Draft tokens per active slot for this step's verify tile:
        ``{slot: [n_j] int tokens}`` with ``1 <= n_j <= spec_k``; a slot
        missing here single-steps (rides the verify program with zero
        drafts, or the plain decode program when nobody proposed).

        Caps: ``n_j <= remaining_budget - 1`` — emitting more than the
        budget is wasted AND would write cache positions past the
        admission reservation (the worst-case block count covers exactly
        positions < prompt + budget - 1). Failure semantics (ISSUE 9
        style): a proposer exception — including the ``serve.draft``
        fault site — degrades THAT slot to plain decode for the rest of
        its request (counted, never sheds, never hangs; output is
        identical because drafting is advisory)."""
        out: dict[int, np.ndarray] = {}
        want: list[int] = []
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            req = self._req[slot]
            r = req.max_new_tokens - len(self._tokens[slot])
            if r < 2 or self._slot_spec_degraded[slot]:
                continue
            try:
                faults.maybe_raise("serve.draft", key=req.id)
            except Exception as e:
                self._spec_degrade(slot, e)
                continue
            want.append(slot)
        if not want:
            return out
        if self.spec_mode == "ngram":
            for slot in want:
                req = self._req[slot]
                r = req.max_new_tokens - len(self._tokens[slot])
                try:
                    hist = np.concatenate(
                        [req.prompt,
                         np.asarray(self._tokens[slot], np.int32)]
                    )
                    d = ngram_propose(
                        hist, min(self.spec_k, r - 1),
                        max_ngram=self.spec_ngram_max,
                    )
                except Exception as e:
                    self._spec_degrade(slot, e)
                    continue
                if d.size:
                    out[slot] = d.astype(np.int64)
            return out
        # Draft-model tier: one batched propose over every wanting slot.
        w = self.spec_window
        windows = np.zeros((self.num_slots, w), np.int32)
        lens = np.ones(self.num_slots, np.int32)
        for slot in want:
            req = self._req[slot]
            hist = np.concatenate(
                [req.prompt, np.asarray(self._tokens[slot], np.int32)]
            )[-w:]
            windows[slot, w - hist.size :] = hist
            lens[slot] = hist.size
        try:
            with self._trace_ctx():
                drafts = np.asarray(jax.device_get(
                    self._call(
                        "draft", None, self._draft_fn(),
                        self._draft[1],
                        jnp.asarray(windows),
                        jnp.asarray(lens),
                    )
                ))
        except Exception as e:
            # The batched call failed: every participating slot degrades
            # (a crashing draft model would crash every later round too).
            for slot in want:
                self._spec_degrade(slot, e)
            return out
        for slot in want:
            req = self._req[slot]
            r = req.max_new_tokens - len(self._tokens[slot])
            d = drafts[slot, : min(self.spec_k, r - 1)]
            if d.size:
                out[slot] = d.astype(np.int64)
        return out

    def _spec_degrade(self, slot: int, err: Exception) -> None:
        """Sticky per-request degradation to plain single-token decode."""
        self._slot_spec_degraded[slot] = True
        self._m_spec_draft_failures.inc()
        self.stats["spec_draft_failures"] += 1
        from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

        get_logger().warning(
            "serving: draft proposer failed for slot %d (%s: %s) — "
            "degrading to plain single-token decode for this request",
            slot, type(err).__name__, err,
        )

    def _spec_verify(self, drafts: dict[int, np.ndarray]) -> None:
        """One speculative step over the slot array: build the [B, k+1]
        tile (each row's last token + its drafts, zero-padded — pad
        positions write into the trash block or past-occupancy slots,
        masked out of every later read), run THE verify program, accept
        each row's longest draft prefix matching the greedy predictions
        (EXACT, so the emitted tokens equal plain decode's), then roll
        back: freed tail blocks return to the pool via the reservation
        accounting and every row's device cursor rewinds to its accepted
        length. Deadlines/sheds/quarantine see the emitted group
        ATOMICALLY (PR 9 semantics): eos/budget retire mid-group, the
        deadline check runs once after the group."""
        k = self.spec_k
        tile = np.zeros((self.num_slots, k + 1), np.int32)
        tile[:, 0] = self._last_tok
        n_prop = 0
        for slot, d in drafts.items():
            tile[slot, 1 : 1 + d.size] = d
            self._slot_spec_proposed[slot] += d.size
            n_prop += int(d.size)
        self._m_spec_proposed.inc(n_prop)
        self.stats["spec_proposed"] += n_prop
        n_active = int(self._active.sum())
        t0 = time.perf_counter()
        with self._span("verify", active=n_active, proposed=n_prop, k=k):
            with self._span("dispatch"), self._trace_ctx():
                preds, self.cache = self._call(
                    "verify", None, self._verify_fn(),
                    self.params, self.cache, jnp.asarray(tile),
                )
            with self._span("fetch"):
                preds = np.asarray(jax.device_get(preds))
        dt = time.perf_counter() - t0
        with self._span("emit_tokens"):
            self._emit_verified(drafts, tile, preds, t0, dt)

    def _emit_verified(self, drafts, tile, preds, t0, dt) -> None:
        """The host half of a verify step: accept, emit, retire, roll
        back (``_spec_verify``'s `emit_tokens` span)."""
        self.stats["decode_verify"] += 1
        self.stats["decode_steps"] += 1
        self.stats["slot_steps"] += int(self._active.sum())
        self._m_decodes.inc()
        self._m_spec_verifies.inc()
        self.watchdog.beat()
        self._sample_hbm()

        bs = self.block_size
        for slot in range(self.num_slots):
            if not self._active[slot]:
                continue
            req = self._req[slot]
            d = drafts.get(slot)
            n_j = int(d.size) if d is not None else 0
            # Longest accepted draft prefix: draft j survives iff it
            # equals the target's greedy prediction at position j-1.
            a = 0
            while a < n_j and tile[slot, a + 1] == preds[slot, a]:
                a += 1
            # Emitted group: the accepted drafts plus the target's own
            # next token at the first mismatch (the bonus/corrected
            # token — a verify step ALWAYS emits at least one token, so
            # speculation never regresses below plain decode).
            group = [int(x) for x in tile[slot, 1 : a + 1]]
            group.append(int(preds[slot, a]))
            per_tok = dt / len(group)
            emitted = 0
            retired = False
            t_group = time.perf_counter() - req.t_submit
            for i, tok in enumerate(group):
                self._tokens[slot].append(tok)
                self._len[slot] += 1
                self._latency[slot].append(per_tok)
                # The group lands together — one verify program — so its
                # tokens share one arrival time (gaps inside a group are
                # zero; the next gap spans the next verify).
                self._tok_times[slot].append(t_group)
                self._m_tpot.observe(per_tok)
                self._last_tok[slot] = tok
                emitted += 1
                if i < a:
                    self._slot_spec_accepted[slot] += 1
                    self._m_spec_accepted.inc()
                    self.stats["spec_accepted"] += 1
                if self._finishes(slot, tok):
                    retired = True
                    break
            self.stats["step_tokens"] += emitted
            if n_j > 0:
                # Accepted-per-verify accounting covers SPECULATING
                # slots only — a zero-draft slot riding the tile is
                # just a plain decode step for that row (its token
                # still counts in slot_steps/step_tokens, the honest
                # whole-engine invocations-per-token denominator).
                self.stats["spec_emitted"] += emitted
                self.stats["spec_slot_verifies"] += 1
                self._m_spec_per_verify.observe(float(emitted))
            self._phase(
                "decode_tick", t0=t0, dur_s=dt, trace=req.trace,
                parent=req.span, slot=slot,
                token=len(self._tokens[slot]) - 1, spec_emitted=emitted,
            )
            if retired:
                continue
            # Mid-decode deadline cancellation, ATOMIC over the group.
            if self._expired(req):
                self._m_deadline.inc()
                self._retire(slot, "deadline")
                continue
            # Table-pointer rollback: blocks appended for rejected draft
            # positions return to the pool — popped off the table tail,
            # re-counted as future reservations (the admission worst
            # case still holds, so later appends still cannot fail).
            need = (int(self._len[slot]) - 1) // bs + 1
            while len(self._slot_blocks[slot]) > need:
                bid = self._slot_blocks[slot].pop()
                self._tables[slot, len(self._slot_blocks[slot])] = 0
                self._tables_dirty = True
                self._deref(bid)
                self._slot_future[slot] += 1
                self._reserved_future += 1
                self.stats["block_rollback"] += 1
        # Cursor rewind, one donated pointer-move program: the verify
        # step advanced every row's cache_index/pos_index by k+1; the
        # true occupancy is the accepted length (cache_index == _len - 1,
        # the engine invariant). Inactive rows park at 0 — their writes
        # land in the trash block regardless.
        new_idx = np.where(self._active, self._len - 1, 0).astype(np.int32)
        with self._trace_ctx():
            self.cache = self._call(
                "rewind", None, self._rewind_fn(),
                self.cache, jnp.asarray(new_idx),
            )
        self._m_pool_util.set(self.pool_utilization())

    # ------------------------------------------------- paged block allocator

    def _deref(self, bid: int) -> None:
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)

    def _evict_one(self) -> bool:
        """Drop the least-recently-used prefix-cache entry; its blocks
        free once no slot (and no other entry) references them."""
        if not self._prefix_cache:
            return False
        _, ids = self._prefix_cache.popitem(last=False)
        for bid in ids:
            self._deref(bid)
        self.stats["prefix_evictions"] += 1
        return True

    def _match_prefix(self, prompt: np.ndarray) -> tuple[int, tuple[int, ...]]:
        """Longest cached full-block chain matching the prompt's leading
        tokens, capped so at least one token remains to prefill (the
        suffix prefill produces the first sampled token's logits).
        Sharing is FULL-block granular: the block containing the first
        divergent (or final partial) position is never shared — it is
        re-derived privately at admission, the copy-on-write that keeps
        shared blocks immutable."""
        if not self.prefix_cache_enabled:
            return 0, ()
        bs = self.block_size
        n_full = (int(prompt.size) - 1) // bs
        # Keys are the EXACT token bytes per chain length (O(L^2/bs) key
        # bytes per unique prompt) — deliberately not per-block chain
        # hashes: a hash collision here would serve one tenant's KV to
        # another, and serving prompts are bounded by seq_len.
        for i in range(n_full, 0, -1):
            key = prompt[: i * bs].tobytes()
            entry = self._prefix_cache.get(key)
            if entry is not None:
                self._prefix_cache.move_to_end(key)
                return i, entry
        return 0, ()

    def _register_prefix(self, prompt: np.ndarray, blocks: list[int]) -> None:
        """Publish every full-block chain of this prompt (each entry
        holds one reference per block, released at eviction)."""
        if not self.prefix_cache_enabled:
            return
        bs = self.block_size
        for i in range(1, int(prompt.size) // bs + 1):
            key = prompt[: i * bs].tobytes()
            if key in self._prefix_cache:
                self._prefix_cache.move_to_end(key)
                continue
            ids = tuple(blocks[:i])
            for bid in ids:
                self._ref[bid] += 1
            self._prefix_cache[key] = ids

    def _request_blocks(self, l: int, n_new: int) -> tuple[int, int]:
        """(blocks allocated at admission, worst-case total): positions
        cached over the request's life are [0, l + n_new - 1) (the final
        sampled token is never written back), and admission allocates
        through position ``l`` — the first decode write — so appends are
        the only growth left."""
        bs = self.block_size
        highest = l + n_new - 2 if n_new >= 2 else l - 1
        total = highest // bs + 1
        now = min(total, l // bs + 1)
        return now, total

    def _pool_reserve(self, req: ServeRequest) -> dict | None:
        """Admission headroom: match the prefix, then reserve every block
        the request can ever need (private-now + future appends) against
        the free list, evicting idle prefix entries LRU if required.
        ``None`` = the pool cannot host the request yet — the queue head
        WAITS (retiring slots release blocks; with bounded admission the
        growing queue sheds new submits, the documented composition)."""
        l, n_new = int(req.prompt.size), req.max_new_tokens
        m, shared = self._match_prefix(req.prompt)
        n_now, n_total = self._request_blocks(l, n_new)
        need = (n_now - m) + (n_total - n_now)
        # Shared blocks are pinned FIRST so the eviction loop can never
        # free the chain we are about to reuse.
        for bid in shared:
            self._ref[bid] += 1
        if len(self._free) - self._reserved_future < need:
            # Evict ONLY if eviction can actually satisfy the request:
            # count the blocks the cache could free (ref held exclusively
            # by cache entries) before touching it — otherwise a
            # deferred oversized head request would strip the whole
            # prefix cache every step() while gaining nothing, silently
            # defeating prefill-once under exactly the load it targets.
            cache_refs = collections.Counter(
                bid for ids in self._prefix_cache.values() for bid in ids
            )
            freeable = sum(
                1 for bid, n in cache_refs.items() if self._ref[bid] == n
            )
            if (
                len(self._free) + freeable - self._reserved_future < need
            ):
                for bid in shared:
                    self._deref(bid)
                return None
            while (
                len(self._free) - self._reserved_future < need
                and self._evict_one()
            ):
                pass
        window = {}
        if self.window:
            # The sliding kind: a window's worth, whatever the length —
            # the blocks that hold positions (l - W, l] now; the most the
            # slot will ever hold at once goes on the `admit` span.
            window = {
                "wblocks": {
                    j: self._wfree.pop()
                    for j in range(self._window_first_block(l), n_now)
                },
                "wbudget": min(self.window_places, n_total),
            }
        priv = [self._free.pop() for _ in range(n_now - m)]
        for bid in priv:
            self._ref[bid] += 1
        self._reserved_future += n_total - n_now
        return {
            "m": m,
            "shared": list(shared),
            "priv": priv,
            "future": n_total - n_now,
            **window,
        }

    def _pool_release(self, res: dict) -> None:
        """Roll back a reservation whose admission failed (quarantine).
        Private ids were popped off the free list; _deref re-appends
        them at refcount zero, so the list is whole again."""
        for bid in res["priv"] + res["shared"]:
            self._deref(bid)
        self._reserved_future -= res["future"]
        if "wblocks" in res:
            self._wfree.extend(res["wblocks"].values())

    def _note_pool_peak(self) -> None:
        """High-watermark of pool DEMAND — blocks held by slots (and by
        PARKED requests: preemption moves ownership out of the slot
        array, not out of the pool) plus worst-case reservations, with
        prefix sharing counted once. This is what serve_bench's paged
        capacity column prices a concurrent slot at: blocks held ONLY by
        the prefix cache are deliberately excluded (they are evicted on
        demand when admission needs the room, so they are a cache, not a
        capacity cost)."""
        held = {bid for blks in self._slot_blocks for bid in blks}
        held.update(
            bid for blks in self._parked_held.values() for bid in blks
        )
        demand = len(held) + self._reserved_future
        if demand > self.stats["pool_peak_blocks"]:
            self.stats["pool_peak_blocks"] = demand

    def pool_utilization(self) -> float:
        """Allocated blocks / usable blocks (trash excluded)."""
        if not self.paged:
            return 0.0
        usable = self.pool_blocks - 1
        return (usable - len(self._free)) / max(usable, 1)

    def block_bytes(self) -> int:
        """HBM bytes of one pool block (all layers, scales included) —
        the unit paged admission is priced in. 0 before the pool exists."""
        if not self.paged or self.cache is None:
            return 0
        return pool_block_bytes(self.cache)

    # --------------------------------------------------------- scheduling

    def _bucket_for(self, needed: int) -> int:
        return next_cache_bucket(self.seq_len, needed, floor=self.min_bucket)

    def _empty_cache(self, slot_cache, s: int):
        """Zeros shaped like a 1-request slot cache widened to the slot
        array (row axis per ``cache_batch_axis``) at cache capacity ``s``
        (capacity-bearing leaves — K/V and scale stacks — per
        ``cache_capacity_axis``, the same taxonomy ``_grow_fn`` pads)."""
        n = self.num_slots

        def leaf(e):
            ax = cache_batch_axis(e, 1)  # slot cache has batch 1
            assert ax is not None, f"cache leaf {e.shape} carries no rows"
            shape = list(e.shape)
            shape[ax] = n
            cap = cache_capacity_axis(e, s)
            if cap is not None:
                shape[cap] = s
            return jnp.zeros(tuple(shape), e.dtype)

        return jax.tree.map(leaf, slot_cache)

    def _ensure_bucket(self, needed: int) -> None:
        """Grow the cache to cover ``needed`` tokens; raises
        ``CacheGrowError`` (counted) when the pad allocation fails — the
        callers degrade per-request instead of crashing the engine."""
        target = self._bucket_for(needed)
        if target > self.bucket:
            t0 = time.perf_counter()
            try:
                faults.maybe_raise(
                    "serve.grow", CacheGrowError,
                    msg=f"injected grow failure {self.bucket}->{target}",
                )
                grown = self._call(
                    "grow", (self.bucket, target),
                    self._grow_fn(self.bucket, target), self.cache,
                )
            except Exception as e:
                self._m_grow_failures.inc()
                self.stats["grow_failures"] += 1
                if isinstance(e, CacheGrowError):
                    raise
                raise CacheGrowError(
                    f"cache grow {self.bucket}->{target} failed: {e}"
                ) from e
            self.cache = grown
            self.stats[f"grow_{self.bucket}->{target}"] += 1
            self._m_grows.inc()
            # Grows belong to the ENGINE lane, not any one request: the
            # pad reshapes the shared slot-array cache (the span's tee
            # keeps the old bucket_grow timeline record alive).
            self._phase(
                "bucket_grow", t0=t0, dur_s=time.perf_counter() - t0,
                trace=self._engine_trace,
                frm=self.bucket, to=target,
            )
            self.bucket = target
            self._m_bytes_slot.set(self.bytes_per_slot())

    def _admit(self) -> None:
        for slot in range(self.num_slots):
            if self._active[slot]:
                continue
            # One free slot keeps consuming the queue until a request
            # actually admits: expired and poison requests resolve typed
            # and must not burn the slot's admission for this step.
            while self._queue:
                req = self._queue[0]
                if self._expired(req):
                    # Past deadline while still queued: shedding now is
                    # strictly better than prefilling work whose answer
                    # the caller has already abandoned.
                    self._queue.popleft()
                    self._m_deadline.inc()
                    self._complete_unadmitted(req, "deadline")
                    continue
                res = None
                if self.paged:
                    res = self._pool_reserve(req)
                    if res is None:
                        # Pool headroom exhausted: the head request
                        # WAITS (FIFO — no smaller request jumps it) for
                        # retiring slots to release blocks. Backpressure,
                        # not failure: with max_queue_depth set, the
                        # queue growing past the bound sheds new submits
                        # typed, which is the documented pool-exhaustion
                        # x bounded-admission composition.
                        self.stats["admission_deferred"] += 1
                        return
                self._queue.popleft()
                if self._try_admit(slot, req, res):
                    break

    def _prefill_package(self, req: ServeRequest, res: dict | None, sub):
        """The PREFILL-WORKER half of admission (ISSUE 12): gather the
        shared-prefix seed from the pool (when hit) and run the shared
        prefill recipe (``prefill_request``) against this engine's own
        programs/params. Must run under ``_trace_ctx`` with the paged
        pool initialized; device arrays come back un-fetched so a
        disaggregated caller can dispatch asynchronously."""
        return prefill_request(
            req, res, sub,
            block_size=self.block_size if self.paged else 0,
            bucket_for=self._bucket_for, params=self.params,
            prefill_fn=lambda s_p: functools.partial(
                self._call, "prefill", s_p, self._prefill_fn(s_p)
            ),
            seeded_fn=lambda s_p, s_c: functools.partial(
                self._call, "prefill_seeded", (s_p, s_c),
                self._prefill_seeded_fn(s_p, s_c),
            ),
            seed_cache=self._seed_for(req, res),
        )

    def _seed_for(self, req: ServeRequest, res: dict | None):
        """The SEED half of a shared-prefix admission, in one place for
        both admission paths (colocated ``_prefill_package`` and the
        disaggregated scheduler): gather the matched prefix blocks from
        the pool into a slot-cache seed — ``None`` when there is no
        prefix hit. Must run under ``_trace_ctx`` (the pool lives on the
        decode partition; a separate prefill partition receives the seed
        via the scheduler's transfer)."""
        m = res["m"] if res is not None else 0
        if m == 0:
            return None
        s_c = self._bucket_for(int(req.prompt.size))
        return self._call(
            "seed", (s_c, m), self._seed_fn(s_c, m),
            self.cache, jnp.asarray(res["shared"], jnp.int32),
        )

    def _graft_package(
        self, slot: int, req: ServeRequest, res: dict | None,
        slot_cache, s_p: int, s_c: int, m: int, m0: int | None = None,
    ) -> None:
        """The SPLICE half of admission: move the prefilled cache into
        the shared engine cache. Paged: the block-table splice —
        ``generation.splice_pool_blocks`` writes only the private blocks
        that change owner into the pool, then ownership lands as a
        host-side table-row write (zero logical-cache copy; the handoff
        the disaggregated scheduler rides). Bucketed: the
        dynamic-update-slice graft. Must run under ``_trace_ctx``."""
        l = int(req.prompt.size)
        if self.paged:
            n_g = blocks_for_tokens(l, self.block_size)
            if self.mixed:
                # The sliding kind takes the prompt's last window only:
                # logical blocks [n_g - n_w, n_g), those already behind
                # the window to the trash block.
                held = res.get("wblocks", {})
                n_w = min(self.window_places, n_g) if self.window else 0
                fourth = jnp.asarray(
                    [held.get(j, 0) for j in range(n_g - n_w, n_g)], jnp.int32)
            else:
                # ``m0`` is the private blocks' logical offset WITHIN the
                # slot cache: ``m`` for a full bucketed cache, 0 when the
                # scheduler pre-sliced the cross-partition transfer down
                # to the private window.
                fourth = jnp.int32(m if m0 is None else m0)
            self.cache = self._call(
                "paged_graft", (s_c, n_g - m),
                self._paged_graft_fn(s_c, n_g - m),
                self.cache,
                slot_cache,
                jnp.asarray(res["priv"][: n_g - m], jnp.int32),
                fourth,
                jnp.int32(slot),
            )
            # The re-own: ownership moves as one table-row write.
            blocks = res["shared"] + res["priv"]
            self._tables[slot, :] = 0
            self._tables[slot, : len(blocks)] = blocks
            if self.window:
                self._wtables[slot, :] = 0
                for j, bid in res["wblocks"].items():
                    self._wtables[slot, j % self.window_places] = bid
            self._tables_dirty = True
        else:
            if self.cache is None:
                self.cache = self._empty_cache(slot_cache, s_p)
                self.bucket = s_p
            self._ensure_bucket(max(s_p, l + 1))
            self.cache = self._call(
                "graft", (s_p, self.bucket),
                self._graft_fn(s_p, self.bucket),
                self.cache, slot_cache, jnp.int32(slot),
            )

    def _try_admit(
        self, slot: int, req: ServeRequest, res: dict | None = None
    ) -> bool:
        """Prefill + graft ``req`` into ``slot``. A failure ANYWHERE in
        the request's own admission work (poison prompt crashing the
        prefill, cache growth failing) quarantines THIS request with a
        typed ``"error"`` completion and leaves the engine serving — one
        failing request must never wedge the batch (ISSUE 9). The shared
        cache is only rebound to outputs of successful programs, so a
        failed admission cannot corrupt live slots."""
        l = int(req.prompt.size)
        prev_rng = self._rng
        self._rng, sub = jax.random.split(self._rng)
        t0 = time.perf_counter()
        # Queue wait is only known now — emit it retrospectively,
        # spanning submit→admission, as the request tree's first leaf.
        self._phase(
            "queue_wait", t0=req.t_submit, dur_s=t0 - req.t_submit,
            trace=req.trace, parent=req.span, slot=slot,
        )
        try:
            # The request's `prefill` span runs from here until its first
            # token is on the host: the prefill program, the graft (a
            # span of its own inside it) and the token's fetch.
            with self._span(
                "prefill", trace=req.trace, parent=req.span,
                slot=slot, request=req.id,
            ) as span:
                faults.maybe_raise("serve.prefill", key=req.id)
                with self._trace_ctx():
                    if self.paged and self.cache is None:
                        self._init_paged_cache()
                    tok, slot_cache, s_p, s_c, m, l_suf = (
                        self._prefill_package(req, res, sub)
                    )
                    span.set(**self._prefill_attrs(s_p, m))
                    with self._span(
                        "graft", trace=req.trace, parent=req.span, slot=slot,
                        **({"blocks": blocks_for_tokens(l, self.block_size)
                            - m, "shared": m} if self.paged else {}),
                    ) as graft:
                        self._graft_package(
                            slot, req, res, slot_cache, s_p, s_c, m
                        )
                        graft.set(bucket=self.bucket)
                    if self._looks_ahead:
                        # The first token goes to the device's last tokens
                        # before the host has it: enqueued behind the step
                        # in flight, in front of the step that reads it.
                        self._tok_dev = self._call(
                            "place_token", None, self._place_token_fn(),
                            self._last_tokens(), tok, jnp.int32(slot),
                        )
                tok = int(jax.device_get(tok)[0])
            dt = time.perf_counter() - t0
        except Exception as e:
            # Quarantine: typed resolution + counter + a loud log with
            # the cause — systemic breakage (every request failing) shows
            # up immediately in serve_quarantined_total's rate. The
            # failed admission's RNG split is rolled back, so later
            # requests see exactly the splits a fault-free run would
            # give them — chaos token-identity holds for SAMPLED
            # (temperature>0) decode too, not just greedy.
            self._rng = prev_rng
            if res is not None:
                self._pool_release(res)
            self._m_quarantined.inc()
            self.stats["quarantined"] += 1
            from frl_distributed_ml_scaffold_tpu.utils.logging import (
                get_logger,
            )

            get_logger().warning(
                "serving: request %d quarantined at admission "
                "(%s: %s) — slot %d stays free, batch keeps decoding",
                req.id, type(e).__name__, e, slot,
            )
            self._complete_unadmitted(req, "error")
            return False
        self._finish_admit(
            slot, req, res, tok, dt=dt, s_p=s_p, m=m, l_suf=l_suf,
        )
        return True

    def _prefill_attrs(self, s_p: int, m: int) -> dict:
        """What a `prefill` span says beside its slot and request."""
        if not self.paged:
            return {"bucket": s_p}
        return {"bucket": s_p, "prefix_hit": m > 0,
                "tokens_saved": m * self.block_size}

    def _finish_admit(
        self, slot: int, req: ServeRequest, res: dict | None, tok: int,
        *, dt: float, s_p: int, m: int, l_suf: int,
    ) -> None:
        """Admission bookkeeping shared by the colocated path
        (``_try_admit``) and the disaggregated handoff
        (``admit_handoff``), after either has recorded the `prefill`
        span: stats, SLO observations, prefix publication, and slot
        activation. ``dt`` is the TTFT this engine charges the request
        (prefill + splice, however they were scheduled)."""
        l = int(req.prompt.size)
        bs = self.block_size if self.paged else 0
        self.stats[f"prefill_{s_p}"] += 1
        self.stats["admitted"] += 1
        self.stats["prefill_tokens"] += l_suf
        # TTFT = submit-to-first-token work this engine performed for
        # the request: prefill + graft + the forced first-token fetch.
        # (Queue wait is visible separately via serve_queue_depth.)
        self._m_ttft.observe(dt)
        self._m_prefills.inc()
        self._m_grafts.inc()
        self._m_bytes_slot.set(self.bytes_per_slot())
        if self.paged:
            self._slot_blocks[slot] = res["shared"] + res["priv"]
            self._slot_future[slot] = res["future"]
            if self.window:
                self._wslot_blocks[slot] = dict(res["wblocks"])
                self.stats["reserved_blocks_sliding"] += res["wbudget"]
            self.stats["reserved_blocks_full"] += (
                len(res["priv"]) + res["future"])
            self._note_pool_peak()
            self._slot_prefix_hit[slot] = m > 0
            self._slot_tokens_saved[slot] = m * bs
            if m > 0:
                self.stats["prefix_hits"] += 1
                self.stats["prefill_tokens_saved"] += m * bs
                self._m_prefix_hits.inc()
                self._m_prefix_saved.inc(m * bs)
            self._m_prefix_hit_rate.set(
                self.stats["prefix_hits"] / self.stats["admitted"]
            )
            self._m_pool_util.set(self.pool_utilization())
            # Publish this prompt's full-block chains for later
            # admissions (refcounted by the cache itself).
            self._register_prefix(req.prompt, self._slot_blocks[slot])
        self.watchdog.beat()

        self._req[slot] = req
        self._tokens[slot] = [tok]
        self._len[slot] = l + 1
        self._active[slot] = self._decoding[slot] = True
        self._latency[slot] = [dt]
        self._tok_times[slot] = [time.perf_counter() - req.t_submit]
        self._last_tok[slot] = tok
        self._slot_spec_degraded[slot] = False
        self._slot_spec_proposed[slot] = 0
        self._slot_spec_accepted[slot] = 0
        # The first sampled token can already finish the request.
        self._finishes(slot, tok)

    # ------------------------------------------- disaggregated entry points

    def admit_handoff(
        self, slot: int, req: ServeRequest, res: dict,
        slot_cache, tok: int, *, m: int, prefill_s: float,
        sliced: bool = False,
    ) -> None:
        """DECODE-WORKER admission of a prefill-worker package (ISSUE
        12): splice the package's private blocks into the pool —
        ``generation.splice_pool_blocks``, the same program colocated
        admission jits, so the two paths cannot drift — and activate the
        slot. ``prefill_s`` is the prefill worker's wall time, folded
        into the request's TTFT. Raises on splice failure: the scheduler
        RE-QUEUES the request (quarantine is the colocated admission
        contract; re-queue is the disaggregated one — the prefill can be
        retried on a healthy worker), and the engine state is untouched
        because the pool is only rebound to a successful program's
        output and the table/slot bookkeeping runs after it."""
        self._refuse_for_layer_kinds("the disaggregated engine (admit_handoff)")
        assert self.paged, "handoff admission is a paged-engine contract"
        self._drain_inflight()
        assert not self._active[slot], f"slot {slot} is occupied"
        l = int(req.prompt.size)
        bs = self.block_size
        s_c = self._bucket_for(l)
        t0 = time.perf_counter()
        with self._trace_ctx():
            if self.cache is None:
                self._init_paged_cache()
            self._graft_package(
                slot, req, res, slot_cache, self._bucket_for(l - m * bs),
                s_c, m, m0=0 if sliced else None,
            )
        dt_splice = time.perf_counter() - t0
        self.stats["handoff_splices"] += 1
        self._phase(
            "handoff", t0=t0, dur_s=dt_splice, trace=req.trace,
            parent=req.span, slot=slot,
            blocks=blocks_for_tokens(l, bs) - m, shared=m,
        )
        # The prefill span must END now, not prefill_s in the future:
        # the prefill ran on the worker BEFORE the splice, so the span's
        # honest interval is [splice_start - prefill_s, now] (it may
        # overlap other requests' spans — concurrent prefill is the
        # point of the split).
        s_p = self._bucket_for(l - m * bs)
        self._phase(
            "prefill", t0=t0 - prefill_s, dur_s=prefill_s + dt_splice,
            trace=req.trace, parent=req.span, slot=slot, request=req.id,
            **self._prefill_attrs(s_p, m),
        )
        self._finish_admit(
            slot, req, res, tok,
            dt=prefill_s + dt_splice, s_p=s_p, m=m, l_suf=l - m * bs,
        )

    def park_slot(self, slot: int) -> dict:
        """Preemption PARK (ISSUE 12): deactivate ``slot`` while its
        request keeps owning its KV blocks — ZERO device work (the paged
        pool is what makes parking free: the row's table points back at
        the trash block, the physical blocks stay referenced by the
        parked request, and the worst-case reservation stays accounted so
        the resumed request's appends still can never fail). Returns the
        opaque parked state ``resume_parked`` restores."""
        self._refuse_for_layer_kinds("park / resume (park_slot)")
        assert self.paged, "parking is a paged-engine contract"
        # The step in flight lands first: the parked tokens are whole. (A
        # caller that picks its victim from slot state drains before it
        # looks, or the victim may have finished by now.)
        self._drain_inflight()
        assert self._active[slot], f"slot {slot} has nothing to park"
        parked = {
            "req": self._req[slot],
            "tokens": self._tokens[slot],
            "len": int(self._len[slot]),
            "last_tok": int(self._last_tok[slot]),
            "latency": self._latency[slot],
            "tok_times": self._tok_times[slot],
            "blocks": self._slot_blocks[slot],
            "future": int(self._slot_future[slot]),
            "prefix_hit": bool(self._slot_prefix_hit[slot]),
            "tokens_saved": int(self._slot_tokens_saved[slot]),
            "spec": (
                bool(self._slot_spec_degraded[slot]),
                int(self._slot_spec_proposed[slot]),
                int(self._slot_spec_accepted[slot]),
            ),
        }
        self._req[slot] = None
        self._active[slot] = self._decoding[slot] = False
        self._tokens[slot] = []
        self._latency[slot] = []
        self._tok_times[slot] = []
        self._len[slot] = 0
        self._slot_blocks[slot] = []
        self._slot_future[slot] = 0
        self._parked_held[parked["req"].id] = parked["blocks"]
        self._tables[slot, :] = 0
        self._tables_dirty = True
        self.stats["parked"] += 1
        req = parked["req"]
        self._phase(
            "park", t0=time.perf_counter(), dur_s=0.0,
            trace=req.trace, parent=req.span, slot=slot,
            n_tokens=len(parked["tokens"]),
        )
        return parked

    def resume_parked(self, parked: dict, slot: int) -> None:
        """Preemption RESUME: re-own the parked block table into ``slot``
        (a table-row write) and restore the row's device cursors with one
        pointer-move program (``rewind_cache_indices`` — the speculation
        rollback reused: active rows already sit at ``len - 1``, the
        engine invariant, so the move only touches the resumed row). The
        request then continues decoding from its parked ``last_tok``,
        token-identically — nothing about its K/V ever moved."""
        self._refuse_for_layer_kinds("park / resume (resume_parked)")
        self._drain_inflight()
        assert self.paged and not self._active[slot]
        req = parked["req"]
        self._req[slot] = req
        self._tokens[slot] = parked["tokens"]
        self._len[slot] = parked["len"]
        self._last_tok[slot] = parked["last_tok"]
        self._latency[slot] = parked["latency"]
        self._tok_times[slot] = parked["tok_times"]
        self._slot_blocks[slot] = parked["blocks"]
        self._slot_future[slot] = parked["future"]
        self._slot_prefix_hit[slot] = parked["prefix_hit"]
        self._slot_tokens_saved[slot] = parked["tokens_saved"]
        (self._slot_spec_degraded[slot], self._slot_spec_proposed[slot],
         self._slot_spec_accepted[slot]) = parked["spec"]
        self._parked_held.pop(req.id, None)
        self._active[slot] = self._decoding[slot] = True
        self._tables[slot, :] = 0
        self._tables[slot, : len(parked["blocks"])] = parked["blocks"]
        self._tables_dirty = True
        new_idx = np.where(self._active, self._len - 1, 0).astype(np.int32)
        with self._trace_ctx():
            self.cache = self._call(
                "rewind", None, self._rewind_fn(),
                self.cache, jnp.asarray(new_idx),
            )
        self.stats["resumed"] += 1
        self._phase(
            "resume", t0=time.perf_counter(), dur_s=0.0,
            trace=req.trace, parent=req.span, slot=slot,
            n_tokens=len(parked["tokens"]),
        )

    def retire_parked(self, parked: dict, reason: str) -> None:
        """Resolve a PARKED request without resuming it (ISSUE 12 —
        today's caller: the scheduler's parked-deadline sweep): build
        the typed completion carrying the tokens generated before the
        park, release the request's blocks and worst-case reservation,
        and close the span. Needs no slot and no device work — the
        parked K/V are simply abandoned."""
        assert self.paged, "parking is a paged-engine contract"
        req = parked["req"]
        lat = parked["latency"]
        tpot = _log2_quantiles(lat[1:], (0.50, 0.99))
        comp = Completion(
            id=req.id,
            tokens=np.concatenate(
                [req.prompt, np.asarray(parked["tokens"], np.int32)]
            ),
            prompt_len=int(req.prompt.size),
            finish_reason=reason,
            token_latencies_s=lat,
            ttft_s=lat[0] if lat else 0.0,
            tpot_p50_s=tpot[0],
            tpot_p99_s=tpot[1],
            prefix_cache_hit=parked["prefix_hit"],
            prefill_tokens_saved=parked["tokens_saved"],
            spec_accept_rate=(
                parked["spec"][2] / parked["spec"][1]
                if parked["spec"][1] else 0.0
            ),
            token_times_s=parked["tok_times"],
        )
        self._completed.append(comp)
        for bid in parked["blocks"]:
            self._deref(bid)
        self._reserved_future -= parked["future"]
        self._parked_held.pop(req.id, None)
        self._m_pool_util.set(self.pool_utilization())
        self.stats["completed"] += 1
        self.stats[f"finish_{reason}"] += 1
        self._m_completed.inc()
        self._phase(
            "retire", t0=time.perf_counter(), dur_s=0.0,
            trace=req.trace, parent=req.span,
            request=req.id, reason=reason, n_tokens=len(parked["tokens"]),
        )
        req.span.end(finish_reason=reason, n_tokens=len(parked["tokens"]))

    def respread_pool(self, new_env, *, scratch_limit_bytes=None) -> dict:
        """Live model-axis RE-SPREAD (ISSUE 15, the serving-autoscaling
        seam): move the engine — params, the paged KV pool with its
        quantization-scale leaves, and every cursor/table leaf — onto
        ``new_env``'s mesh when the model axis grows or shrinks, without
        dropping in-flight work:

        1. every active slot PARKS (free under the paged pool — the PR
           12 machinery: blocks stay owned, the reservation stays
           accounted, zero device work);
        2. the redistribution service moves params (specs carried over,
           per-axis degradation) and the cache tree (pool leaves re-spread
           over heads per the ``generation.pool_heads_axis`` taxonomy;
           block ids are LOGICAL, so tables, the allocator free list,
           refcounts, and the prefix cache all survive untouched);
        3. the jitted program caches are dropped (they traced under the
           old mesh) and every parked slot RESUMES — decode continues
           token-identically (sharded == replicated is the pinned decode
           contract; the RNG is sharding-invariant by construction).

        ``new_env`` is a ``MeshEnv`` or an int model-axis size (a
        model-only mesh over the first N devices). Returns the executed
        plans (``{"params": ..., "cache": ..., "draft_params": ...}``)
        for cost attribution — ``bytes_moved`` is the shard delta, not
        the pool size. The move is DONATED end to end (the subsystem's
        in-place contract: peak transient ~= one leaf's src + dst, not
        two trees): the engine takes ownership of the param buffers it
        was constructed with, so callers sharing that exact tree with
        another consumer must re-place their copy first."""
        self._refuse_for_layer_kinds("the live re-spread (respread_pool)")
        self._drain_inflight()
        if not self.paged:
            raise ValueError(
                "respread_pool is a paged-engine contract "
                "(serving.kv_block_size > 0): the bucketed cache has no "
                "shared pool to re-spread"
            )
        from frl_distributed_ml_scaffold_tpu import redistribute
        from frl_distributed_ml_scaffold_tpu.dist.mesh import (
            MeshConfig as _MeshCfg,
            build_mesh,
        )
        from frl_distributed_ml_scaffold_tpu.models.generation import (
            pool_leaf_spec,
        )

        if isinstance(new_env, int):
            n = new_env
            new_env = build_mesh(
                _MeshCfg(data=1, model=n), devices=jax.devices()[:n]
            )
        n_model = new_env.axis_size("model")
        if n_model > 1 and self.model.config.num_heads % n_model != 0:
            raise ValueError(
                f"model axis {n_model} does not divide num_heads="
                f"{self.model.config.num_heads} — the pool shards heads"
            )
        t0 = time.perf_counter()
        # COMPILE every plan before touching any engine state: plan
        # errors (unclean layouts, indivisible dims, non-addressable
        # shards caught at chunking) surface with nothing parked and
        # nothing donated.
        plans: dict[str, Any] = {}
        plans["params"] = redistribute.compile_tree_plan(
            self.params,
            redistribute.mesh_shardings(self.params, new_env),
            scratch_limit_bytes=scratch_limit_bytes,
        )
        if self._draft is not None:
            plans["draft_params"] = redistribute.compile_tree_plan(
                self._draft[1],
                redistribute.mesh_shardings(self._draft[1], new_env),
                scratch_limit_bytes=scratch_limit_bytes,
            )
        if self.cache is not None:
            from flax.traverse_util import flatten_dict, unflatten_dict

            flat = flatten_dict(self.cache)
            dst = {}
            for kp, leaf in flat.items():
                spec = pool_leaf_spec(kp[-1], leaf)
                if spec is None:
                    spec = getattr(
                        getattr(leaf, "sharding", None), "spec", None
                    )
                if spec is None:
                    from jax.sharding import PartitionSpec as P

                    spec = P()
                dst[kp] = redistribute.spec_on(new_env.mesh, leaf, spec)
            plans["cache"] = redistribute.compile_tree_plan(
                self.cache, unflatten_dict(dst),
                scratch_limit_bytes=scratch_limit_bytes,
            )
        parked = [
            (int(s), self.park_slot(int(s)))
            for s in np.flatnonzero(self._active)
        ]
        try:
            self.params = redistribute.execute(
                plans["params"], self.params, donate=True
            )
            if self._draft is not None:
                dm, dp = self._draft
                self._draft = (
                    dm,
                    redistribute.execute(
                        plans["draft_params"], dp, donate=True
                    ),
                )
            if self.cache is not None:
                self.cache = redistribute.execute(
                    plans["cache"], self.cache, donate=True
                )
        except BaseException:
            # A mid-move failure leaves the device state partially
            # migrated (donation is per-leaf) — the engine cannot
            # safely resume decoding, but the NEVER-HANGS contract
            # survives: every parked request resolves typed "error"
            # (blocks + reservations released, host-side only) instead
            # of being stranded in an unreachable parked dict.
            for _slot, p in parked:
                self.retire_parked(p, "error")
            raise
        # Programs traced under the old mesh are unusable (and would
        # silently recompute on stale shardings): drop every jit cache;
        # they rebuild lazily under the new mesh context. The key is an
        # output of the decode program, so it sits on the old mesh's
        # devices: it comes over the host, committed to none.
        self._rng = jax.random.wrap_key_data(
            np.asarray(jax.random.key_data(self._rng)),
            impl=jax.random.key_impl(self._rng),
        )
        self._env = new_env
        self._prefill_jit.clear()
        self._decode_jit.clear()
        self._graft_jit.clear()
        self._grow_jit.clear()
        self._paged_decode_jit = None
        self._place_token_jit = None
        self._prefill_seeded_jit.clear()
        self._seed_jit.clear()
        self._paged_graft_jit.clear()
        self._verify_jit = None
        self._rewind_jit = None
        self._draft_jit = None
        self._ran.clear()
        self._tables_dirty = True
        for slot, p in parked:
            self.resume_parked(p, slot)
        moved = sum(p.bytes_moved for p in plans.values())
        self.stats["respread"] += 1
        self._m_respread.inc()
        self._m_respread_bytes.inc(moved)
        self._phase(
            "respread", t0=t0, dur_s=time.perf_counter() - t0,
            trace=self._engine_trace, model_axis=n_model,
            bytes_moved=moved, parked=len(parked),
        )
        return plans

    def _finishes(self, slot: int, tok: int) -> bool:
        req = self._req[slot]
        if self.eos_id is not None and tok == self.eos_id:
            self._retire(slot, "eos")
            return True
        if len(self._tokens[slot]) >= req.max_new_tokens:
            self._retire(slot, "length")
            return True
        return False

    def _retire(self, slot: int, reason: str) -> None:
        req = self._req[slot]
        lat = self._latency[slot]
        # Per-request SLO columns, through the same log2-bucket estimator
        # the aggregate serve_tpot_seconds histogram uses: ttft is the
        # prefill latency (lat[0]); tpot covers the decode steps (lat[1:]).
        tpot = _log2_quantiles(lat[1:], (0.50, 0.99))
        comp = Completion(
            id=req.id,
            tokens=np.concatenate(
                [req.prompt, np.asarray(self._tokens[slot], np.int32)]
            ),
            prompt_len=int(req.prompt.size),
            finish_reason=reason,
            token_latencies_s=lat,
            ttft_s=lat[0] if lat else 0.0,
            tpot_p50_s=tpot[0],
            tpot_p99_s=tpot[1],
            prefix_cache_hit=(
                bool(self._slot_prefix_hit[slot]) if self.paged else False
            ),
            prefill_tokens_saved=(
                int(self._slot_tokens_saved[slot]) if self.paged else 0
            ),
            spec_accept_rate=(
                float(self._slot_spec_accepted[slot])
                / float(self._slot_spec_proposed[slot])
                if self._slot_spec_proposed[slot] else 0.0
            ),
            token_times_s=self._tok_times[slot],
        )
        self._completed.append(comp)
        self._req[slot] = None
        self._active[slot] = self._decoding[slot] = False
        if self.paged:
            # Release the slot's block references (prefix-cache entries
            # keep shared chains alive past retirement — that is the
            # prefill-once cache), drop the unexercised reservation, and
            # point the table row at the trash block so this row's
            # writes in the shared decode program can never land in a
            # freed — possibly reallocated — block. (A step in flight
            # still holds the old row and writes its blocks once more,
            # but it is enqueued ahead of their next writer.)
            for bid in self._slot_blocks[slot]:
                self._deref(bid)
            self._reserved_future -= int(self._slot_future[slot])
            self._slot_blocks[slot] = []
            self._slot_future[slot] = 0
            self._tables[slot, :] = 0
            if self.window:
                self._wfree.extend(self._wslot_blocks[slot].values())
                self._wslot_blocks[slot] = {}
                self._wtables[slot, :] = 0
            self._tables_dirty = True
            self._set_pool_gauges()
        self.stats["completed"] += 1
        self.stats[f"finish_{reason}"] += 1
        self._m_completed.inc()
        self._phase(
            "retire", t0=time.perf_counter(), dur_s=0.0,
            trace=req.trace, parent=req.span,
            slot=slot, request=req.id, reason=reason,
            n_tokens=len(self._tokens[slot]),
        )
        # Close the root: the request tree now spans enqueue→retire.
        req.span.end(finish_reason=reason, n_tokens=len(self._tokens[slot]))

    # --------------------------------------------------------------- step

    def _drain_completed(self) -> list[Completion]:
        out = self._completed
        self._completed = []
        return out

    def step(self) -> list[Completion]:
        """Admit into free slots, run ONE decode iteration over the slot
        array, retire finished rows. Returns requests completed during
        this step (possibly at admission, for 1-token budgets; typed
        shed/deadline/error resolutions ride along).

        One iteration fetches one step's tokens and, in front of that,
        enqueues the step AHEAD: the engine keeps one plain decode step
        in flight on the device while the host fetches, emits and books
        the step before it (the module docstring's lookahead).

        On the record: one engine-lane `step` span per call, whose
        children (`admit`, `append_blocks` / `propose`, `decode` or
        `verify` with `dispatch` and `fetch` inside, `emit_tokens`) leave
        only the few lines between them uncovered — `step`'s self time
        is host time that no phase owns. A `decode` span's attributes
        describe the step whose tokens it FETCHES; its `dispatch` child
        is the enqueueing of the step ahead (and, after an engine that
        stood empty, of the fetched step itself: `ahead` 0)."""
        with self._span("step", trace=self._engine_trace) as span:
            self._step_span = span
            try:
                self._step()
            finally:
                self._step_span = None
            return self._drain_completed()

    def _drain_inflight(self) -> None:
        """THE rule for whatever reads or rewrites slot state outside
        ``_step``'s own loop (a verify step's proposals, park / resume,
        the live re-spread, a handoff admission, reset, close, lowering
        the decode step): the step in flight lands first — fetched,
        emitted, booked — so that the host's tokens, lengths and tables
        are whole, and the host's last tokens rule again (the next
        dispatch uploads them). Completions it resolves come back from
        the next ``step()`` or ``run()``."""
        if self._inflight is not None:
            cur, self._inflight = self._inflight, None
            self._decode(cur, look_ahead=False)
        self._tok_dev = None

    def _step(self) -> None:
        depth = len(self._queue)
        self._m_queue.set(depth)
        with self._span("admit", queue=depth) as span:
            before = {k: self.stats[k] for k in (
                "admitted", "reserved_blocks_full", "reserved_blocks_sliding")}
            self._admit()
            span.set(
                admitted=self.stats["admitted"] - before["admitted"],
                **({f"reserved_{kind}": self.stats[f"reserved_blocks_{kind}"]
                    - before[f"reserved_blocks_{kind}"]
                    for kind in ("full", "sliding")} if self.paged else {}),
            )
        # Typed completions resolved since the last step (shed at
        # submit) and during this admission round (expired/quarantined).
        self._completed.extend(self._early)
        self._early.clear()
        self._m_occupancy.set(float(self._active.sum()) / self.num_slots)
        if not self._active.any():
            # (A step in flight whose rows all ended is never fetched.)
            self._drop_wasted()
            return

        # Speculative proposal round (ISSUE 11): drafts per slot for
        # this step's verify tile — BEFORE the block-append loop, which
        # must cover each row's draft write positions too. An engine
        # that does not look ahead (``_looks_ahead``) starts every step
        # from the host's tokens.
        look_ahead = self._looks_ahead
        drafts: dict[int, np.ndarray] = {}
        if not look_ahead:
            self._drain_inflight()
        if self.spec_mode != "off":
            with self._span("propose") as span:
                drafts = self._propose()
                span.set(slots=len(drafts))

        # The dispatch half of the booking, for the step this call
        # enqueues: the step ahead of the one in flight, or the next one
        # where nothing is.
        if self.paged:
            with self._span("append_blocks") as span:
                span.set(appended=self._append_blocks(drafts))
        else:
            self._fit_bucket()
        if drafts:
            # At least one slot speculates: the whole batch rides the ONE
            # verify program (slots without drafts single-step inside it
            # — the mixed-batch contract).
            if self._active.any():
                self._spec_verify(drafts)
            return
        self._drop_wasted()
        if self._inflight is None and not self._decoding.any():
            return
        cur, self._inflight = self._inflight, None
        self._decode(cur, look_ahead=look_ahead)

    def _last_tokens(self):
        """The last sampled token of each row, on the device: the value
        the decode program handed on, else the host's mirror. (A COPY of
        the mirror, like every upload of an array the host writes again:
        with a step in flight the host no longer waits for the program
        that reads the upload, and on the CPU an upload may alias.)"""
        if self._tok_dev is not None:
            return self._tok_dev
        return jnp.asarray(self._last_tok.copy())

    def _owed(self, step: _StepAhead) -> list[tuple[int, ServeRequest]]:
        """The rows of ``step`` whose request still holds the slot: those
        its tokens go to."""
        return [(s, r) for s, r in step.rows if self._req[s] is r]

    def _drop_wasted(self) -> None:
        """A step in flight none of whose rows is still owed its token
        (each ended by eos, deadline or cancel at the step before) is not
        fetched: nothing waits for it, and the device's order keeps what
        is enqueued behind it whole."""
        cur = self._inflight
        if cur is not None and not self._owed(cur):
            self._inflight = None
            self._count_wasted(len(cur.rows))

    def _count_wasted(self, rows: int) -> None:
        if rows:
            self.stats["decode_wasted_rows"] += rows
            self._m_wasted_rows.inc(rows)

    def _fit_bucket(self) -> None:
        """The bucketed cache's dispatch-time booking: the bucket must
        hold every decoding row's next write position. A decoding row
        holds cache_index == _len - 1 (prefill sets idx=l with _len=l+1;
        both advance together as a step is enqueued), so the next step
        writes position _len - 1 and needs capacity exactly _len."""
        if not self._decoding.any():
            return
        try:
            self._ensure_bucket(int(self._len[self._decoding].max()))
        except CacheGrowError as e:
            # Degrade, don't die: rows that NEED the larger bucket are
            # retired typed ("error", carrying the tokens the host has:
            # one in flight is dropped); rows still inside the current
            # bucket keep decoding — a capacity failure at high occupancy
            # costs the big requests, never the whole batch.
            from frl_distributed_ml_scaffold_tpu.utils.logging import (
                get_logger,
            )

            victims = [
                s for s in np.flatnonzero(self._decoding)
                if self._len[s] > self.bucket
            ]
            get_logger().warning(
                "serving: cache grow failed (%s); retiring %d slot(s) "
                "needing the larger bucket, %d keep decoding",
                e, len(victims), int(self._active.sum()) - len(victims),
            )
            for s in victims:
                self._retire(int(s), "error")

    def _enqueue(self, ahead: int) -> _StepAhead:
        """Enqueue one decode program over the rows that are decoding and
        book what its dispatch already tells (``step``'s `dispatch`
        span): the lengths advance, and a row whose budget this step
        fills is in no later step. Nothing here waits on a fetch: the
        tokens come from the device, the key is split in the program."""
        rows = [(int(s), self._req[s]) for s in np.flatnonzero(self._decoding)]
        attrs = {"bucket": self.bucket, **self._kind_attrs(), "ahead": ahead}
        if self.paged:
            program, key, fn = "paged_decode", None, self._paged_decode_fn()
        else:
            program, key, fn = (
                "decode", self.bucket, self._decode_fn(self.bucket))
        self._tok_dev, out, self.cache, self._rng = self._call(
            program, key, fn,
            self.params,
            self.cache,
            self._last_tokens(),
            self._rng,
        )
        out.copy_to_host_async()
        for slot, req in rows:
            self._len[slot] += 1
            if self._len[slot] - req.prompt.size >= req.max_new_tokens:
                # Death by length is a count: the row is not in the step
                # after its last. It stays active, and owns its blocks,
                # until that last token is on the host.
                self._decoding[slot] = False
                if self.paged:
                    self._tables[slot, :] = 0
                    if self.window:
                        self._wtables[slot, :] = 0
                    self._tables_dirty = True
        return _StepAhead(out, rows, attrs)

    def _decode(self, cur: _StepAhead | None, *, look_ahead: bool) -> None:
        """One `decode` span and its `emit_tokens`: `dispatch` enqueues
        what is not on the device yet — ``cur`` itself where nothing was
        in flight (the engine stood empty, or was drained), then the step
        ahead — and `fetch` waits for ``cur``'s tokens, which are then
        emitted to the requests they were computed for."""
        lane = {"trace": self._engine_trace, "parent": self._step_span}
        t0 = time.perf_counter()
        # One engine-lane span per slot-array decode program FETCHED...
        with self._span("decode", **lane) as decode_span:
            # `dispatch` returns when the programs are enqueued; `fetch`
            # is where the host waits for the device.
            with self._span("dispatch"), self._trace_ctx():
                if cur is None:
                    cur = self._enqueue(ahead=0)
                    if look_ahead:  # the step ahead's half of the booking
                        (self._append_blocks({}) if self.paged
                         else self._fit_bucket())
                if look_ahead and self._decoding.any():
                    self._inflight = self._enqueue(ahead=1)
            with self._span("fetch"):
                out = np.asarray(jax.device_get(cur.out))
            rows = self._owed(cur)
            decode_span.set(active=len(rows), **cur.attrs)
            if self.mixed:
                out, (touched, pairs) = out[:-2], out[-2:]
                decode_span.set(
                    experts_touched=int(touched), expert_pairs=int(pairs))
        dt = time.perf_counter() - t0
        self._count_wasted(len(cur.rows) - len(rows))
        if cur.attrs["ahead"]:
            self.stats["decode_ahead"] += 1
            self._m_ahead.inc()
        with self._span("emit_tokens", **lane):
            self._emit_decoded(cur.attrs["bucket"], rows, out, t0, dt)

    def _append_blocks(self, drafts: dict[int, np.ndarray]) -> int:
        """Paged growth, the dispatch half of a step's booking (``step``'s
        `append_blocks` span): a decoding row crossing a block boundary
        APPENDS one reserved block to its table — a host-side int write
        plus a table push, never a device-side cache clone. The
        reservation made at admission guarantees a free block, so the
        only failure left is the injected serve.grow fault (kept on the
        same degrade-per-row contract as bucketed growth: the crossing
        row retires typed, the batch lives). A speculating row
        additionally covers its draft write positions (idx .. idx +
        n_drafts — within the worst-case reservation because drafts are
        capped at budget - 1); rejected drafts hand their tail blocks
        back after the verify step. Returns the blocks appended."""
        appended = 0
        for slot in np.flatnonzero(self._decoding):
            extra = len(drafts.get(int(slot), ()))
            need = (
                int(self._len[slot]) - 1 + extra
            ) // self.block_size + 1
            while len(self._slot_blocks[slot]) < need:
                try:
                    faults.maybe_raise(
                        "serve.grow", CacheGrowError,
                        msg=f"injected block-append failure slot {slot}",
                    )
                    bid = self._free.pop()
                except Exception as e:
                    self._m_grow_failures.inc()
                    self.stats["grow_failures"] += 1
                    from frl_distributed_ml_scaffold_tpu.utils.logging import (
                        get_logger,
                    )

                    get_logger().warning(
                        "serving: block append failed for slot %d "
                        "(%s: %s); retiring it, batch keeps decoding",
                        slot, type(e).__name__, e,
                    )
                    drafts.pop(int(slot), None)
                    self._retire(int(slot), "error")
                    break
                self._reserved_future -= 1
                self._slot_future[slot] -= 1
                self._ref[bid] += 1
                # (No peak sample here: an append converts one
                # reservation into one held block — demand is
                # unchanged, the admission-time sample covers it.)
                self._slot_blocks[slot].append(bid)
                self._tables[slot, len(self._slot_blocks[slot]) - 1] = bid
                self._tables_dirty = True
                appended += 1
                self.stats["block_append"] += 1
                self._m_block_appends.inc()
                self._phase(
                    "block_append", t0=time.perf_counter(), dur_s=0.0,
                    trace=self._engine_trace, slot=int(slot), block=bid,
                )
        if self.window:
            appended += self._slide_windows()
        self._set_pool_gauges()
        if self._tables_dirty and self._decoding.any():
            self.cache = {
                **self.cache,
                "block_tables": jnp.asarray(self._tables.copy()),
                **({"block_tables_sliding": jnp.asarray(self._wtables.copy())}
                   if self.window else {}),
            }
            self._tables_dirty = False
        return appended

    def _slide_windows(self) -> int:
        """The sliding kind's turn of ``append_blocks``: before the step
        that writes position p, a block the window (p - W, p] has wholly
        left goes back to the free list AT ONCE and its place in the ring
        table is free for the block that many further on; the block that
        holds p comes off the free list, which the pool's size keeps from
        running dry. So a slot never holds more than ``ceil(W / block) +
        1`` sliding blocks, whatever its context. Returns the blocks
        appended."""
        appended = 0
        for slot in np.flatnonzero(self._decoding):
            p = int(self._len[slot]) - 1  # this step's write position
            held = self._wslot_blocks[slot]
            first = self._window_first_block(p)
            for j in [j for j in held if j < first]:
                # The step in flight may still read this block. It is free
                # at once all the same: whatever writes it next (this
                # step, a later one, an admission's graft) is enqueued
                # behind the step in flight and takes the pool from it.
                self._wfree.append(held.pop(j))
                self._wtables[slot, j % self.window_places] = 0
                self._tables_dirty = True
                self.stats["window_blocks_released"] += 1
                self._m_window_released.inc()
            j = p // self.block_size
            if j not in held:
                held[j] = self._wfree.pop()
                self._wtables[slot, j % self.window_places] = held[j]
                self._tables_dirty = True
                appended += 1
                self.stats["block_append_sliding"] += 1
        return appended

    def _set_pool_gauges(self) -> None:
        self._m_pool_util.set(self.pool_utilization())
        self._m_blocks_full.set(self.pool_blocks - 1 - len(self._free))
        if self.window:
            self._m_blocks_window.set(self._window_blocks_in_use())

    def _window_blocks_in_use(self) -> int:
        return self.window_pool_blocks - 1 - len(self._wfree)

    def _kind_attrs(self) -> dict:
        """What a `decode` span says of the two layer kinds: the positions
        ONE full layer and ONE sliding layer attend over in this step,
        summed over the live rows (a row that writes position p attends
        p + 1 positions, a window's worth at most in a sliding layer), and
        the sliding pool's fill. The uniform paged engine says
        ``kv_blocks_live``: the blocks ONE layer's paged kernel walks in
        this step (table places, slots x width, are static: the share of
        them that is walked is this over that)."""
        if not self.paged:
            return {}
        attended = self._len[self._decoding]  # p + 1 with p = len - 1
        if not self.mixed:
            return {"kv_blocks_live": int(
                (-(-attended // self.block_size)).sum())}
        out = {"kv_tokens_full": int(attended.sum())}
        if self.window:
            out.update(
                kv_tokens_window=int(np.minimum(attended, self.window).sum()),
                window_blocks_in_use=self._window_blocks_in_use(),
                window_pool_blocks=self.window_pool_blocks - 1,
            )
        return out

    def _emit_decoded(self, bucket: int, rows, nxt, t0: float, dt: float) -> None:
        """The fetch half of a decode step's booking (``step``'s
        `emit_tokens` span): counters, then per row that is still owed
        its token the token's bookkeeping, its `decode_tick`, and
        whatever retires it."""
        self.stats["decode_paged" if self.paged else f"decode_{bucket}"] += 1
        self.stats["decode_steps"] += 1
        # Slot-level invocation accounting (ISSUE 11): a plain step is
        # one invocation per live slot, emitting one token each — the
        # denominator serve_bench's decode-invocations-per-token column
        # (and the speculative reduction ratio) reads from.
        self.stats["slot_steps"] += len(rows)
        self.stats["step_tokens"] += len(rows)
        self._m_decodes.inc()
        self.watchdog.beat()
        self._sample_hbm()

        for slot, req in rows:
            tok = int(nxt[slot])
            self._tokens[slot].append(tok)
            self._latency[slot].append(dt)
            self._tok_times[slot].append(
                time.perf_counter() - req.t_submit
            )
            self._m_tpot.observe(dt)
            self._last_tok[slot] = tok
            # ...and one request-lane tick per live row, sharing the
            # program's timing (rows decode together in one program, so
            # a per-row clock would be fiction).
            self._phase(
                "decode_tick", t0=t0, dur_s=dt, trace=req.trace,
                parent=req.span, slot=slot,
                token=len(self._tokens[slot]) - 1,
            )
            if self._finishes(slot, tok):
                continue
            # Mid-decode deadline cancellation (ISSUE 9): a natural
            # finish (eos/budget) wins; otherwise a request past its
            # deadline retires NOW with the tokens it has — the slot is
            # freed for refill instead of burning decode steps on an
            # answer the caller has stopped waiting for.
            if self._expired(req):
                self._m_deadline.inc()
                self._retire(slot, "deadline")
