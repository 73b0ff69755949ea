#!/usr/bin/env python
"""Serving throughput/latency A/B: dense-decode vs flash-decode, replicated
vs model-sharded KV cache — and bf16/fp32 vs int8-quantized KV cache —
through the continuous-batching engine.

Runs end-to-end on CPU simulation (the sim devices come from
``--sim-devices``, set BEFORE jax initializes) so the whole pipeline —
bucketed prefill, slot grafts, decode steps, eos retirement — is exercised
without hardware; the on-chip capture at the real operating point is the
queued A/B (BACKLOG R8-1). Measures tokens/sec and p50/p99 per-token
latency per arm — plus the CAPACITY columns the quantized cache is for:
``hbm_bytes_per_slot`` (actual engine cache, scale tensors included —
``generation.cache_bytes_per_slot``), a bf16-cache reference at the same
bucket, and ``max_slots_at_hbm`` under ``--hbm-gb`` of cache budget — and
emits one BENCH_TABLE-schema row per arm (printed as a JSON line;
``--out`` appends to a file). CPU-sim rows are diagnostics — only on-chip
rows get committed to BENCH_TABLE.jsonl.

Arms are
``{dense|flash}_{replicated|sharded}[_paged][_int8|_fp8][_spec[_ngram|_draft]]``;
the ``_int8`` suffix serves the same workload with
``model.kv_cache_quant=int8`` (``_fp8`` maps to ``fp8_e4m3``), and the
``_paged`` suffix (ISSUE 10) serves it through the block-table pool
engine (``--block-size``/``--pool-blocks``). Paged arms report the paged
capacity columns — block bytes, measured peak pool blocks, HBM per
ACTIVE slot (peak blocks x block bytes / slots, prefix sharing counted
once) and the resulting ``max_slots_at_hbm`` — and additionally run a
SHARED-PREFIX workload (a few unique system prompts, several requests
each) whose ``serving.prefix`` sub-dict shows prefill work scaling with
unique prefixes rather than requests, measured per request via
``Completion.prefix_cache_hit`` / ``prefill_tokens_saved``.

The ``_spec`` suffix (ISSUE 11, paged arms only) serves the workload
with speculative decoding — ``_spec_ngram`` (default) drafts via
prompt-lookup self-speculation, ``_spec_draft`` via a tiny draft GPT
sharing the tokenizer (``--speculate-k`` drafts per verify). Spec arms
report acceptance-rate, mean-accepted-per-verify, and
decode-invocations-per-token next to the TTFT/TPOT columns, and
additionally run a REPETITIVE-TEXT workload (periodic prompts whose
greedy continuations cycle — where n-gram drafting shines) whose
``serving.spec_repetitive`` sub-dict measures the speculative headline:
mean accepted tokens per verify and the invocations-per-token reduction
vs a ``speculate=off`` engine on the same workload. Output is
token-identical either way (greedy acceptance is exact), so the columns
are pure perf.

The ``_disagg`` suffix (ISSUE 12, paged arms only) serves the workload
through the disaggregated prefill/decode scheduler
(serving/scheduler.py) and additionally runs a MIXED BURST workload —
a decode-heavy latency tenant under a prefill-heavy best-effort burst —
through BOTH engines, reporting decode TPOT p99 under the burst for
each (``serving.disagg``): colocated admission prefills into every free
slot inline before each decode tick, so the burst lands in the decode
tenant's inter-token gaps; the scheduler's decoupled admission defers
the burst instead (tail isolation, pinned >= 2x in test_serving.py).
The handoff is a block-table splice — ``handoff_transfer_bytes`` is 0
when the partitions share the pool (re-own). Under ``--chaos`` the
disagg sub-dict adds a worker-fault pass (``serve.prefill_worker`` /
``serve.handoff`` injections re-queue; every request still resolves).

    python tools/serve_bench.py --preset tiny --requests 12 --slots 4
    python tools/serve_bench.py --preset tiny --arms flash_sharded,flash_sharded_int8
    python tools/serve_bench.py --preset tiny --arms flash_replicated,flash_replicated_paged
    python tools/serve_bench.py --preset tiny --arms flash_replicated_paged_spec_ngram
    python tools/serve_bench.py --preset tiny --arms flash_replicated_paged_disagg
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="tiny",
                   choices=["tiny", "gpt2_medium"],
                   help="model size (tiny = CPU-sim friendly)")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sim-devices", type=int, default=8,
                   help="CPU-sim device count (0 = leave backend alone)")
    p.add_argument("--arms", default="dense_replicated,flash_replicated,"
                   "dense_sharded,flash_sharded,flash_replicated_int8,"
                   "flash_sharded_int8,flash_replicated_paged,"
                   "flash_replicated_paged_int8,"
                   "flash_replicated_paged_spec_ngram,"
                   "flash_replicated_paged_disagg",
                   help="comma-separated: {dense,flash}_{replicated,"
                   "sharded}[_paged][_int8|_fp8][_spec[_ngram|_draft]]"
                   "[_disagg]")
    p.add_argument("--model-axis", type=int, default=2,
                   help="model-axis size for the sharded arms")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV block size (tokens) for the paged arms "
                   "(power of two)")
    p.add_argument("--pool-blocks", type=int, default=0,
                   help="KV pool size in blocks for the paged arms "
                   "(0 = auto: never blocks admission; the capacity "
                   "column prices slots at MEASURED peak blocks either "
                   "way)")
    p.add_argument("--speculate-k", type=int, default=4,
                   help="draft tokens per verify step for the _spec arms")
    p.add_argument("--hbm-gb", type=float, default=16.0,
                   help="per-replica KV-cache HBM budget for the "
                   "max-concurrent-slots column")
    p.add_argument("--out", default=None,
                   help="append emitted rows to this jsonl file")
    p.add_argument("--chaos", action="store_true",
                   help="after the measured pass, serve the workload "
                   "again under injected faults (bounded queue, tiny "
                   "deadlines on every 3rd request, one poison prefill) "
                   "and report shed rate, deadline-miss rate, and "
                   "non-faulted-request p99 in serving.chaos")
    return p.parse_args(argv)


def _setup_backend(args) -> None:
    """Must run before jax import (the conftest.py discipline)."""
    if args.sim_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.sim_devices}"
            ).strip()


def _build(preset: str):
    import jax

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    if preset == "tiny":
        # heads=2 (head_dim 32): CPU-sim friendly while keeping the
        # head_dim representative enough that the int8 arms' bytes-per-
        # slot accounting reflects real geometry (scale overhead is
        # 2/head_dim of the payload — at head_dim 8 it would dominate).
        cfg = GPTConfig(
            vocab_size=256, num_layers=2, num_heads=2, hidden_dim=64,
            seq_len=256, dropout=0.0,
        )
    else:
        cfg = GPTConfig(
            vocab_size=50257, num_layers=24, num_heads=16, hidden_dim=1024,
            seq_len=1024, dropout=0.0,
        )
    model = GPT(cfg, get_policy(PrecisionConfig(policy="fp32")))
    tokens = jax.random.randint(
        jax.random.key(0), (2, 8), 0, cfg.vocab_size
    )
    params = jax.jit(
        lambda: model.init(
            {"params": jax.random.key(0)}, tokens, train=False
        )["params"]
    )()
    return model, params


def _workload(cfg, n_requests: int, max_new: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    ceil = max(4, min(cfg.seq_len - max_new - 1, cfg.seq_len // 4))
    work = []
    for _ in range(n_requests):
        l = int(rng.integers(2, ceil))
        n_new = int(rng.integers(max(1, max_new // 2), max_new + 1))
        # Clamp to the model context so an aggressive --max-new degrades
        # to shorter generations instead of aborting the A/B at submit().
        work.append(
            (
                rng.integers(0, cfg.vocab_size, size=l).astype(np.int32),
                max(1, min(n_new, cfg.seq_len - l)),
            )
        )
    return work


def _decode_flops_per_token(model, params, num_slots: int) -> int:
    """Jaxpr-counted FLOPs of one decode step / slots (the per-token cost
    at full occupancy — the utils/flops.py counter, same convention as the
    BENCH_TABLE backfills)."""
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.utils.flops import fn_flops

    m = model.clone(cache_len=model.config.seq_len)
    tok = jnp.zeros((num_slots, 1), jnp.int32)
    _, vars_out = m.apply(
        {"params": params}, tok, decode=True, mutable=["cache"]
    )
    cache = vars_out["cache"]

    def step(params, cache, tok):
        out, vo = m.apply(
            {"params": params, "cache": cache}, tok, decode=True,
            mutable=["cache"],
        )
        return out, vo["cache"]

    return fn_flops(step, params, cache, tok) // num_slots


def _chaos_pass(
    model, run_params, args, work, kv_kwargs=None, draft_kwargs=None
) -> dict:
    """Serve the workload again under injected faults (ISSUE 9): a
    bounded admission queue (2x slots) sheds the submit burst's tail, a
    microscopic deadline on every 3rd request forces typed deadline
    misses, and the second request's prefill is poisoned via the
    ``serve.prefill`` fault site. A speculative engine additionally gets
    its draft proposer failed once via ``serve.draft`` (ISSUE 11) — the
    hit slot degrades to plain decode, counted, output unchanged.
    Reports the degradation headline: shed rate, deadline-miss rate,
    quarantine count, and the p50/p99 token latency of the NON-faulted
    requests — the number that proves chaos does not bleed into healthy
    traffic (tests/test_faults.py pins the stronger token-identity
    form)."""
    import numpy as np

    from frl_distributed_ml_scaffold_tpu import faults
    from frl_distributed_ml_scaffold_tpu.config.schema import ServingConfig
    from frl_distributed_ml_scaffold_tpu.faults import FaultPlan
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    eng = ServingEngine(
        model, run_params, num_slots=args.slots, temperature=0.0,
        serving=ServingConfig(
            max_queue_depth=max(2, args.slots * 2), **(kv_kwargs or {})
        ),
        **(draft_kwargs or {}),
    )
    # Warm-up discipline (the measured-pass contract everywhere in this
    # tool): compile every shape the chaos pass will hit, then reset, so
    # nonfaulted_p99 measures serving under chaos — not XLA. The warm
    # pass must submit INSIDE the queue bound (no faults armed yet).
    for prompt, n_new in work:
        eng.submit(prompt, n_new)
        eng.run()
    eng.reset_cache()
    # The warm pass consumed ids 0..n-1: the chaos pass's ids continue at
    # n, so the poison key targets its SECOND request (id n+1) — inside
    # the queue bound, failing at prefill.
    specs = [dict(site="serve.prefill", key=str(len(work) + 1), times=0)]
    if (kv_kwargs or {}).get("speculate", "off") != "off":
        # Fail the first draft-proposal consultation: the hit slot
        # degrades to plain single-token decode (sticky for its
        # request) and the run completes token-identically.
        specs.append(dict(site="serve.draft", at=1, times=1))
    plan = FaultPlan(specs, seed=args.seed)
    with faults.active(plan):
        for i, (prompt, n_new) in enumerate(work):
            eng.submit(
                prompt, n_new, deadline_s=1e-4 if i % 3 == 2 else 0.0
            )
        done = eng.run()
    eng.close()
    assert len(done) == len(work), (len(done), len(work))
    n = len(done)
    by_reason: dict[str, int] = {}
    for c in done:
        by_reason[c.finish_reason] = by_reason.get(c.finish_reason, 0) + 1
    ok = [c for c in done if c.ok]
    lat = [dt for c in ok for dt in c.token_latencies_s]
    return {
        "requests": n,
        "max_queue_depth": eng.max_queue_depth,
        "injected": dict(plan.injected),
        "by_reason": by_reason,
        "shed_rate": round(by_reason.get("shed", 0) / n, 4),
        "deadline_miss_rate": round(by_reason.get("deadline", 0) / n, 4),
        "quarantined": by_reason.get("error", 0),
        "completed_ok": len(ok),
        "nonfaulted_p50_ms": (
            round(float(np.percentile(lat, 50)) * 1e3, 3) if lat else 0.0
        ),
        "nonfaulted_p99_ms": (
            round(float(np.percentile(lat, 99)) * 1e3, 3) if lat else 0.0
        ),
        "draft_failures": int(eng.stats["spec_draft_failures"]),
    }


def _bucketed_ref_bucket(cfg, work) -> int:
    """The terminal cache bucket the BUCKETED engine reaches on this
    workload (every slot pays it — the shared slot-array bucket grows to
    the largest active row): the honest bf16 reference the paged
    capacity ratio is measured against."""
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        next_cache_bucket,
    )

    need = max(len(p) + n_new for p, n_new in work)
    return next_cache_bucket(cfg.seq_len, need)


def _prefix_pass(model, run_params, args, kv_kwargs) -> dict:
    """Shared-prefix workload through the paged engine (ISSUE 10
    acceptance): a few unique "system prompts" (each an exact number of
    KV blocks), several requests per prompt with short unique tails.
    Reports prefill work against the no-sharing cost, so the headline —
    prefill scales with UNIQUE prefixes, not requests — is a measured
    column, corroborated per request by the Completion SLO fields."""
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    bs = kv_kwargs["kv_block_size"]
    vocab = model.config.vocab_size
    rng = np.random.default_rng(args.seed + 1)
    uniq, per, prefix_blocks = 3, 3, 2
    work = []
    for _ in range(uniq):
        pre = rng.integers(0, vocab, size=prefix_blocks * bs)
        for _ in range(per):
            tail = rng.integers(0, vocab, size=int(rng.integers(2, 6)))
            work.append(np.concatenate([pre, tail]).astype(np.int32))
    # The prefix pass measures prefix caching, not speculation — strip
    # the spec knobs so spec arms reuse it unchanged.
    kv_kwargs = {
        k: v for k, v in kv_kwargs.items() if not k.startswith("speculate")
    }
    eng = ServingEngine(
        model, run_params, num_slots=args.slots, temperature=0.0,
        **kv_kwargs,
    )
    for p in work:
        eng.submit(p, 4)
    done = eng.run()
    eng.close()
    assert len(done) == len(work), (len(done), len(work))
    prompt_tokens = int(sum(len(p) for p in work))
    prefilled = int(eng.stats["prefill_tokens"])
    saved = int(eng.stats["prefill_tokens_saved"])
    return {
        "unique_prefixes": uniq,
        "requests_per_prefix": per,
        "requests": len(work),
        "prefix_blocks": prefix_blocks,
        "prompt_tokens_total": prompt_tokens,
        "prefill_tokens": prefilled,
        "prefill_tokens_saved": saved,
        "prefix_hits": int(eng.stats["prefix_hits"]),
        "prefix_hit_rate": round(
            eng.stats["prefix_hits"] / len(work), 4
        ),
        # Per-request corroboration (the Completion SLO fields): the
        # aggregate savings must be exactly the sum of what each
        # completion says it saved.
        "per_request_hits": int(sum(c.prefix_cache_hit for c in done)),
        "per_request_tokens_saved": int(
            sum(c.prefill_tokens_saved for c in done)
        ),
    }


def _build_draft(cfg):
    """Tier-B draft model for the _spec_draft arms: a 1-layer GPT
    sharing the target's tokenizer (vocab), ~1/8 the width — small
    enough that a propose round costs a fraction of a verify step."""
    import jax

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    dcfg = GPTConfig(
        vocab_size=cfg.vocab_size, num_layers=1, num_heads=2,
        hidden_dim=max(32, cfg.hidden_dim // 8), seq_len=cfg.seq_len,
        dropout=0.0,
    )
    draft = GPT(dcfg, get_policy(PrecisionConfig(policy="fp32")))
    tokens = jax.random.randint(
        jax.random.key(7), (2, 8), 0, dcfg.vocab_size
    )
    dparams = jax.jit(
        lambda: draft.init(
            {"params": jax.random.key(7)}, tokens, train=False
        )["params"]
    )()
    return dict(draft_model=draft, draft_params=dparams)


def _simulate_ngram_serving(prompt, cont, k: int) -> tuple[int, int]:
    """Replay the engine's tier-A accept loop on a KNOWN greedy
    continuation, host-side: returns (tokens emitted, verify steps).
    Greedy decode is deterministic, so this is exactly what the engine
    will do — the workload builder uses it to SCORE candidate texts by
    repetitiveness (no device work)."""
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.serving.engine import ngram_propose

    hist = np.asarray(prompt)
    i, verifies = 0, 0
    while i < len(cont):
        r = len(cont) - i
        d = ngram_propose(hist, min(k, r - 1)) if r >= 2 else hist[:0]
        a = 0
        while a < d.size and d[a] == cont[i + a]:
            a += 1
        emitted = min(a + 1, r)
        verifies += 1
        hist = np.concatenate([hist, cont[i : i + emitted]])
        i += emitted
    return len(cont), verifies


def _spec_workload(model, params, n_requests: int, max_new: int, seed: int,
                   k: int = 4):
    """REPETITIVE-TEXT workload for the speculative arms: each prompt is
    a short random seed plus a prefix of the model's OWN greedy
    continuation — the prompt-lookup setting (extraction, templated
    completion, code) where the text the model is about to emit repeats
    n-grams already present in its context. Candidate texts are scored
    by simulated drafting acceptance (``_simulate_ngram_serving`` —
    greedy decode is deterministic, so the score is exact) and the most
    REPETITIVE continuations are kept: this sub-workload measures the
    text class n-gram drafting targets, the way the shared-prefix
    workload measures common-system-prompt traffic. Random-weight tiny
    models write noisier text than trained ones, so the selection pool
    is a few times the request count."""
    import jax.numpy as jnp
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.models.generation import generate

    cfg = model.config
    rng = np.random.default_rng(seed + 2)
    carry = min(32, cfg.seq_len // 8)  # continuation tokens in the prompt
    n_new = min(max(max_new, 64), cfg.seq_len // 2)
    scored = []
    for _ in range(3 * n_requests):
        s = rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 9)))
        full = np.asarray(
            generate(
                model, params, jnp.asarray(s.astype(np.int32))[None],
                max_new_tokens=carry + n_new, temperature=0.0,
            )
        )[0].astype(np.int32)
        prompt = full[: s.size + carry]
        budget = min(n_new, cfg.seq_len - prompt.size)
        cont = full[prompt.size : prompt.size + budget]
        tokens, verifies = _simulate_ngram_serving(prompt, cont, k)
        scored.append((tokens / max(verifies, 1), prompt, budget))
    scored.sort(key=lambda t: -t[0])
    return [(p, b) for _, p, b in scored[:n_requests]]


def _spec_pass(model, run_params, args, kv_kwargs, draft_kwargs) -> dict:
    """The speculative headline, measured (ISSUE 11 acceptance): serve
    the repetitive-text workload through the spec engine AND through a
    speculate=off paged engine, and report mean accepted tokens per
    verify step plus the target-model decode-invocations-per-token
    reduction. Both engines follow the warm-up discipline; outputs are
    token-identical by the greedy-acceptance contract (pinned in
    tests/test_serving.py), so this sub-dict is pure perf."""
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    work = _spec_workload(
        model, run_params, max(4, args.slots), args.max_new, args.seed,
        k=kv_kwargs.get("speculate_k", 4),
    )

    def serve(spec: bool):
        kw = dict(kv_kwargs)
        dk = dict(draft_kwargs) if spec else {}
        if not spec:
            kw.pop("speculate", None)
            kw.pop("speculate_k", None)
        eng = ServingEngine(
            model, run_params, num_slots=args.slots, temperature=0.0,
            **kw, **dk,
        )
        for prompt, n_new in work:  # warm pass: compiles
            eng.submit(prompt, n_new)
        eng.run()
        eng.reset_cache()
        for prompt, n_new in work:  # measured pass
            eng.submit(prompt, n_new)
        done = eng.run()
        eng.close()
        assert len(done) == len(work), (len(done), len(work))
        return eng, done

    eng, done = serve(spec=True)
    eng_off, _ = serve(spec=False)
    s = eng.stats
    verifies = max(int(s["spec_slot_verifies"]), 1)
    inv = s["slot_steps"] / max(s["step_tokens"], 1)
    inv_off = eng_off.stats["slot_steps"] / max(
        eng_off.stats["step_tokens"], 1
    )
    return {
        "mode": kv_kwargs.get("speculate", "ngram"),
        "k": kv_kwargs.get("speculate_k", 0),
        "requests": len(work),
        "tokens": int(s["step_tokens"]),
        "proposed": int(s["spec_proposed"]),
        "accepted": int(s["spec_accepted"]),
        "acceptance_rate": round(
            s["spec_accepted"] / max(s["spec_proposed"], 1), 4
        ),
        "mean_accepted_per_verify": round(s["spec_emitted"] / verifies, 4),
        "verify_steps": int(s["decode_verify"]),
        "decode_invocations_per_token": round(inv, 4),
        "off_decode_invocations_per_token": round(inv_off, 4),
        "invocations_reduction_x": round(inv_off / max(inv, 1e-9), 4),
        "per_request_accept_rate_mean": round(
            sum(c.spec_accept_rate for c in done) / len(done), 4
        ),
    }


def _disagg_workload(cfg, slots: int, max_new: int, seed: int):
    """The mixed prefill-heavy/decode-heavy workload the disaggregation
    A/B serves: a small DECODE-HEAVY foreground (short prompts, long
    budgets — the latency tenant whose TPOT tail is measured) plus a
    PREFILL-HEAVY burst (near-half-context prompts, budget 1 — the
    embedding/classification shape that is pure prefill, the workload
    disaggregation exists for)."""
    import numpy as np

    rng = np.random.default_rng(seed + 5)
    vocab = cfg.vocab_size
    dec_budget = max(16, min(2 * max_new, cfg.seq_len // 8))
    dec = [
        (rng.integers(0, vocab, size=int(rng.integers(4, 9)))
         .astype(np.int32), dec_budget)
        for _ in range(2)
    ]
    long_l = cfg.seq_len // 2
    pre = [
        (rng.integers(0, vocab, size=long_l - int(rng.integers(0, 8)))
         .astype(np.int32), 1)
        for _ in range(3 * slots)
    ]
    return dec, pre


def _decode_gaps_ms(done, dec_ids):
    """Inter-token gaps (ms) of the decode-heavy requests, from the
    Completion token-arrival times — the TPOT a decoding tenant actually
    experiences, inline prefill stalls included."""
    import numpy as np

    gaps = []
    for c in done:
        if c.id in dec_ids and len(c.token_times_s) > 1:
            gaps.extend(np.diff(np.asarray(c.token_times_s)) * 1e3)
    return np.asarray(gaps, np.float64)


def _max_prefills_between_decode_ticks(eng) -> int:
    """The most prefills that ran between two consecutive decode ticks,
    read off the engine's own ordered phase record (its Timeline) — the
    COUNT behind the decode-gap tail: colocated admission fills every
    free slot before the next tick (k prefills in one gap), the
    scheduler starts at most ``prefill_max_per_tick``. Unlike the gap
    itself it does not depend on the host's clock."""
    timeline = getattr(eng, "decode", eng).timeline
    worst = run = 0
    ticking = False  # prefills before the first tick delay no token
    for event in timeline.tail(len(timeline)):
        if event["name"] == "decode":
            # A run counts once a tick CLOSES it: prefills after the last
            # tick sit in no running request's gap.
            worst, run, ticking = max(worst, run), 0, True
        elif event["name"] == "prefill" and ticking:
            run += 1
    return worst


def _disagg_pass(model, run_params, args, kv_kwargs) -> dict:
    """The disaggregation headline, measured (ISSUE 12 acceptance):
    serve the mixed burst workload through the colocated paged engine
    AND through the disaggregated scheduler, and report decode TPOT
    under the prefill burst for both. Colocated admission runs a full
    prefill into EVERY free slot inline before each decode tick, so the
    burst's wall time lands inside the foreground's inter-token gaps;
    the scheduler's decoupled admission (``prefill_max_per_tick``)
    defers the burst instead — tail isolation without touching decode
    throughput. Both passes follow the warm-up discipline; outputs are
    token-identical (pinned in tests/test_serving.py), so the columns
    are pure scheduling. With ``--chaos``, a third disaggregated pass
    injects the ``serve.prefill_worker``/``serve.handoff`` sites and
    proves the re-queue path: every request still resolves."""
    import numpy as np

    from frl_distributed_ml_scaffold_tpu import faults
    from frl_distributed_ml_scaffold_tpu.faults import FaultPlan
    from frl_distributed_ml_scaffold_tpu.serving import (
        DisaggServingEngine,
        ServingEngine,
        TenantSpec,
    )

    slots = max(args.slots, 6)
    dec, pre = _disagg_workload(
        model.config, slots, args.max_new, args.seed
    )
    kv = {
        k: v for k, v in kv_kwargs.items() if not k.startswith("speculate")
    }

    def serve(disagg: bool, plan=None):
        if disagg:
            eng = DisaggServingEngine(
                model, run_params, num_slots=slots, temperature=0.0,
                tenants=[
                    TenantSpec("fg", "latency"),
                    TenantSpec("bg", "best_effort"),
                ],
                **kv,
            )
        else:
            eng = ServingEngine(
                model, run_params, num_slots=slots, temperature=0.0, **kv
            )

        def submit_all():
            ids = []
            for p, n in dec:
                ids.append(
                    eng.submit(p, n, tenant="fg") if disagg
                    else eng.submit(p, n)
                )
            for p, n in pre:
                (eng.submit(p, n, tenant="bg") if disagg
                 else eng.submit(p, n))
            return set(ids)

        submit_all()  # warm pass: compiles every shape
        eng.run()
        eng.reset_cache()
        if plan is not None:
            with faults.active(plan):
                dec_ids = submit_all()
                done = eng.run()
        else:
            dec_ids = submit_all()
            done = eng.run()
        eng.close()
        assert len(done) == len(dec) + len(pre), (len(done),)
        return eng, done, dec_ids

    eng_c, done_c, ids_c = serve(disagg=False)
    eng_d, done_d, ids_d = serve(disagg=True)
    gaps_c = _decode_gaps_ms(done_c, ids_c)
    gaps_d = _decode_gaps_ms(done_d, ids_d)
    colo_p99 = float(np.percentile(gaps_c, 99))
    dis_p99 = float(np.percentile(gaps_d, 99))
    handoff_h = eng_d.telemetry.histogram("serve_handoff_seconds")
    out = {
        "slots": slots,
        "decode_requests": len(dec),
        "burst_requests": len(pre),
        "decode_budget": int(dec[0][1]),
        "burst_prompt_tokens": int(sum(len(p) for p, _ in pre)),
        # The acceptance number: decode TPOT p99 UNDER THE PREFILL
        # BURST, colocated vs disaggregated (gap-based — the tail the
        # decoding tenant actually sees).
        "colocated_decode_tpot_p50_ms": round(
            float(np.percentile(gaps_c, 50)), 3
        ),
        "colocated_decode_tpot_p99_ms": round(colo_p99, 3),
        "disagg_decode_tpot_p50_ms": round(
            float(np.percentile(gaps_d, 50)), 3
        ),
        "disagg_decode_tpot_p99_ms": round(dis_p99, 3),
        "tail_isolation_x": round(colo_p99 / max(dis_p99, 1e-9), 4),
        "colocated_max_prefills_between_decode_ticks":
            _max_prefills_between_decode_ticks(eng_c),
        "disagg_max_prefills_between_decode_ticks":
            _max_prefills_between_decode_ticks(eng_d),
        "handoffs": int(eng_d.stats["handoffs"]),
        "handoff_p50_ms": round(handoff_h.quantile(0.50) * 1e3, 3),
        "prefill_deferred": int(eng_d.stats["prefill_deferred"]),
        "preemptions": int(eng_d.stats["preemptions"]),
        # 0 when the partitions share the pool: the splice is a re-own.
        "handoff_transfer_bytes": int(
            eng_d.stats["handoff_transfer_bytes"]
        ),
    }
    if args.chaos:
        # Worker-boundary chaos (the serve.prefill_worker/serve.handoff
        # sites): one prefill-worker death and one handoff failure mid
        # burst — both re-queue and every request still resolves (the
        # assert inside serve()), the never-hangs contract across the
        # worker boundary.
        plan = FaultPlan(
            [
                dict(site="serve.prefill_worker", at=2, times=1),
                dict(site="serve.handoff", at=3, times=1),
            ],
            seed=args.seed,
        )
        eng_f, done_f, _ = serve(disagg=True, plan=plan)
        out["chaos"] = {
            "injected": dict(plan.injected),
            "prefill_worker_failures": int(
                eng_f.stats["prefill_worker_failures"]
            ),
            "handoff_failures": int(eng_f.stats["handoff_failures"]),
            "requeued": int(
                eng_f.stats["prefill_worker_requeued"]
                + eng_f.stats["handoff_requeued"]
            ),
            "completed": len(done_f),
            "completed_ok": sum(1 for c in done_f if c.ok),
        }
    return out


def run_arm(model, params, arm: str, args, flops_per_token: int) -> dict:
    """One (decode impl, sharding) arm through the engine; returns the
    BENCH_TABLE-schema row."""
    import dataclasses
    import datetime

    import jax
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.config.schema import MeshConfig
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        build_mesh,
        mesh_context,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT, gpt_tp_rules
    from frl_distributed_ml_scaffold_tpu.parallel.partition import (
        shard_params_for_serving,
    )
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine
    from frl_distributed_ml_scaffold_tpu.utils.flops import peak_flops_per_chip

    parts = arm.split("_")
    suffixes = parts[2:]
    paged = "paged" in suffixes
    quants = [s for s in suffixes if s in ("int8", "fp8")]
    spec = "spec" in suffixes
    spec_mode = "draft" if "draft" in suffixes else "ngram"
    disagg = "disagg" in suffixes
    if (
        len(parts) < 2
        or parts[0] not in ("dense", "flash")
        or parts[1] not in ("replicated", "sharded")
        or len(quants) > 1
        or any(s not in ("paged", "int8", "fp8", "spec", "ngram", "draft",
                         "disagg")
               for s in suffixes)
        or (("ngram" in suffixes or "draft" in suffixes) and not spec)
        or (spec and not paged)
        or (disagg and not paged)
    ):
        raise ValueError(
            f"unknown arm {arm!r}: want {{dense,flash}}_{{replicated,"
            "sharded}[_paged][_int8|_fp8][_spec[_ngram|_draft]][_disagg] "
            "(spec and disagg require paged)"
        )
    impl, sharding = parts[:2]
    quant = {"int8": "int8", "fp8": "fp8_e4m3"}[quants[0]] if quants else "none"
    m = dataclasses.replace(
        model.config, decode_attention=impl, kv_cache_quant=quant
    )
    model = GPT(m, model.policy)

    mesh_sizes = {"pipe": 1, "data": 1, "fsdp": 1, "seq": 1, "expert": 1,
                  "model": 1}
    if sharding == "sharded":
        n = len(jax.devices())
        tp = args.model_axis
        if n % tp != 0 or model.config.num_heads % tp != 0:
            raise ValueError(
                f"sharded arm needs model axis {tp} dividing both device "
                f"count {n} and num_heads {model.config.num_heads}"
            )
        env = build_mesh(MeshConfig(data=n // tp, model=tp))
        mesh_sizes.update(data=n // tp, model=tp)
        with mesh_context(env):
            run_params = shard_params_for_serving(params, env, gpt_tp_rules())
    else:
        env = None
        run_params = params

    work = _workload(model.config, args.requests, args.max_new, args.seed)
    kv_kwargs = (
        dict(kv_block_size=args.block_size, kv_pool_blocks=args.pool_blocks)
        if paged else {}
    )
    draft_kwargs = {}
    if spec:
        kv_kwargs.update(speculate=spec_mode, speculate_k=args.speculate_k)
        if spec_mode == "draft":
            draft_kwargs = _build_draft(model.config)
    with mesh_context(env):
        if disagg:
            # The disaggregated facade serves the main pass (same public
            # API; single default tenant) — the burst A/B sub-dict below
            # additionally compares it against the colocated engine.
            from frl_distributed_ml_scaffold_tpu.serving import (
                DisaggServingEngine,
            )

            eng = DisaggServingEngine(
                model, run_params, num_slots=args.slots, temperature=0.0,
                **kv_kwargs, **draft_kwargs,
            )
        else:
            eng = ServingEngine(
                model, run_params, num_slots=args.slots, temperature=0.0,
                **kv_kwargs, **draft_kwargs,
            )
        # Warm-up pass: the SAME workload once through the engine, so
        # every compiled shape the measured pass will hit (each prompt
        # bucket's prefill, each cache bucket's decode step, the grafts
        # and growths between them) is already in the jit caches — the
        # timed window must measure serving, not XLA compilation, or the
        # A/B reads as whichever arm compiles fewer programs. The cache
        # state is then RESET so the measured pass replays the same
        # bucket trajectory (same shapes, warm) instead of decoding
        # everything at the warm pass's terminal bucket.
        for prompt, n_new in work:
            eng.submit(prompt, n_new)
        eng.run()
        eng.reset_cache()
        for prompt, n_new in work:
            eng.submit(prompt, n_new)
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
    assert len(done) == len(work), (len(done), len(work))
    chaos = None
    if args.chaos:
        with mesh_context(env):
            chaos = _chaos_pass(
                model, run_params, args, work, kv_kwargs, draft_kwargs
            )

    # Capacity accounting (the quantized-cache arms' raison d'être):
    # actual per-slot bytes of the terminal-bucket engine cache (scale
    # tensors included) vs a bf16-cache reference at the SAME bucket, and
    # the concurrent slots each fits in the --hbm-gb cache budget.
    from frl_distributed_ml_scaffold_tpu.models.generation import (
        estimate_cache_bytes_per_slot,
    )

    bytes_per_slot = eng.bytes_per_slot()
    hbm_budget = int(args.hbm_gb * (1 << 30))
    paged_cols = None
    if paged:
        # Paged capacity accounting: a concurrent slot costs what its
        # requests actually allocated — MEASURED peak pool blocks (prefix
        # sharing counted once, worst-case reservations included) spread
        # over the slot array, priced at actual block bytes. The bf16
        # bucketed reference is what the same workload costs the legacy
        # engine: every slot pays the terminal bucket.
        block_bytes = eng.block_bytes()
        peak_blocks = int(eng.stats["pool_peak_blocks"])
        bytes_per_active_slot = max(
            1, block_bytes * peak_blocks // args.slots
        )
        # Dtype-consistent reference: the paged win is STRUCTURAL (fewer
        # tokens held), so the bucketed reference prices its cache in
        # the same element width the measured pool actually uses (fp32
        # on the CPU sim, bf16 on chip) — except the quantized-pool
        # arms, whose reference stays bf16 (the compounding claim:
        # 1-byte pool vs bf16 buckets).
        import numpy as np

        ref_elem = (
            2 if quant != "none"
            else np.dtype(model.policy.compute_dtype).itemsize
        )
        bytes_bf16_ref = estimate_cache_bytes_per_slot(
            dataclasses.replace(model.config, kv_cache_quant="none"),
            _bucketed_ref_bucket(model.config, work),
            kv_dtype_bytes=ref_elem,
        )
        paged_cols = {
            "block_size": eng.block_size,
            "pool_blocks": eng.pool_blocks,
            "block_bytes": block_bytes,
            "pool_peak_blocks": peak_blocks,
            "pool_peak_utilization": round(
                peak_blocks / max(eng.pool_blocks - 1, 1), 4
            ),
            "hbm_bytes_per_active_slot": bytes_per_active_slot,
            "prefix_hits": int(eng.stats["prefix_hits"]),
            "prefill_tokens": int(eng.stats["prefill_tokens"]),
            "prefill_tokens_saved": int(
                eng.stats["prefill_tokens_saved"]
            ),
            # (prefix_hit_rate lives in the arm-uniform top-level
            # serving columns, not here — one site, no drift.)
        }
        max_slots = hbm_budget // bytes_per_active_slot
    else:
        bytes_bf16_ref = estimate_cache_bytes_per_slot(
            dataclasses.replace(model.config, kv_cache_quant="none"),
            eng.bucket, kv_dtype_bytes=2,
        )
        max_slots = hbm_budget // max(bytes_per_slot, 1)
    prefix = None
    if paged:
        with mesh_context(env):
            prefix = _prefix_pass(model, run_params, args, kv_kwargs)
    specd = None
    if spec:
        with mesh_context(env):
            specd = _spec_pass(
                model, run_params, args, kv_kwargs, draft_kwargs
            )
    disagg_cols = None
    if disagg:
        with mesh_context(env):
            disagg_cols = _disagg_pass(model, run_params, args, kv_kwargs)
    # SLO columns from the engine's telemetry histograms (ISSUE 7): the
    # warm-up pass's observations were dropped by reset_cache, so these
    # aggregate exactly the measured pass. TTFT is the prefill+graft
    # latency; TPOT covers the decode steps.
    ttft_h = eng.telemetry.histogram("serve_ttft_seconds")
    tpot_h = eng.telemetry.histogram("serve_tpot_seconds")
    lat = np.asarray(
        [dt for c in done for dt in c.token_latencies_s], np.float64
    )
    n_tokens = int(sum(len(c.tokens) - c.prompt_len for c in done))
    n_chips = len(jax.devices())
    tok_per_sec = n_tokens / wall
    chip = jax.devices()[0].device_kind
    per_chip = tok_per_sec / n_chips
    peak = peak_flops_per_chip()
    row = {
        "config": f"serve_bench_{args.preset}",
        "model": "gpt",
        "mesh": mesh_sizes,
        "param_sharding": "tp" if sharding == "sharded" else "replicated",
        "precision": "fp32",
        "grad_accum": 1,
        "remat": "none",
        "global_batch_size": args.slots,
        "per_chip_batch_size": args.slots,
        "n_chips": n_chips,
        "chip": chip,
        # Serving semantics: a "sample" is one generated token.
        "samples_per_sec_per_chip": round(per_chip, 3),
        "step_time_median_s": round(float(np.median(lat)), 6),
        "model_flops_per_sample": int(flops_per_token),
        # Absent on the CPU (no published peak — utils/flops.py).
        **({"mfu": flops_per_token * per_chip / peak} if peak else {}),
        "serving": {
            "arm": arm,
            "decode_attention": impl,
            "kv_cache_sharding": sharding,
            "kv_cache_quant": quant,
            "tokens_per_sec": round(tok_per_sec, 3),
            "latency_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "latency_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "ttft_s": round(ttft_h.quantile(0.50), 6),
            "ttft_p99_s": round(ttft_h.quantile(0.99), 6),
            "tpot_p50_s": round(tpot_h.quantile(0.50), 6),
            "tpot_p99_s": round(tpot_h.quantile(0.99), 6),
            "requests": len(work),
            "slots": args.slots,
            "cache_bucket": eng.bucket,
            "hbm_bytes_per_slot": bytes_per_slot,
            "bytes_per_slot_bf16_ref": bytes_bf16_ref,
            "max_slots_at_hbm": max_slots,
            "max_slots_at_hbm_bf16_ref": hbm_budget // max(bytes_bf16_ref, 1),
            "hbm_budget_gb": args.hbm_gb,
            # Per-request prefix SLO columns (every arm: 0 on bucketed).
            "prefix_hit_rate": round(
                sum(c.prefix_cache_hit for c in done) / len(done), 4
            ),
            "prefill_tokens_saved": int(
                sum(c.prefill_tokens_saved for c in done)
            ),
            # Speculative SLO columns (ISSUE 11; every arm — 1.0
            # invocations/token and 0.0 accept rate when speculate=off):
            # the per-request Completion.spec_accept_rate mean next to
            # the slot-level decode-invocations-per-emitted-token.
            "speculate": spec_mode if spec else "off",
            "disaggregated": disagg,
            "spec_accept_rate": round(
                sum(c.spec_accept_rate for c in done) / len(done), 4
            ),
            "decode_invocations_per_token": round(
                eng.stats["slot_steps"] / max(eng.stats["step_tokens"], 1),
                4,
            ),
            "engine_stats": dict(eng.stats),
            **({"paged": paged_cols} if paged_cols is not None else {}),
            **({"prefix": prefix} if prefix is not None else {}),
            **({"spec_repetitive": specd} if specd is not None else {}),
            **({"disagg": disagg_cols} if disagg_cols is not None else {}),
            **({"chaos": chaos} if chaos is not None else {}),
        },
        "note": (
            "continuous-batching serve bench (tools/serve_bench.py): "
            "tokens/sec and per-token latency through serving/engine.py; "
            "CPU-sim rows are diagnostics, the on-chip A/B at the "
            "gpt2_medium operating point is BACKLOG R8-1"
        ),
        "captured_at": datetime.datetime.now(datetime.timezone.utc)
        .strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    return row


def main(argv=None) -> int:
    args = _parse_args(argv)
    _setup_backend(args)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax

    if args.sim_devices:
        jax.config.update("jax_platforms", "cpu")

    model, params = _build(args.preset)
    flops = _decode_flops_per_token(model, params, args.slots)
    rows = []
    for arm in args.arms.split(","):
        arm = arm.strip()
        if not arm:
            continue
        row = run_arm(model, params, arm, args, flops)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "a") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")

    # Human-readable A/B summary.
    for row in rows:
        s = row["serving"]
        print(
            f"# {s['arm']:>23s}: {s['tokens_per_sec']:9.1f} tok/s  "
            f"p50 {s['latency_p50_ms']:7.2f} ms  "
            f"p99 {s['latency_p99_ms']:7.2f} ms  "
            f"{s['hbm_bytes_per_slot']:>9d} B/slot  "
            f"{s['max_slots_at_hbm']:>8d} slots@{s['hbm_budget_gb']:g}G",
            file=sys.stderr,
        )
        if "paged" in s:
            p = s["paged"]
            x = s["prefix"]
            print(
                f"# {'paged':>23s}: {p['block_bytes']:>6d} B/block  "
                f"peak {p['pool_peak_blocks']} blocks  "
                f"{p['hbm_bytes_per_active_slot']:>9d} B/active-slot  "
                f"prefix saved {x['prefill_tokens_saved']}/"
                f"{x['prompt_tokens_total']} tok over "
                f"{x['requests']} reqs ({x['unique_prefixes']} unique)",
                file=sys.stderr,
            )
        if "spec_repetitive" in s:
            sp = s["spec_repetitive"]
            print(
                f"# {'spec':>23s}: {sp['mode']} k={sp['k']}  "
                f"accept {sp['acceptance_rate']:.0%}  "
                f"{sp['mean_accepted_per_verify']:.2f} tok/verify  "
                f"{sp['decode_invocations_per_token']:.3f} inv/tok "
                f"({sp['invocations_reduction_x']:.2f}x fewer vs off)",
                file=sys.stderr,
            )
        if "disagg" in s:
            d = s["disagg"]
            print(
                f"# {'disagg':>23s}: decode TPOT p99 under burst "
                f"{d['disagg_decode_tpot_p99_ms']:.2f} ms vs colocated "
                f"{d['colocated_decode_tpot_p99_ms']:.2f} ms "
                f"({d['tail_isolation_x']:.2f}x isolation)  "
                f"{d['handoffs']} handoffs  "
                f"{d['handoff_transfer_bytes']} B moved  "
                f"{d['prefill_deferred']} deferred",
                file=sys.stderr,
            )
        if "chaos" in s:
            c = s["chaos"]
            print(
                f"# {'chaos':>23s}: shed {c['shed_rate']:.0%}  "
                f"deadline-miss {c['deadline_miss_rate']:.0%}  "
                f"quarantined {c['quarantined']}  "
                f"non-faulted p99 {c['nonfaulted_p99_ms']:.2f} ms",
                file=sys.stderr,
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
