"""Window driver for cells of kind "serve": the program's `ServingEngine`,
driven through `submit` / `step` by an open-loop generator in this thread.

Requests are due at times fixed by the mix and the seed whether or not the
engine keeps up; each is submitted at the first turn of the loop at or after
its due time (how late is reported), and timed from when it was DUE.
Arrivals start `ramp_s` before the window so that it opens on a steady
engine; after it closes nothing new is sent and what is in flight drains.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from . import correct, trace as tracelib
from .common import ROOT, limits, log, memory_peak_bytes, percentile, phase, reference_of, sized
from .traffic import serve_schedule, warmup_prompt_lengths
from .weights import flat, make_params

TRACE_FROM_S = 4.0  # the traced part of the window starts here
TRACE_FOR_S = 3.0
SYNC_MARK = "bench_clock_sync"
# The model family a file's `model_overrides` states where it states one; else
# this one, the program's decoder-only language models (config/schema.py).
DEFAULT_FAMILY = "gpt"
# The served comparison pads a sampled request to the shortest of these shares
# of `seq_len` that holds it, and takes the reference's head in blocks of rows
# of at most this many logits: no [seq_len, vocab] array is made.
PAD_LADDER = (8, 4, 2, 1)
HEAD_BLOCK_LOGITS = 2**26


def build_model(cfg_file: dict, sizes: dict, policy):
    """The program's model from the file's `model` + `model_overrides`, built
    by the program's own loader, which picks the class by `family`."""
    from frl_distributed_ml_scaffold_tpu.config import ExperimentConfig, config_from_dict
    from frl_distributed_ml_scaffold_tpu.models import create_model

    group = {"family": DEFAULT_FAMILY, **sizes, **cfg_file["model_overrides"]}
    model_cfg = config_from_dict(ExperimentConfig, {"model": group}).model
    unknown = sorted(set(group) - {f.name for f in dataclasses.fields(model_cfg)})
    if unknown:  # the loader passes over a key it does not know
        raise SystemExit(f"`model` / `model_overrides` hold keys that the program's "
                         f"{type(model_cfg).__name__} lacks: {unknown}")
    return create_model(model_cfg, policy)


def build_engine(cell: dict, seed: int, rehearse: bool, traced: bool):
    import jax
    import jax.numpy as jnp
    from frl_distributed_ml_scaffold_tpu.precision import get_policy
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine
    from frl_distributed_ml_scaffold_tpu.telemetry import MetricsRegistry, Tracer

    cfg_file = cell["config_file"]
    sizes = sized(cfg_file, "model", rehearse)
    eng_kw = sized(cfg_file, "engine", rehearse)
    policy = get_policy(cfg_file["policy"])
    model = build_model(cfg_file, sizes, policy)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False)["params"])
    params = make_params(shapes, seed, dtype=policy.param_dtype)
    # Spans in memory for the whole run: the engine's own default ring
    # (8192) would drop most of a window's.
    tracer = Tracer(capacity=4_000_000, annotate=traced, origin=0.0)
    engine = ServingEngine(model, params, telemetry=MetricsRegistry(), tracer=tracer,
                           **eng_kw)
    return engine, tracer, params, sizes, eng_kw


def warm_up(engine, mix: dict, sizes: dict, block: int, seed: int) -> int:
    """One request for every prefill bucket and graft shape the mix can
    draw, two tokens each, so that the decode program compiles too."""
    rng = np.random.default_rng([int(seed), 0xC01D])
    lengths = warmup_prompt_lengths(mix, block, sizes["seq_len"])
    for n in lengths:
        engine.submit(rng.integers(0, sizes["vocab_size"], size=n).astype(np.int32), 2)
    done = engine.run()
    bad = [c.finish_reason for c in done if not c.ok]
    if bad or len(done) != len(lengths):
        raise RuntimeError(f"warm-up requests failed: {bad}")
    return len(lengths)


def drive(engine, schedule: list[dict], seconds: float, drain_s: float,
          on_time=None) -> dict:
    """The open loop. Time 0 is the window's opening; the first request is
    due at minus the ramp. Returns per-request records and the clock."""
    first_due = schedule[0]["due_s"]
    t_zero = time.perf_counter() - first_due  # perf_counter value of window time 0
    by_id: dict[int, dict] = {}
    done: dict[int, object] = {}
    nxt, outstanding = 0, 0
    n = len(schedule)
    while True:
        now = time.perf_counter() - t_zero
        if on_time is not None:
            on_time(now)
        while nxt < n and schedule[nxt]["due_s"] <= now:
            req = schedule[nxt]
            t_sub = time.perf_counter()
            rid = engine.submit(req["prompt"], req["max_new"])
            by_id[rid] = dict(req, submit_s=t_sub - t_zero)
            nxt += 1
            outstanding += 1
        if outstanding:
            for comp in engine.step():
                if comp.id in by_id:
                    done[comp.id] = comp
                    outstanding -= 1
        elif nxt < n:
            wait = schedule[nxt]["due_s"] - (time.perf_counter() - t_zero)
            if wait > 0:
                time.sleep(min(wait, 0.002))
        if nxt >= n and not outstanding:
            break
        if now > seconds + drain_s:
            break
    t_end = time.perf_counter() - t_zero
    return {"t_zero": t_zero, "t_end": t_end, "requests": by_id, "done": done}


def in_system(res: dict, t: float) -> int:
    """Requests submitted by window time t and not finished by then."""
    count = 0
    for rid, req in res["requests"].items():
        if req["submit_s"] > t:
            continue
        comp = res["done"].get(rid)
        if comp is None or not comp.token_times_s or \
                req["submit_s"] + comp.token_times_s[-1] > t:
            count += 1
    return count


def pool_fill(res: dict, seconds: float, block: int, usable_blocks: int) -> dict | None:
    """How much of the KV pool the window's traffic fills, from the
    benchmark's own records and the sizes alone: at each token arrival inside
    the window, the blocks that hold the live requests' positions (prompt and
    tokens so far) and the blocks those requests own until they finish (a
    request reserves the blocks of its whole length when it is admitted).
    A request is live from its first token to its last. Returns the mean of
    the first and the peak of the second as shares of the usable pool."""
    if not usable_blocks:
        return None
    events = []  # (time, change in held blocks, change in reserved blocks)
    for rid, req in res["requests"].items():
        comp = res["done"].get(rid)
        if comp is None or not comp.token_times_s:
            continue
        n_prompt, n_out = len(req["prompt"]), len(comp.token_times_s)
        # Positions cached over its life are [0, prompt + tokens - 1): the last
        # token is never written back.
        reserved = max(n_prompt + max(req["max_new"], n_out) - 2, n_prompt - 1) // block + 1
        held, last = 0, n_out - 1
        for j, t in enumerate(comp.token_times_s):
            if j == last:  # the slot and its blocks are released
                events.append((req["submit_s"] + t, -held, -reserved if last else 0))
                break
            now = min((n_prompt + j - 1) // block + 1, reserved)
            events.append((req["submit_s"] + t, now - held, reserved if j == 0 else 0))
            held = now
    events.sort(key=lambda e: e[0])
    held = reserved = 0
    held_sum, n, reserved_peak = 0, 0, 0
    for t, dh, dr in events:
        held += dh
        reserved += dr
        if 0.0 <= t < seconds:
            held_sum += held
            n += 1
            reserved_peak = max(reserved_peak, reserved)
    if not n:
        return None
    return {"held_mean_share": held_sum / n / usable_blocks,
            "reserved_peak_share": reserved_peak / usable_blocks}


def window_metrics(res: dict, seconds: float) -> dict:
    """End-to-end numbers over the requests DUE in [0, seconds)."""
    ttft, gaps, late, why_failed = [], [], [], []
    attempted = failed = 0
    tokens_out = tokens_processed = 0
    for rid, req in res["requests"].items():
        comp = res["done"].get(rid)
        in_window = 0.0 <= req["due_s"] < seconds
        arrivals = []
        if comp is not None:
            arrivals = [req["submit_s"] + t for t in comp.token_times_s]
            inside = sum(1 for a in arrivals if 0.0 <= a < seconds)
            if comp.ok:
                tokens_out += inside
            tokens_processed += inside
            if arrivals and 0.0 <= arrivals[0] < seconds:
                tokens_processed += comp.prompt_len
        if not in_window:
            continue
        attempted += 1
        late.append(req["submit_s"] - req["due_s"])
        if comp is None or not comp.ok or not comp.token_times_s:
            failed += 1
            why_failed.append("unfinished" if comp is None else
                              f"{comp.finish_reason} after {len(comp.token_times_s)} tokens")
            ttft.append(res["t_end"] - req["due_s"])
            continue
        ttft.append(arrivals[0] - req["due_s"])
        gaps.extend(b - a for a, b in zip(arrivals, arrivals[1:]))
    return {
        "attempted": attempted, "failed": failed, "why_failed": why_failed,
        "ttft_p95_ms": 1e3 * percentile(ttft, 95),
        "gap_p95_ms": 1e3 * percentile(gaps, 95) if gaps else float("nan"),
        "serve_tokens_per_s": tokens_out / seconds,
        "tokens_processed": tokens_processed,
        "gen_late_s": late,
        "ttft_s": ttft, "gaps_s": gaps,
        "backlog_mid": in_system(res, seconds / 2),
        "backlog_at_close": in_system(res, seconds),
    }


def _head_columns(ref, pflat, feats, picked, model: dict, lowp: bool):
    """Per position of `feats` [T, D]: the reference's best logit, the row it
    sits in, and the logit of row `picked` [T], with the head taken in blocks
    of rows (the last block steps back so that it ends with the vocabulary)."""
    import jax
    import jax.numpy as jnp

    t, v = feats.shape[0], model["vocab_size"]
    n = min(v, max(1, HEAD_BLOCK_LOGITS // t))
    starts = jnp.minimum(jnp.arange(-(-v // n)) * n, v - n)

    def block(carry, lo):
        best, at, of_picked = carry
        lg = ref.head(pflat, feats, lo, n, model, lowp=lowp)
        top, here = lg.argmax(-1), lg.max(-1)
        better = here > best  # strictly: of equal logits the first row stays, as in argmax
        inside = (picked >= lo) & (picked < lo + n)
        mine = jnp.take_along_axis(lg, jnp.clip(picked - lo, 0, n - 1)[:, None], -1)[:, 0]
        return (jnp.where(better, here, best), jnp.where(better, lo + top, at),
                jnp.where(inside, mine, of_picked)), None

    first = (jnp.full((t,), -jnp.inf, jnp.float32), jnp.zeros((t,), jnp.int32),
             jnp.zeros((t,), jnp.float32))
    return jax.lax.scan(block, first, starts)[0]


def served_gap(ref, model: dict, params, sample: list, lowp: bool = False):
    """Widest gap by which a served token's logit lies below the reference's
    best, over every served token of the sampled requests. With `lowp` the
    token judged is the one the lower precision puts first (the control).
    `ref` is the configuration's reference module, `model` its `model` group."""
    import jax
    import jax.numpy as jnp

    pflat = flat(params)
    ladder = sorted({-(-model["seq_len"] // k) for k in PAD_LADDER})

    @jax.jit
    def gaps(pflat, tokens, lo, hi):
        picked = jnp.roll(tokens, -1)
        if lowp:
            low = ref.features(pflat, tokens[None], model, lowp=True)[0]
            picked = _head_columns(ref, pflat, low, picked, model, True)[1]
        feats = ref.features(pflat, tokens[None], model)[0]
        best, _, of_picked = _head_columns(ref, pflat, feats, picked, model, False)
        pos = jnp.arange(tokens.shape[0])
        return jnp.where((pos >= lo) & (pos < hi), best - of_picked, 0.0).max()

    worst, n_tokens = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for tokens, prompt_len in sample:
            # Causal attention: the compared positions do not see the padding,
            # so the shortest rung that holds the request reads what seq_len would.
            padded = np.zeros(min(t for t in ladder if t >= len(tokens)), np.int32)
            padded[: len(tokens)] = tokens
            # Position i's logits choose token i + 1: the served tokens are
            # those at prompt_len .. len - 1.
            g = float(gaps(pflat, jnp.asarray(padded), prompt_len - 1, len(tokens) - 1))
            worst = max(worst, g)
            n_tokens += len(tokens) - prompt_len
    return worst, n_tokens


def pick_sample(res: dict, seconds: float, k: int, seed: int) -> list:
    """The longest finished request of the window and k - 1 others drawn from
    the seed, as (all tokens, prompt length)."""
    ok = [c for rid, c in sorted(res["done"].items())
          if c.ok and 0.0 <= res["requests"][rid]["due_s"] < seconds]
    if not ok:
        return []
    longest = max(ok, key=lambda c: len(c.tokens))
    rest = [c for c in ok if c is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3B])
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[: k - 1]]
    return [(np.asarray(c.tokens, np.int32), int(c.prompt_len)) for c in picks]


def run(cell: dict, args, dev: dict, t_start: float, peaks: dict | None) -> dict:
    import jax

    cfg_file, mix = cell["config_file"], cell["traffic_file"]
    ref = reference_of(cfg_file)  # a file that names none ends the run here, not after the window
    seed = args.seed
    traced = bool(args.trace) and not args.rehearse
    engine, tracer, params, sizes, eng_kw = build_engine(cell, seed, args.rehearse, traced)
    phase("engine and weights")
    if args.rehearse:
        mix = rehearsal_mix(mix, sizes)
    n_warm = warm_up(engine, mix, sizes, eng_kw["kv_block_size"], seed)
    phase(f"warm-up of {n_warm} prompt shapes (compile or cache load)")
    schedule = serve_schedule(mix, args.seconds, seed, sizes["vocab_size"])
    tracer.drain()

    prof = Profiled(os.path.join(ROOT, ".bench_work", cell["name"], "profile"),
                    min(TRACE_FROM_S, args.seconds / 2), TRACE_FOR_S) if traced else None
    res = drive(engine, schedule, args.seconds, float(mix.get("drain_s", 60.0)),
                on_time=prof.on_time if prof else None)
    if prof:
        prof.stop()
    setup_s = res["t_zero"] - t_start
    log(f"phase ramp-in: window opened at {setup_s:.2f} s")
    w = window_metrics(res, args.seconds)
    log(f"window: {w['attempted']} requests due, {w['failed']} failed, "
        f"in system at mid-window {w['backlog_mid']} and at close {w['backlog_at_close']}, drained by {res['t_end']:.2f} s; "
        f"generator lateness p95 {1e3 * percentile(w['gen_late_s'], 95):.3f} ms")
    if w["why_failed"]:
        log(f"failed requests: {w['why_failed']}")
    log("ttft ms p50/p90/p95/p99 " + "/".join(f"{1e3 * percentile(w['ttft_s'], q):.2f}" for q in (50, 90, 95, 99))
        + "; gap ms p50/p95/p99 " + "/".join(f"{1e3 * percentile(w['gaps_s'], q):.2f}" for q in (50, 95, 99))
        + f"; tokens/s {w['serve_tokens_per_s']:.2f}")

    pool_usable = int(getattr(engine, "pool_blocks", 0) or 0) - 1
    fill = pool_fill(res, args.seconds, eng_kw["kv_block_size"], pool_usable)
    if fill:
        log(f"KV pool of {pool_usable} usable blocks: live positions hold "
            f"{100 * fill['held_mean_share']:.1f} % of it on average, the requests in "
            f"their slots own {100 * fill['reserved_peak_share']:.1f} % at the peak")
    t_zero = res["t_zero"]
    spans = [s for s in tracer.spans()
             if 0.0 <= s["t0_s"] - t_zero < args.seconds]
    ctx = {
        "kind": "serve", "config": cfg_file, "traffic": mix, "chips": dev["count"],
        "peaks": peaks, "model": sizes, "num_slots": eng_kw["num_slots"],
        "pool": fill,
        "window": {"seconds": args.seconds, "tokens_processed": w["tokens_processed"],
                   "tokens_per_s": w["serve_tokens_per_s"]},
        "spans": spans, "gen_late_s": w["gen_late_s"], "ttft_s": w["ttft_s"],
        "prompt_len_by_request": {rid: len(r["prompt"]) for rid, r in res["requests"].items()},
        "trace": None,
    }
    device = dict(dev)
    if prof:
        t_reduce = time.perf_counter()
        ctx["trace"] = prof.reduce(tracer, res)
        ctx["trace"]["reduce_s"] = time.perf_counter() - t_reduce
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    device["memory_peak_bytes"] = memory_peak_bytes()

    sample = pick_sample(res, args.seconds, cfg_file["correct"]["sample_requests"], seed)
    engine.close()
    del engine, tracer
    t_ref = time.perf_counter()
    gap, n_tokens = served_gap(ref, sizes, params, sample)
    log(f"reference and comparison: {time.perf_counter() - t_ref:.2f} s after the window")
    log(f"compared {n_tokens} served tokens of {len(sample)} requests")
    ok, compared = correct.decide(
        {"logit_gap": gap}, limits(cfg_file, args.rehearse),
        extra_ok=w["failed"] == 0 and n_tokens > 0)
    return {
        "correct": ok, "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": {"gap_p95_ms": w["gap_p95_ms"], "ttft_p95_ms": w["ttft_p95_ms"],
                       "serve_tokens_per_s": w["serve_tokens_per_s"], "setup_s": setup_s},
        "ctx": ctx, "device": device, "compared": compared,
    }


def rehearsal_mix(mix: dict, sizes: dict) -> dict:
    """The same mix cut to the rehearsal model's context."""
    t = sizes["seq_len"]
    cut = lambda spec, hi: dict(spec, mean=min(spec["mean"], hi / 2), low=min(spec["low"], hi // 2),
                                high=min(spec["high"], hi))
    return dict(mix, rate_per_s=min(mix["rate_per_s"], 8.0), ramp_s=1.0, drain_s=60.0,
                prompt_tokens=cut(mix["prompt_tokens"], t // 2),
                output_tokens=cut(mix["output_tokens"], t // 4),
                max_total_tokens=t)


class Profiled:
    """Starts and stops the profiler around a short steady part of the
    window, from inside the open loop, and marks the trace's clock."""

    def __init__(self, log_dir: str, start_s: float, for_s: float):
        self.dir, self.start_s, self.for_s = log_dir, start_s, for_s
        self.state = "before"
        self.mark_pc = None

    def on_time(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.start_s:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            with jax.profiler.TraceAnnotation(SYNC_MARK):
                self.mark_pc = time.perf_counter()
                time.sleep(0.0002)
            self.state = "on"
        elif self.state == "on" and now >= self.start_s + self.for_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self, tracer, res: dict) -> dict:
        t_zero = res["t_zero"]
        raw = tracelib.read_xplane(tracelib.find_xplane(self.dir))
        marks = [e for e in raw["host"] if e[0] == SYNC_MARK]
        if not marks:
            raise RuntimeError("the trace holds no clock mark")
        offset = marks[0][1] - self.mark_pc  # trace clock minus perf_counter
        spans = [[s["name"], s["t0_s"] + offset, s["t0_s"] + offset + s["dur_s"]]
                 for s in tracer.spans() if s["name"] != "decode_tick"]
        t0 = marks[0][2]
        ops = next(iter(raw["devices"].values()))["ops"]
        t1 = max(e[2] for e in ops)
        out = tracelib.reduce(raw, t0, t1, extra_spans=spans)
        out["xplane"] = raw  # parsed once: the readers of program runs take it from here
        # K and V positions the decode steps inside the traced part attended
        # over. Token j >= 1 of a request comes from a decode step over its
        # prompt and its j earlier tokens (token 0 comes from the prefill).
        ctx_tokens = 0
        for rid, req in res["requests"].items():
            comp = res["done"].get(rid)
            if comp is None:
                continue
            base = t_zero + req["submit_s"] + offset
            for j, t in enumerate(comp.token_times_s):
                if j >= 1 and t0 <= base + t <= t1:
                    ctx_tokens += comp.prompt_len + j
        out["context_tokens"] = ctx_tokens
        return out


def sweep(cell: dict, args, dev: dict, rates: list[float]) -> None:
    """Offer each rate in turn for --seconds on one engine and print the
    table from which the knee is read: the highest rate at which the backlog
    at the window's close does not grow."""
    engine, tracer, params, sizes, eng_kw = build_engine(cell, args.seed, args.rehearse, False)
    mix = cell["traffic_file"]
    if args.rehearse:
        mix = rehearsal_mix(mix, sizes)
    warm_up(engine, mix, sizes, eng_kw["kv_block_size"], args.seed)
    print("rate_per_s requests failed backlog_mid backlog_at_close drained_by_s tokens_per_s "
          "ttft_p50_ms ttft_p95_ms gap_p50_ms gap_p95_ms late_p95_ms pool_held_mean_pct "
          "pool_reserved_peak_pct", flush=True)
    usable = int(getattr(engine, "pool_blocks", 0) or 0) - 1
    for i, rate in enumerate(rates):
        schedule = serve_schedule(mix, args.seconds, args.seed + i, sizes["vocab_size"], rate=rate)
        tracer.drain()
        res = drive(engine, schedule, args.seconds, float(mix.get("drain_s", 60.0)))
        w = window_metrics(res, args.seconds)
        print(f"{rate:g} {w['attempted']} {w['failed']} {w['backlog_mid']} "
              f"{w['backlog_at_close']} {res['t_end']:.2f} {w['serve_tokens_per_s']:.1f} "
              f"{1e3 * percentile(w['ttft_s'], 50):.2f} {w['ttft_p95_ms']:.2f} "
              f"{1e3 * percentile(w['gaps_s'], 50):.2f} {w['gap_p95_ms']:.2f} "
              f"{1e3 * percentile(w['gen_late_s'], 95):.3f} "
              + " ".join(f"{100 * v:.1f}" for v in (pool_fill(
                  res, args.seconds, eng_kw["kv_block_size"], usable) or {}).values()), flush=True)
    engine.close()
