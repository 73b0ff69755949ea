"""The cell `laguna-xs2-serve.conv-mixed-steady` rehearsed on the CPU at its
tiny preset: `correct` comes out true, and false with the reference's window
or its shared expert planted wrong; every metric file the cell brings finds
its reader, and the readers return None on a context without the new spans
(the parent's program has none of them). Not tier-1."""

import json
import os

import pytest

from test_runs import BENCH, _in_process

CELL = "laguna-xs2-serve.conv-mixed-steady"
ARGS = ["--workload", CELL, "--seed", str(2**31 + 17), "--seconds", "3", "--trace", "0",
        "--rehearse"]
NEW_METRICS = ["moe_experts_roofline", "mixed_attn_roofline", "mixed_decode_program_device_ms",
               "mixed_graft_program_device_ms", "moe_device_share.decode",
               "kv_window_blocks_held_share", "mixed_kv_pool_held_share",
               "mixed_kv_pool_reserved_peak"]


def test_rehearsal_is_correct(capsys):
    line = _in_process(ARGS, capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}
    assert line["compared"]["logit_gap"]["value"] < line["compared"]["logit_gap"]["limit"]


def _planted(monkeypatch, change):
    """The reference's `features` handed `change(params, model)` instead."""
    from reference import laguna

    real = laguna.features

    def faulty(params, tokens, model, lowp=False):
        params, model = change(params, model)
        return real(params, tokens, model, lowp)

    monkeypatch.setattr(laguna, "features", faulty)


def test_the_references_window_planted_wrong_is_not_correct(monkeypatch, capsys):
    _planted(monkeypatch, lambda params, model: (
        params, dict(model, sliding_window=model["sliding_window"] // 2)))
    assert _in_process(ARGS, capsys)["correct"] is False


def test_the_references_shared_expert_planted_wrong_is_not_correct(monkeypatch, capsys):
    import jax.numpy as jnp

    _planted(monkeypatch, lambda params, model: (
        {k: jnp.zeros_like(v) if "/moe/shared/w2/" in k else v for k, v in params.items()},
        model))
    assert _in_process(ARGS, capsys)["correct"] is False


def test_the_reference_is_served_only():
    from reference import laguna

    with pytest.raises(NotImplementedError, match="served only"):
        laguna.train_steps({}, [], {}, {})


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_find_their_reader_and_read_nothing_without_the_spans(name):
    import run
    from lib import common

    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        spec = json.load(fh)
    cell = common.load_cell(CELL)
    assert run.applies(spec, cell)
    assert not run.applies(spec, common.load_cell("gpt2m-serve.chat-steady"))
    read = run.reader_for(spec)
    # The parent's engine: `decode` spans with no counts of layer kinds or
    # experts, and no trace (an untraced run) or a trace whose file is gone.
    spans = [{"name": "decode", "t0_s": 1.0, "dur_s": 0.01, "active": 3, "bucket": 0}]
    for trace in (None, {"t0": 0.0, "t1": 1.0, "ops": [], "steps": 0}):
        ctx = {"kind": "serve", "config": cell["config_file"], "model": cell["config_file"]["model"],
               "spans": spans, "trace": trace, "peaks": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
        assert read(ctx, spec) is None


def test_counting_functions_by_hand():
    import run

    moe = run.reader_for({"name": "moe_experts_roofline"}).__globals__["routed_experts_work"]
    # 200 experts touched, 384 pairs at hidden 2048 x width 512 in bf16.
    flops, nbytes = moe(200, 384, 2048, 512)
    assert nbytes == 200 * 3 * 2048 * 512 * 2 and flops == 384 * 6 * 2048 * 512
    attn = run.reader_for({"name": "mixed_attn_roofline"}).__globals__[
        "mixed_decode_attention_work"]
    model = {"layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
             "head_dim": 128, "num_kv_heads": 8, "num_heads": 48, "num_heads_sliding": 64}
    flops, nbytes = attn(1000, 400, model)
    assert nbytes == (1000 * 2 + 400 * 3) * 2 * 8 * 128 * 2
    assert flops == 4 * 128 * (1000 * 2 * 48 + 400 * 3 * 64)


def test_program_time_reader_reads_what_the_accepted_one_reads():
    """`mixed_*_program_device_ms` read with the accepted reader, over the
    trace the run's reduction parsed: the same number."""
    import run
    from test_step_metrics import raw_trace

    spec = common_spec("mixed_decode_program_device_ms")
    accepted = common_spec("decode_program_device_ms")
    tr = {"t0": 0.5, "t1": 9.9, "xplane": raw_trace()}
    assert spec["reader_file"] == "decode_program_device_ms"
    assert run.reader_for(spec)({"trace": tr}, spec) == run.reader_for(accepted)(
        {"trace": tr}, accepted) == pytest.approx(1375.0)
    graft = common_spec("mixed_graft_program_device_ms")
    assert run.reader_for(graft)({"trace": tr}, graft) is None  # no such module in this trace


def old_pair(runs, host, spans, first):
    """The pairing before the spans' `ahead` was read, verbatim: a run goes
    to the `decode` span it began in. The oracle where no span is ahead."""
    import bisect

    starts = [a for a, _ in host]
    steps, kept = [], []
    for a, b in runs:
        k = bisect.bisect_right(starts, a) - 1  # the host span the run began in
        if k >= 0 and a <= host[k][1]:
            steps.append(spans[first + k])
            kept.append((a, b))
    return steps, kept


def decode_trace(aheads, enqueued, seed=0):
    """A traced part of steps about 6 ms apart, one `decode` span a step; span k
    (`ahead` aheads[k]) enqueues the runs `enqueued[k]` lists in order, each
    run beginning inside the span (the step before it still on the device).
    The spans carry the counts of pools by layer kind and experts; every
    third op of a run is the expert kernel, every third the mixed kernel.
    Returns (ctx, {run index: its start}). The engine's spans begin before
    the trace and run past it, as a traced part's do."""
    import random

    from lib import common

    rng = random.Random(seed)
    step = 6e-3
    spans, host, ops, modules, starts = [], [], [], [], {}
    names = ["%moe_expert_ffn.1 = bf16[8,512] custom-call(", "%fusion.2 = bf16[64,2048] fusion(",
             "%attn_mixed_decode_full.3 = bf16[64,1,48,128] custom-call("]
    for k in range(-2, len(aheads) + 2):
        h = 1.0 + k * step + rng.uniform(0.0, 1e-3)  # uneven: one alignment fits
        spans.append({"name": "decode", "t0_s": h + 5.0, "dur_s": 4e-3, "active": 7,
                      "ahead": aheads[k] if 0 <= k < len(aheads) else 0,
                      "kv_tokens_full": 1000 + k, "kv_tokens_window": 400, "experts_touched": 90 + k,
                      "expert_pairs": 150 + k, "bucket": 0})
        if not 0 <= k < len(aheads):
            continue
        host.append(["decode", h, h + 4e-3])
        for j, r in enumerate(enqueued[k]):
            a = h + 1e-3 + j * 1.5e-3 + rng.uniform(0.0, 1e-4)
            starts[r] = a
            for i in range(6):
                ops.append([names[i % 3], a + i * 2e-4, a + i * 2e-4 + 1.5e-4])
            modules.append(["jit_serve_paged_decode(3)", a, a + 1.2e-3])
    ops.sort(key=lambda e: e[1])
    raw = {"devices": {0: {"ops": ops, "modules": modules}}, "host": host}
    # The engine's clock runs 5 s ahead of the profiler's: found by alignment.
    tr = {"t0": 1.0 - 1e-4, "t1": 1.0 + len(aheads) * step, "xplane": raw}
    cell = common.load_cell(CELL)
    ctx = {"kind": "serve", "config": cell["config_file"], "model": cell["config_file"]["model"],
           "spans": spans, "trace": tr, "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    return ctx, starts


READERS = ["moe_experts_roofline", "mixed_attn_roofline", "moe_device_share.decode"]


def test_pairing_without_lookahead_is_the_span_each_run_began_in(monkeypatch):
    """Pools by layer kind land every step (`ahead` 0 on every span): each
    run goes to the span it began in, as the earlier pairing had it, and
    the three readers of paired steps read what they read through it."""
    import run
    from lib import decode_steps

    n = 30
    ctx, _ = decode_trace([0] * n, [[k] for k in range(n)])
    spec = common_spec("moe_device_share.decode")
    got = decode_steps.traced(ctx, spec)
    raw = ctx["trace"]["xplane"]
    runs = [(a, b) for _, a, b in raw["devices"][0]["modules"]]
    host = [(a, b) for _, a, b in raw["host"]]
    spans = sorted(ctx["spans"], key=lambda s: s["t0_s"])
    first = decode_steps._aligned(host, spans)
    assert first == 2 and len(got["runs"]) == n
    assert (got["steps"], got["runs"]) == old_pair(runs, host, spans, first)
    new = {name: run.reader_for(common_spec(name))(ctx, common_spec(name)) for name in READERS}
    assert all(v is not None for v in new.values())

    monkeypatch.setattr(decode_steps, "pair",
                        lambda runs, host, spans: old_pair(runs, host, spans, 0))
    assert new == {name: run.reader_for(common_spec(name))(ctx, common_spec(name))
                   for name in READERS}


def test_pairing_under_lookahead_is_the_step_the_span_fetches():
    """Span 0 (`ahead` 0) enqueues its own step, run 0, and the step ahead,
    run 1, which span 1 fetches; spans 1 and 2 each enqueue the next; run 3,
    enqueued in span 2, is dropped unfetched (every row ended: span 3
    enqueues its own step, run 4, and `ahead` reads 0); span 4 fetches run 5
    and enqueues nothing (a drain). Each run goes to the span that fetches
    it, one step later than the span it began in; run 3 to none."""
    from lib import decode_steps

    aheads = [0, 1, 1, 0, 1, 0]
    ctx, starts = decode_trace(aheads, [[0, 1], [2], [3], [4, 5], [], [6]])
    got = decode_steps.traced(ctx, common_spec("moe_device_share.decode"))
    spans = sorted(ctx["spans"], key=lambda s: s["t0_s"])[2:]
    fetched_by = {0: 0, 1: 1, 2: 2, 4: 3, 5: 4, 6: 5}
    assert got["runs"] == [(starts[r], starts[r] + 1.2e-3) for r in sorted(fetched_by)]
    assert got["steps"] == [spans[fetched_by[r]] for r in sorted(fetched_by)]
    # The earlier pairing gave run k + 1 the counts of span k.
    raw = ctx["trace"]["xplane"]
    old_steps, _ = old_pair([(a, b) for _, a, b in raw["devices"][0]["modules"]],
                            [(a, b) for _, a, b in raw["host"]], spans, 0)
    assert old_steps[1] is spans[0] and got["steps"][1] is spans[1]


def common_spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        return json.load(fh)
