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


def test_program_time_reader_reads_what_the_accepted_one_reads(monkeypatch):
    """`mixed_*_program_device_ms` use a reader of their own only so that the
    run's trace is parsed once: on the same trace it gives the accepted
    reader's number, and the parse is shared between the readers of a run."""
    import run
    from lib import decode_steps
    from test_step_metrics import raw_trace

    spec = common_spec("mixed_decode_program_device_ms")
    accepted = run.reader_for({"name": "decode_program_device_ms"})
    calls = []
    monkeypatch.setitem(accepted.__globals__, "own_xplane", lambda tr: raw_trace())
    monkeypatch.setattr(decode_steps, "own_xplane", lambda tr: calls.append(1) or raw_trace())
    decode_steps._XPLANE.clear()
    tr = {"t0": 0.5, "t1": 9.9}
    mine = run.reader_for(spec)
    assert mine({"trace": tr}, spec) == accepted({"trace": tr}, spec) == pytest.approx(1375.0)
    graft = common_spec("mixed_graft_program_device_ms")
    assert run.reader_for(graft)({"trace": tr}, graft) is None  # no such module in this trace
    assert calls == [1]
    decode_steps._XPLANE.clear()


def common_spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        return json.load(fh)
