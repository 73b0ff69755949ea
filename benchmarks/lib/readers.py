"""Readers of the per-layer metrics: each takes what one run gathered (`ctx`)
and its metric's file (`spec`) and returns the number, or None where it finds
nothing to read; the harness then leaves the metric out of the line."""

from __future__ import annotations

from . import roofline, trace as tracelib


def _peak_flops(ctx):
    return ctx["peaks"]["flops_per_s"] if ctx.get("peaks") else None


def data_wait_share(ctx, spec):
    records = ctx.get("log_records")
    if not records:
        return None
    waited = sum(steps * rec["data_wait_s"] for steps, rec in records)
    return 100.0 * waited / ctx["window"]["seconds"]


def step_device_ms(ctx, spec):
    tr = ctx.get("trace")
    return 1e3 * tr["busy_s"] / tr["steps"] if tr and tr.get("steps") else None


def idle_share(ctx, spec):
    tr = ctx.get("trace")
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None


def mfu_train(ctx, spec):
    peak = _peak_flops(ctx)
    if peak is None:
        return None
    return 100.0 * ctx["config"]["flops"]["per_sample"] * ctx["window"]["samples_per_s_chip"] / peak


def flash_roofline_train(ctx, spec):
    tr, m = ctx.get("trace"), ctx["model"]
    if not tr or not ctx.get("peaks"):
        return None
    secs, count = tracelib.matched(tr["ops"], spec["patterns"], tr["t0"], tr["t1"])
    if not count:
        return None
    flops, nbytes = roofline.causal_attention_train(
        m["batch"] // ctx["chips"], m["num_heads"], m["seq_len"],
        m["hidden_dim"] // m["num_heads"], m["num_layers"])
    least, _ = roofline.least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * tr["steps"] / secs


def pool_share(ctx, spec):
    pool = ctx.get("pool")
    return 100.0 * pool[spec["field"]] if pool else None


def decode_occupancy(ctx, spec):
    active = [s["active"] for s in ctx.get("spans", ()) if s["name"] == "decode"]
    return 100.0 * sum(active) / len(active) / ctx["num_slots"] if active else None


def mfu_decode(ctx, spec):
    peak = _peak_flops(ctx)
    spans = [s for s in ctx.get("spans", ()) if s["name"] == "decode"]
    if peak is None or not spans:
        return None
    work = sum(s["active"] for s in spans) * ctx["config"]["flops"]["per_token"]
    return 100.0 * work / sum(s["dur_s"] for s in spans) / peak


def mfu_prefill(ctx, spec):
    peak = _peak_flops(ctx)
    spans = [s for s in ctx.get("spans", ()) if s["name"] == "prefill"]
    if peak is None or not spans:
        return None
    lens = ctx["prompt_len_by_request"]
    tokens = sum(lens[s["request"]] for s in spans if s.get("request") in lens)
    return 100.0 * tokens * ctx["config"]["flops"]["per_token"] / sum(s["dur_s"] for s in spans) / peak


def decode_attn_roofline(ctx, spec):
    tr, m = ctx.get("trace"), ctx["model"]
    if not tr or not ctx.get("peaks") or not tr.get("context_tokens"):
        return None
    secs, count = tracelib.matched(tr["ops"], spec["patterns"], tr["t0"], tr["t1"])
    if not count:
        return None
    flops, nbytes = roofline.paged_decode_attention(
        tr["context_tokens"], m["num_heads"], m["hidden_dim"] // m["num_heads"],
        m["num_layers"])
    least, _ = roofline.least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least / secs
