"""The per-layer metrics that read the engine's step spans, the programs'
names and the kernels' names: each reader on a hand-made `ctx`, and on what a
program without those spans and names leaves behind (nothing, not a number)."""

import json
import os

import pytest

import run
from lib import common

HERE = os.path.dirname(os.path.abspath(__file__))
SPECS = {name: common.load_json("metrics", name + ".json") for name in (
    "engine_host_ms_per_step", "admit_ms_per_admission", "decode_program_device_ms",
    "flash_fwd_ms.train", "flash_bwd_ms.train")}


def read(name, ctx):
    return run.reader_for(SPECS[name])(ctx, SPECS[name])


def span(name, t0, dur, span_id=0, parent=None, **attrs):
    rec = {"name": name, "t0_s": t0, "dur_s": dur, "span": span_id, "trace": 1, **attrs}
    if parent is not None:
        rec["parent"] = parent
    return rec


def step_spans():
    """Two decoding steps and one that only admitted. Step 1 (10 ms): admit
    1 ms, decode 7 ms -> 3 ms of host time. Step 2 (30 ms) admits two
    requests in 18 ms, of which the prefills (with their grafts inside) wait
    on the device for 8 + 6 ms, then decodes 9 ms -> 30 - 23 = 7 ms."""
    return [
        span("step", 0.000, 0.010, 1), span("admit", 0.000, 0.001, 2, 1, queue=0, admitted=0),
        span("decode", 0.002, 0.007, 3, 1, active=4),
        span("step", 0.020, 0.030, 4), span("admit", 0.020, 0.018, 5, 4, queue=2, admitted=2),
        span("prefill", 0.021, 0.008, 6, 90, request=7), span("graft", 0.026, 0.002, 7, 90),
        span("prefill", 0.031, 0.006, 8, 91, request=8), span("graft", 0.035, 0.001, 9, 91),
        span("decode", 0.040, 0.009, 10, 4, active=6),
        span("emit_tokens", 0.049, 0.001, 11, 4),
        span("step", 0.060, 0.004, 12), span("admit", 0.060, 0.003, 13, 12, queue=1, admitted=1),
        span("request", 0.0, 5.0, 90), span("decode_tick", 0.040, 0.009, 14, 90),
    ]


def test_engine_host_time_is_the_step_less_its_device_waits():
    assert read("engine_host_ms_per_step", {"spans": step_spans()}) == pytest.approx((3.0 + 7.0) / 2)
    # A `program_build` inside a `decode` is counted once, not twice.
    spans = step_spans() + [span("program_build", 0.003, 0.004, 20, 1, program="decode")]
    assert read("engine_host_ms_per_step", {"spans": spans}) == pytest.approx(5.0)
    # ... and one beside it (a cache grown before the decode) is a wait of its own.
    spans = step_spans() + [span("program_build", 0.0012, 0.0005, 20, 1, program="grow")]
    assert read("engine_host_ms_per_step", {"spans": spans}) == pytest.approx((2.5 + 7.0) / 2)


def test_admission_cost_is_per_request_admitted():
    # (18 + 3) ms over 2 + 1 admissions; the admit that admitted nothing is left out.
    assert read("admit_ms_per_admission", {"spans": step_spans()}) == pytest.approx(7.0)


def test_span_metrics_read_nothing_from_a_program_without_step_spans():
    """The parent of PR 27 records `decode` and `prefill` and no `step`."""
    old = [s for s in step_spans() if s["name"] in ("decode", "prefill", "graft", "request")]
    for s in old:
        s.pop("parent", None)
    for name in ("engine_host_ms_per_step", "admit_ms_per_admission"):
        assert read(name, {"spans": old}) is None
        assert read(name, {"spans": []}) is None
        assert read(name, {}) is None


def raw_trace():
    ops = [["%fusion.1 = f32[8] fusion(", 1.0, 1.5], ["%attn_paged_decode.3 = bf16[48,1,16,64] custom-call(", 1.5, 2.5],
           ["%copy.2 = bf16[1,1281] copy(", 2.4, 3.0],   # overlaps the kernel: a union, not a sum
           ["%fusion.9 = f32[8] fusion(", 5.0, 5.5],     # inside the prefill's run
           ["%fusion.1 = f32[8] fusion(", 7.0, 7.25], ["%attn_paged_decode.3 = bf16[48,1,16,64] custom-call(", 7.5, 8.0],
           ["%fusion.1 = f32[8] fusion(", 9.5, 9.9]]     # a run cut by the trace's end
    modules = [["jit_serve_paged_decode(123)", 1.0, 3.5], ["jit_serve_prefill(77)", 4.9, 5.6],
               ["jit_serve_paged_decode(123)", 7.0, 8.1], ["jit_serve_paged_decode_quant(5)", 8.2, 8.3],
               ["jit_serve_paged_decode(123)", 9.4, 10.5]]
    return {"devices": {0: {"ops": ops, "modules": modules}}, "host": []}


def test_decode_program_time_is_the_busy_union_inside_its_runs():
    spec = SPECS["decode_program_device_ms"]
    reader = run.reader_for(spec)
    # The trace the run's reduction parsed. Runs 1 and 2 lie inside the
    # traced part: busy 2.0 s and 0.75 s.
    tr = {"t0": 0.5, "t1": 9.9, "xplane": raw_trace()}
    assert reader({"trace": tr}, spec) == pytest.approx(1e3 * (2.0 + 0.75) / 2)
    # No run of that module (the parent calls every program jit_fn): nothing.
    raw = raw_trace()
    for m in raw["devices"][0]["modules"]:
        m[0] = "jit_fn(1)"
    assert reader({"trace": dict(tr, xplane=raw)}, spec) is None
    # No parsed trace, or no traced run at all: nothing.
    assert reader({"trace": {"t0": 0.5, "t1": 9.9}}, spec) is None
    assert reader({"trace": None}, spec) is None and reader({}, spec) is None


def old_program_ms(raw, tr, module):
    """The reader before the per-run slices, verbatim after the parse: the oracle."""
    import re

    from lib import trace as tracelib

    dev = next(iter(raw["devices"].values()))
    named = re.compile(module)
    runs = [(a, b) for name, a, b in dev["modules"]
            if named.search(name) and a >= tr["t0"] and b <= tr["t1"]]
    if not runs:
        return None
    busy = sum(tracelib.total(tracelib.busy_union(dev["ops"], a, b)) for a, b in runs)
    return 1e3 * busy / len(runs)


@pytest.mark.parametrize("seed", [5, 6, 2**31 + 3])
def test_program_time_reads_what_the_old_reader_read(seed):
    from test_reduction import random_trace

    ops, runs, _ = random_trace(seed)
    modules = [["jit_serve_paged_decode(1)" if i % 3 else "jit_serve_paged_graft(2)", a, b]
               for i, (a, b) in enumerate(runs)]
    raw = {"devices": {0: {"ops": ops, "modules": modules}}, "host": []}
    for name in ("decode_program_device_ms", "graft_program_device_ms"):
        spec = common.load_json("metrics", name + ".json")
        for t0, t1 in ((0.0, 1.0), (0.2, 0.7)):
            tr = {"t0": t0, "t1": t1, "xplane": raw}
            got = run.reader_for(spec)({"trace": tr}, spec)
            assert got is not None and got == old_program_ms(raw, tr, spec["module"])
    raw = raw_trace()
    tr = {"t0": 0.5, "t1": 9.9, "xplane": raw}
    assert read("decode_program_device_ms", {"trace": tr}) == old_program_ms(
        raw, tr, SPECS["decode_program_device_ms"]["module"])


def test_kernel_time_splits_forward_from_backward():
    ops = [["%attn_flash_fwd.13 = (bf16[8,16,1024,64]) custom-call(", 0.0, 1.0],
           ["%attn_flash_fwd.14 = (bf16[8,16,1024,64]) custom-call(", 1.0, 2.0],
           ["%attn_flash_dq.10 = bf16[8,16,1024,64] custom-call(", 2.0, 4.0],
           ["%attn_flash_dkv.10 = (bf16[8,16,1024,64]) custom-call(", 4.0, 7.0],
           ["%fusion.3 = f32[8] fusion(", 7.0, 8.0],
           ["%attn_flash_fwd.13 = (bf16[8,16,1024,64]) custom-call(", 9.5, 10.5]]  # past t1
    ctx = {"trace": {"ops": ops, "t0": 0.0, "t1": 10.0, "steps": 2}}
    assert read("flash_fwd_ms.train", ctx) == pytest.approx(1e3 * 2.0 / 2)
    assert read("flash_bwd_ms.train", ctx) == pytest.approx(1e3 * 5.0 / 2)
    # The three kernels still match the accepted roofline's pattern: the two
    # new metrics sum to the time it divides by.
    from lib import trace as tracelib
    old = common.load_json("metrics", "flash_roofline.train.json")["patterns"]
    assert tracelib.matched(ops, old, 0.0, 10.0) == (7.0, 4)
    assert read("flash_fwd_ms.train", {"trace": None}) is None


def test_the_recorded_trace_has_no_named_kernels_and_reads_as_nothing():
    """Recorded before the kernels had names: all three are `%attn`."""
    with open(os.path.join(os.path.dirname(HERE), "recorded", "train_steps.json")) as fh:
        rec = json.load(fh)
    ctx = {"trace": {"ops": rec["trace"]["devices"]["0"]["ops"], "t0": rec["t0"], "t1": rec["t1"],
                     "steps": 1}}
    assert rec["hand_checked"]["matched_count"] > 0
    assert read("flash_fwd_ms.train", ctx) is None
    assert read("flash_bwd_ms.train", ctx) is None
