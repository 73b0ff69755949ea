"""Tracing tier (ISSUE 8): spans across serve/train/elastic + the perf
ledger's exporters.

Layers, mirroring the subsystem:

- **Tracer**: trace/span/parent id semantics, implicit nesting, ring
  bound, enabled=False no-ops; the Chrome-trace-event export is
  golden-tested on fixed spans (tests/golden/trace_events.json).
- **Serving**: a CPU-sim serve run exports valid Chrome-trace JSON with
  ONE connected span tree per request spanning enqueue→retire, and
  tracing-on decode is token-identical to tracing-off with bounded
  step-time overhead (the PR 7 telemetry pin discipline).
- **Trainer**: fit() writes <run_dir>/trace_events.json with the
  step/load_batch/dispatch spans on the run's named lane; tracing=false
  keeps the telemetry.jsonl phase records and writes no trace file.
- **tools**: telemetry_report --diff percentile-delta table is
  golden-tested (tests/golden/telemetry_report_diff.json).
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

pytestmark = pytest.mark.obs

from frl_distributed_ml_scaffold_tpu.telemetry import (
    MetricsRegistry,
    Timeline,
    Tracer,
)

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ tracer


@pytest.mark.fast
def test_tracer_ids_nesting_ring_and_disabled():
    tr = Tracer(capacity=2)
    t = tr.new_trace("x")
    with tr.span("outer", trace=t) as outer:
        with tr.span("inner") as inner:  # implicit parent + trace
            assert inner.parent_id == outer.span_id
            assert inner.trace == t
    recs = tr.spans()
    assert [r["name"] for r in recs] == ["inner", "outer"]  # end order
    assert recs[0]["parent"] == outer.span_id
    assert recs[1].get("parent") is None
    # Ring bound: a third record drops the oldest, counts it.
    tr.emit("c", t0=0.0, dur_s=0.1, trace=t)
    assert len(tr) == 2 and tr.dropped == 1
    # drain() empties; a second drain is empty, not an error.
    assert len(tr.drain()) == 2
    assert tr.drain() == [] and len(tr) == 0
    # Disabled: null spans, nothing recorded, emit returns id 0.
    off = Tracer(enabled=False)
    with off.span("a") as s:
        s.end()
    assert off.begin("b").span_id == 0
    assert off.emit("c", t0=0.0, dur_s=0.0) == 0
    assert len(off) == 0


@pytest.mark.fast
def test_tracer_tees_finished_spans_into_timeline():
    """The drain-buffer contract: the Timeline keeps carrying the phase
    records (name/dur_s/attrs + span ids) for the telemetry.jsonl path
    while the tracer ring holds the tree for the Chrome export."""
    tl = Timeline()
    tr = Tracer(timeline=tl)
    t = tr.new_trace("lane")
    tr.emit("prefill", t0=0.0, dur_s=0.25, trace=t, cat="serve", slot=1)
    (rec,) = tl.drain()
    assert rec["event"] == "timeline" and rec["name"] == "prefill"
    assert rec["dur_s"] == 0.25 and rec["slot"] == 1
    assert rec["trace"] == t and rec["span"] > 0


@pytest.mark.fast
def test_trace_name_table_bounded_and_disabled_allocates_nothing():
    """A long-lived engine calls new_trace() per request forever: the
    lane-label table must stay bounded like the span ring, disabled
    tracers must not grow it at all, and the export must not emit
    metadata rows for lanes whose spans are gone (drained/evicted)."""
    off = Tracer(enabled=False)
    assert off.new_trace("request 1") == 0
    assert off._trace_names == {}
    tr = Tracer(capacity=4, origin=0.0)
    tids = [tr.new_trace(f"request {i}") for i in range(10)]
    assert len(tr._trace_names) == 4  # oldest labels evicted
    tr.emit("request", t0=0.0, dur_s=0.1, trace=tids[-1])
    events = tr.chrome_trace()["traceEvents"]
    lanes = [e for e in events if e["name"] == "thread_name"]
    assert [(e["tid"], e["args"]["name"]) for e in lanes] == [
        (tids[-1], "request 9")
    ]


@pytest.mark.fast
def test_chrome_trace_matches_golden():
    """The export acceptance golden: fixed spans → byte-stable
    Chrome-trace-event JSON (object form, "X" completes + "M" metadata,
    tid = trace lane). Regenerate deliberately if the format changes —
    this is what Perfetto/chrome://tracing parse."""
    tr = Tracer(origin=0.0)
    t = tr.new_trace("request 0")
    root = tr.emit(
        "request", t0=0.0005, dur_s=0.0125, trace=t, cat="serve",
        request=0, prompt_len=4, finish_reason="length", n_tokens=2,
    )
    tr.emit(
        "queue_wait", t0=0.0005, dur_s=0.001, trace=t, parent=root,
        cat="serve", slot=0,
    )
    tr.emit(
        "prefill", t0=0.0015, dur_s=0.004, trace=t, parent=root,
        cat="serve", slot=0, bucket=8, request=0,
    )
    tr.emit(
        "graft", t0=0.0035, dur_s=0.001, trace=t, parent=root,
        cat="serve", slot=0, bucket=16,
    )
    e = tr.new_trace("engine")
    tr.emit(
        "decode", t0=0.006, dur_s=0.003, trace=e, cat="serve",
        bucket=16, active=1,
    )
    tr.emit(
        "decode_tick", t0=0.006, dur_s=0.003, trace=t, parent=root,
        cat="serve", slot=0, token=1,
    )
    tr.emit(
        "retire", t0=0.013, dur_s=0.0, trace=t, parent=root, cat="serve",
        slot=0, request=0, reason="length", n_tokens=2,
    )
    golden = json.load(open(os.path.join(GOLDEN, "trace_events.json")))
    assert tr.chrome_trace() == golden


# ----------------------------------------------------------------- serving


@pytest.fixture(scope="module")
def gpt():
    import jax

    from _jit import jit_init
    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy

    model = GPT(
        GPTConfig(
            vocab_size=64, num_layers=2, num_heads=4, hidden_dim=64,
            seq_len=64, dropout=0.0,
        ),
        get_policy(PrecisionConfig(policy="fp32")),
    )
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, 64)
    params = jit_init(model, tokens, train=False)["params"]
    return model, params


def _workload(n=5, seed=7):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.integers(0, 64, size=int(rng.integers(2, 12))).astype(np.int32),
            int(rng.integers(2, 8)),
        )
        for _ in range(n)
    ]


def _serve(model, params, workload, **kw):
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    eng = ServingEngine(model, params, num_slots=3, temperature=0.0, **kw)
    for prompt, n_new in workload:
        eng.submit(prompt, n_new)
    done = {c.id: c for c in eng.run()}
    return eng, done


def test_serve_trace_export_is_connected_per_request(gpt, tmp_path):
    """The serve acceptance gate: the exported trace is valid
    Chrome-trace-event JSON, and every request is ONE connected span
    tree — a single parentless "request" root per trace id spanning
    enqueue→retire, with queue_wait/prefill/decode_tick/retire leaves
    all chained to it."""
    model, params = gpt
    work = _workload()
    eng, done = _serve(model, params, work)
    try:
        assert len(done) == len(work)
        path = tmp_path / "serve_trace.json"
        eng.export_trace(str(path))
        trace = json.loads(path.read_text())  # valid JSON by construction
        assert set(trace) == {"traceEvents", "displayTimeUnit"}
        events = trace["traceEvents"]
        spans = [e for e in events if e["ph"] == "X"]
        meta = [e for e in events if e["ph"] == "M"]
        assert spans and meta
        for e in spans:  # the chrome-trace-event complete-event schema
            assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        # Per-request lanes: metadata names them "request <id>".
        lane_names = {
            e["tid"]: e["args"]["name"] for e in meta
            if e["name"] == "thread_name"
        }
        roots = [
            e for e in spans
            if e["name"] == "request" and "parent" not in e["args"]
        ]
        assert len(roots) == len(work)  # one root per request, each closed
        for root in roots:
            rid = root["args"]["request"]
            lane = root["tid"]
            assert lane_names[lane] == f"request {rid}"
            tree = [e for e in spans if e["tid"] == lane]
            kids = [e for e in tree if e is not root]
            # Connectedness: every other span on the lane chains to the
            # root (depth 1 by construction — assert the edge exactly).
            assert kids and all(
                e["args"].get("parent") == root["args"]["span"] for e in kids
            )
            names = {e["name"] for e in kids}
            assert {"queue_wait", "prefill", "graft", "retire"} <= names
            n_new = len(done[rid].tokens) - done[rid].prompt_len
            assert (
                len([e for e in kids if e["name"] == "decode_tick"])
                == n_new - 1
            )
            # The root spans enqueue→retire: it contains its children.
            t0, t1 = root["ts"], root["ts"] + root["dur"]
            for e in kids:
                assert t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1e-3
        # Engine-lane spans (decode programs, grows) ride their own lane.
        eng_lanes = [t for t, n in lane_names.items() if n == "engine"]
        assert len(eng_lanes) == 1
        assert any(
            e["name"] == "decode" and e["tid"] == eng_lanes[0] for e in spans
        )
    finally:
        eng.close()


def test_tracing_off_token_identical_with_bounded_overhead(gpt):
    """The overhead pin (same discipline as the PR 7 telemetry pin):
    tracing must never touch the jitted programs — tokens identical with
    the tracer enabled vs disabled, median per-token latency within a
    generous 3x. Telemetry stays ON in both arms so only tracing moves."""
    model, params = gpt
    work = _workload(n=5, seed=13)
    runs = {}
    for label, tracer in (
        ("on", None),  # engine default: enabled tracer
        ("off", Tracer(enabled=False)),
    ):
        eng, _ = _serve(model, params, work, tracer=tracer)  # warm pass
        eng.reset_cache()
        for prompt, n_new in work:
            eng.submit(prompt, n_new)
        done = {c.id: c for c in eng.run()}
        runs[label] = (
            {rid: c.tokens for rid, c in done.items()},
            [dt for c in done.values() for dt in c.token_latencies_s[1:]],
        )
        eng.close()
    tokens_on, lat_on = runs["on"]
    tokens_off, lat_off = runs["off"]
    assert sorted(tokens_on) == sorted(tokens_off)
    for rid in tokens_on:
        np.testing.assert_array_equal(
            tokens_on[rid], tokens_off[rid],
            err_msg=f"tracing changed request {rid}'s tokens",
        )
    med_on = float(np.median(lat_on))
    med_off = float(np.median(lat_off))
    assert med_on <= 3.0 * max(med_off, 1e-9), (med_on, med_off)


#: What `step`'s children may leave uncovered: the few lines between them
#: (gauge writes, the drain of the completed list), on a loaded CI host.
STEP_SLACK_S = 2e-3


def _children(spans, parent):
    return sorted(
        (s for s in spans if s.get("parent") == parent["span"]),
        key=lambda s: s["t0_s"],
    )


def _end(span):
    return span["t0_s"] + span["dur_s"]


@pytest.mark.parametrize(
    "eng_kw",
    [{}, {"kv_block_size": 8},
     {"kv_block_size": 8, "speculate": "ngram", "speculate_k": 2}],
    ids=["bucketed", "paged", "speculative"],
)
def test_step_span_is_covered_by_its_children(gpt, eng_kw):
    """The serving step on the record: every `step` span that ran a device
    program is covered by its children (`admit`, `append_blocks`,
    `propose`, `decode` / `verify`, `emit_tokens`) to within a stated
    slack, they do not overlap, and `dispatch` and `fetch` tile the
    `decode` / `verify` between them. (`program_build`, the one child of
    `step` that lies INSIDE its siblings, is left out of the tiling.)"""
    import gc

    model, params = gpt
    work = _workload(n=6, seed=21)
    eng, _ = _serve(model, params, work, **eng_kw)
    # A phase the spans leave out shows in EVERY step; a pause of the host
    # shows in one. The collector is held off for the timed run (late in a
    # long worker process one collection outlasts the slack), and one step
    # of the run may still be over it (six workers share the CI host).
    gc.collect()
    gc.disable()
    over = []
    try:
        eng.tracing.drain()
        for prompt, n_new in work:
            eng.submit(prompt, n_new)
        eng.run()
        spans = [
            s for s in eng.tracing.spans() if s["name"] != "program_build"
        ]
        lanes = {s["trace"] for s in spans if s["name"] == "step"}
        assert len(lanes) == 1  # the engine lane
        steps = [s for s in spans if s["name"] == "step"]
        ran = 0
        for step in steps:
            kids = _children(spans, step)
            assert all(k["trace"] == step["trace"] for k in kids)
            names = [k["name"] for k in kids]
            assert names[0] == "admit" and set(kids[0]) >= {"queue", "admitted"}
            program = [k for k in kids if k["name"] in ("decode", "verify")]
            if not program:
                continue
            ran += 1
            assert len(program) == 1 and names[-1] == "emit_tokens"
            if eng_kw:
                assert "append_blocks" in names
                assert "appended" in kids[names.index("append_blocks")]
            # Covered, in order, without overlap.
            assert step["t0_s"] <= kids[0]["t0_s"]
            for a, b in zip(kids, kids[1:]):
                assert _end(a) <= b["t0_s"] + 1e-9, (a, b)
            assert _end(kids[-1]) <= _end(step) + 1e-9
            covered = sum(k["dur_s"] for k in kids)
            if step["dur_s"] - covered >= STEP_SLACK_S:
                over.append((step, names))
            # dispatch + fetch tile the program's span.
            parts = _children(spans, program[0])
            assert [p["name"] for p in parts] == ["dispatch", "fetch"]
            assert program[0]["t0_s"] <= parts[0]["t0_s"]
            assert _end(parts[0]) <= parts[1]["t0_s"] + 1e-9
            assert _end(parts[1]) <= _end(program[0]) + 1e-9
            assert program[0]["dur_s"] - parts[0]["dur_s"] - parts[1]["dur_s"] < 1e-3
        assert ran >= 3
        assert len(over) <= 1, over
    finally:
        gc.enable()
        eng.close()


def test_spans_the_benchmark_reads_keep_their_fields(gpt):
    """`benchmarks/lib/readers.py` reads `active` and `dur_s` off every
    `decode` span (decode_occupancy, mfu.decode) and `request` and `dur_s`
    off every `prefill` span (mfu.prefill): both kept their names, fields
    and extents when they became scoped spans. A `prefill` still holds its
    request's `graft`; a `decode` still ends after the tokens' fetch."""
    model, params = gpt
    work = _workload(n=4, seed=9)
    eng, done = _serve(model, params, work)
    try:
        spans = eng.tracing.spans()
        decodes = [s for s in spans if s["name"] == "decode"]
        prefills = [s for s in spans if s["name"] == "prefill"]
        assert len(prefills) == len(work)
        assert len(decodes) == eng.stats["decode_steps"]
        for s in decodes:
            assert 1 <= s["active"] <= eng.num_slots and "bucket" in s
            assert s["dur_s"] > 0.0 and s["cat"] == "serve"
        for s in prefills:
            assert s["request"] in done and "bucket" in s and "slot" in s
            grafts = [
                g for g in spans
                if g["name"] == "graft" and g["trace"] == s["trace"]
            ]
            assert len(grafts) == 1
            assert s["t0_s"] <= grafts[0]["t0_s"]
            assert _end(grafts[0]) <= _end(s) + 1e-9
        # Every token after a request's first comes out of one decode span.
        n_ticks = sum(len(c.tokens) - c.prompt_len - 1 for c in done.values())
        assert sum(s["active"] for s in decodes) == n_ticks
        # A `decode` span describes the step whose tokens it FETCHES (with
        # one step of lookahead its `dispatch` enqueues another): `active`
        # is the count of tokens its own `emit_tokens` delivers — the ring
        # is in order of finishing, so those ticks lie between the two.
        for i, s in enumerate(spans):
            if s["name"] == "decode":
                rest = spans[i + 1:]
                emit = next(
                    k for k, r in enumerate(rest) if r["name"] == "emit_tokens")
                assert s["active"] == sum(
                    r["name"] == "decode_tick" for r in rest[:emit])
                assert s["ahead"] in (0, 1)
    finally:
        eng.close()


def test_annotating_tracer_with_profiler_off_changes_nothing(gpt):
    """A tracer built ``annotate=True`` writes the engine's scoped phases
    into the profiler's host plane; with no profile running the
    annotations are inert: same tokens, same span tree, nothing raised."""
    model, params = gpt
    work = _workload(n=5, seed=17)
    runs = {}
    for annotate in (False, True):
        eng, done = _serve(
            model, params, work,
            tracer=Tracer(capacity=1 << 16, annotate=annotate),
        )
        runs[annotate] = (
            {rid: c.tokens for rid, c in done.items()},
            sorted(s["name"] for s in eng.tracing.spans()),
        )
        eng.close()
    assert sorted(runs[True][0]) == sorted(runs[False][0])
    for rid, tokens in runs[True][0].items():
        np.testing.assert_array_equal(tokens, runs[False][0][rid])
    assert runs[True][1] == runs[False][1]
    assert {"step", "admit", "decode", "dispatch", "fetch",
            "emit_tokens"} <= set(runs[True][1])


def test_engine_timeline_phases_survive_external_tracer(gpt):
    """telemetry.jsonl's phase records (PR 7 contract) must not depend on
    tracing state: with a caller-supplied DISABLED tracer the engine
    falls back to bare timeline events, and with the default tee the
    same phases arrive exactly once (no double records)."""
    model, params = gpt
    work = _workload(n=2, seed=3)
    for tracer in (None, Tracer(enabled=False)):
        eng, done = _serve(model, params, work, tracer=tracer)
        try:
            assert len(done) == len(work)
            recs = eng.timeline.drain()
            names = [r["name"] for r in recs]
            assert {"queue_wait", "prefill", "graft", "decode",
                    "retire"} <= set(names)
            # Exactly one retire phase per request in BOTH arms.
            assert names.count("retire") == len(work)
        finally:
            eng.close()


def test_reset_cache_drops_warm_pass_spans(gpt):
    """The serve_bench warm-up discipline extends to spans: after
    reset_cache the ring carries only the measured pass's trees."""
    model, params = gpt
    work = _workload(n=2, seed=5)
    eng, _ = _serve(model, params, work)
    try:
        assert len(eng.tracing) > 0
        eng.reset_cache()
        assert len(eng.tracing) == 0
        for prompt, n_new in work:
            eng.submit(prompt, n_new)
        eng.run()
        roots = [
            r for r in eng.tracing.spans() if r["name"] == "request"
        ]
        assert len(roots) == len(work)
    finally:
        eng.close()


# ----------------------------------------------------------------- trainer


def _tiny_fit(workdir, overrides=()):
    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(
        get_config("mnist_mlp"),
        [
            "trainer.total_steps=6",
            "trainer.log_every=3",
            "data.global_batch_size=32",
            "checkpoint.enabled=false",
            f"workdir={workdir}",
            *overrides,
        ],
    )
    Trainer(cfg).fit()
    return os.path.join(workdir, cfg.name)


def test_trainer_fit_exports_chrome_trace(tmp_path):
    """fit() writes <run_dir>/trace_events.json: the run's named lane
    carrying step → load_batch/dispatch spans for every step, children
    chained to their step span."""
    run_dir = _tiny_fit(str(tmp_path))
    trace = json.loads(
        open(os.path.join(run_dir, "trace_events.json")).read()
    )
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    lanes = {
        e["args"]["name"] for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "mnist_mlp" in lanes
    steps = [e for e in spans if e["name"] == "step"]
    assert len(steps) == 6
    by_id = {e["args"]["span"]: e for e in spans}
    for name in ("load_batch", "dispatch"):
        kids = [e for e in spans if e["name"] == name]
        assert len(kids) == 6
        for e in kids:  # nested under that step's root span
            parent = by_id[e["args"]["parent"]]
            assert parent["name"] == "step"
            assert parent["args"]["step"] == e["args"]["step"]
    # The spans also landed in telemetry.jsonl via the timeline tee.
    recs = [
        json.loads(l)
        for l in open(os.path.join(run_dir, "telemetry.jsonl"))
    ]
    phases = {r["name"] for r in recs if r["event"] == "timeline"}
    assert {"step", "load_batch", "dispatch"} <= phases


def test_trainer_tracing_off_keeps_timeline_phases(tmp_path):
    """trainer.tracing=false: no trace file, but telemetry.jsonl still
    carries the load_batch/dispatch phase records (the PR 7 contract
    must not regress when tracing is off)."""
    run_dir = _tiny_fit(str(tmp_path), ["trainer.tracing=false"])
    assert not os.path.exists(os.path.join(run_dir, "trace_events.json"))
    recs = [
        json.loads(l)
        for l in open(os.path.join(run_dir, "telemetry.jsonl"))
    ]
    phases = {r["name"] for r in recs if r["event"] == "timeline"}
    assert {"load_batch", "dispatch"} <= phases


# ------------------------------------------------------- telemetry_report


def _write_run_jsonl(path, bucket_counts, steps, extra_scalar=None):
    """A minimal telemetry.jsonl with one cumulative snapshot whose
    histogram carries serialized CUMULATIVE bucket counts."""
    metrics = {
        "lat": {
            "type": "histogram", "count": bucket_counts[-1],
            "sum": 1.0, "p50": 0.0, "p95": 0.0, "p99": 0.0,
            "buckets": {"0.001": bucket_counts[0],
                        "0.004": bucket_counts[1],
                        "0.016": bucket_counts[2],
                        "+Inf": bucket_counts[-1]},
        },
        "steps_total": float(steps),
    }
    if extra_scalar:
        metrics.update(extra_scalar)
    with open(path, "w") as fh:
        fh.write(json.dumps(
            {"event": "timeline", "name": "dispatch", "ts": 1.0,
             "dur_s": 0.01}
        ) + "\n")
        fh.write(json.dumps(
            {"event": "telemetry", "ts": 2.0, "metrics": metrics}
        ) + "\n")


@pytest.mark.fast
def test_telemetry_report_diff_matches_golden(tmp_path, capsys):
    """Satellite: --diff recomputes each side's percentiles from the raw
    buckets and renders the B-A delta table; the --json payload is
    golden-tested byte-stable."""
    import sys as _sys

    tools = os.path.join(REPO, "tools")
    if tools not in _sys.path:
        _sys.path.insert(0, tools)
    import telemetry_report

    a = tmp_path / "a.jsonl"
    b = tmp_path / "b.jsonl"
    _write_run_jsonl(str(a), (4, 8, 10, 10), steps=10)
    _write_run_jsonl(
        str(b), (1, 3, 10, 12), steps=12, extra_scalar={"queue_depth": 2.0}
    )
    out = tmp_path / "diff.json"
    rc = telemetry_report.main(
        ["--diff", str(a), str(b), "--json", str(out)]
    )
    assert rc == 0
    text = capsys.readouterr().out
    assert "telemetry diff" in text and "d_p99_s" in text
    golden = json.load(
        open(os.path.join(GOLDEN, "telemetry_report_diff.json"))
    )
    assert json.loads(out.read_text()) == golden
    # Deltas tie out against the single-run reports they join.
    rep = golden["histograms"][0]
    assert rep["delta"]["count"] == rep["b"]["count"] - rep["a"]["count"]
    assert rep["delta"]["p50_s"] == pytest.approx(
        rep["b"]["p50_s"] - rep["a"]["p50_s"], abs=1e-6
    )
