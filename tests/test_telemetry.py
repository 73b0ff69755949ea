"""Telemetry tier (ISSUE 7): the metrics/tracing layer across train,
serve, and elastic.

Four layers, mirroring the subsystem:

- **Registry**: counter/gauge/histogram semantics, log2-bucket quantile
  estimation, enabled=False no-ops, reset; the Prometheus text format is
  golden-tested byte-for-byte (tests/golden/telemetry_snapshot.prom).
- **Watchdog**: a silent loop fires exactly once per silence (counter +
  faulthandler dump + metric snapshot in the dump file); a beating loop
  never fires.
- **Serving**: the engine exports TTFT/TPOT histograms, occupancy/HBM/
  bytes-per-slot gauges and grow/graft counters through BOTH exporters;
  completions carry ttft/tpot SLO columns; telemetry-on decode is
  token-identical to telemetry-off with step time within noise (the
  overhead pin).
- **Trainer/elastic**: fit() writes telemetry.jsonl + metrics.prom with
  the data-wait/compute split and MFU; tools/telemetry_report.py renders
  them; the membership heartbeat-age gauge tracks stale peers.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

pytestmark = pytest.mark.obs

from frl_distributed_ml_scaffold_tpu.telemetry import (
    LOG2_LATENCY_BUCKETS_S,
    MetricsRegistry,
    StallWatchdog,
    Timeline,
    jsonl_record,
    prometheus_text,
    write_prometheus_file,
)

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


# ---------------------------------------------------------------- registry


@pytest.mark.fast
def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="< 0"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    h = reg.histogram("lat")
    assert h.buckets == LOG2_LATENCY_BUCKETS_S
    h.observe(0.001)
    h.observe(100.0)  # past the last bound -> +Inf bucket
    assert h.count == 2 and h.sum == pytest.approx(100.001)
    # Same name returns the same object; a type conflict refuses.
    assert reg.counter("x_total") is c
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total")


@pytest.mark.fast
def test_histogram_quantiles_within_bucket_resolution():
    """The log2 estimator must bracket the true quantile within its
    containing bucket (the 2x-granularity contract) and clamp the +Inf
    bucket to the last finite bound."""
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    vals = [0.001] * 50 + [0.1] * 50
    for v in vals:
        h.observe(v)
    for q, true in ((0.25, 0.001), (0.75, 0.1)):
        est = h.quantile(q)
        # true value's bucket: (lo, hi] with hi = smallest bound >= true
        hi = min(b for b in h.buckets if b >= true)
        lo = max([b for b in h.buckets if b < hi], default=0.0)
        assert lo <= est <= hi, (q, est, lo, hi)
    h2 = reg.histogram("inf_heavy")
    h2.observe(1e9)
    assert h2.quantile(0.99) == h2.buckets[-1]
    assert reg.histogram("empty").quantile(0.5) == 0.0


@pytest.mark.fast
def test_disabled_registry_noops_and_reset():
    off = MetricsRegistry(enabled=False)
    off.counter("c").inc()
    off.gauge("g").set(5)
    off.histogram("h").observe(1.0)
    assert off.counter("c").value == 0.0
    assert off.histogram("h").count == 0
    reg = MetricsRegistry()
    reg.counter("c").inc(4)
    reg.histogram("h").observe(0.5)
    reg.reset()
    assert reg.counter("c").value == 0.0
    assert reg.histogram("h").count == 0
    assert reg.histogram("h").quantile(0.5) == 0.0


@pytest.mark.fast
def test_prometheus_text_matches_golden():
    """The acceptance golden: the text exposition format byte-for-byte
    (cumulative buckets, _sum/_count, HELP/TYPE headers, sorted names).
    Regenerate deliberately if the format changes — this is the contract
    scrape configs parse."""
    reg = MetricsRegistry()
    c = reg.counter("serve_completed_total", help="requests finished")
    c.inc()
    c.inc(4)
    g = reg.gauge("serve_slot_occupancy", help="active slots / num_slots")
    g.set(0.75)
    h = reg.histogram(
        "serve_tpot_seconds",
        help="per-output-token latency over live slots (decode steps)",
        buckets=(0.001, 0.004, 0.016, 0.064, 0.256),
    )
    for v in (0.0005, 0.002, 0.002, 0.01, 0.05, 1.5):
        h.observe(v)
    golden = open(os.path.join(GOLDEN, "telemetry_snapshot.prom")).read()
    assert prometheus_text(reg) == golden


@pytest.mark.fast
def test_prometheus_text_never_tears_under_concurrent_observes():
    """Regression for the graft-lint concurrency audit of
    telemetry/metrics.py: ``prometheus_text`` renders ENTIRELY under the
    registry lock. The previous shape copied the metrics dict under the
    lock but read ``_counts``/``count``/``sum`` outside it, so a scrape
    racing ``observe()`` could publish a histogram whose bucket rows
    disagree with ``_count``/``_sum``. Every observation here adds
    exactly 1.0, so any torn render shows ``sum != count`` or an +Inf
    cumulative != count."""
    import threading

    reg = MetricsRegistry()
    h = reg.histogram("tear_check_seconds", buckets=(0.5, 2.0))
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            h.observe(1.0)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 1.0
        renders = 0
        while time.monotonic() < deadline:
            rows = dict(
                line.rsplit(" ", 1)
                for line in prometheus_text(reg).strip().splitlines()
                if not line.startswith("#")
            )
            count = int(rows["tear_check_seconds_count"])
            assert float(rows["tear_check_seconds_sum"]) == float(count)
            assert int(rows['tear_check_seconds_bucket{le="+Inf"}']) == count
            renders += 1
    finally:
        stop.set()
        t.join(5)
    assert renders > 50 and h.count > 0  # the race was actually exercised


@pytest.mark.fast
def test_snapshot_jsonl_roundtrip_and_prom_file(tmp_path):
    """snapshot() survives a JSONL round trip with the raw bucket counts
    intact (the telemetry_report merge contract), and the .prom sidecar
    is written atomically."""
    from frl_distributed_ml_scaffold_tpu.utils.logging import JsonlWriter

    reg = MetricsRegistry()
    reg.counter("n_total").inc(3)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    path = tmp_path / "t.jsonl"
    w = JsonlWriter(str(path))
    w.write(jsonl_record(reg, step=7))
    w.close()
    rec = json.loads(path.read_text())
    assert rec["event"] == "telemetry" and rec["step"] == 7
    m = rec["metrics"]
    assert m["n_total"] == 3.0
    assert m["lat"]["count"] == 3
    assert m["lat"]["buckets"] == {"0.1": 1, "1": 2, "+Inf": 3}
    prom = tmp_path / "m.prom"
    write_prometheus_file(reg, str(prom))
    assert 'lat_bucket{le="+Inf"} 3' in prom.read_text()
    assert not (tmp_path / "m.prom.tmp").exists()


@pytest.mark.fast
def test_timeline_ring_buffer_and_drain():
    tl = Timeline(capacity=4)
    for i in range(6):
        tl.event("phase", dur_s=0.1, step=i)
    assert len(tl) == 4 and tl.dropped == 2
    assert [r["step"] for r in tl.tail(2)] == [4, 5]
    recs = tl.drain()
    assert [r["step"] for r in recs] == [2, 3, 4, 5]
    assert all(r["event"] == "timeline" for r in recs)
    assert len(tl) == 0
    off = Timeline(enabled=False)
    off.event("x")
    assert len(off) == 0


@pytest.mark.fast
def test_timeline_double_drain_and_post_wraparound_refill():
    """Satellite: drain() is idempotent on empty (the crash-path finally
    block re-drains after the log-boundary drain — must yield [] not
    duplicates), and the ring keeps accepting/counting after wrapping."""
    tl = Timeline(capacity=3)
    for i in range(5):
        tl.event("p", step=i)
    first = tl.drain()
    assert [r["step"] for r in first] == [2, 3, 4]
    assert tl.drain() == [] and tl.drain() == []  # double (and triple)
    assert tl.dropped == 2  # dropped survives drains: it is a counter
    # Refill after wraparound+drain behaves like a fresh ring.
    for i in range(4):
        tl.event("q", step=10 + i)
    assert tl.dropped == 3
    assert [r["step"] for r in tl.drain()] == [11, 12, 13]
    assert tl.tail() == []


@pytest.mark.fast
def test_jsonl_writer_truncates_partial_line_on_reopen(tmp_path):
    """Satellite: a run killed mid-write leaves a torn final line; the
    next JsonlWriter open repairs the file (truncate to the last
    newline) so every line stays parseable across the crash."""
    from frl_distributed_ml_scaffold_tpu.utils.logging import JsonlWriter

    path = tmp_path / "t.jsonl"
    w = JsonlWriter(str(path))
    w.write({"step": 1})
    w.write({"step": 2})
    w.close()
    with open(path, "a") as fh:  # the torn write (no trailing newline)
        fh.write('{"step": 3, "partial')
    w2 = JsonlWriter(str(path))
    w2.write({"step": 4})
    w2.close()
    recs = [json.loads(l) for l in open(path)]  # every line parses
    assert [r["step"] for r in recs] == [1, 2, 4]
    # A torn FIRST line (no complete record at all) truncates to empty.
    p2 = tmp_path / "torn.jsonl"
    p2.write_text('{"never finished')
    w3 = JsonlWriter(str(p2))
    w3.write({"ok": 1})
    w3.close()
    assert [json.loads(l)["ok"] for l in open(p2)] == [1]
    # A cleanly-closed file reopens untouched.
    w4 = JsonlWriter(str(path))
    w4.close()
    assert [r["step"] for r in (json.loads(l) for l in open(path))] == [1, 2, 4]


# ---------------------------------------------------------------- watchdog


@pytest.mark.fast
def test_watchdog_fires_once_per_stall_with_dump(tmp_path):
    """A silent loop: exactly one stalls_total increment per silence
    window, and the dump carries the faulthandler traceback + the live
    metric snapshot + the timeline tail."""
    reg = MetricsRegistry()
    reg.counter("serve_decode_steps_total").inc(5)
    tl = Timeline()
    tl.event("decode", dur_s=0.01, step=41)
    dump = tmp_path / "stall.txt"
    wd = StallWatchdog(
        0.1, name="t", registry=reg, timeline=tl,
        dump_path=str(dump), poll_s=0.02,
    )
    try:
        wd.beat()
        time.sleep(0.5)  # several polls past the deadline: still ONE fire
        assert wd.fired == 1
        assert reg.counter("stalls_total").value == 1
        text = dump.read_text()
        assert "watchdog[t] stall" in text
        assert "Current thread" in text  # faulthandler traceback
        assert "serve_decode_steps_total" in text  # metric snapshot
        assert '"name": "decode"' in text  # timeline tail
        wd.beat()  # re-arm; a second silence fires again
        time.sleep(0.3)
        assert wd.fired == 2
    finally:
        wd.stop()


@pytest.mark.fast
def test_watchdog_healthy_loop_never_fires():
    reg = MetricsRegistry()
    wd = StallWatchdog(0.5, registry=reg, poll_s=0.02)
    try:
        for _ in range(25):
            wd.beat()
            time.sleep(0.01)
    finally:
        wd.stop()
    assert wd.fired == 0
    assert reg.counter("stalls_total").value == 0


@pytest.mark.fast
def test_watchdog_disabled_spawns_no_thread():
    wd = StallWatchdog(0.0)
    assert not wd.enabled
    wd.beat()
    wd.stop()  # no-op, no thread to join


@pytest.mark.fast
def test_watchdog_first_beat_grace_absorbs_compile():
    """Satellite: before the FIRST beat the deadline is scaled by
    first_beat_scale (the step-0 compile window) — a slow first beat
    does not fire; a LATER silence of the same length does."""
    reg = MetricsRegistry()
    wd = StallWatchdog(
        0.2, registry=reg, poll_s=0.02, first_beat_scale=10.0
    )
    try:
        # 3x the deadline, but 1.4 s under the 10x first-beat grace —
        # wide enough that a loaded CI host cannot false-fire it.
        time.sleep(0.6)
        assert wd.fired == 0
        wd.beat()  # "compile finished, step 0 dispatched"
        time.sleep(0.6)  # the SAME silence after a beat: normal deadline
        assert wd.fired == 1
    finally:
        wd.stop()


@pytest.mark.fast
def test_watchdog_unbeaten_still_fires_at_scaled_deadline():
    """The grace is a multiplier, not a disable: a child that never
    beats at all (hung before step 0) fires once the scaled deadline
    passes."""
    reg = MetricsRegistry()
    wd = StallWatchdog(
        0.1, registry=reg, poll_s=0.02, first_beat_scale=3.0
    )
    try:
        time.sleep(0.6)  # past 3 * 0.1
        assert wd.fired == 1
        assert reg.counter("stalls_total").value == 1
    finally:
        wd.stop()


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


def _serve(model, params, workload, **kw):
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    eng = ServingEngine(model, params, num_slots=3, temperature=0.0, **kw)
    for prompt, n_new in workload:
        eng.submit(prompt, n_new)
    done = {c.id: c for c in eng.run()}
    return eng, done


def _workload(n=6, seed=3):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.integers(0, 64, size=int(rng.integers(2, 12))).astype(np.int32),
            int(rng.integers(2, 8)),
        )
        for _ in range(n)
    ]


def test_engine_exports_serving_catalog_via_both_exporters(gpt):
    """The acceptance gate: TTFT/TPOT histograms, slot-occupancy /
    bytes-per-slot / HBM gauges and grow/graft counters present in BOTH
    the JSONL snapshot and the Prometheus text, with counts that agree
    with the completions."""
    model, params = gpt
    work = _workload()
    eng, done = _serve(model, params, work)
    try:
        assert len(done) == len(work)
        snap = eng.telemetry.snapshot()
        # Histogram counts tie out: one TTFT per admitted request, one
        # TPOT observation per generated-token-after-the-first.
        n_decode_tokens = sum(
            len(c.tokens) - c.prompt_len - 1 for c in done.values()
        )
        assert snap["serve_ttft_seconds"]["count"] == len(work)
        assert snap["serve_tpot_seconds"]["count"] == n_decode_tokens
        assert snap["serve_completed_total"] == len(work)
        assert snap["serve_prefill_total"] == len(work)
        assert snap["serve_cache_graft_total"] == len(work)
        assert snap["serve_bytes_per_slot"] == eng.bytes_per_slot() > 0
        assert 0.0 <= snap["serve_slot_occupancy"] <= 1.0
        for k in ("serve_hbm_in_use_gib", "serve_hbm_peak_gib",
                  "serve_queue_depth", "stalls_total"):
            assert k in snap  # registered up front, 0 on CPU sim
        txt = prometheus_text(eng.telemetry)
        for name in (
            "serve_ttft_seconds_bucket", "serve_tpot_seconds_sum",
            "serve_slot_occupancy", "serve_bytes_per_slot",
            "serve_hbm_in_use_gib", "serve_bucket_grow_total",
            "serve_cache_graft_total", "stalls_total",
        ):
            assert name in txt, name
        # The per-step timeline recorded the serving phases.
        names = {r["name"] for r in eng.timeline.tail(10**6)}
        assert {"prefill", "decode", "retire"} <= names
    finally:
        eng.close()


def test_completion_slo_columns_consistent_with_latencies(gpt):
    """ttft_s is the prefill latency; tpot p50/p99 bracket the true
    decode-step percentiles within their log2 bucket (the estimator's
    documented resolution)."""
    from frl_distributed_ml_scaffold_tpu.telemetry import (
        LOG2_LATENCY_BUCKETS_S as B,
    )

    model, params = gpt
    eng, done = _serve(model, params, _workload())
    try:
        for c in done.values():
            lat = c.token_latencies_s
            assert c.ttft_s == lat[0]
            decode = lat[1:]
            if not decode:
                assert c.tpot_p50_s == 0.0 and c.tpot_p99_s == 0.0
                continue
            assert c.tpot_p99_s >= c.tpot_p50_s > 0.0
            for est, q in ((c.tpot_p50_s, 50), (c.tpot_p99_s, 99)):
                # inverted_cdf matches the estimator's semantics (smallest
                # observation whose cumulative count reaches q*n); default
                # linear interpolation invents midpoints between distant
                # observations that no bucket estimator can reproduce.
                true = float(
                    np.percentile(decode, q, method="inverted_cdf")
                )
                hi = min(b for b in B if b >= min(true, B[-1]))
                lo = max([b for b in B if b < hi], default=0.0)
                # estimate lives in [true's bucket lo, bucket hi] modulo
                # interpolation across equal-count neighbors; assert the
                # 2x-granularity contract loosely: within one bucket.
                assert lo / 2 <= est <= hi * 2, (est, true, lo, hi)
    finally:
        eng.close()


def test_engine_telemetry_overhead_pin(gpt):
    """The overhead pin: telemetry-on vs telemetry-off serve the same
    workload TOKEN-IDENTICALLY (telemetry must never touch the jitted
    programs), and the measured-pass per-token latency stays within
    noise (generous 3x bound on medians — what it catches is a metric
    accidentally forcing a device sync or landing inside a trace)."""
    model, params = gpt
    work = _workload(n=6, seed=11)
    runs = {}
    for label, reg in (
        ("on", None),  # engine default: enabled registry
        ("off", MetricsRegistry(enabled=False)),
    ):
        eng, _ = _serve(model, params, work, telemetry=reg)  # warm pass
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
            err_msg=f"telemetry changed request {rid}'s tokens",
        )
    med_on = float(np.median(lat_on))
    med_off = float(np.median(lat_off))
    assert med_on <= 3.0 * max(med_off, 1e-9), (med_on, med_off)


def test_engine_watchdog_fires_on_decode_silence(gpt, tmp_path):
    """Engine wiring: a stalled engine (no step() calls) trips the
    watchdog — stalls_total increments and the dump lands; an engine
    that keeps stepping does not fire."""
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    model, params = gpt
    dump = tmp_path / "serve_stall.txt"
    eng = ServingEngine(
        model, params, num_slots=2, temperature=0.0,
        stall_timeout_s=0.15, stall_dump_path=str(dump),
    )
    try:
        eng.submit(np.arange(4, dtype=np.int32), 30)
        eng.step()  # beats
        time.sleep(0.6)  # silence: the "decode loop wedged" scenario
        assert eng.telemetry.counter("stalls_total").value >= 1
        assert "watchdog[serve] stall" in dump.read_text()
        # Recovery: serving still completes after the stall report.
        done = eng.run()
        assert len(done) == 1
    finally:
        eng.close()


# ---------------------------------------------------------- trainer tier


@pytest.mark.fast
def test_step_timer_summary_reports_tail_percentiles():
    """Satellite 2: p50/p95/p99 in StepTimer.summary(), ordered and
    consistent with the recorded times."""
    from frl_distributed_ml_scaffold_tpu.utils.timing import StepTimer

    t = StepTimer(warmup=0)
    t._times = [0.01] * 96 + [0.5] * 4  # 4% straggler steps
    s = t.summary(samples_per_step=8)
    assert s["step_time_p50_s"] == s["step_time_median_s"] == 0.01
    assert s["step_time_p95_s"] <= s["step_time_p99_s"]
    assert s["step_time_p99_s"] > 0.4  # the tail the mean hides
    assert s["step_time_mean_s"] < 0.05
    assert s["samples_per_sec_per_chip"] > 0


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """One telemetry-enabled trainer run shared by the trainer-tier tests
    (>= 2 post-warmup log windows so MFU and the step histogram fill).
    A CPU has no published peak, so a real CPU run carries NO mfu
    (tests/test_chip_smoke.py pins that); a peak is injected into the
    repo's one peaks table here so the path a TPU run takes is exercised."""
    import jax

    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer
    from frl_distributed_ml_scaffold_tpu.utils import flops

    workdir = tmp_path_factory.mktemp("telemetry_run")
    cfg = apply_overrides(
        get_config("mnist_mlp"),
        [
            "trainer.total_steps=12",
            "trainer.log_every=3",
            "trainer.stall_timeout_s=120",
            "data.global_batch_size=32",
            "checkpoint.enabled=false",
            f"workdir={workdir}",
        ],
    )
    kind = jax.devices()[0].device_kind
    flops.PEAK_BF16_FLOPS[kind] = 1e12
    try:
        _, last = Trainer(cfg).fit()
    finally:
        del flops.PEAK_BF16_FLOPS[kind]
    return os.path.join(workdir, cfg.name), last


def test_trainer_fit_exports_telemetry(telemetry_run):
    """The trainer tier end-to-end: metrics.jsonl carries the p50/p95/p99
    + data-wait/compute split + MFU extras; telemetry.jsonl carries
    timeline phases and cumulative snapshots; metrics.prom scrapes."""
    run_dir, last = telemetry_run
    for k in ("step_time_p50_s", "step_time_p95_s", "step_time_p99_s",
              "data_wait_s", "compute_s", "mfu"):
        assert k in last, (k, last)
    assert last["mfu"] > 0
    assert last["compute_s"] >= 0 and last["data_wait_s"] >= 0
    recs = [
        json.loads(l)
        for l in open(os.path.join(run_dir, "telemetry.jsonl"))
    ]
    kinds = {r["event"] for r in recs}
    assert kinds == {"timeline", "telemetry"}
    phases = {r["name"] for r in recs if r["event"] == "timeline"}
    assert {"load_batch", "dispatch"} <= phases
    final = [r for r in recs if r["event"] == "telemetry"][-1]["metrics"]
    assert final["train_steps_total"] == 12
    assert final["train_step_seconds"]["count"] >= 2  # post-warmup windows
    assert final["train_data_wait_seconds"]["count"] == 12
    assert final["stalls_total"] == 0  # healthy run: watchdog never fired
    assert final["train_mfu"] > 0
    prom = open(os.path.join(run_dir, "metrics.prom")).read()
    for name in ("train_step_seconds_bucket", "train_data_wait_seconds_sum",
                 "train_mfu", "train_hbm_peak_gib", "stalls_total"):
        assert name in prom, name


def test_telemetry_report_renders_run(telemetry_run, tmp_path, capsys):
    """tools/telemetry_report.py over the run's JSONL: percentile table
    + --json machine output whose quantiles come from the raw buckets."""
    import sys as _sys

    tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    )
    if tools not in _sys.path:
        _sys.path.insert(0, tools)
    import telemetry_report

    run_dir, _ = telemetry_run
    out = tmp_path / "rep.json"
    rc = telemetry_report.main(
        [os.path.join(run_dir, "telemetry.jsonl"), "--json", str(out)]
    )
    assert rc == 0
    text = capsys.readouterr().out
    assert "train_step_seconds" in text and "p99_s" in text
    rep = json.loads(out.read_text())
    names = {h["name"] for h in rep["histograms"]}
    assert {"train_step_seconds", "train_data_wait_seconds"} <= names
    for h in rep["histograms"]:
        assert h["p50_s"] <= h["p90_s"] <= h["p95_s"] <= h["p99_s"]
        if h["count"]:
            assert h["p99_s"] > 0
    assert rep["timeline"]["dispatch"]["count"] == 12
    assert rep["scalars"]["train_steps_total"] == 12


@pytest.mark.fast
def test_telemetry_report_bucket_quantile_math():
    """The report's from-serialized-buckets estimator agrees with the
    live Histogram estimator it reconstructs."""
    import sys as _sys

    tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    )
    if tools not in _sys.path:
        _sys.path.insert(0, tools)
    from telemetry_report import bucket_quantile

    reg = MetricsRegistry()
    h = reg.histogram("x")
    rng = np.random.default_rng(0)
    for v in rng.lognormal(mean=-6, sigma=1.5, size=500):
        h.observe(float(v))
    snap = reg.snapshot()["x"]
    for q in (0.5, 0.9, 0.99):
        assert bucket_quantile(
            snap["buckets"], snap["count"], q
        ) == pytest.approx(h.quantile(q))


# --------------------------------------------------------------- elastic


@pytest.mark.fast
def test_membership_heartbeat_age_gauge(tmp_path):
    """The elastic tier's scrape signal: after a liveness read the gauge
    carries the oldest LIVE member heartbeat age. An evicted (stale) peer
    must NOT feed the gauge: a hard-crashed host's file is never unlinked
    (only clean retire() removes it), so folding its ever-growing age in
    would saturate the gauge forever and mask live-member lag — evictions
    show up in the shrink/reform counters, not here."""
    from frl_distributed_ml_scaffold_tpu.launcher.elastic import _Membership

    reg = MetricsRegistry()
    m = _Membership(str(tmp_path), uid=0, endpoint="h:1", registry=reg)
    m.beat()
    surv = m.survivors(peer_timeout_s=60.0)
    assert [r["uid"] for r in surv] == [0]
    age_fresh = reg.gauge("elastic_heartbeat_age_s").value
    assert 0.0 <= age_fresh < 5.0
    # A peer whose heartbeat is 120 s old: evicted from the survivor set,
    # and the gauge keeps tracking the live members only.
    peer = os.path.join(str(tmp_path), "members", "host_1.json")
    with open(peer, "w") as fh:
        json.dump({"uid": 1, "endpoint": "h:2", "ts": 0.0}, fh)
    old = time.time() - 120.0
    os.utime(peer, (old, old))
    surv = m.survivors(peer_timeout_s=60.0)
    assert [r["uid"] for r in surv] == [0]
    assert reg.gauge("elastic_heartbeat_age_s").value < 5.0
    # A LIVE-but-lagging peer (30 s < timeout) is what the gauge warns
    # about: stays in the survivor set, age shows up.
    lag = time.time() - 30.0
    os.utime(peer, (lag, lag))
    surv = m.survivors(peer_timeout_s=60.0)
    assert [r["uid"] for r in surv] == [0, 1]
    assert 20.0 < reg.gauge("elastic_heartbeat_age_s").value <= 60.0
    m.retire()


# ---------------------------------------------------------- trace_analyze


@pytest.mark.fast
def test_trace_analyze_lane_report_matches_golden():
    """Satellite 3's golden: the --json lane structure on fixed synthetic
    spans is byte-stable across PRs, so overlap classifications diff."""
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    from tools.trace_analyze import lane_report

    ms = int(1e9)
    events = [
        ("fusion.loop_multiply.9", 0 * ms, 6 * ms),
        ("collective-permute-start.1", 1 * ms, 3 * ms),
        ("collective-permute-done.2", 8 * ms, 10 * ms),
        ("all-gather-fusion.3", 5 * ms, 7 * ms),
        ("custom-call.decode_kernel.1", 10 * ms, 12 * ms),
        ("scatter.9", 12 * ms, 13 * ms),
    ]
    golden = json.load(
        open(os.path.join(GOLDEN, "trace_analyze_lane.json"))
    )
    assert lane_report(events, top_n=4) == golden


@pytest.mark.fast
def test_trace_analyze_lane_report_no_decode_lane():
    """A training lane (no decode kernel) reports decode: null — the
    field is present (schema-stable) but unclassified."""
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    from tools.trace_analyze import lane_report

    rep = lane_report([("fusion.matmul.1", 0, int(1e9))])
    assert rep["decode"] is None
    assert rep["overlap"] == {}
    assert rep["top_ops"][0]["op"] == "fusion.matmul.1"
