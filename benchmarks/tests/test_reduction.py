"""The trace reduction on fixed inputs and on the small recorded trace; the
sweep and the sliced busy time against the quadratic code they replaced,
equal to the last bit; and the whole reduction of a trace of a serving cell's
size inside its time."""

import json
import os
import random
import time

import pytest

from lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_interval_arithmetic_by_hand():
    ops = [["while", 0.0, 10.0], ["a.1", 1.0, 3.0], ["b", 3.0, 4.0], ["a.2", 5.0, 9.0], ["c", 12.0, 13.0]]
    busy = trace.busy_union(ops, 0.0, 14.0)
    assert busy == [(0.0, 10.0), (12.0, 13.0)] and trace.total(busy) == 11.0
    assert trace.idle_gaps(busy, 0.0, 14.0) == [(10.0, 12.0), (13.0, 14.0)]
    assert trace.matched(ops, [r"^a\."], 0.0, 14.0) == (6.0, 2)
    assert trace.matched(ops, [r"^a\."], 2.0, 14.0) == (4.0, 1)  # a.1 starts before
    assert trace.top_ops(ops, 0.0, 14.0)[:2] == [["a", 6.0], ["while", 3.0]]
    spans = [["step", 0.0, 14.0], ["load_batch", 9.5, 12.5], ["dispatch", 12.9, 13.2]]
    assert trace.attribute_gaps(trace.idle_gaps(busy, 0.0, 14.0), spans) == [
        ["load_batch", 2.0], ["step", 1.0]]
    assert trace.attribute_gaps([(20.0, 21.0)], spans) == [["no_host_span", 1.0]]
    # The loop op that starts before the run and ends inside it counts.
    assert trace.busy_in_runs(ops, [(2.0, 4.0), (9.5, 12.5), (14.0, 15.0)]) == [2.0, 1.0, 0]


def test_reduce_on_the_recorded_trace():
    rec = recorded()
    raw = {"devices": {int(k): v for k, v in rec["trace"]["devices"].items()},
           "host": rec["trace"]["host"]}
    out = trace.reduce(raw, rec["t0"], rec["t1"])
    want = rec["hand_checked"]
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    secs, count = trace.matched(out["ops"], want["patterns"], rec["t0"], rec["t1"])
    assert count == want["matched_count"] and secs == pytest.approx(want["matched_s"], rel=1e-9)
    assert out["idle_gaps"][0][0] == want["largest_gap_under"]


def recorded():
    with open(os.path.join(os.path.dirname(HERE), "recorded", "train_steps.json")) as fh:
        return json.load(fh)


# The code the sweep and the slices replaced, verbatim: the oracle.

def old_attribute_gaps(gaps, host_spans, k: int = 10):
    """Each idle gap goes to the shortest host span that covers at least half
    of it (so a child wins over its parent), else to the span that overlaps
    it most, else to "no_host_span". Returns [[name, seconds], ...]."""
    acc: dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[1])
    for a, b in gaps:
        covering, most = None, None
        for name, sa, sb in spans:
            if sa >= b:
                break
            ov = min(b, sb) - max(a, sa)
            if ov <= 0:
                continue
            if 2 * ov >= b - a and (covering is None or sb - sa < covering[1]):
                covering = (name, sb - sa)
            if most is None or ov > most[1]:
                most = (name, ov)
        best = (covering or most or ("no_host_span", 0.0))[0]
        acc[best] = acc.get(best, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def old_busy_per_run(ops, runs):
    return sum(trace.total(trace.busy_union(ops, a, b)) for a, b in runs)


def random_trace(seed: int, n_ops: int = 3000, n_spans: int = 400):
    """Ops, runs and host spans over [0, 1) s with what the sweep and the
    slices must get right: times on a coarse grid (equal starts, ends and
    lengths: ties), loop ops around their body, ops that start before a run
    and end inside it, spans nested in spans, spans that outlast the window,
    and stretches that no span reaches."""
    rng = random.Random(seed)
    grid = rng.choice([1e-4, 2.0**-12, 1e-3 / 3])
    t = lambda lo, hi: round(rng.uniform(lo, hi) / grid) * grid
    ops = []
    for _ in range(n_ops):
        a = t(0.0, 1.0)
        ops.append([rng.choice(["%fusion.1", "%attn_x.2", "%moe_y.3", "%copy.4"]), a,
                    a + t(0.0, 0.0004)])
    for _ in range(n_ops // 300):  # loops: one op over many
        a = t(0.0, 0.95)
        ops.append(["%while.9", a, a + t(0.005, 0.02)])
    ops.sort(key=lambda e: e[1])
    runs = []
    for _ in range(60):
        a = t(0.0, 1.0)
        runs.append((a, a + rng.choice([t(0.0, 0.02), 0.004, 0.0])))
    runs.sort()
    spans = []
    for _ in range(n_spans):
        a = t(-0.05, 0.9)
        dur = rng.choice([t(0.0, 0.01), 0.002, 0.002, t(0.0, 0.2)])
        spans.append([rng.choice(["step", "decode", "fetch", "admit"]), a, a + dur])
        if rng.random() < 0.3:  # a child inside it
            ca = a + dur * rng.random()
            spans.append([rng.choice(["fetch", "np.asarray"]), ca, ca + (a + dur - ca) * rng.random()])
    spans += [["request", -0.5, 2.0], ["request", 0.2, 0.7], ["window", -1.0, 3.0]][: seed % 4]
    rng.shuffle(spans)
    return ops, runs, spans


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 2**31 + 11])
def test_sweep_and_slices_equal_the_old_code_on_random_traces(seed):
    ops, runs, spans = random_trace(seed)
    for t0, t1 in ((0.0, 1.0), (0.3, 0.6), (-0.1, 1.2)):
        gaps = trace.idle_gaps(trace.busy_union(ops, t0, t1), t0, t1)
        assert len(gaps) > 50
        for k in (10, 1000):
            assert trace.attribute_gaps(gaps, spans, k) == old_attribute_gaps(gaps, spans, k)
        # Only the spans inside the window, as `reduce` hands them: some gaps meet none.
        inside = [s for s in spans if s[1] > 0.45 and s[2] < 0.5]
        got = trace.attribute_gaps(gaps, inside, 1000)
        assert got == old_attribute_gaps(gaps, inside, 1000)
        assert "no_host_span" in [n for n, _ in got]
    assert trace.busy_in_runs(ops, runs) == [trace.total(trace.busy_union(ops, a, b))
                                             for a, b in runs]
    assert sum(trace.busy_in_runs(ops, runs)) == old_busy_per_run(ops, runs)


def test_sweep_and_slices_equal_the_old_code_on_the_recorded_trace():
    rec = recorded()
    dev = rec["trace"]["devices"]["0"]
    t0, t1 = rec["t0"], rec["t1"]
    gaps = trace.idle_gaps(trace.busy_union(dev["ops"], t0, t1), t0, t1)
    spans = [s for s in rec["trace"]["host"] if s[2] > t0 and s[1] < t1]
    assert trace.attribute_gaps(gaps, spans) == old_attribute_gaps(gaps, spans)
    assert trace.reduce({"devices": {0: dev}, "host": rec["trace"]["host"]}, t0, t1)[
        "idle_gaps"] == old_attribute_gaps(gaps, spans)
    rng = random.Random(7)
    runs = [(a, b) for _, a, b in dev["modules"]] + sorted(
        (a, a + rng.uniform(0.0, 0.01)) for a in (rng.uniform(t0, t1) for _ in range(50)))
    assert sum(trace.busy_in_runs(dev["ops"], runs)) == old_busy_per_run(dev["ops"], runs)


def serving_trace(runs: int = 1400, ops_a_run: int = 358, spans_a_run: int = 10):
    """A traced part the size of a serving cell's at a 2.1 ms step: runs of
    the decode program, each of `ops_a_run` short ops with an idle gap
    after every op, `spans_a_run` host spans a step (with a `decode` span
    that is ahead, as the engine's lookahead makes it) and a request span
    of 0.5 s every 50 steps. Returns the parsed trace, the engine's spans
    and the traced part's ends."""
    step, op, gap = 2.1e-3, 4.5e-6, 1.3e-6
    ops, modules, host, spans = [], [], [], []
    names = ["%fusion.1 = bf16[48,1,1024] fusion(", "%moe_expert_ffn.2 = bf16[8,512] custom-call(",
             "%attn_paged_decode.3 = bf16[48,1,16,64] custom-call("]
    for r in range(runs):
        a = 1.0 + r * (step + 0.05e-3)
        for i in range(ops_a_run):
            s = a + i * (op + gap)
            ops.append([names[i % 3], s, s + op])
        modules.append(["jit_serve_paged_decode(1)", a, ops[-1][2]])
        h = a - 0.3e-3  # the decode span that enqueued this run
        host.append(["decode", h, h + step])
        spans.append({"name": "decode", "t0_s": h, "dur_s": step, "active": 3, "ahead": 1,
                      "experts_touched": 40, "expert_pairs": 64, "kv_tokens_full": 900,
                      "kv_tokens_window": 300})
        for j in range(spans_a_run - 1):
            host.append([("step", "fetch", "dispatch", "np.asarray", "admit")[j % 5],
                         h + j * 0.2e-3, h + j * 0.2e-3 + 0.15e-3])
        if r % 50 == 0:
            host.append(["request", a, a + 0.5])
    host.sort(key=lambda e: e[1])
    raw = {"devices": {0: {"ops": ops, "modules": modules}}, "host": host}
    return raw, spans, 1.0, ops[-1][2]


def test_a_serving_cells_trace_reduces_in_under_30_s():
    import run
    from lib import common

    raw, spans, t0, t1 = serving_trace()
    assert len(raw["devices"][0]["ops"]) >= 500_000 and len(raw["host"]) >= 14_000
    ctx = {"spans": spans}
    specs = [common.load_json("metrics", n + ".json") for n in (
        "decode_program_device_ms", "graft_program_device_ms", "moe_device_share.decode")]
    began = time.perf_counter()
    out = trace.reduce(raw, t0, t1)
    out["xplane"] = raw
    ctx["trace"] = out
    got = {s["name"]: run.reader_for(s)(ctx, s) for s in specs}
    took = time.perf_counter() - began
    gaps = trace.idle_gaps(trace.busy_union(raw["devices"][0]["ops"], t0, t1), t0, t1)
    assert len(gaps) >= 500_000 and len(raw["devices"][0]["modules"]) >= 1400
    assert took < 30.0, took
    assert got["decode_program_device_ms"] == pytest.approx(358 * 4.5e-3, rel=1e-6)
    assert got["graft_program_device_ms"] is None
    assert got["moe_device_share.decode"] == pytest.approx(100.0 / 3, rel=0.01)
