"""The trace reduction on fixed inputs and on the small recorded trace."""

import json
import os

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


def test_reduce_on_the_recorded_trace():
    with open(os.path.join(os.path.dirname(HERE), "recorded", "train_steps.json")) as fh:
        rec = json.load(fh)
    raw = {"devices": {int(k): v for k, v in rec["trace"]["devices"].items()},
           "host": rec["trace"]["host"]}
    out = trace.reduce(raw, rec["t0"], rec["t1"])
    want = rec["hand_checked"]
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    secs, count = trace.matched(out["ops"], want["patterns"], rec["t0"], rec["t1"])
    assert count == want["matched_count"] and secs == pytest.approx(want["matched_s"], rel=1e-9)
    assert out["idle_gaps"][0][0] == want["largest_gap_under"]
