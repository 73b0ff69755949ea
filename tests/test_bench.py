"""Benchmark harness: protocol record completeness (BASELINE.md §protocol),
and the rule that a measurement path which finds no chip FAILS — it never
benches the CPU under the chip's name, never republishes an older number,
and never exits 0 after a failed benchmark."""

from __future__ import annotations
import pytest as _pytest_mark  # noqa: E402

# Sub-2-minute smoke tier (COVERAGE.md "Test tiers"): this module's
# measured wall time keeps `pytest -m fast` under the tier budget.
pytestmark = _pytest_mark.mark.fast


import json
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

import pytest


def test_bench_config_emits_protocol_record():
    perf = bench.bench_config(
        "mnist_mlp",
        ["data.global_batch_size=64", "trainer.log_every=1000000"],
        steps=4,
        warmup=1,
    )
    rec = perf["_record"]
    for key in (
        "config", "model", "global_batch_size", "per_chip_batch_size",
        "mesh", "param_sharding", "precision", "n_chips", "platform",
        "chip", "steps_per_sec", "samples_per_sec_per_chip", "step_time_median_s",
        "step_time_p90_s",
    ):
        assert key in rec, f"protocol record missing {key}"
    assert rec["samples_per_sec_per_chip"] > 0
    assert rec["per_chip_batch_size"] * rec["n_chips"] == 64
    # Every record names the device it ran on; a CPU has no published
    # peak, so a CPU wall time is never written as a utilization.
    assert rec["platform"] == "cpu"
    assert "mfu" not in rec


def test_protocol_record_reports_mfu_when_peak_known(monkeypatch):
    """On chips with a known bf16 peak the record must carry model FLOPs +
    MFU (BASELINE.md protocol). CPU has no honest peak, so inject one into
    the repo's one peaks table — this exercises the path a TPU run takes."""
    import jax

    from frl_distributed_ml_scaffold_tpu.utils import flops

    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(flops.PEAK_BF16_FLOPS, kind, 1e12)
    # 2-step windows: the production default of 30 would run 90+ MNIST
    # steps here just to time them — irrelevant to what this test asserts.
    monkeypatch.setenv("FRL_BENCH_WINDOW", "2")
    perf = bench.bench_config(
        "mnist_mlp",
        ["data.global_batch_size=64", "trainer.log_every=1000000"],
        steps=4,
        warmup=1,
    )
    rec = perf["_record"]
    assert rec.get("model_flops_per_sample", 0) > 0
    assert 0 < rec["mfu"] < 1.0


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize(
    "platform,kind,want",
    [
        ("tpu", "TPU v5 lite", 197e12),
        ("cpu", "cpu", None),
        ("tpu", "TPU v99", ValueError),
        ("gpu", "NVIDIA H100", ValueError),
    ],
)
def test_peak_table_knows_its_devices_and_refuses_others(platform, kind, want):
    """One peaks table keyed by device_kind: a listed chip gets its
    published peak, a CPU gets None (callers then write no MFU), and an
    accelerator that is not listed raises — it is never handed the v5e's
    peak by default."""
    from frl_distributed_ml_scaffold_tpu.utils.flops import peak_flops_per_chip

    dev = _FakeDevice(platform, kind)
    if want is ValueError:
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            peak_flops_per_chip(dev)
    else:
        assert peak_flops_per_chip(dev) == want


def test_probe_refuses_a_cpu_unless_asked():
    """bench.py's device query is in-process and requires platform 'tpu';
    only a caller that asks for whatever device there is gets the CPU."""
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.probe_backend()
    assert bench.probe_backend(require_tpu=False) == "cpu"


def test_main_without_a_tpu_fails_and_prints_no_number(monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_run_all_writes_jsonl(tmp_path, monkeypatch):
    monkeypatch.setenv("FRL_BENCH_WINDOW", "2")
    monkeypatch.setattr(
        bench, "ALL_CONFIGS",
        [("mnist_mlp", ["data.global_batch_size=64"], 4)],
    )
    out = tmp_path / "table.jsonl"
    assert bench.run_all(str(out), require_tpu=False) == 0
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == 1 and lines[0]["config"] == "mnist_mlp"


def test_run_all_preserves_table_when_no_tpu(tmp_path):
    """A run that finds no chip must never overwrite an earlier output
    file: it raises before the file is opened."""
    table = tmp_path / "BENCH_TABLE.jsonl"
    table.write_text('{"config": "imagenet_rn50_ddp", "good": true}\n')
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.run_all(str(table))
    assert table.read_text() == '{"config": "imagenet_rn50_ddp", "good": true}\n'


def test_run_all_preserves_table_when_all_configs_fail(tmp_path, monkeypatch):
    """The device answers but every config raises: the run returns
    non-zero and the earlier output file survives (staged-tmp-file
    invariant)."""
    table = tmp_path / "BENCH_TABLE.jsonl"
    table.write_text('{"config": "imagenet_rn50_ddp", "good": true}\n')
    def boom(*a, **k):
        raise RuntimeError("backend died mid-run")
    monkeypatch.setattr(bench, "bench_config", boom)
    rc = bench.run_all(str(table), require_tpu=False)
    assert rc == 1
    assert table.read_text() == '{"config": "imagenet_rn50_ddp", "good": true}\n'
    assert not (tmp_path / "BENCH_TABLE.jsonl.tmp").exists()


def test_run_all_preserves_table_on_partial_failure(tmp_path, monkeypatch):
    """Replacement is all-or-nothing: one config succeeding while others
    fail returns non-zero and must not drop the failed configs' earlier
    rows."""
    table = tmp_path / "BENCH_TABLE.jsonl"
    table.write_text('{"config": "old", "good": true}\n')
    calls = []

    def flaky(name, overrides, *, steps, warmup):
        calls.append(name)
        if len(calls) > 1:
            raise RuntimeError("backend died mid-run")
        return {"_record": {"config": name, "samples_per_sec_per_chip": 1.0,
                            "step_time_median_s": 0.001, "mesh": {}}}

    monkeypatch.setattr(bench, "bench_config", flaky)
    rc = bench.run_all(str(table), require_tpu=False)
    assert rc == 1
    assert table.read_text() == '{"config": "old", "good": true}\n'
    assert not (tmp_path / "BENCH_TABLE.jsonl.tmp").exists()


def test_main_fails_when_the_headline_benchmark_fails(monkeypatch, capsys):
    """No ladder: when the headline benchmark raises, main() must not
    report some other model and exit 0 — the failure propagates (a
    non-zero exit) and no result line is printed."""
    monkeypatch.setattr(bench, "probe_backend", lambda **kw: "fake-chip")
    calls = []

    def boom(name, overrides, *, steps, warmup):
        calls.append(name)
        raise RuntimeError("simulated OOM")

    monkeypatch.setattr(bench, "bench_config", boom)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(RuntimeError, match="simulated OOM"):
        bench.main()
    assert calls == ["imagenet_rn50_ddp"]
    assert capsys.readouterr().out == ""
