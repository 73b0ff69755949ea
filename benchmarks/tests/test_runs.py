"""Whole runs of run.py on the CPU at the rehearsal preset: the last line's
shape, a 2 x 2 training cell and a configuration with a reference of its own
added as files only, and `correct` coming out false under each fault and
under the lower-precision control."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

TRAIN, CHAT = "gpt2m-train.b8-t1024", "gpt2m-serve.chat-steady"


def _run(argv, cwd=ROOT, script=None, env=None):
    proc = subprocess.run(
        [sys.executable, script or os.path.join(BENCH, "run.py"), *argv],
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        capture_output=True, text=True, timeout=900)
    return proc


def _last(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("cell,trace", [(TRAIN, 0), (TRAIN, 1), (CHAT, 0), (CHAT, 1)])
def test_rehearsal_ends_in_one_well_formed_line_with_no_device_metric(cell, trace):
    line, out = _last(_run(["--workload", cell, "--seed", str(2**31 + 17), "--seconds", "3",
                            "--trace", str(trace), "--rehearse"]))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and "REHEARSAL" in out.splitlines()[0]
    assert line["device"]["platform"] == "cpu" and "busy_s" not in line["device"]
    assert all(set(v) == {"value", "limit"} for v in line["compared"].values())


def test_no_chip_and_no_rehearsal_ends_without_a_result():
    proc = _run(["--workload", TRAIN, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0 and not proc.stdout.strip().startswith("{")
    assert '"correct"' not in proc.stdout


def _tree_with_one_more_cell(tmp_path, like, config, cell, chips, change):
    """A copy of the benchmark (run.py and lib/ untouched) with one more
    configuration, `like`'s file as `change` alters it, and one more cell."""
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    old = next(w for w in bench["workloads"] if w["name"] == like)
    with open(os.path.join(BENCH, "configs", old["config"] + ".json")) as fh:
        cfg = json.load(fh)
    change(cfg)
    with open(tmp_path / "benchmarks" / "configs" / (config + ".json"), "w") as fh:
        json.dump(cfg, fh)
    bench["configs"].append({"name": config, "source": "x", "reduced": [], "why": "x",
                             "file": f"benchmarks/configs/{config}.json"})
    bench["workloads"].append({"name": cell, "config": config, "traffic": old["traffic"],
                               "chips": chips, "why": "x"})
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)


def _run_in(tmp_path, cell, seed, env=None):
    return _run(["--workload", cell, "--seed", str(seed), "--seconds", "2", "--trace", "1",
                 "--rehearse"], cwd=tmp_path, script=str(tmp_path / "benchmarks" / "run.py"),
                env={"PYTHONPATH": ROOT, **(env or {})})


def test_a_two_by_two_training_cell_is_files_and_one_entry(tmp_path):
    """A four-chip cell needs a configs/*.json whose overrides set the mesh
    and entries in BENCHMARK.json: run.py and lib/ are copied untouched."""
    def mesh(cfg):
        cfg["overrides"] += ["mesh.data=1", "mesh.fsdp=2", "mesh.model=2",
                             "parallel.param_sharding=fsdp", "parallel.opt_sharding=like_params"]

    _tree_with_one_more_cell(tmp_path, TRAIN, "gpt2m-train-fsdp2tp2", "gpt2m-fsdp2tp2.b64-t1024",
                             4, mesh)
    proc = _run_in(tmp_path, "gpt2m-fsdp2tp2.b64-t1024", 9,
                   env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    line, _ = _last(proc)
    assert line["correct"] is True and line["device"]["count"] == 4
    said = proc.stdout + proc.stderr
    assert "'fsdp': 2" in said and "'model': 2" in said


WHOLE_STACK = "x, _ = jax.lax.scan(body, x, stack)"
LAST_BLOCK_LEFT_OUT = "x, _ = jax.lax.scan(body, x, jax.tree.map(lambda v: v[:-1], stack))"


@pytest.mark.parametrize("like", [TRAIN, CHAT])
@pytest.mark.parametrize("fault", [False, True], ids=["copied", "last_block_left_out"])
def test_a_configuration_brings_its_reference_as_a_file(tmp_path, like, fault):
    """A configuration of another architecture is a configs/*.json that names
    a reference/<module>.py of its own: here a copy of the one that is there
    under another name, whole (`correct` true) and with its last block left
    out (`correct` false, so the drivers do run the module the file names)."""
    def other_reference(cfg):
        cfg["reference"] = "other_arch"

    _tree_with_one_more_cell(tmp_path, like, "other", "other.cell", 1, other_reference)
    ref_dir = tmp_path / "benchmarks" / "reference"
    source = (ref_dir / "gpt2.py").read_text()
    assert source.count(WHOLE_STACK) == 1
    (ref_dir / "other_arch.py").write_text(
        source.replace(WHOLE_STACK, LAST_BLOCK_LEFT_OUT) if fault else source)
    (ref_dir / "gpt2.py").unlink()  # nothing may fall back on it
    line, _ = _last(_run_in(tmp_path, "other.cell", 2**31 + 29))
    assert line["correct"] is (not fault), line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0


def test_a_configuration_that_names_no_reference_ends_the_run_saying_so(tmp_path):
    _tree_with_one_more_cell(tmp_path, TRAIN, "other", "other.cell", 1,
                             lambda cfg: cfg.pop("reference"))
    proc = _run_in(tmp_path, "other.cell", 3)
    assert proc.returncode != 0 and "`reference`" in proc.stderr
    assert '"correct"' not in proc.stdout


def _in_process(argv, capsys):
    import run

    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


ARGS = ["--seed", "23", "--seconds", "2", "--trace", "0", "--rehearse"]


def test_fault_state_returned_unchanged_is_not_correct(monkeypatch, capsys):
    from frl_distributed_ml_scaffold_tpu.trainer import loop

    def broken(*a, **kw):
        real = loop_make(*a, **kw)

        def step_fn(state, batch):
            new, metrics = real(state, batch)
            return state.replace(step=new.step), metrics

        return step_fn

    loop_make = loop.make_train_step
    monkeypatch.setattr(loop, "make_train_step", broken)
    line = _in_process(["--workload", TRAIN, *ARGS], capsys)
    assert line["correct"] is False
    assert line["compared"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_the_batch_left_out_is_not_correct(monkeypatch, capsys):
    from frl_distributed_ml_scaffold_tpu.trainer import loop

    real = loop.make_loss_fn

    def broken(model, data_name):
        loss_fn = real(model, data_name)

        def half(params, extras, batch, rng, train):
            n = batch["tokens"].shape[0] // 2
            return loss_fn(params, extras, {"tokens": batch["tokens"][:n]}, rng, train)

        return half

    monkeypatch.setattr(loop, "make_loss_fn", broken)
    line = _in_process(["--workload", TRAIN, *ARGS], capsys)
    assert line["correct"] is False


def test_fault_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch, capsys):
    from frl_distributed_ml_scaffold_tpu.serving import engine

    real = engine._sample
    monkeypatch.setattr(engine, "_sample", lambda logits, rng, **kw: (real(logits, rng, **kw) + 1) % logits.shape[-1])
    line = _in_process(["--workload", CHAT, *ARGS], capsys)
    assert line["correct"] is False


def test_control_in_the_precision_below_is_not_correct(capsys):
    """The reference in float8 put in the program's place, at the rehearsal
    size and on the same seed: `correct.decide` under the cells' committed
    limits says not correct, and against the full-precision reference it
    reads at least three times what the program reads, in a number of each
    kind of cell (on the chip, at the cells' own size, PERF.md gives the
    readings the limits were set from)."""
    import jax
    import numpy as np

    from lib import common, correct, train
    from lib.serve import served_gap
    from lib.weights import make_params
    from reference import gpt2

    prog = _in_process(["--workload", TRAIN, "--seed", "5", "--seconds", "1", "--trace", "0",
                        "--rehearse"], capsys)["compared"]
    cell = common.load_cell(TRAIN)
    cfg = train.build_config(cell, 5, True)
    ref = train.reference_readings(cell, cfg, 5, 3, True)
    low = train.reference_readings(cell, cfg, 5, 3, True, lowp=True)
    control = correct.train_numbers(low, ref)
    limits = cell["config_file"]["correct"]["limits"]
    ok, compared = correct.decide(control, limits)
    assert ok is False and set(compared) == set(limits), compared
    ratios = {k: control[k] / prog[k]["value"] for k in limits}
    assert ratios["grad_direction_gap"] >= 3.0, (ratios, control, prog)
    half = correct.train_numbers(
        train.reference_readings(cell, cfg, 5, 3, True, fault="half_batch"), ref)
    assert correct.decide(half, limits)[0] is False
    assert half["grad_direction_gap"] >= 10.0 * prog["grad_direction_gap"]["value"]

    served = _in_process(["--workload", CHAT, "--seed", "5", "--seconds", "2", "--trace", "0",
                          "--rehearse"], capsys)["compared"]["logit_gap"]["value"]
    serve = common.load_cell(CHAT)["config_file"]
    sizes = common.sized(serve, "model", True)
    params = make_params(gpt2.param_shapes(sizes), 5, dtype=jax.numpy.bfloat16)
    rng = np.random.default_rng(5)
    sample = [(rng.integers(0, sizes["vocab_size"], size=100).astype(np.int32), 40) for _ in range(8)]
    gap, n = served_gap(gpt2, sizes, params, sample, lowp=True)
    assert n == 480 and gap >= 3.0 * served, (gap, served)
    assert correct.decide({"logit_gap": gap}, common.limits(serve, True))[0] is False, gap
