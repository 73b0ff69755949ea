"""chip_smoke.py without the chip: it must refuse to run, and its phases —
plain functions that take their sizes as arguments — must work end to end
on the CPU at a tiny size (rehearsals 1 and 2 of the on-chip-measurement
guide), so a chip call is never spent on a wrong path or argument.
Also the launcher's two device rules the smoke leans on: ``--device=tpu``
without a TPU is an error, and the compile cache can be placed from outside.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_MODEL = dict(
    vocab_size=512, num_layers=2, num_heads=4, hidden_dim=128, seq_len=128
)
TINY_TRAIN = (
    "model.num_layers=2", "model.hidden_dim=128", "model.num_heads=4",
    "model.vocab_size=512", "model.seq_len=128", "data.seq_len=128",
    "data.vocab_size=512", "data.global_batch_size=8", "trainer.grad_accum=1",
    "model.attention=flash", "model.lm_loss_chunk=64", "trainer.remat=none",
    "model.block_remat=full",
)


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    """chip_smoke with its outputs under tmp_path and a compile clock."""
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    return chip_smoke, chip_smoke.CompileClock()


def _phase_line(capsys, name: str) -> dict:
    lines = [
        json.loads(l) for l in capsys.readouterr().out.splitlines()
        if l.startswith('{"phase"')
    ]
    (line,) = [l for l in lines if l["phase"] == name]
    return line


def test_chip_smoke_refuses_a_cpu():
    """On a CPU-only process the script exits non-zero at its device check
    and never prints a result line: the CPU is not passed off as the chip."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok"' not in r.stdout
    assert "found no TPU" in r.stderr


def test_train_and_serve_phases_rehearse_on_cpu(smoke, capsys):
    """Rehearsal 1: launch.main trains, saves and resumes; the engine's
    tokens equal generate()'s with the (interpreted) kernel and with the
    dense reference. Same functions, tiny sizes, no option of the script."""
    cs, clock = smoke
    with cs.phase("train", clock) as f:
        cs.phase_train(f, device="cpu", overrides=TINY_TRAIN, steps=3)
    line = _phase_line(capsys, "train")
    assert line["ok"] and line["resumed_at"] == 3 and len(line["losses"]) == 4
    assert line["flash_kernel_in_step"] is False  # no Mosaic off the chip
    assert line["step_flops"] > 0
    # A CPU wall time is never written as a utilization.
    run_dir = os.path.join(cs.OUT_DIR, "train", "gpt2_medium_zero1")
    assert not any("mfu" in r for r in cs._read_metrics(run_dir))
    assert not os.path.exists(
        os.path.join(run_dir, "ckpt")
    ), "the train phase must not leave its checkpoint behind"

    da = importlib.import_module(
        "frl_distributed_ml_scaffold_tpu.ops.decode_attention"
    )
    da.FORCE_INTERPRET = True  # run the Pallas kernels, interpreted
    try:
        with cs.phase("serve", clock) as f:
            cs.phase_serve(
                f, model_kw=TINY_MODEL, prompt_lens=(5, 17, 33, 9, 3),
                max_new=14, num_slots=2, block_size=16,
            )
    finally:
        da.FORCE_INTERPRET = None
    line = _phase_line(capsys, "serve")
    assert line["tokens_equal_generate_flash"]
    assert line["tokens_equal_generate_dense"]
    assert line["block_appends"] > 0  # 5 + 14 tokens cross a 16-block


def test_kernel_phase_rehearses_on_cpu(smoke, capsys):
    """Every kernel check of the smoke, interpreted at a tiny size: the
    pools, tables and references it builds are right before a chip sees
    them."""
    cs, clock = smoke
    with cs.phase("kernels", clock) as f:
        cs.phase_kernels(
            f, batch=2, heads=2, head_dim=32, seq=128, block_sizes=(16,),
            verify_len=3, adamw_shape=(512, 256), interpret=True,
            kinds=dict(kv_heads=2, head_dim=128, block=16,
                       layers={"full": (6, 0), "sliding": (8, 40)}),
        )
    errs = _phase_line(capsys, "kernels")["max_abs_err"]
    assert {"flash_fwd", "flash_bwd_dq", "decode", "decode_int8",
            "paged_decode_bs16", "paged_decode_int8_bs16",
            "paged_verify_bs16", "mixed_decode_full", "mixed_decode_sliding",
            "mixed_vs_contiguous_sliding", "fused_adamw"} <= set(errs)


def test_four_chip_phase_rehearses_on_virtual_devices(smoke, capsys):
    """Rehearsal 2: the fsdp=2 x model=2 arms against the one-device steps
    on four of the virtual CPU devices. The vocabulary is odd, as GPT-2's
    published 50257 is, so the embedding's Megatron rule cannot apply and
    must fall to replication instead of refusing the mesh."""
    cs, clock = smoke
    odd_vocab = tuple(
        o.replace("vocab_size=512", "vocab_size=513") for o in TINY_TRAIN
    ) + ("parallel.fsdp_min_size=1",)
    with cs.phase("sharded", clock) as f:
        cs.phase_sharded(
            f, devices=jax.devices()[:4], overrides=odd_vocab, steps=3
        )
    line = _phase_line(capsys, "sharded")
    for arm in ("gspmd", "overlap"):
        assert line[arm]["mesh"]["fsdp"] == 2 and line[arm]["mesh"]["model"] == 2
        held = line[arm]["param_bytes_per_device"].values()
        assert len(held) == 4 and max(held) <= sum(held) / 3


def test_launch_device_tpu_without_a_tpu_exits_nonzero(tmp_path):
    """--device=tpu sets nothing, so with no chip JAX would quietly train
    on the CPU; the launcher must stop and say so instead."""
    from frl_distributed_ml_scaffold_tpu.launcher import launch

    with pytest.raises(SystemExit) as e:
        launch.main([
            "--config=mnist_mlp", "--device=tpu", "trainer.total_steps=1",
            f"workdir={tmp_path}",
        ])
    assert e.value.code not in (0, None)
    assert "--device=tpu" in str(e.value.code)
    assert not os.path.exists(tmp_path / "mnist_mlp"), "it must not train"


@pytest.mark.parametrize("placed_outside", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, placed_outside):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory (jax
    reads the variable itself); unset, the cache is the fixed
    <checkout>/.jax_cache. Run in a subprocess: the suite's own cache
    stays as conftest placed it."""
    code = (
        "import jax\n"
        "from frl_distributed_ml_scaffold_tpu.launcher.launch import "
        "enable_compile_cache\n"
        "enable_compile_cache()\n"
        "print('CACHE_DIR=' + str(jax.config.jax_compilation_cache_dir))\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(REPO, ".jax_cache")
    if placed_outside:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outside")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"CACHE_DIR={want}" in r.stdout.splitlines()
