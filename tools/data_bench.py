#!/usr/bin/env python
"""Input-pipeline throughput microbench (SURVEY §7 hard part 5).

The chip-side benchmark (bench.py) deliberately excludes the loader; this
tool answers the complementary question — can the host pipeline outrun the
chip? — by timing each real-data loader's ``batch()`` on generated corpora,
native C++ core vs numpy fallback. One JSONL line per measurement.

    python tools/data_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

# Host-only tool: never bring up an accelerator backend (nothing here needs
# a device, and a chip belongs to one process at a time).
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from frl_distributed_ml_scaffold_tpu.config.schema import DataConfig  # noqa: E402
from frl_distributed_ml_scaffold_tpu.data import native  # noqa: E402


def timed(fn, *, n=20, warm=3) -> float:
    for _ in range(warm):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def emit(loader, impl, batch, dt, samples):
    print(
        json.dumps(
            {
                "loader": loader,
                "impl": impl,
                "batch_size": batch,
                "batch_ms": round(dt * 1e3, 2),
                "samples_per_sec": round(samples / dt, 1),
            }
        ),
        flush=True,
    )


def with_fallback(fn):
    """Run fn with the native core masked off (numpy paths)."""
    real = native._load
    native._load = lambda: None
    try:
        return fn()
    finally:
        native._load = real


# Label honestly: without g++ the "native" measurement IS the numpy path.
NATIVE_IMPL = "native" if native.native_available() else "numpy (no native core)"


def bench_imagenet(root):
    from frl_distributed_ml_scaffold_tpu.data.imagenet import ImageNet

    rng = np.random.default_rng(0)
    d = root / "imagenet"
    d.mkdir()
    for shard in range(2):
        np.save(d / f"train_images_{shard:03d}.npy",
                rng.random((256, 64, 64, 3), np.float32))
        np.save(d / f"train_labels_{shard:03d}.npy",
                rng.integers(0, 100, 256))
    cfg = DataConfig(name="imagenet", data_dir=str(d), image_size=56,
                     num_classes=100, channels=3)
    src = ImageNet(cfg, split="train")
    assert not src.is_synthetic
    bs = 256
    step = iter(range(10**9))
    emit("imagenet_shards", NATIVE_IMPL, bs,
         timed(lambda: src.batch(next(step), bs)), bs)
    emit("imagenet_shards", "numpy", bs,
         with_fallback(lambda: timed(lambda: src.batch(next(step), bs))), bs)


def bench_lm(root):
    from frl_distributed_ml_scaffold_tpu.data.lm import TokenBinLM, write_token_bin

    d = root / "lm"
    d.mkdir()
    rng = np.random.default_rng(1)
    write_token_bin(str(d / "train.bin"),
                    rng.integers(0, 50000, size=4_000_000), vocab_size=50257)
    cfg = DataConfig(name="lm", data_dir=str(d), seq_len=1024, vocab_size=50257)
    src = TokenBinLM(cfg, split="train")
    assert not src.is_synthetic
    bs = 64
    step = iter(range(10**9))
    emit("lm_token_bin", NATIVE_IMPL, bs,
         timed(lambda: src.batch(next(step), bs)), bs)
    emit("lm_token_bin", "numpy", bs,
         with_fallback(lambda: timed(lambda: src.batch(next(step), bs))), bs)


def bench_video(root):
    from frl_distributed_ml_scaffold_tpu.data.video import (
        VideoClips,
        write_clip_shards,
    )

    d = root / "video"
    d.mkdir()
    rng = np.random.default_rng(2)
    write_clip_shards(
        str(d),
        rng.random((128, 8, 64, 64, 3)).astype(np.float32),
        rng.integers(0, 50, 128),
        shard_size=64,
    )
    cfg = DataConfig(name="video", data_dir=str(d), num_frames=8,
                     image_size=64, channels=3, num_classes=50)
    src = VideoClips(cfg, split="train")
    assert not src.is_synthetic
    bs = 32
    step = iter(range(10**9))
    emit("video_clips", NATIVE_IMPL, bs,
         timed(lambda: src.batch(next(step), bs), n=10), bs)
    emit("video_clips", "numpy", bs,
         with_fallback(lambda: timed(lambda: src.batch(next(step), bs), n=10)),
         bs)


if __name__ == "__main__":
    import pathlib

    with tempfile.TemporaryDirectory() as tmp:
        root = pathlib.Path(tmp)
        bench_imagenet(root)
        bench_lm(root)
        bench_video(root)
