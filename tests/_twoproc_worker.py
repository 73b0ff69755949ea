"""Worker half of the 2-process jax.distributed integration test.

Launched (twice) by tests/test_multiprocess.py with FRL_TPU_* rendezvous env
vars. Exercises the real multi-process branches that single-process CI can
never reach: ``jax.distributed.initialize``, ``process_count() > 1`` host
collectives, per-process data sharding, and two global train steps.
Prints ``CHECK <json>`` lines the parent asserts on.
"""

import json
import os
import sys


def main() -> int:
    # JAX_PLATFORMS=cpu comes with the environment (_mp_harness sets it).
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    import jax
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.dist import collectives
    from frl_distributed_ml_scaffold_tpu.dist.initialize import (
        initialize_distributed,
        process_count,
        process_index,
        shutdown_distributed,
    )

    initialize_distributed()  # resolves from FRL_TPU_* env vars
    pid = process_index()
    out = {"process_count": process_count(), "pid": pid}
    out["local_devices"] = jax.local_device_count()
    out["global_devices"] = jax.device_count()

    # Host-tier collectives (SURVEY C2): the branches with process_count>1.
    got = collectives.host_broadcast(np.array([41.0 + pid], np.float32))
    out["broadcast"] = float(got[0])  # both must see process 0's 41.0
    gathered = collectives.host_all_gather(np.array([pid], np.int32))
    out["all_gather"] = np.asarray(gathered).ravel().tolist()
    collectives.barrier("twoproc-test")

    # Global-batch assembly + two real train steps over a 2-process mesh.
    from frl_distributed_ml_scaffold_tpu.config import apply_overrides, get_config
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(
        get_config("mnist_mlp"),
        [
            "data.global_batch_size=16",
            "data.prefetch=0",
            "model.hidden_sizes=32",
            "trainer.log_every=1000",
            "checkpoint.enabled=false",
            "workdir=" + os.environ["FRL_TEST_WORKDIR"],
        ],
    )
    trainer = Trainer(cfg)
    out["local_batch"] = trainer.pipeline.local_batch_size
    state = trainer.init_state()
    for step in range(2):
        batch = trainer.pipeline.global_batch(step)
        state, metrics = trainer.train_step(state, batch)
    # The loss is a global reduction — every process must report the same.
    out["loss"] = round(float(jax.device_get(metrics["loss"])), 6)

    # Hybrid ICI x DCN mesh across REAL process boundaries: with 2
    # processes x 4 local devices, dcn_data=2 puts the slice boundary
    # exactly at the process boundary — the closest a test can get to a
    # multi-slice pod without pod hardware.
    cfg_dcn = apply_overrides(
        cfg, ["mesh.dcn_data=2", "workdir=" + os.environ["FRL_TEST_WORKDIR"] + "/dcn"]
    )
    t2 = Trainer(cfg_dcn)
    out["dcn_mesh"] = dict(t2.env.mesh.shape)
    s2 = t2.init_state()
    for step in range(2):
        b2 = t2.pipeline.global_batch(step)
        s2, m2 = t2.train_step(s2, b2)
    out["dcn_loss"] = round(float(jax.device_get(m2["loss"])), 6)

    # Per-host distinct-batch contract over a REAL on-disk corpus (SURVEY
    # C16 "sharded per-host input"): each process draws its own sample
    # indices (host_offset folds into the sampling rng) and the global
    # batch assembles every host's local slice into the right global
    # shards (jax.make_array_from_process_local_data path). The corpus is
    # written by the parent test: constant-valued images whose pixel value
    # encodes the sample index, labels = index — so pairing survives
    # gather + augment (flip/crop of a constant image is the identity;
    # normalization is invertible).
    from frl_distributed_ml_scaffold_tpu.config.schema import DataConfig
    from frl_distributed_ml_scaffold_tpu.data.native import (
        _IMAGENET_MEAN,
        _IMAGENET_STD,
    )
    from frl_distributed_ml_scaffold_tpu.data.pipeline import build_pipeline

    corpus_dir = os.path.join(os.environ["FRL_TEST_WORKDIR"], "corpus")
    dcfg = DataConfig(
        name="imagenet", data_dir=corpus_dir, global_batch_size=16,
        image_size=8, channels=3, num_classes=256, prefetch=0,
    )
    pipe = build_pipeline(dcfg, trainer.env, split="train")
    inner = getattr(pipe, "_p", pipe)
    assert not inner.source.is_synthetic, "corpus not picked up"
    local = pipe.local_batch(0)
    out["rd_local_labels"] = np.asarray(local["label"]).astype(int).tolist()
    # Pixel value decodes back to the sample index: pairing preserved
    # through the native gather + augment path.
    decoded = (
        np.asarray(local["image"])[:, 0, 0, 0] * _IMAGENET_STD[0]
        + _IMAGENET_MEAN[0]
    ) * 255.0
    out["rd_pixel_decode_ok"] = bool(
        np.allclose(decoded, np.asarray(local["label"]), atol=1.0)
    )
    gb = pipe.global_batch(0)
    shards = sorted(
        gb["label"].addressable_shards, key=lambda s: s.index[0].start or 0
    )
    mine = np.concatenate([np.asarray(s.data) for s in shards]).astype(int)
    # This process's addressable slice of the GLOBAL batch must be exactly
    # the local draw, in order.
    out["rd_global_matches_local"] = bool(
        np.array_equal(mine, np.asarray(local["label"]).astype(int))
    )

    print("CHECK " + json.dumps(out), flush=True)
    shutdown_distributed()
    return 0


if __name__ == "__main__":
    sys.exit(main())
