#!/usr/bin/env python
"""Flash-kernel long-context block sweep (BASELINE.md long-context table).

Round-2 finding: the (512, 512) block optimum was tuned at T=1024, yet the
kernel's long-T efficiency was judged from that same tiling — 38 TFLOP/s
at T=32k vs 197 peak. This sweep separates "the grid is bound elsewhere"
from "the blocks are wrong at long T": block_q x block_k over T up to 64k,
fwd+bwd through the custom-VJP Pallas kernel, one JSONL row each.

    python tools/flash_sweep.py                 # full sweep (live TPU)
    python tools/flash_sweep.py --t 32768       # one sequence length
    python tools/flash_sweep.py --blocks 512    # one block candidate

Timing: device_get of a scalar that depends on the whole call (the sync
utils/timing.py uses).
FLOPs convention (matches BASELINE.md): causal fwd = 2·B·H·T²·D
(two matmuls over the lower triangle, MAC=2), bwd = 2.5x fwd (FA-2's five
backward matmuls), total 7·B·H·T²·D.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sweep(args) -> int:
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
        flash_attention,
    )

    b, h, d = args.batch, args.heads, args.head_dim
    lengths = [args.t] if args.t else [8192, 16384, 32768, 65536]
    blocks = (
        [(args.blocks, args.blocks)]
        if args.blocks
        else [(256, 256), (512, 512), (1024, 512), (512, 1024), (1024, 1024),
              (2048, 512), (512, 2048)]
    )

    for t in lengths:
        key = jax.random.key(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, t, h, d), jnp.bfloat16)
        flops = 7.0 * b * h * float(t) * t * d  # fwd + 2.5x bwd, causal

        for bq, bk in blocks:
            if bq > t or bk > t:
                continue

            @jax.jit
            @functools.partial(jax.value_and_grad, argnums=(0, 1, 2))
            def fwd_bwd(q_, k_, v_, _bq=bq, _bk=bk):
                out = flash_attention(
                    q_, k_, v_, causal=True, block_q=_bq, block_k=_bk
                )
                return jnp.sum(out.astype(jnp.float32))

            rec = {"t": t, "block_q": bq, "block_k": bk}
            try:
                loss, grads = fwd_bwd(q, k, v)  # compile + settle
                # Settle on the grads too: device_get of the scalar loss
                # alone can return while the backward of the last iter is
                # still executing (collective_bench settle-ordering class).
                jax.device_get(jax.tree.map(lambda a: a.ravel()[0], grads))
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    loss, grads = fwd_bwd(q, k, v)
                jax.device_get(jax.tree.map(lambda a: a.ravel()[0], grads))
                dt = (time.perf_counter() - t0) / args.iters
                rec.update(
                    fwd_bwd_ms=round(dt * 1e3, 2),
                    tflops=round(flops / dt / 1e12, 1),
                )
            except Exception as e:
                rec["error"] = str(e)[:200]
            print(json.dumps(rec), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=0, help="one T (default: ladder to 64k)")
    ap.add_argument("--blocks", type=int, default=0, help="one square block size")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    return sweep(args)


if __name__ == "__main__":
    raise SystemExit(main())
