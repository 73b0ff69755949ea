#!/usr/bin/env python
"""MoE dispatch-cost comparison: einsum (one-hot GSEC) vs sort (ragged).

VERDICT r4 weak #5: the dispatch/combine einsums spend O(N*E*C*D) MACs
against mostly-zero one-hots, and no number existed for what that costs
versus a sort/ragged formulation at the audited shapes (N=4096, E=64).
This tool asks XLA's own cost model: jit the MoE block's train-mode
value+grad under each ``moe.dispatch`` and read ``cost_analysis()`` —
the same FLOP source bench.py's MFU uses — plus an analytic expert-FFN
FLOP count for scale.

    JAX_PLATFORMS=cpu python tools/moe_dispatch_cost.py

One JSONL row per (shape, dispatch) + a verdict row. Results recorded in
docs/perf_playbook.md "Dispatch FLOPs"; the einsum default stands or
falls on these numbers plus the on-chip step-time A/B (ROADMAP B1).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def measure(b: int, t: int, d: int, e: int, k: int, dispatch: str) -> dict:
    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import GPTConfig, MoEConfig
    from frl_distributed_ml_scaffold_tpu.models.moe import MoEMlp

    cfg = GPTConfig(
        hidden_dim=d, num_heads=4, seq_len=t,
        moe=MoEConfig(num_experts=e, top_k=k, dispatch=dispatch,
                      num_groups=1),
    )
    m = MoEMlp(cfg, jnp.bfloat16)
    x = jnp.zeros((b, t, d), jnp.bfloat16)
    variables = jax.eval_shape(lambda: m.init(jax.random.key(0), x, train=True))

    def loss_fn(v, xx):
        y, aux = m.apply(v, xx, train=True)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    grad = jax.grad(loss_fn)
    lowered = jax.jit(grad).lower(variables, x)
    cost = lowered.compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    n = b * t
    capacity = max(1, int(cfg.moe.capacity_factor * n * k / e))
    hidden = d * cfg.mlp_ratio
    # Expert FFN MACs (fwd): E*C*D*H twice (wi, wo); x3 for fwd+bwd; x2
    # FLOPs/MAC. Exchange einsum MACs (fwd): N*E*C*D for each of
    # dispatch/combine; x3 for fwd+bwd.
    ffn_flops = 3 * 2 * 2 * e * capacity * d * hidden
    exchange_einsum_flops = 3 * 2 * 2 * n * e * capacity * d
    return {
        "shape": f"N={n} E={e} C={capacity} D={d} k={k}",
        "dispatch": dispatch,
        "xla_total_flops": float(cost.get("flops", -1)),
        "analytic_expert_ffn_flops": float(ffn_flops),
        "analytic_exchange_einsum_flops": float(exchange_einsum_flops),
    }


def main() -> int:
    # Pin the CPU backend UNCONDITIONALLY (overwrite, before jax is
    # imported): a FLOP census never takes the chip. XLA's cost model is
    # platform-independent for FLOP counting purposes.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    # The audited shapes (perf_playbook "MoE dispatch memory at real
    # shapes"): gpt2_moe protocol point N=4096, E=64 — plus a small
    # sanity shape.
    shapes = [
        (4, 256, 256, 16, 2),   # sanity
        (4, 1024, 1024, 64, 2), # audited: N=4096, E=64, D=1024
    ]
    rows = []
    for b, t, d, e, k in shapes:
        for dispatch in ("einsum", "sort"):
            r = measure(b, t, d, e, k, dispatch)
            rows.append(r)
            print(json.dumps(r), flush=True)
    for i in range(0, len(rows), 2):
        ein, srt = rows[i], rows[i + 1]
        if ein["xla_total_flops"] > 0 and srt["xla_total_flops"] > 0:
            print(json.dumps({
                "mode": "verdict",
                "shape": ein["shape"],
                "einsum_over_sort_flops": round(
                    ein["xla_total_flops"] / srt["xla_total_flops"], 3
                ),
                "exchange_share_of_einsum_total": round(
                    ein["analytic_exchange_einsum_flops"]
                    / ein["xla_total_flops"], 3
                ),
            }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
