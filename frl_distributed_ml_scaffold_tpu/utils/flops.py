"""Backend-free FLOP counting by walking a jaxpr (BASELINE.md protocol),
and the ONE table of per-chip peak rates MFU is divided by.

``jax.stages.Lowered.cost_analysis`` is the trainer's first source of step
FLOPs (``Trainer.step_cost_analysis``); this counter is the one that needs
no backend at all — the MPMD pipeline (no single lowered step), ``--describe``
and the analytic ledger use it: trace the step to a jaxpr (abstract shapes
only) and sum matmul/conv FLOPs directly — the count covers everything the
jaxpr actually contains, forward AND backward AND optimizer, with no
3x-forward heuristics.

Convention: one multiply-add = 2 FLOPs (the MFU convention used by chip
peak numbers). Only ``dot_general`` and ``conv_general_dilated`` are
counted — elementwise/reduction FLOPs are noise next to them on any model
this framework benchmarks (they are also the ops the MXU peak refers to).

Control flow: ``scan``/``pjit``/``cond``/``remat`` bodies are descended
into (scan multiplied by trip count, cond by its worst branch);
``while_loop`` bodies are counted ONCE — trip counts are not static. The
ring-attention hop loop is the only hot while in this codebase, and ring
configs aren't single-chip bench candidates.
"""

from __future__ import annotations

from functools import reduce
from operator import mul

import jax
import numpy as np


def _prod(xs) -> int:
    return int(reduce(mul, xs, 1))


def _dot_flops(eqn) -> int:
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
    batch = _prod(a.shape[i] for i in lb)
    contract = _prod(a.shape[i] for i in lc)
    m = _prod(a.shape[i] for i in range(a.ndim) if i not in set(lc) | set(lb))
    n = _prod(b.shape[i] for i in range(b.ndim) if i not in set(rc) | set(rb))
    return 2 * batch * m * n * contract


def _conv_flops(eqn) -> int:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    # Output spatial positions x output channels x batch ...
    out_elems = _prod(out.shape)
    # ... each costs kernel_spatial x in_channels/groups MACs.
    k_spatial = _prod(rhs.shape[i] for i in dn.rhs_spec[2:])
    cin_per_group = rhs.shape[dn.rhs_spec[1]]
    return 2 * out_elems * k_spatial * cin_per_group


def jaxpr_flops(jaxpr) -> int:
    """Total matmul+conv FLOPs of a (closed) jaxpr, recursively."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            total += _dot_flops(eqn)
        elif prim == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif prim == "scan":
            total += eqn.params["length"] * jaxpr_flops(eqn.params["jaxpr"])
        elif prim == "while":
            # Trip count unknown statically; count one iteration of body
            # (+ cond) so the figure is a lower bound, not zero.
            total += jaxpr_flops(eqn.params["body_jaxpr"])
            total += jaxpr_flops(eqn.params["cond_jaxpr"])
        elif prim == "cond":
            total += max(
                (jaxpr_flops(b) for b in eqn.params["branches"]), default=0
            )
        elif prim == "pallas_call":
            # The jaxpr param is the PER-GRID-CELL kernel body: multiply by
            # the grid size or flash-attention FLOPs undercount by the whole
            # grid (B*H*Tq_blocks*Tk_blocks).
            grid = tuple(getattr(eqn.params["grid_mapping"], "grid", ()) or ())
            mult = (
                _prod(grid)
                if grid and all(isinstance(g, int) for g in grid)
                else 1  # dynamic grid dims: count one cell (lower bound)
            )
            total += mult * jaxpr_flops(eqn.params["jaxpr"])
        else:
            # pjit / remat / custom_vjp / shard_map wrappers all carry their
            # body under one of these params.
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key) if eqn.params else None
                if sub is not None:
                    total += jaxpr_flops(sub)
                    break
    return int(total)


#: Dense bf16 peak FLOP/s of ONE chip, keyed by jax ``device_kind`` — the
#: only peaks table in the repo (bench.py, the trainer's MFU gauge,
#: tools/serve_bench.py and tools/perf_ledger.py all divide by it). Source
#: of every figure: Google Cloud TPU documentation, the system-architecture
#: page of each generation ("TPU v5e": 197 TFLOP/s bf16; "TPU v5p": 459;
#: "TPU v4": 275; "TPU v6e" / Trillium: 918).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e, as jax reports it
    "TPU v5e": 197e12,
    "TPU v5": 459e12,  # v5p, as jax reports it
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,  # v6e, as jax reports it
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> float | None:
    """Peak bf16 FLOP/s of ``device`` (default: ``jax.devices()[0]``) for
    MFU denominators. A CPU has no published peak: it gives None and the
    caller writes NO MFU field (a CPU wall time over a chip's peak is not
    a utilization). An accelerator that is not in the table raises — a
    device is never handed another chip's peak."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind
    if kind in PEAK_BF16_FLOPS:
        return PEAK_BF16_FLOPS[kind]
    if device.platform == "cpu":
        return None
    raise ValueError(
        f"no peak FLOP/s known for device_kind {kind!r}: add it to "
        "utils/flops.py PEAK_BF16_FLOPS with the source of the figure"
    )


def fn_flops(fn, *example_args) -> int:
    """FLOPs of ``fn(*example_args)`` — traced abstractly, nothing runs."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        if hasattr(x, "dtype")
        else x,
        example_args,
    )
    return jaxpr_flops(jax.make_jaxpr(fn)(*shapes))
