"""Pass 3 — materialization budget.

Generalizes PR 4's decode pin ("no full-seq_len arrays in a bucketed
decode step"): every eqn OUTPUT in a program is an array the step may
materialize; any one larger than the per-recipe byte budget — or carrying
a forbidden dimension — is a finding.  Program INPUTS (params, caches)
are exempt by construction: only eqn outvars are walked, so a big weight
passing through untouched never trips the budget, exactly like the
original pin's "seq_len appears only in the wpe PARAM" carve-out.

What this pass CANNOT see: it reads the jaxpr, not what the device's
compiler makes of it. The paged serving programs passed their cache-copy
budget here while the chip moved gigabytes a step: the passes over the KV
pool (layout transposes around a scatter and a kernel call, a scanned
leaf sliced out of its stack and written back) were put in by the TPU's
compiler and appear in no jaxpr (PERF.md §6, PR 28). The programs' compiled
HLO is pinned in tests/test_chip_compile.py
(``test_pool_program_leaves_the_pool_in_place``): a budget kept here is
necessary, not sufficient.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from frl_distributed_ml_scaffold_tpu.analysis.findings import Finding
from frl_distributed_ml_scaffold_tpu.analysis.jaxpr_utils import (
    aval_bytes,
    close,
    iter_eqns,
)


@dataclasses.dataclass(frozen=True)
class Intermediate:
    shape: tuple[int, ...]
    dtype: str
    bytes: int
    primitive: str
    path: tuple[str, ...]

    def to_dict(self) -> dict[str, Any]:
        return {
            "shape": list(self.shape),
            "dtype": self.dtype,
            "bytes": self.bytes,
            "primitive": self.primitive,
            "path": list(self.path),
        }


def intermediates(jaxpr: Any) -> list[Intermediate]:
    """Every eqn output in the program, with its byte size."""
    out = []
    for eqn, path, _trips in iter_eqns(close(jaxpr)):
        for v in eqn.outvars:
            aval = v.aval
            if not hasattr(aval, "shape"):
                continue
            out.append(
                Intermediate(
                    shape=tuple(aval.shape),
                    dtype=str(getattr(aval, "dtype", "?")),
                    bytes=aval_bytes(aval),
                    primitive=str(eqn.primitive),
                    path=path,
                )
            )
    return out


def max_materialized_bytes(jaxpr: Any) -> int:
    """The largest single intermediate in the program (bytes)."""
    return max((i.bytes for i in intermediates(jaxpr)), default=0)


def oversized_intermediates(
    jaxpr: Any, budget_bytes: int
) -> list[Intermediate]:
    """Intermediates whose single-array size exceeds the budget."""
    return [i for i in intermediates(jaxpr) if i.bytes > budget_bytes]


def intermediates_with_dim(jaxpr: Any, dim: int) -> list[Intermediate]:
    """Intermediates carrying ``dim`` in their shape — the decode pin's
    "full-seq_len array materialized" detector."""
    return [i for i in intermediates(jaxpr) if dim in i.shape]


def _itemsize(i: Intermediate) -> int:
    """Element width recovered from the byte census itself (no dtype-
    string parsing: ``bytes / numel`` is already exact)."""
    import numpy as np

    n = int(np.prod(i.shape, dtype=np.int64)) if i.shape else 1
    return i.bytes // max(n, 1)


def wide_intermediates_with_dims(
    jaxpr: Any, dims: tuple[int, ...], *, min_itemsize: int = 2
) -> list[Intermediate]:
    """Float intermediates of element width >= ``min_itemsize`` whose
    shape contains every dim of ``dims`` (with multiplicity, in ANY
    order — a layout transpose must not dodge the pin) — the quantized-
    cache pin's detector: with an int8 KV cache of geometry
    ``(S, H, hd)``, a decode step materializing a wide-float array
    carrying all three dims has dequantized the whole cache, whether in
    the storage layout ``[B, S, H, hd]`` or the kernel's transposed
    ``[B, H, S, hd]`` (the exact allocation the quantized cache exists
    to avoid; its 1-byte cache updates and its small per-chunk/per-scale
    floats all lack the full ``S`` dim and pass)."""
    from collections import Counter

    need = Counter(dims)
    out = []
    for i in intermediates(jaxpr):
        if not i.dtype.startswith(("float", "bfloat")):
            continue
        if _itemsize(i) < min_itemsize:
            continue
        if not need - Counter(i.shape):
            out.append(i)
    return out


def materialization_findings(
    jaxpr: Any,
    *,
    budget_bytes: int | None = None,
    forbidden_dim: int | None = None,
    top_k: int = 3,
    label: str = "",
) -> list[Finding]:
    """Budget + forbidden-dim checks as findings; always reports the
    ``top_k`` largest intermediates as info rows (the diffable census of
    where the memory goes)."""
    out: list[Finding] = []
    ints = intermediates(jaxpr)
    for i in sorted(ints, key=lambda x: -x.bytes)[:top_k]:
        out.append(
            Finding(
                "materialization", "info", "largest-intermediate",
                f"{label}{i.dtype}{list(i.shape)} = {i.bytes} bytes "
                f"({i.primitive})",
                {"intermediate": i.to_dict()},
            )
        )
    if budget_bytes is not None:
        for i in ints:
            if i.bytes > budget_bytes:
                out.append(
                    Finding(
                        "materialization", "error", "over-budget",
                        f"{label}intermediate {i.dtype}{list(i.shape)} is "
                        f"{i.bytes} bytes > budget {budget_bytes} "
                        f"({i.primitive} at {'/'.join(i.path) or 'top'})",
                        {"intermediate": i.to_dict(), "budget": budget_bytes},
                    )
                )
    if forbidden_dim is not None:
        for i in (x for x in ints if forbidden_dim in x.shape):
            out.append(
                Finding(
                    "materialization", "error", "forbidden-dim",
                    f"{label}intermediate {i.dtype}{list(i.shape)} carries "
                    f"forbidden dim {forbidden_dim} ({i.primitive})",
                    {"intermediate": i.to_dict(), "dim": forbidden_dim},
                )
            )
    return out
