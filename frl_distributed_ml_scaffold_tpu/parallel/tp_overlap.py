"""Latency-hiding tensor parallelism: the collective-matmul schedule.

The plain TP path (``mesh.model > 1`` + ``gpt_tp_rules``/``vit_tp_rules``)
leaves the per-layer ``model``-axis collectives to GSPMD: one monolithic
allreduce after each row-parallel matmul (attn-out, fc_out), serialized
against the matmuls on every layer's critical path. Following "Scalable
Training of Language Models using JAX pjit and TPUv4" (PAPERS.md), this
module decomposes each TP matmul into per-shard blocks chained by
``ppermute`` (ops/collective_matmul.py) so each block's communication
hides under the previous block's compute:

- the residual stream between sublayers lives *sharded over the model
  axis* (sequence-sharded for the GPT stack — Megatron sequence
  parallelism — and batch-sharded for ViT/video, whose token count is not
  divisible by the axis);
- the column-parallel projections (QKV / fc_in) consume it through a
  bidirectional all-gather-matmul ring — the gather streams in while the
  resident chunk multiplies — with the QKV trio sharing ONE ring (the
  first projection returns the assembled gather for its two siblings);
- the row-parallel projections (attn-out / fc_out) produce it through the
  transpose ring, matmul-reduce-scatter, whose rotating partial-sum
  accumulators replace the exposed allreduce.

Wiring is the ``fsdp_overlap`` hook pattern: the Trainer clones the model
with ``tp_overlap=TpHooks(...)`` for the loss path only (init/decode stay
on the plain model — the params tree is identical either way), and the
hooks ride flax's injectable ``dot_general`` so ``nn.Dense`` /
``nn.MultiHeadDotProductAttention`` param creation is untouched.

Correctness is sim-gated in tests/test_tp_overlap.py (numerics vs the
GSPMD TP path across mesh compositions, grad accumulation, remat modes;
jaxpr pins on the blockwise ppermute chains); the on-chip step-time A/B
rides ``tools/perf_sweep.py gpt2_tp_overlap`` (BACKLOG R7).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

from jax import lax
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.dist.mesh import (
    BATCH_AXES,
    current_mesh_env,
    shard_map_unchecked,
)
from frl_distributed_ml_scaffold_tpu.ops.collective_matmul import (
    all_gather_matmul,
    matmul_reduce_scatter,
)

#: Model families with collective-matmul dot_general hooks wired up.
SUPPORTED_FAMILIES = ("gpt", "vit", "video")


def _canonicalize(x, w, dimension_numbers):
    """Fold a flax Dense/DenseGeneral contraction into the canonical
    ``[batch, chunkable, K] x [K, M]`` matmul the ring ops speak.

    Returns ``(x2, w2, restore)`` where ``restore(y2)`` unfolds the result
    features back to the caller's layout, or ``None`` if the contraction
    is not the trailing-dims-of-x against leading-dims-of-w pattern every
    hooked projection uses (callers then fall back to ``lax.dot_general``).
    """
    (lhs_c, rhs_c), (lhs_b, rhs_b) = dimension_numbers
    nc = len(lhs_c)
    if (
        lhs_b
        or rhs_b
        or tuple(lhs_c) != tuple(range(x.ndim - nc, x.ndim))
        or tuple(rhs_c) != tuple(range(nc))
        or x.ndim - nc != 2  # [batch, tokens, features...]
    ):
        return None
    k = math.prod(x.shape[x.ndim - nc :])
    feats = w.shape[nc:]
    x2 = x.reshape(x.shape[: x.ndim - nc] + (k,))
    w2 = w.reshape((k, math.prod(feats) if feats else 1))

    def restore(y2):
        return y2.reshape(y2.shape[:-1] + feats)

    return x2, w2, restore


@dataclass(frozen=True)
class TpHooks:
    """Collective-matmul schedule for one model family.

    ``chunk_axis`` — which activation dim the residual stream shards over
    the model axis: 1 (tokens) for the GPT scan stack, 0 (batch) for
    ViT/video (197 tokens is prime; the batch dim divides instead).

    ``lowp`` — the low-precision fast path (``parallel.low_precision``):
    when set ("int8" | "fp8_e4m3" | "fp8_e5m2"), the rings ppermute
    quantized chunks + scales and the four hooked matmuls run as scaled
    low-precision matmuls with straight-through grads
    (ops/collective_matmul.py module docstring; ops/quantization.py).
    """

    axis: str = "model"
    chunk_axis: int = 1
    lowp: str | None = None

    # ------------------------------------------------------------- specs

    def stream_spec(self) -> P:
        """Logical spec of the sharded residual stream ([B, T, D])."""
        if self.chunk_axis == 1:
            return P(BATCH_AXES, self.axis, None)
        return P((*BATCH_AXES, self.axis), None, None)

    def _gathered_spec(self) -> P:
        return P(BATCH_AXES, None, None)

    def _split_spec(self) -> P:
        """Feature-split activation ([B, T, M_local])."""
        return P(BATCH_AXES, None, self.axis)

    # ------------------------------------------------------------ helpers

    def _env(self):
        env = current_mesh_env()
        if env is None or env.axis_size(self.axis) <= 1:
            return None
        return env

    def constrain_stream(self, x):
        """Pin the residual stream to its sharded layout between the
        collective matmuls (the adds/LayerNorms in between are per-token,
        so GSPMD keeps them local once anchored)."""
        env = self._env()
        if env is None or x.ndim != 3:
            return x
        return lax.with_sharding_constraint(x, env.sharding(self.stream_spec()))

    def _check_chunkable(self, x2, n: int) -> bool:
        dim = x2.shape[self.chunk_axis]
        if self.chunk_axis == 0:
            # The batch dim also carries the data/fsdp sharding; the ring
            # chunks what remains per batch shard.
            env = current_mesh_env()
            per = math.prod(env.axis_size(a) for a in BATCH_AXES)
            return dim % (per * n) == 0
        return dim % n == 0

    # ----------------------------------------------------- dot_general API

    def ag_dot_general(self, x, w, dimension_numbers, precision=None, **kw):
        """Column-parallel projection: bidirectional all-gather-matmul."""
        env = self._env()
        canon = _canonicalize(x, w, dimension_numbers) if env else None
        if canon is None or not self._check_chunkable(
            canon[0], env.axis_size(self.axis)
        ):
            return lax.dot_general(
                x, w, dimension_numbers, precision=precision
            )
        x2, w2, restore = canon
        inner = partial(
            all_gather_matmul,
            axis_name=self.axis,
            chunk_axis=self.chunk_axis,
            return_full=False,
            precision=precision,
            lowp=self.lowp,
        )
        y2 = shard_map_unchecked(
            inner,
            mesh=env.mesh,
            in_specs=(self.stream_spec(), P(None, self.axis)),
            out_specs=self._split_spec(),
        )(x2, w2)
        return restore(y2)

    def mrs_dot_general(self, x, w, dimension_numbers, precision=None, **kw):
        """Row-parallel projection: bidirectional matmul-reduce-scatter."""
        env = self._env()
        canon = _canonicalize(x, w, dimension_numbers) if env else None
        if canon is None:
            return lax.dot_general(
                x, w, dimension_numbers, precision=precision
            )
        x2, w2, restore = canon
        n = env.axis_size(self.axis)
        # The OUTPUT is what gets chunk-sharded here; its chunkable dim is
        # x2's (they share batch/token dims).
        if not self._check_chunkable(x2, n):
            return lax.dot_general(
                x, w, dimension_numbers, precision=precision
            )
        inner = partial(
            matmul_reduce_scatter,
            axis_name=self.axis,
            chunk_axis=self.chunk_axis,
            precision=precision,
            lowp=self.lowp,
        )
        z2 = shard_map_unchecked(
            inner,
            mesh=env.mesh,
            in_specs=(self._split_spec(), P(self.axis, None)),
            out_specs=self.stream_spec(),
        )(x2, w2)
        return restore(z2)

    def qkv_context(self) -> "_QkvContext":
        """Shared-ring context for a fused QKV (or any multi-consumer)
        projection trio: the first projection runs the gather ring and
        keeps the assembled copy; siblings on the SAME input reuse it with
        a plain local matmul — one ring, not three."""
        return _QkvContext(self)


class _QkvContext:
    """Stateful dot_general shared by the q/k/v projections of one
    attention call (state lives only for that trace)."""

    def __init__(self, hooks: TpHooks):
        self._hooks = hooks
        self._x_ref = None  # strong ref: keeps id() comparisons sound
        self._full = None

    def dot_general(self, x, w, dimension_numbers, precision=None, **kw):
        hooks = self._hooks
        env = hooks._env()
        canon = _canonicalize(x, w, dimension_numbers) if env else None
        if canon is None or not hooks._check_chunkable(
            canon[0], env.axis_size(hooks.axis)
        ):
            return lax.dot_general(
                x, w, dimension_numbers, precision=precision
            )
        x2, w2, restore = canon
        if self._x_ref is x:
            # Sibling projection of the same input: the gathered copy from
            # the first ring is replicated over the model axis, the kernel
            # is column-split — a comm-free local matmul under GSPMD
            # (quantized under the low-precision fast path, so ALL of the
            # QKV trio's matmuls run low-precision, not just the ring's).
            if hooks.lowp is not None:
                from frl_distributed_ml_scaffold_tpu.ops.quantization import (
                    quantized_matmul,
                )

                return restore(quantized_matmul(self._full, w2, hooks.lowp))
            y2 = lax.dot_general(
                self._full,
                w2,
                (((self._full.ndim - 1,), (0,)), ((), ())),
                precision=precision,
            )
            return restore(y2)
        inner = partial(
            all_gather_matmul,
            axis_name=hooks.axis,
            chunk_axis=hooks.chunk_axis,
            return_full=True,
            precision=precision,
            lowp=hooks.lowp,
        )
        y2, full = shard_map_unchecked(
            inner,
            mesh=env.mesh,
            in_specs=(hooks.stream_spec(), P(None, hooks.axis)),
            out_specs=(hooks._split_spec(), hooks._gathered_spec()),
        )(x2, w2)
        self._x_ref = x
        self._full = full
        return restore(y2)


# ------------------------------------------------------------- validation


def validate_ring_schedule(cfg, *, lowp: str | None = None) -> None:
    """Fail fast on configs the collective-matmul schedule cannot honor
    (a silent fallback to the GSPMD TP schedule would invalidate any A/B
    built on it) — the fsdp_overlap validation contract. Called by the
    schedule layer (parallel/schedule.py ``validate_schedule_config``)
    for every ``granularity="ring_chunk"`` gather; the legacy knob path
    reaches it through ``validate_tp_overlap_config``."""
    family = getattr(cfg.model, "family", None)
    if family not in SUPPORTED_FAMILIES:
        raise ValueError(
            f"parallel.tp_overlap=true: model family {family!r} has no "
            f"collective-matmul hooks (supported: {SUPPORTED_FAMILIES})"
        )
    if (
        getattr(cfg.model, "pipeline_stages", 1) > 1
        and getattr(cfg.model, "pipeline_impl", "spmd") != "mpmd"
    ):
        # The SPMD stage-vmap path owns its own block schedule; the MPMD
        # backend (ISSUE 14) builds the rings INSIDE each per-stage
        # program — no stage vmap to collide with.
        raise ValueError(
            "parallel.tp_overlap composes with data/fsdp/model meshes but "
            "not with the SPMD pipeline backend (the stage-vmap path owns "
            "its own block schedule); set model.pipeline_stages=1 or "
            "model.pipeline_impl='mpmd'"
        )
    if cfg.parallel.sequence != "none" or cfg.mesh.seq > 1:
        raise ValueError(
            "parallel.tp_overlap owns the token dim's model-axis sharding; "
            "it does not compose with sequence parallelism "
            "(parallel.sequence, mesh.seq)"
        )
    if getattr(cfg.model, "attention", "dense") not in ("dense", "flash"):
        raise ValueError(
            "parallel.tp_overlap requires attention='dense'|'flash' "
            f"(got {cfg.model.attention!r}: ring/ulysses reshard the token "
            "dim themselves)"
        )
    moe = getattr(cfg.model, "moe", None)
    if moe is not None and moe.num_experts > 0:
        raise ValueError(
            "parallel.tp_overlap: the MoE MLP has no collective-matmul "
            "hooks (its dispatch owns the token exchange); set "
            "model.moe.num_experts=0"
        )
    if lowp is not None:
        from frl_distributed_ml_scaffold_tpu.ops.quantization import (
            lowp_dtype,
        )

        lowp_dtype(lowp)  # KeyError (with the vocabulary) on typos


def validate_tp_overlap_config(cfg) -> None:
    """Legacy-knob adapter: validate ``parallel.tp_overlap=true`` by
    deriving its schedule declaration and running the schedule layer's
    checks (the ``low_precision`` knob becomes the ring pair's ``lowp``
    transfer attribute)."""
    from frl_distributed_ml_scaffold_tpu.ops.quantization import resolve_lowp
    from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
        OverlapSchedule,
        gather,
        scatter,
        validate_schedule_config,
    )

    lowp = resolve_lowp(getattr(cfg.parallel, "low_precision", "none"))
    sched = OverlapSchedule.build(
        gather("model", granularity="ring_chunk", lowp=lowp),
        scatter("model", lowp=lowp),
    )
    validate_schedule_config(sched, cfg)


def make_tp_hooks(cfg, env) -> TpHooks:
    """Build the hooks for a resolved mesh, validating what only the mesh
    knows (axis size, chunk divisibility). ``lowp`` comes from the
    config's RESOLVED schedule declaration (parallel/schedule.py) — low
    precision is a transfer attribute of the declared ring, whether the
    ring was requested via the legacy ``tp_overlap``/``low_precision``
    knobs or an explicit ``parallel.schedule`` string."""
    from frl_distributed_ml_scaffold_tpu.ops.quantization import resolve_lowp
    from frl_distributed_ml_scaffold_tpu.parallel.schedule import (
        schedule_from_config,
    )

    sched = schedule_from_config(cfg)
    ring = sched.ring_gather() if sched is not None else None
    lowp = (
        ring.lowp if ring is not None
        else resolve_lowp(getattr(cfg.parallel, "low_precision", "none"))
    )
    validate_ring_schedule(cfg, lowp=lowp)
    m = env.axis_size("model")
    if m <= 1:
        raise ValueError(
            "parallel.tp_overlap=true requires mesh.model > 1 (the "
            f"resolved model axis is {m}); there is no TP communication "
            "to hide on this mesh"
        )
    family = cfg.model.family
    # The shard_map in_specs split the Megatron feature dims exactly
    # (P(None, "model") / P("model", None)): indivisible widths must fail
    # HERE, not as an obscure shard_map trace error — GSPMD pads uneven
    # shards, the explicit rings do not.
    d = cfg.model.hidden_dim
    if d % m != 0 or (d * cfg.model.mlp_ratio) % m != 0:
        raise ValueError(
            f"parallel.tp_overlap: model.hidden_dim={d} (and mlp width "
            f"{d * cfg.model.mlp_ratio}) must divide by mesh.model={m} — "
            "the collective-matmul rings split the Megatron feature dims "
            "exactly, without GSPMD's padding"
        )
    # num_heads need NOT divide by m: the attention segment between the
    # rings stays GSPMD-owned (head-split F is just a feature dim to it,
    # and it pads/reshards as it always did — equivalence is gated at
    # heads=4, model=8 in tests/test_tp_overlap.py).
    if family == "gpt":
        if cfg.model.seq_len % m != 0:
            raise ValueError(
                f"parallel.tp_overlap: model.seq_len={cfg.model.seq_len} "
                f"must divide by mesh.model={m} (the residual stream is "
                "sequence-sharded over the model axis)"
            )
        return TpHooks(axis="model", chunk_axis=1, lowp=lowp)
    # vit/video: the token count (1 + patches) is generally not divisible;
    # the batch dim carries the chunking instead.
    per_shard = (
        env.axis_size("data") * env.axis_size("fsdp") * m * cfg.trainer.grad_accum
    )
    if cfg.data.global_batch_size % per_shard != 0:
        raise ValueError(
            "parallel.tp_overlap: "
            f"data.global_batch_size={cfg.data.global_batch_size} must "
            f"divide by data*fsdp*model*grad_accum={per_shard} (the "
            f"{family} residual stream is batch-sharded over the model axis)"
        )
    return TpHooks(axis="model", chunk_axis=0, lowp=lowp)
