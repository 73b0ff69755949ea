"""Disaggregated prefill/decode serving + the multi-tenant SLO scheduler
(ISSUE 12).

Prefill is compute-bound (one big ragged forward over the prompt), decode
is bandwidth-bound (one tiny forward per token against the whole KV
pool); co-scheduling them in one engine lets a prefill burst blow up
decode TPOT tails — the colocated engine admits into EVERY free slot
inline before each decode tick, so a burst of prompt-heavy arrivals runs
several full prefills ahead of the next token. This module splits the
workload per the MPMD per-worker-program shape (arXiv 2412.14374):

- **PrefillWorker**: owns the prefill programs (bucketed ragged prefill,
  shared-prefix seeded suffix prefill) and, optionally, its own mesh
  partition — on the CPU sim a submesh of the device set (``prefill_env``
  built over a device subset), on hardware a separate slice. With a
  separate partition the worker holds its own params replica and prefill
  dispatches are ASYNC (jax async dispatch + ``Array.is_ready`` polling):
  the decode partition never waits on prefill wall time.
- **DecodeWorker**: a paged ``ServingEngine`` (serving/engine.py) driven
  with an empty queue — it only ever runs the ONE compiled decode /
  verify shape plus the handoff splice. Speculative decoding (ISSUE 11)
  rides the decode worker unchanged.
- **The handoff is a block-table SPLICE**, never a cache copy
  (``generation.splice_pool_blocks``, the same program colocated
  admission jits): the prefilled private blocks scatter into their pool
  homes and ownership moves as one host-side table-row write. When the
  partitions share the pool (the CPU-sim default) the blocks merely
  RE-OWN — zero bytes move; with a separate prefill partition exactly
  the suffix blocks transfer (``jax.device_put``, counted), the targeted
  instance of portable array redistribution (arXiv 2112.01075).
  graft-lint's ``serving:handoff`` program pins the splice clone-free
  and the perf ledger prices it at table bytes, not cache bytes.

On top sits the multi-tenant **SLO scheduler** — PR 9's deadline/shed
machinery promoted to real SLO classes:

- **Per-tenant priority queues** (``TenantSpec``): strict class priority
  ``latency > standard > best_effort``, weighted round-robin within a
  class, per-tenant and global queue bounds. A full GLOBAL queue sheds
  the newest request of the LOWEST queued class, not the arriving
  high-class request (shed ordering follows the SLO, not arrival order).
- **Decoupled prefill/decode admission**: at most
  ``prefill_max_per_tick`` prefills start per decode tick, so a prefill
  burst DEFERS in the queue while running decodes keep their cadence —
  the tail-isolation mechanism ``serve_bench``'s ``*_disagg`` arm
  measures.
- **Decode-slot preemption**: a latency-class handoff with no free slot
  PARKS a best-effort slot (``ServingEngine.park_slot`` — free, because
  the paged pool keeps the parked request's blocks owned) and takes it;
  the parked request resumes later (``resume_parked`` — a table re-own
  plus one cursor pointer-move) and completes token-identically.

Failure semantics extend PR 9's never-hangs contract across the worker
boundary: a prefill-worker death or handoff failure (fault sites
``serve.prefill_worker`` / ``serve.handoff``) releases the pool
reservation and RE-QUEUES the request at the head of its tenant queue,
bounded by ``handoff_retries`` before a typed ``"error"`` completion.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from frl_distributed_ml_scaffold_tpu import faults
from frl_distributed_ml_scaffold_tpu.config.schema import ServingConfig
from frl_distributed_ml_scaffold_tpu.models.generation import (
    blocks_for_tokens,
    cache_capacity_axis,
    next_cache_bucket,
)
from frl_distributed_ml_scaffold_tpu.serving.engine import (
    Completion,
    ServeRequest,
    ServingEngine,
)
from frl_distributed_ml_scaffold_tpu.telemetry import MetricsRegistry, Tracer

#: SLO classes in strict priority order: a class admits (and, for
#: ``latency``, preempts) ahead of every class to its right.
SLO_CLASSES = ("latency", "standard", "best_effort")
_RANK = {c: i for i, c in enumerate(SLO_CLASSES)}


def _sanitize(name: str) -> str:
    """Tenant name -> metric-name-safe suffix."""
    return re.sub(r"[^A-Za-z0-9_]", "_", name)


def _capacity_slice(tree, start_tok: int, stop_tok: int, cap: int):
    """Slice a slot-cache tree's capacity-bearing leaves
    (``generation.cache_capacity_axis`` — K/V and scale stacks at
    capacity ``cap``) to positions ``[start_tok, stop_tok)``;
    bookkeeping leaves pass through. The cross-partition handoff moves
    ONLY this window — the blocks that change owner — never the whole
    bucketed cache."""

    def leaf(e):
        ax = cache_capacity_axis(e, cap)
        if ax is None:
            return e
        return jax.lax.slice_in_dim(e, start_tok, stop_tok, axis=ax)

    return jax.tree.map(leaf, tree)


def _capacity_pad(tree, cap_from: int, cap_to: int):
    """Inverse of ``_capacity_slice`` for the receiving partition: pad
    capacity-bearing leaves from ``cap_from`` back to ``cap_to`` (the
    padded region is exactly the zeros the un-sliced tree carried, so
    the downstream program sees an identical cache)."""

    def leaf(e):
        ax = cache_capacity_axis(e, cap_from)
        if ax is None:
            return e
        pad = [(0, 0)] * e.ndim
        pad[ax] = (0, cap_to - cap_from)
        return jnp.pad(e, pad)

    return jax.tree.map(leaf, tree)


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's SLO contract.

    ``slo_class`` orders admission (and preemption rights: only
    ``latency`` tenants preempt, and only ``best_effort`` slots are
    preemptible); ``weight`` is the weighted-round-robin share WITHIN a
    class; ``max_queue_depth`` bounds this tenant's own queue (0 = only
    the scheduler's global bound applies); ``default_deadline_s`` stamps
    requests that pass no explicit deadline."""

    name: str
    slo_class: str = "standard"
    weight: int = 1
    max_queue_depth: int = 0
    default_deadline_s: float = 0.0

    def __post_init__(self):
        if self.slo_class not in SLO_CLASSES:
            raise ValueError(
                f"tenant {self.name!r}: slo_class={self.slo_class!r} "
                f"unknown (want one of {SLO_CLASSES})"
            )
        if self.weight < 1:
            raise ValueError(
                f"tenant {self.name!r}: weight={self.weight} < 1"
            )


@dataclasses.dataclass
class _Package:
    """One in-flight prefill→decode handoff: the request, its pool
    reservation, and the (possibly still-computing) prefill outputs."""

    req: ServeRequest
    res: dict
    spec: TenantSpec
    t_launch: float
    seq: int
    tok: Any  # [1] device array (un-fetched: async failures surface at get)
    slot_cache: Any
    s_p: int
    s_c: int
    m: int
    l_suf: int
    #: The RNG split this attempt consumed (reused verbatim on retry —
    #: the worker-failure rng-neutrality contract).
    rng: Any = None
    #: Stamped when the prefill COMPLETED (readiness confirmed) — the
    #: honest end of prefill wall time; slot-wait in the ready list is
    #: queueing, not prefill, and must not pollute TTFT.
    t_ready: float = 0.0


class PrefillWorker:
    """The prefill half of the disaggregated engine: owns the prefill
    jit caches and (optionally) a separate mesh partition with its own
    params replica. Stateless across requests — every package it emits
    is self-contained, which is what makes worker death recoverable by
    re-queueing (nothing to reconstruct)."""

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        sample_kw: dict,
        min_bucket: int,
        seq_len: int,
        shared_env: Any,
        partition: Any = None,
    ):
        self.model = model
        self.seq_len = seq_len
        self.min_bucket = int(min_bucket)
        self._sample_kw = dict(sample_kw)
        #: None = share the decode partition (programs trace under the
        #: decode mesh env; the handoff is a pure re-own). A MeshEnv
        #: over a device subset = a separate partition: params are
        #: replicated onto it and prefills run (async) there.
        self.partition = partition
        self._shared_env = shared_env
        #: Cost record of the replica placement when it rode the
        #: redistribution service (the Checkpointer.last_restore_plan
        #: convention); None on the shared partition / device_put path.
        self.replica_plan = None
        if partition is not None:
            # The worker's replica rides the redistribution service
            # (ISSUE 15) when the decode-side shards are addressable
            # (single-process): leaf-at-a-time bounded assembly with a
            # plan recording what moved. Multi-process falls back to
            # the plain device_put — the chunked executor needs every
            # source shard in-process, and a worker replica must never
            # fail to construct over an accounting nicety.
            import jax as _jax

            from frl_distributed_ml_scaffold_tpu import redistribute
            from jax.sharding import PartitionSpec as P

            addressable = all(
                getattr(l, "is_fully_addressable", True)
                for l in _jax.tree_util.tree_leaves(params)
            )
            if addressable:
                params, self.replica_plan = redistribute.to_mesh(
                    params, partition, spec_of=lambda _p, _l: P()
                )
            else:
                params = jax.device_put(params, partition.replicated())
        self.params = params
        self._prefill_jit: dict[int, Any] = {}
        self._seeded_jit: dict[tuple[int, int], Any] = {}

    @property
    def separate(self) -> bool:
        return self.partition is not None

    def _ctx(self):
        from frl_distributed_ml_scaffold_tpu.dist.mesh import mesh_context

        return mesh_context(
            self.partition if self.partition is not None else self._shared_env
        )

    def _bucket_for(self, needed: int) -> int:
        return next_cache_bucket(self.seq_len, needed, floor=self.min_bucket)

    def _model_at(self, cache_len: int):
        return self.model.clone(cache_len=int(cache_len))

    def _prefill_fn(self, s_p: int):
        from frl_distributed_ml_scaffold_tpu.serving.engine import (
            make_prefill_program,
        )

        if s_p not in self._prefill_jit:
            self._prefill_jit[s_p] = make_prefill_program(
                self._model_at(s_p), self._sample_kw
            )
        return self._prefill_jit[s_p]

    def _prefill_seeded_fn(self, s_p: int, s_c: int):
        from frl_distributed_ml_scaffold_tpu.serving.engine import (
            make_seeded_prefill_program,
        )

        if (s_p, s_c) not in self._seeded_jit:
            self._seeded_jit[(s_p, s_c)] = make_seeded_prefill_program(
                self._model_at(s_c), self._sample_kw
            )
        return self._seeded_jit[(s_p, s_c)]

    def prefill(
        self, req: ServeRequest, res: dict, rng, *,
        block_size: int, seed_cache: Any = None,
    ) -> tuple[Any, Any, int, int, int, int]:
        """Run (dispatch) the request's prefill; returns the un-fetched
        package ``(tok, slot_cache, s_p, s_c, m, l_suf)`` by the shared
        ``engine.prefill_request`` recipe — the exact code colocated
        ``_prefill_package`` runs — against THIS worker's
        params/partition, so the two admission paths cannot drift.
        Consults the ``serve.prefill_worker`` fault site; on a separate
        partition the dispatch is async, so program failures surface at
        the scheduler's readiness check and take the same re-queue
        path."""
        from frl_distributed_ml_scaffold_tpu.serving.engine import (
            prefill_request,
        )

        faults.maybe_raise("serve.prefill_worker", key=req.id)
        with self._ctx():
            return prefill_request(
                req, res, rng,
                block_size=block_size, bucket_for=self._bucket_for,
                params=self.params, prefill_fn=self._prefill_fn,
                seeded_fn=self._prefill_seeded_fn, seed_cache=seed_cache,
            )


class DisaggServingEngine:
    """The disaggregated serving facade: ``ServingEngine``'s public face
    (submit/step/run/close, typed ``Completion``s) over a PrefillWorker
    + DecodeWorker pair coordinated by the multi-tenant SLO scheduler.
    Paged-cache only — the handoff is a block-table splice.

    Usage::

        eng = DisaggServingEngine(
            model, params, num_slots=4, kv_block_size=16,
            tenants=[TenantSpec("fg", "latency"),
                     TenantSpec("bg", "best_effort")],
        )
        eng.submit(prompt, max_new_tokens=32, tenant="fg")
        done = eng.run()
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        num_slots: int = 4,
        eos_id: int | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        rng: jax.Array | None = None,
        min_bucket: int = 8,
        serving: ServingConfig | None = None,
        max_queue_depth: int = 0,
        default_deadline_s: float = 0.0,
        kv_block_size: int = 0,
        kv_pool_blocks: int = 0,
        prefix_cache: bool | None = None,
        speculate: str | None = None,
        speculate_k: int = 0,
        draft_model: Any = None,
        draft_params: Any = None,
        tenants: Sequence[TenantSpec] | None = None,
        prefill_env: Any = None,
        prefill_max_per_tick: int | None = None,
        handoff_retries: int | None = None,
        telemetry: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
        stall_timeout_s: float = 0.0,
        stall_dump_path: str | None = None,
        stall_first_beat_scale: float = 5.0,
    ):
        if getattr(model.config, "layer_types", ()):
            raise NotImplementedError(
                "not on a model with sliding-window layers (layer_types) "
                "yet: the disaggregated engine — its handoff splices one "
                "kind of pool, and such a model keeps a pool for each "
                "layer kind"
            )
        if serving is not None:
            if (max_queue_depth or default_deadline_s or kv_block_size
                    or kv_pool_blocks or prefix_cache is not None
                    or speculate is not None or speculate_k):
                raise ValueError(
                    "pass either serving=ServingConfig(...) or the "
                    "scalar knobs, not both"
                )
            max_queue_depth = serving.max_queue_depth
            default_deadline_s = serving.default_deadline_s
            kv_block_size = serving.kv_block_size
            if prefill_max_per_tick is None:
                prefill_max_per_tick = serving.prefill_max_per_tick
            if handoff_retries is None:
                handoff_retries = serving.handoff_retries
            # The decode worker never sheds or deadline-checks at its
            # (empty) queue — the scheduler owns admission policy.
            decode_serving = dataclasses.replace(
                serving, max_queue_depth=0, default_deadline_s=0.0,
                disaggregate=False,
            )
        else:
            decode_serving = None
        if kv_block_size <= 0:
            raise ValueError(
                "disaggregated serving requires the paged cache "
                "(kv_block_size > 0): the prefill→decode handoff is a "
                "block-table splice — the bucketed cache would need a "
                "cache copy, which is exactly what this engine exists "
                "to avoid"
            )
        self.prefill_max_per_tick = int(
            1 if prefill_max_per_tick is None else prefill_max_per_tick
        )
        if self.prefill_max_per_tick < 1:
            raise ValueError(
                f"prefill_max_per_tick={self.prefill_max_per_tick} < 1: "
                "the scheduler could never admit"
            )
        self.handoff_retries = int(
            2 if handoff_retries is None else handoff_retries
        )
        self.max_queue_depth = int(max_queue_depth)
        self.default_deadline_s = float(default_deadline_s)
        self._rng = jax.random.key(0) if rng is None else rng

        # The decode worker: a paged ServingEngine driven with an empty
        # queue (the scheduler admits via admit_handoff, never submit).
        decode_kw = (
            dict(serving=decode_serving) if decode_serving is not None
            else dict(
                kv_block_size=kv_block_size, kv_pool_blocks=kv_pool_blocks,
                prefix_cache=prefix_cache, speculate=speculate,
                speculate_k=speculate_k,
            )
        )
        self.decode = ServingEngine(
            model, params,
            num_slots=num_slots, eos_id=eos_id, temperature=temperature,
            top_k=top_k, top_p=top_p, min_bucket=min_bucket,
            draft_model=draft_model, draft_params=draft_params,
            telemetry=telemetry, tracer=tracer,
            stall_timeout_s=stall_timeout_s, stall_dump_path=stall_dump_path,
            stall_first_beat_scale=stall_first_beat_scale,
            **decode_kw,
        )
        self.prefill_worker = PrefillWorker(
            self.decode.model, self.decode.params,
            sample_kw=self.decode._sample_kw,
            min_bucket=self.decode.min_bucket,
            seq_len=self.decode.seq_len,
            shared_env=self.decode._env,
            partition=prefill_env,
        )

        # Tenant registry + queues. Unknown tenants at submit() register
        # themselves with the default (standard, weight 1) contract, so
        # single-tenant callers never touch TenantSpec.
        self._tenants: dict[str, TenantSpec] = {}
        self._queues: dict[str, collections.deque[ServeRequest]] = {}
        self._rr_cycle: dict[str, list[str]] = {c: [] for c in SLO_CLASSES}
        self._rr_pos: dict[str, int] = {c: 0 for c in SLO_CLASSES}
        self._tenant_of: dict[int, str] = {}
        self._retries: dict[int, int] = {}
        # RNG key a failed attempt consumed, reused verbatim on the
        # retry: a worker failure must not shift any request's sampling
        # stream (the chaos token-identity contract for temperature>0 —
        # the disaggregated analog of colocated _try_admit's rng
        # rollback, which cannot work here because other launches may
        # split between failure and retry).
        self._retry_rng: dict[int, Any] = {}
        self._inflight: list[_Package] = []
        self._ready: list[_Package] = []
        self._parked: list[dict] = []  # {state, spec, seq}
        self._seq = 0
        self._stats = collections.Counter()

        t = self.telemetry
        self._m_t_ttft: dict[str, Any] = {}
        self._m_t_tpot: dict[str, Any] = {}
        self._m_t_shed: dict[str, Any] = {}
        self._m_handoff = t.histogram(
            "serve_handoff_seconds",
            help="prefill→decode handoff latency (transfer + splice; "
            "the block-table re-own — prefill wall time excluded)",
        )
        self._m_handoffs = t.counter(
            "serve_handoff_total", help="prefill→decode handoffs spliced"
        )
        self._m_handoff_failures = t.counter(
            "serve_handoff_failures_total",
            help="handoff splices that failed (request re-queued)",
        )
        self._m_pw_failures = t.counter(
            "serve_prefill_worker_failures_total",
            help="prefill-worker failures (request re-queued)",
        )
        self._m_preempt = t.counter(
            "serve_preemption_total",
            help="best-effort decode slots parked for latency-class "
            "handoffs",
        )
        self._m_resume = t.counter(
            "serve_resume_total", help="parked requests resumed"
        )
        self._m_parked_g = t.gauge(
            "serve_parked_requests", help="requests currently parked"
        )
        self._m_deferred = t.counter(
            "serve_prefill_deferred_total",
            help="scheduler ticks that deferred queued prefills "
            "(decoupled admission: the burst queues, decodes keep cadence)",
        )
        self._m_transfer = t.counter(
            "serve_handoff_transfer_bytes_total",
            help="bytes moved across partitions at handoff (0 when the "
            "partitions share the pool — the blocks merely re-own)",
        )
        for spec in tenants or ():
            self.register_tenant(spec)

    # ------------------------------------------------------------- plumbing

    @property
    def telemetry(self) -> MetricsRegistry:
        return self.decode.telemetry

    @property
    def paged(self) -> bool:
        return True

    @property
    def num_slots(self) -> int:
        return self.decode.num_slots

    @property
    def eos_id(self):
        return self.decode.eos_id

    @property
    def bucket(self) -> int:
        return self.decode.bucket

    @property
    def block_size(self) -> int:
        return self.decode.block_size

    @property
    def pool_blocks(self) -> int:
        return self.decode.pool_blocks

    @property
    def stats(self) -> collections.Counter:
        merged = collections.Counter(self.decode.stats)
        merged.update(self._stats)
        return merged

    def block_bytes(self) -> int:
        return self.decode.block_bytes()

    def bytes_per_slot(self) -> int:
        return self.decode.bytes_per_slot()

    def pool_utilization(self) -> float:
        return self.decode.pool_utilization()

    def export_trace(self, path: str) -> None:
        self.decode.export_trace(path)

    def close(self) -> None:
        self.decode.close()

    def reset_cache(self) -> None:
        """The serve_bench warm-up contract, facade-wide."""
        if self.pending:
            raise RuntimeError("reset_cache with requests in flight")
        self.decode.reset_cache()
        self._stats.clear()
        self._retries.clear()
        self._retry_rng.clear()
        self._tenant_of.clear()

    @property
    def pending(self) -> int:
        return (
            sum(len(q) for q in self._queues.values())
            + len(self._inflight)
            + len(self._ready)
            + len(self._parked)
            + int(self.decode._active.sum())
        )

    # ------------------------------------------------------------- frontend

    def register_tenant(self, spec: TenantSpec) -> None:
        if spec.name in self._tenants:
            raise ValueError(f"tenant {spec.name!r} already registered")
        clash = next(
            (n for n in self._tenants if _sanitize(n) == _sanitize(spec.name)),
            None,
        )
        if clash is not None:
            raise ValueError(
                f"tenant {spec.name!r} collides with {clash!r} after metric-"
                f"name sanitization ({_sanitize(spec.name)!r}) — their "
                "per-tenant histograms/counters would silently merge"
            )
        self._tenants[spec.name] = spec
        self._queues[spec.name] = collections.deque()
        # Weighted round-robin: the tenant appears ``weight`` times in
        # its class's cycle, so a weight-3 tenant gets 3 of every
        # (3 + peers) admissions while both have queued work.
        self._rr_cycle[spec.slo_class].extend([spec.name] * spec.weight)
        t, s = self.telemetry, _sanitize(spec.name)
        self._m_t_ttft[spec.name] = t.histogram(
            f"serve_ttft_seconds_tenant_{s}",
            help=f"TTFT, tenant {spec.name} ({spec.slo_class})",
        )
        self._m_t_tpot[spec.name] = t.histogram(
            f"serve_tpot_seconds_tenant_{s}",
            help=f"inter-token gap, tenant {spec.name} ({spec.slo_class})",
        )
        self._m_t_shed[spec.name] = t.counter(
            f"serve_shed_total_tenant_{s}",
            help=f"requests shed, tenant {spec.name}",
        )

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        request_id: int | None = None,
        *,
        deadline_s: float | None = None,
        tenant: str = "default",
    ) -> int:
        """Enqueue under ``tenant``'s SLO contract; returns the id.
        Sheds are typed (ISSUE 9) and SLO-ordered: a full global queue
        sheds the newest request of the LOWEST queued class to make room
        for a higher-class arrival."""
        spec = self._tenants.get(tenant)
        if spec is None:
            spec = TenantSpec(name=tenant)
            self.register_tenant(spec)
        if deadline_s is None and spec.default_deadline_s:
            deadline_s = spec.default_deadline_s
        req = self.decode._new_request(
            prompt, max_new_tokens, request_id,
            deadline_s=(self.default_deadline_s if deadline_s is None
                        else deadline_s),
        )
        self._tenant_of[req.id] = tenant
        q = self._queues[tenant]
        if spec.max_queue_depth and len(q) >= spec.max_queue_depth:
            self._shed(req, spec)
            return req.id
        if self.max_queue_depth:
            total = sum(len(qq) for qq in self._queues.values())
            if total >= self.max_queue_depth:
                victim = self._shed_victim(than=spec)
                if victim is None:
                    self._shed(req, spec)
                    return req.id
                vq, vspec = victim
                self._shed(vq.pop(), vspec)
        q.append(req)
        return req.id

    def _shed(self, req: ServeRequest, spec: TenantSpec) -> None:
        self.decode._m_shed.inc()
        self._m_t_shed[spec.name].inc()
        self._stats[f"shed_{spec.name}"] += 1
        self.decode._complete_unadmitted(req, "shed")

    def _shed_victim(self, than: TenantSpec):
        """The newest queued request of the lowest class STRICTLY below
        ``than`` — the request the SLO ordering says to sacrifice when
        the global queue is full. Lowest class first; among same-class
        tenants, the one whose queue TAIL arrived last (each queue is
        FIFO, so the tail is that tenant's newest). None = nothing
        outranked (the arrival itself sheds)."""
        best = None  # (rank, tail t_submit, name)
        for name, q in self._queues.items():
            if not q:
                continue
            r = _RANK[self._tenants[name].slo_class]
            if r <= _RANK[than.slo_class]:
                continue
            key = (r, q[-1].t_submit)
            if best is None or key > (best[0], best[1]):
                best = (r, q[-1].t_submit, name)
        if best is None:
            return None
        name = best[2]
        return self._queues[name], self._tenants[name]

    # ----------------------------------------------------------- scheduling

    def _next_request(self):
        """Highest-class, weighted-round-robin queued request (queued
        past-deadline requests shed typed on the way, like colocated
        ``_admit``). Returns ``(queue, req, spec, rr)`` WITHOUT popping
        or advancing the round-robin cursor — the caller pops AND
        commits ``rr`` only once the request actually launches, so a
        deferred head request (pool headroom, slot capacity) keeps its
        turn: same-class peers must not jump it on later ticks (the
        colocated FIFO-within-class contract; advancing eagerly would
        let a stream of small peers starve a large deferred head)."""
        for cls in SLO_CLASSES:
            order = self._rr_cycle[cls]
            n = len(order)
            start = self._rr_pos[cls] % n if n else 0
            for i in range(n):
                name = order[(start + i) % n]
                q = self._queues[name]
                while q:
                    req = q[0]
                    if self.decode._expired(req):
                        q.popleft()
                        self.decode._m_deadline.inc()
                        self.decode._complete_unadmitted(req, "deadline")
                        continue
                    return (
                        q, req, self._tenants[name],
                        (cls, (start + i + 1) % n),
                    )
        return None

    def _commit_rr(self, rr) -> None:
        cls, pos = rr
        self._rr_pos[cls] = pos

    def _preemptible_slots(self) -> list[int]:
        """Active decode slots owned by best-effort tenants (the only
        preemptible class), most-remaining-budget first."""
        out = []
        for slot in np.flatnonzero(self.decode._active):
            slot = int(slot)
            req = self.decode._req[slot]
            spec = self._tenants.get(self._tenant_of.get(req.id, ""), None)
            if spec is not None and spec.slo_class == "best_effort":
                remaining = req.max_new_tokens - len(self.decode._tokens[slot])
                out.append((remaining, slot))
        return [s for _, s in sorted(out, reverse=True)]

    def _launch_prefills(self) -> None:
        """Start up to ``prefill_max_per_tick`` prefills — the decoupled
        admission bound. A prefill only launches when a handoff target
        exists (a free slot net of in-flight handoffs, or — for a
        latency-class request — a preemptible best-effort slot); pool
        headroom defers the head request exactly like colocated
        admission (FIFO within the class, typed sheds via the queue
        bound under sustained pressure)."""
        launched = 0
        while launched < self.prefill_max_per_tick:
            pick = self._next_request()
            if pick is None:
                break
            q, req, spec, rr = pick
            free = int((~self.decode._active).sum())
            pending = len(self._inflight) + len(self._ready)
            # Parked requests do NOT reserve slots here: they already
            # outrank non-latency handoffs at placement time (resumes
            # run before ``_place_ready(only_latency=False)``), and
            # counting them would deadlock against the resume guard —
            # a queued latency request and a parked best-effort victim
            # each waiting for the other's slot.
            cap = free - pending
            if cap <= 0 and spec.slo_class == "latency":
                n_lat_pending = sum(
                    1 for p in self._inflight + self._ready
                    if p.spec.slo_class == "latency"
                )
                cap += max(
                    0, len(self._preemptible_slots()) - n_lat_pending
                )
            if cap <= 0:
                self._stats["prefill_deferred"] += 1
                self._m_deferred.inc()
                break
            res = self.decode._pool_reserve(req)
            if res is None:
                self._stats["admission_deferred"] += 1
                self._m_deferred.inc()
                break
            q.popleft()
            self._commit_rr(rr)
            t_launch = time.perf_counter()
            self.decode._phase(
                "queue_wait", t0=req.t_submit,
                dur_s=t_launch - req.t_submit,
                trace=req.trace, parent=req.span, tenant=spec.name,
            )
            sub = self._retry_rng.pop(req.id, None)
            if sub is None:
                self._rng, sub = jax.random.split(self._rng)
            try:
                # The shared-prefix seed gathers from the POOL — the
                # decode partition's memory (the shared seed half of the
                # admission recipe, ``engine._seed_for``) — and crosses
                # to the prefill partition with the package's arrays.
                with self.decode._trace_ctx():
                    seed_cache = self.decode._seed_for(req, res)
                if seed_cache is not None and self.prefill_worker.separate:
                    # Transfer only the OCCUPIED prefix (m blocks); the
                    # zero tail of the s_c-capacity seed is re-padded on
                    # the prefill partition — the link carries the data,
                    # not the bucket.
                    m_tok = res["m"] * self.decode.block_size
                    s_c = self.decode._bucket_for(int(req.prompt.size))
                    seed_cache, moved = self._put(
                        _capacity_slice(seed_cache, 0, m_tok, s_c),
                        self.prefill_worker.partition,
                    )
                    self._count_transfer(moved)
                    seed_cache = _capacity_pad(seed_cache, m_tok, s_c)
                tok, slot_cache, s_p, s_c, m, l_suf = (
                    self.prefill_worker.prefill(
                        req, res, sub,
                        block_size=self.decode.block_size,
                        seed_cache=seed_cache,
                    )
                )
            except Exception as e:
                self._worker_failed(
                    req, res, spec, e, site="prefill_worker", rng=sub
                )
                continue
            self._seq += 1
            self._inflight.append(_Package(
                req=req, res=res, spec=spec, t_launch=t_launch,
                seq=self._seq, tok=tok, slot_cache=slot_cache,
                s_p=s_p, s_c=s_c, m=m, l_suf=l_suf, rng=sub,
            ))
            self._stats["prefills_launched"] += 1
            launched += 1
        if launched >= self.prefill_max_per_tick and any(
            self._queues.values()
        ):
            # Budget exhausted with work still queued: the deferral the
            # decoupling exists for.
            self._stats["prefill_deferred"] += 1
            self._m_deferred.inc()

    def _poll_inflight(self, block: bool = False) -> None:
        """Move completed prefills to the ready list, stamping
        ``t_ready`` (the end of honest prefill wall time — slot-wait in
        the ready list is queueing, not prefill). Shared partition: the
        package completes here, paying the same wait colocated
        admission's token fetch pays. Separate partition: readiness is
        polled (``Array.is_ready``) so the decode tick never waits on
        prefill wall time; ``block=True`` forces the oldest package (the
        progress guarantee when nothing is decoding). A prefill program
        that FAILED surfaces here — before any preemption decision could
        park a victim for a package that can never splice — and takes
        the prefill-worker re-queue path."""
        still: list[_Package] = []
        for i, pkg in enumerate(self._inflight):
            ready = (
                not self.prefill_worker.separate
                or (block and i == 0 and not still)
                or not hasattr(pkg.tok, "is_ready")
                or pkg.tok.is_ready()
            )
            if not ready:
                still.append(pkg)
                continue
            try:
                jax.block_until_ready(pkg.tok)
            except Exception as e:
                self._worker_failed(
                    pkg.req, pkg.res, pkg.spec, e,
                    site="prefill_worker", rng=pkg.rng,
                )
                continue
            pkg.t_ready = time.perf_counter()
            self._ready.append(pkg)
        self._inflight = still

    def _fill_slots(self) -> None:
        """Place ready handoffs + resume parked requests, SLO-ordered:
        expired parked requests retire typed first (no slot needed —
        their blocks come straight back), then latency handoffs
        (preempting best-effort slots when full), then parked resumes
        (they hold pool blocks hostage — finishing them frees memory),
        then the remaining handoffs."""
        self._expire_parked()
        self._ready.sort(key=lambda p: (_RANK[p.spec.slo_class], p.seq))
        self._place_ready(only_latency=True)
        self._resume_parked()
        self._place_ready(only_latency=False)
        self._m_parked_g.set(float(len(self._parked)))

    def _expire_parked(self) -> None:
        """A parked request past its deadline must not hold its blocks
        hostage waiting for a slot it no longer wants: retire it typed
        ``"deadline"`` IN PLACE (``ServingEngine.retire_parked`` — the
        completion carries the tokens generated before the park, the
        blocks and worst-case reservation release immediately)."""
        still: list[dict] = []
        for entry in self._parked:
            req = entry["state"]["req"]
            if self.decode._expired(req):
                self.decode._m_deadline.inc()
                self.decode.retire_parked(entry["state"], "deadline")
            else:
                still.append(entry)
        self._parked = still

    def _free_slot(self) -> int | None:
        free = np.flatnonzero(~self.decode._active)
        return int(free[0]) if free.size else None

    def _place_ready(self, *, only_latency: bool) -> None:
        rest: list[_Package] = []
        for pkg in self._ready:
            if only_latency and pkg.spec.slo_class != "latency":
                rest.append(pkg)
                continue
            if self.decode._expired(pkg.req):
                # Expired while prefilling / waiting for a slot: resolve
                # typed NOW (queued-shed semantics — the prefill output
                # is discarded) instead of parking a healthy victim and
                # splicing for an answer nobody wants.
                self.decode._pool_release(pkg.res)
                self.decode._m_deadline.inc()
                self._retries.pop(pkg.req.id, None)
                self.decode._complete_unadmitted(pkg.req, "deadline")
                continue
            slot = self._free_slot()
            if slot is None and pkg.spec.slo_class == "latency":
                # The victim is picked from slot state: the decode step
                # in flight lands first (it may free a slot by itself).
                self.decode._drain_inflight()
                slot = self._free_slot()
            if slot is None and pkg.spec.slo_class == "latency":
                victims = self._preemptible_slots()
                if victims:
                    slot = victims[0]
                    vreq = self.decode._req[slot]
                    vspec = self._tenants[self._tenant_of[vreq.id]]
                    state = self.decode.park_slot(slot)
                    self._parked.append(
                        {"state": state, "spec": vspec, "seq": self._seq}
                    )
                    self._seq += 1
                    self._m_preempt.inc()
                    self._stats["preemptions"] += 1
            if slot is None:
                rest.append(pkg)
                continue
            self._complete_handoff(pkg, slot)
        self._ready = rest

    def _resume_parked(self) -> None:
        """Resume parked requests into free slots, class-ordered. A
        best-effort parked request stays parked while a latency handoff
        is waiting for a slot (resuming it would be preempted right
        back — thrash, not progress)."""
        latency_waiting = any(
            p.spec.slo_class == "latency"
            for p in self._inflight + self._ready
        ) or any(
            q and self._tenants[n].slo_class == "latency"
            for n, q in self._queues.items()
        )
        self._parked.sort(
            key=lambda e: (_RANK[e["spec"].slo_class], e["seq"])
        )
        still: list[dict] = []
        for entry in self._parked:
            slot = self._free_slot()
            if slot is None or (
                latency_waiting
                and entry["spec"].slo_class == "best_effort"
            ):
                still.append(entry)
                continue
            self.decode.resume_parked(entry["state"], slot)
            self._m_resume.inc()
        self._parked = still

    def _count_transfer(self, moved: int) -> None:
        self._stats["handoff_transfer_bytes"] += moved
        self._m_transfer.inc(moved)

    @staticmethod
    def _put(tree, target) -> tuple[Any, int]:
        """Move a pytree to ``target`` — a ``MeshEnv`` (replicated onto
        its partition) or a bare device — returning the tree and its
        byte count (the cross-partition handoff traffic, ONE site so
        meshed and unmeshed workers price transfers identically)."""
        moved = sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(tree)
        )
        if hasattr(target, "replicated"):
            target = target.replicated()
        return jax.device_put(tree, target), moved

    def _complete_handoff(self, pkg: _Package, slot: int) -> None:
        """Fetch the package (async prefill failures surface HERE and
        take the prefill-worker re-queue path), transfer its private
        blocks to the decode partition when the partitions are separate,
        and splice. The splice is the ONLY decode-partition work — a
        table re-own when the pool is shared."""
        req, res, spec = pkg.req, pkg.res, pkg.spec
        try:
            tok = int(jax.device_get(pkg.tok)[0])
        except Exception as e:
            self._worker_failed(
                req, res, spec, e, site="prefill_worker", rng=pkg.rng
            )
            return
        # Prefill wall = launch→completion (t_ready, stamped at the
        # readiness check); slot-wait in the ready list is queueing and
        # stays out of TTFT, per the engine's TTFT contract.
        prefill_s = (pkg.t_ready or time.perf_counter()) - pkg.t_launch
        t_h0 = time.perf_counter()
        try:
            faults.maybe_raise("serve.handoff", key=req.id)
            slot_cache = pkg.slot_cache
            sliced = False
            if self.prefill_worker.separate:
                # Transfer EXACTLY the private blocks that change owner
                # — the [m*bs, n_g*bs) capacity window (shared prefix
                # blocks already live in the decode partition's pool;
                # the bucket's zero tail carries nothing). The splice
                # then reads the window at m0=0.
                bs = self.decode.block_size
                n_g = blocks_for_tokens(int(req.prompt.size), bs)
                slot_cache, moved = self._put(
                    _capacity_slice(
                        slot_cache, pkg.m * bs, n_g * bs, pkg.s_c
                    ),
                    self.decode._env if self.decode._env is not None
                    else jax.devices()[0],
                )
                self._count_transfer(moved)
                sliced = True
            self.decode.admit_handoff(
                slot, req, res, slot_cache, tok,
                m=pkg.m, prefill_s=prefill_s, sliced=sliced,
            )
        except Exception as e:
            self._worker_failed(req, res, spec, e, site="handoff",
                                rng=pkg.rng)
            return
        dt = time.perf_counter() - t_h0
        self._m_handoff.observe(dt)
        self._m_handoffs.inc()
        self._stats["handoffs"] += 1
        self._retries.pop(req.id, None)
        self._m_t_ttft[spec.name].observe(prefill_s + dt)

    def _worker_failed(
        self, req: ServeRequest, res: dict, spec: TenantSpec,
        err: Exception, *, site: str, rng: Any = None,
    ) -> None:
        """The cross-worker never-hangs contract (ISSUE 9 extended):
        release the reservation, count, re-queue at the head of the
        tenant queue; past ``handoff_retries`` the request resolves as a
        typed ``"error"`` — a worker death can delay a request, never
        strand it."""
        self.decode._pool_release(res)
        counter = (
            self._m_pw_failures if site == "prefill_worker"
            else self._m_handoff_failures
        )
        counter.inc()
        self._stats[f"{site}_failures"] += 1
        from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

        n = self._retries.get(req.id, 0) + 1
        self._retries[req.id] = n
        if n > self.handoff_retries:
            get_logger().warning(
                "serving: %s failed for request %d (%s: %s) — retries "
                "exhausted (%d), resolving typed error",
                site, req.id, type(err).__name__, err, self.handoff_retries,
            )
            self._retries.pop(req.id, None)
            self._retry_rng.pop(req.id, None)
            self.decode._m_quarantined.inc()
            self.decode.stats["quarantined"] += 1
            self.decode._complete_unadmitted(req, "error")
            return
        get_logger().warning(
            "serving: %s failed for request %d (%s: %s) — re-queueing "
            "(attempt %d/%d)",
            site, req.id, type(err).__name__, err, n, self.handoff_retries,
        )
        self._stats[f"{site}_requeued"] += 1
        if rng is not None:
            # The retry reuses this attempt's split, so the request's
            # sampling stream — and every later request's — matches a
            # fault-free run (rng-neutral chaos, temperature>0 included).
            self._retry_rng[req.id] = rng
        self._queues[spec.name].appendleft(req)

    # ----------------------------------------------------------------- step

    def step(self) -> list[Completion]:
        """One scheduler tick: complete ready handoffs, resume parked
        requests, launch (at most ``prefill_max_per_tick``) prefills,
        then run ONE decode iteration. Returns completions, tenant-
        annotated, typed resolutions included."""
        self._poll_inflight()
        self._fill_slots()
        self._launch_prefills()
        self._poll_inflight()
        self._fill_slots()
        if (
            self._inflight
            and not self._ready
            and not self.decode._active.any()
        ):
            # Progress guarantee: nothing is decoding and everything
            # outstanding is an un-ready async prefill — block on the
            # oldest (the one wait colocated admission always pays).
            self._poll_inflight(block=True)
            self._fill_slots()
        out = self.decode.step()
        self.decode._m_queue.set(
            float(sum(len(q) for q in self._queues.values()))
        )
        for c in out:
            self._annotate(c)
        return out

    def run(self, max_steps: int | None = None) -> list[Completion]:
        """Drain everything; the engine ``run`` contract (every
        submitted id resolves exactly once, typed resolutions ride
        along)."""
        out: list[Completion] = []
        steps = 0
        while self.pending:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        tail = self.decode._drain_completed() + list(self.decode._early)
        self.decode._early.clear()
        for c in tail:
            self._annotate(c)
        out.extend(tail)
        return out

    def _annotate(self, c: Completion) -> None:
        """Tenant attribution + per-tenant SLO observations (TPOT as
        inter-token GAPS — the number a tenant actually experiences,
        inline prefill stalls included, unlike the program-time
        ``token_latencies_s``)."""
        name = self._tenant_of.pop(c.id, "")
        self._retries.pop(c.id, None)
        self._retry_rng.pop(c.id, None)
        c.tenant = name
        h = self._m_t_tpot.get(name)
        if h is not None and len(c.token_times_s) > 1:
            for gap in np.diff(np.asarray(c.token_times_s)):
                h.observe(float(gap))
