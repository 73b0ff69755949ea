"""Config schema: every knob the framework exposes, as typed dataclasses.

One ``ExperimentConfig`` fully describes a run — model, data, mesh,
parallelism strategy, precision, optimizer, checkpointing. The five
BASELINE.json reference recipes are instances of this schema
(config/recipes.py).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional


# --------------------------------------------------------------------------
# Mesh / parallelism
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshConfig:
    """Logical device-mesh shape (SURVEY C2).

    Axis sizes multiply to the device count; ``data = -1`` means "absorb all
    remaining devices". Axes of size 1 are still present in the mesh so
    PartitionSpecs can always name them — XLA drops trivial dimensions at
    compile time.

    The axis vocabulary is the whole parallelism story (SURVEY C4–C9):

    - ``data``:   DP — batch sharded, params replicated (or FSDP-sharded).
    - ``fsdp``:   parameter/optimizer sharding axis (FSDP/ZeRO). Kept
                  separate from ``data`` so DP×FSDP hybrids express naturally.
    - ``model``:  tensor parallelism (Megatron column/row splits).
    - ``seq``:    sequence/context parallelism (ring attention, Ulysses).
    - ``expert``: MoE expert parallelism.
    - ``pipe``:   pipeline stages.
    """

    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1
    # Number of DCN (cross-slice) segments along the data axis; 1 = single
    # slice. When >1, the mesh is built hybrid: data axis spans DCN, all other
    # axes stay inside the ICI slice.
    dcn_data: int = 1

    def axis_sizes(self) -> dict[str, int]:
        return {
            "data": self.data,
            "fsdp": self.fsdp,
            "model": self.model,
            "seq": self.seq,
            "expert": self.expert,
            "pipe": self.pipe,
        }


@dataclass(frozen=True)
class ParallelConfig:
    """How state is laid out over the mesh (SURVEY C4–C9).

    - ``param_sharding``: "replicated" (DDP) or "fsdp" (full shard over the
      fsdp axis — SimpleFSDP-style sharding annotations, no wrapper module).
    - ``opt_sharding``: "like_params" | "zero1" (shard optimizer state over
      the fsdp axis even when params are replicated — ZeRO-1).
    - ``sequence``: "none" | "ring" | "ulysses" — long-context attention mode.
    - ``fsdp_min_size``: leaves smaller than this stay replicated (sharding
      tiny params costs more collective latency than it saves memory).
    - ``fsdp_overlap``: opt-in overlap-scheduled FSDP (SimpleFSDP-style,
      arxiv 2411.00284): instead of leaving parameter gathering to GSPMD
      (which tends to materialize full params up front and serialize the
      collectives against compute), each transformer block / ResNet block
      explicitly ``all_gather``s its shard immediately before its compute
      and the backward ``reduce_scatter``s gradients straight back into
      shards (parallel/fsdp_overlap.py). Requires ``param_sharding="fsdp"``
      and a model family with blockwise apply hooks (gpt, resnet).
    - ``fsdp_prefetch``: how many blocks ahead a gather may be issued
      (default 1 — the SimpleFSDP "one block ahead" schedule). On the
      per-block Python loop (ResNet) the window is enforced structurally
      with optimization barriers; on the scanned transformer stack the
      rolled loop exposes exactly one block of lookahead to XLA's
      collective pipeliner, so values > 1 behave as 1 there.
    - ``tp_overlap``: opt-in latency-hiding tensor parallelism
      (parallel/tp_overlap.py, the collective-matmul schedule of the JAX
      pjit/TPUv4 scaling paper): the four per-block TP matmuls (QKV,
      attn-out, fc_in, fc_out — and the ViT/video equivalents) become
      bidirectional ``ppermute`` rings that hide the model-axis
      communication under their own block compute, with the residual
      stream sharded over the model axis between them, instead of GSPMD's
      monolithic per-layer allreduces. Requires ``mesh.model > 1`` and a
      model family with hooks (gpt, vit, video); composes with data/fsdp
      meshes and ``fsdp_overlap``, not with pipeline/sequence parallelism
      or MoE.
    - ``low_precision``: the low-precision fast path for the collective-
      matmul rings ("none" | "int8" | "fp8_e4m3" | "fp8_e5m2",
      ops/quantization.py): the four hooked TP matmuls run as scaled
      low-precision matmuls (per-tensor activation scales, per-channel
      weight scales, bf16/fp32 master weights, straight-through grads)
      and the rings ``ppermute`` the QUANTIZED chunks + scales — comm
      bytes on the model axis shrink with the element width (4x at fp32,
      2x at bf16), pinned by graft-lint's per-dtype collective census.
      Requires ``tp_overlap=true`` (the knob quantizes the rings; there
      is no GSPMD low-precision path to fall back to). Tolerances and
      when-to-use guidance: docs/perf_playbook.md "Low-precision fast
      path".
    - ``schedule``: the unified overlap-schedule declaration
      (parallel/schedule.py, ROADMAP item 2). "auto" (default) derives
      the per-axis gather/scatter schedule from the knobs above —
      ``fsdp_overlap``/``fsdp_prefetch`` become
      ``gather(fsdp,block,prefetch=N)+scatter(fsdp)``,
      ``tp_overlap``/``low_precision`` become
      ``gather(model,ring_chunk[,lowp=FMT])+scatter(model[,lowp=FMT])``
      — preserving their exact semantics. An explicit declaration string
      in that grammar replaces the derivation (and must agree with any
      legacy knob also set); contradictions raise a typed
      ``ScheduleError`` naming the schedule attribute at Trainer
      construction, never a shape error inside the scan body. Guidance:
      docs/perf_playbook.md "Declaring an overlap schedule".
    """

    param_sharding: str = "replicated"  # replicated | fsdp
    opt_sharding: str = "like_params"  # like_params | zero1
    sequence: str = "none"  # none | ring | ulysses
    fsdp_min_size: int = 1024
    fsdp_overlap: bool = False
    fsdp_prefetch: int = 1
    tp_overlap: bool = False
    low_precision: str = "none"  # none | int8 | fp8_e4m3 | fp8_e5m2
    # "auto" = derive from the knobs above; else an explicit declaration,
    # e.g. "gather(fsdp,block,prefetch=1)+scatter(fsdp)".
    schedule: str = "auto"


@dataclass(frozen=True)
class PrecisionConfig:
    """Mixed-precision policy (SURVEY C10).

    bf16 on TPU needs no loss scaling (8-bit exponent), so the reference's
    GradScaler has no equivalent here — ``bf16_mixed`` keeps fp32 master
    params with bf16 compute, matching "bf16 AMP" semantics.
    """

    policy: str = "bf16_mixed"  # fp32 | bf16 | bf16_mixed


# --------------------------------------------------------------------------
# Trainer / optimizer / checkpoint / data
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"  # adamw | sgd | adam | adafactor | lion | fused_adamw
    learning_rate: float = 1e-3
    warmup_steps: int = 0
    schedule: str = "constant"  # constant | cosine | linear | wsd
    weight_decay: float = 0.0
    b1: float = 0.9
    # None = the optimizer's own canonical default (0.999 for the adam
    # family, 0.99 for lion); an explicit value is always honored.
    b2: Optional[float] = None
    eps: float = 1e-8  # adam family only (adafactor keeps optax's 1e-30)
    momentum: float = 0.9  # sgd only
    grad_clip_norm: Optional[float] = None
    # "wsd" only: fraction of post-warmup steps spent in the final linear
    # decay (the rest holds the peak LR).
    wsd_decay_fraction: float = 0.2


@dataclass(frozen=True)
class TrainerConfig:
    total_steps: int = 1000
    grad_accum: int = 1
    remat: str = "none"  # none | full | dots
    log_every: int = 50
    eval_every: int = 0  # 0 = no eval during training
    eval_steps: int = 10
    seed: int = 0
    # Profiling (SURVEY C19): capture a jax.profiler trace for
    # [profile_start_step, profile_start_step + profile_steps). 0 = off.
    profile_steps: int = 0
    profile_start_step: int = 10
    # Exponential moving average of params, updated inside the compiled
    # step (ema = d*ema + (1-d)*params). 0 = off. When on, eval runs with
    # the EMA weights (the reason to keep them) and they ride the same
    # sharding specs + checkpoint as the live params.
    ema_decay: float = 0.0
    # Initialize model params from a flax-msgpack file (e.g. an imported
    # HF checkpoint from tools/import_hf_gpt2.py) instead of random init.
    # The tree structure/shapes must match the model exactly; params are
    # cast to the precision policy's param dtype and placed into the
    # run's shardings. Optimizer state still initializes fresh.
    init_params_path: str = ""
    # Write metric scalars to TensorBoard (<workdir>/<name>/tb) next to
    # the profiler traces. JSONL remains the record of truth; the sink is
    # lazy-TF and degrades to a warning if TF is unusable.
    tensorboard: bool = False
    # Stall watchdog deadline (ISSUE 7): a host thread fires when no step
    # completes dispatch within this many seconds — faulthandler
    # tracebacks + metric snapshot to <run_dir>/stall_dump.txt and a
    # stalls_total counter increment. 0 = off. Size it to several times
    # the slowest expected STEP; the first-beat grace below absorbs the
    # initial compile.
    stall_timeout_s: float = 0.0
    # First-beat deadline multiplier: beats only start flowing once
    # dispatch does, so the initial silence includes XLA compile time —
    # until the first beat lands the watchdog waits
    # stall_timeout_s * this. ~5x makes a steady-state-sized deadline
    # survive the step-0 compile (the false-fire docs/operations.md used
    # to warn about); 1.0 restores the old strict behavior.
    stall_timeout_first_beat_scale: float = 5.0
    # Host-side span tracing (ISSUE 8, telemetry/tracing.py): per-step
    # spans (step/load_batch/dispatch/checkpoint/eval) recorded around
    # the jitted calls, teed into telemetry.jsonl as timeline events and
    # exported as Chrome-trace-event JSON (<run_dir>/trace_events.json —
    # load in Perfetto next to the device traces profile_steps captures;
    # the span context managers wrap jax.profiler Trace/StepTrace
    # annotations so the two align). Ring-bounded host dicts: overhead
    # is microseconds/step, so it ships on.
    tracing: bool = True
    # Keep the optimizer state in host memory (``pinned_host``): XLA
    # streams it through HBM around the update. A CAPACITY knob, not a
    # speed knob — it pays PCIe traffic every optimizer step to free
    # state-sized HBM (e.g. GPT-2-medium's ~4.3G AdamW fp32 state).
    # TPU-only: the CPU sim backend cannot partition host-memory arrays
    # (the Trainer refuses with a clear error).
    offload_opt_state: bool = False
    # Graceful preemption (SIGTERM → finish the in-flight step → save a
    # synchronized checkpoint → exit rc 0): whether the preemption path
    # SAVES before exiting. Off only for runs whose checkpoints are
    # managed externally (the clean exit itself always happens — a
    # preempted child must never die mid-collective).
    preempt_save: bool = True


@dataclass(frozen=True)
class CheckpointConfig:
    enabled: bool = False
    save_every: int = 1000
    max_to_keep: int = 3
    async_save: bool = True
    resume: bool = True  # restore latest checkpoint if present
    # Restore through the redistribution service (ISSUE 15): each leaf
    # is read at a memory-efficient EVEN layout (every device reads
    # ~1/N — never a replicated staging copy, even for leaves whose
    # target is replication) and then redistributed on-device to the
    # trainer's target shardings by redistribute/'s plan executor. The
    # elastic supervisor's reform path forces this on (a reformed mesh
    # is exactly the saved-on-any-mesh/restored-on-any-other case);
    # default off so unchanged-topology resumes keep the direct Orbax
    # path bit-for-bit.
    restore_redistribute: bool = False
    # Scratch budget for the redistribution's bounded chunking, MiB.
    # 0 = auto (one destination shard + one chunk per leaf — the plan
    # compiler's own ceiling).
    redistribute_scratch_mb: int = 0


@dataclass(frozen=True)
class ServingConfig:
    """Serving-tier failure semantics (ISSUE 9, docs/operations.md
    "Failure semantics"). These are the graceful-degradation knobs the
    continuous-batching engine (serving/engine.py) takes at construction;
    tools/serve_bench.py --chaos exercises them end-to-end."""

    # Bounded admission: submits beyond this many queued (not yet
    # admitted) requests are LOAD-SHED — the caller gets a typed
    # completion (finish_reason="shed") immediately instead of unbounded
    # queue growth eating host memory and blowing every SLO at once.
    # 0 = unbounded (the pre-ISSUE-9 behavior).
    max_queue_depth: int = 0
    # Per-request deadline, seconds from submit: a request still queued
    # past its deadline sheds at admission; one mid-decode is CANCELLED —
    # retired with finish_reason="deadline" and the tokens generated so
    # far, freeing the slot for refill. submit(deadline_s=...) overrides
    # per request. 0 = no deadline.
    default_deadline_s: float = 0.0
    # Paged KV cache (ISSUE 10): > 0 stores K/V in a shared pool of
    # fixed-size blocks (power of two) with per-slot block tables —
    # slots stop reserving power-of-two cache buckets, growth appends a
    # block instead of cloning the cache, and HBM is priced per BLOCK.
    # 0 = the bucketed contiguous cache (pre-ISSUE-10 behavior).
    kv_block_size: int = 0
    # Pool size in blocks (block 0 is the reserved trash block retired
    # slots write into). 0 = auto: num_slots x ceil(seq_len/block) + 1,
    # the never-blocks-admission worst case — size it DOWN deliberately
    # to multiply concurrency (admission then waits on pool headroom,
    # composing with max_queue_depth's shed bound; docs/operations.md).
    kv_pool_blocks: int = 0
    # Refcounted shared-prefix caching over full pool blocks: a prompt
    # whose leading blocks match an earlier prompt's reuses them
    # (prefill runs only on the suffix); the first divergent or partial
    # block is copy-on-write private, so shared blocks are immutable.
    prefix_cache: bool = True
    # Speculative decoding on the paged engine (ISSUE 11). "ngram" =
    # tier-A self-speculation: drafts come from prompt-lookup over the
    # slot's own token history (no second model — wins on repetitive /
    # structured text); "draft" = tier-B small draft GPT sharing the
    # tokenizer (pass draft_model/draft_params to the engine). Greedy
    # decode only (acceptance is exact argmax matching, so speculative
    # output is TOKEN-IDENTICAL to generate() — a pure-perf knob);
    # requires the paged cache (kv_block_size > 0): accept/rollback is
    # block-table pointer bookkeeping there, never cache surgery.
    # "off" = plain single-token decode.
    speculate: str = "off"
    # Draft tokens proposed per verify step: the target model scores
    # k+1 positions in ONE batched forward, amortizing the pool read.
    # The verify program compiles ONCE at this k (no per-k ladder);
    # slots with fewer (or zero) drafts ride the same program.
    speculate_k: int = 4
    # Disaggregated prefill/decode serving (ISSUE 12,
    # serving/scheduler.py): True routes serving through the
    # prefill-worker / decode-worker split coordinated by the
    # multi-tenant SLO scheduler (serving.build_engine dispatches on
    # this). Requires the paged cache (kv_block_size > 0): the
    # prefill→decode handoff is a block-table splice there, never a
    # cache copy.
    disaggregate: bool = False
    # Prefill admissions the scheduler starts per decode tick: the
    # decoupled-admission bound that keeps a prefill burst from starving
    # running decodes — queued prefills DEFER (the burst queues up)
    # instead of running inline ahead of the next decode step the way
    # colocated admission does. 1 is the tail-isolation setting;
    # raising it trades decode TPOT tails for admission throughput.
    prefill_max_per_tick: int = 1
    # Prefill-worker / handoff failures re-queue the request and retry
    # up to this many times before the request resolves as a typed
    # "error" (never hangs — the ISSUE-9 contract across the worker
    # boundary).
    handoff_retries: int = 2


@dataclass(frozen=True)
class ElasticConfig:
    """Checkpoint-restart elasticity (SURVEY C14): the supervisor restarts a
    dead child up to ``max_restarts`` times with exponential backoff."""

    max_restarts: int = 3
    backoff_s: float = 1.0
    # Backoff cap for the restart loop (the supervisor's retry budget is
    # the faults/retry.py RetryPolicy: backoff_s * 2^(n-1), capped here,
    # budgeted by max_restarts) — exponential backoff must not park a
    # crash-looping host for hours.
    max_backoff_s: float = 300.0
    # Membership heartbeat writes that fail (shared-FS outage) are
    # counted (heartbeat_write_failures_total) and retried each
    # interval; after this many CONSECUTIVE failures the supervisor
    # retires its membership record (unlinks it) so peers evict this
    # host deterministically instead of racing the mtime staleness
    # window. 0 = retry forever (the pre-ISSUE-9 behavior).
    heartbeat_retire_after: int = 10
    # A child that survives this long before dying counts as real progress:
    # the restart budget and backoff reset (torchrun-elastic-agent semantics),
    # so a week-long run isn't killed by its 4th once-a-day preemption.
    reset_after_s: float = 600.0
    # Smaller-slice continuation (SURVEY C14 "re-initialize (possibly
    # smaller slice)"): after this many consecutive failed restarts, the
    # supervisor consults the shared-workdir membership heartbeats; peers
    # stale for more than ``peer_timeout_s`` are declared dead, and the
    # child is re-launched over the surviving hosts only (ranks remapped,
    # coordinator re-elected to the lowest surviving host, Orbax restores
    # with resharding). 0 = never shrink — a missing host blocks until the
    # restart budget runs out, the round-2/3 behavior.
    shrink_after: int = 0
    peer_timeout_s: float = 60.0
    # Grow-back after a shrink: when a previously-dead host resumes
    # heartbeating (repaired, or a false-positive eviction), the supervisor
    # preempts the child (SIGTERM -> checkpoint -> clean exit) and
    # re-forms at the larger world — ranks remapped by uid, Orbax
    # resharding restore, no steps lost. false = shrink-only (a wrongly
    # evicted host then needs operator action, the round-4 behavior).
    grow: bool = True


@dataclass(frozen=True)
class DataConfig:
    """Input pipeline selection (SURVEY C16). ``global_batch_size`` is the
    whole-run batch; the pipeline shards it per host and the mesh shards it
    per chip."""

    name: str = "synthetic_mnist"
    global_batch_size: int = 128
    image_size: int = 28
    num_classes: int = 10
    channels: int = 1
    seq_len: int = 1024
    vocab_size: int = 50257
    num_frames: int = 8
    shuffle_seed: int = 0
    # For real datasets: directory to look in; synthetic fallback if absent.
    data_dir: Optional[str] = None
    # Batches built ahead on a background thread (0 = synchronous).
    prefetch: int = 2
    # Online ingestion (data/streaming.py): treat data_dir as APPEND-ONLY
    # GROWABLE — re-scan every `streaming_refresh_every` steps for newly
    # sealed shard pairs and widen the sampling window (hosts agree on
    # the window via the host-tier collective). Determinism contract in
    # the module docstring. false = the corpus freezes at construction.
    streaming: bool = False
    streaming_refresh_every: int = 256
    # Host-side batch-build failures (decode error, transient shared-FS
    # read) are retried under the unified faults/retry.py policy — the
    # batch is a pure function of step, so a rebuild is safe. After the
    # budget the original exception propagates (a permanently bad shard
    # must kill the run loudly, not spin).
    loader_max_retries: int = 2
    loader_retry_backoff_s: float = 0.05


# --------------------------------------------------------------------------
# Model families (SURVEY C15)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MLPConfig:
    family: str = "mlp"
    hidden_sizes: tuple[int, ...] = (512, 256)
    num_classes: int = 10
    dropout: float = 0.0


@dataclass(frozen=True)
class ResNetConfig:
    family: str = "resnet"
    depth: int = 50  # 18 | 34 | 50 | 101 | 152
    num_classes: int = 1000
    width_multiplier: int = 1
    # "conv7" = torchvision 7x7/s2 stem; "s2d" = the mathematically exact
    # space-to-depth rewrite (MXU-friendly; see models/resnet.py).
    stem: str = "conv7"
    # Stem max-pool backward: "scatter" = XLA select_and_scatter (the
    # autodiff default; first-max-wins on ties, and the faster path on
    # v5e — "mask" measured ~8% slower end-to-end, see BASELINE.md
    # "measured and rejected"); "mask" = custom-VJP compare-and-sum pass
    # whose tie semantics split the gradient equally across tied maxima
    # (models/resnet.py::_max_pool_mask_grad).
    pool_grad: str = "scatter"
    # Fused BatchNorm-backward Pallas kernel (ops/fused_bn.py): identical
    # forward, train-mode backward replaced by the two-pass reduction+dx
    # kernel chain attacking the measured ~150 ms/step of HBM-bound
    # BN-backward traffic (docs/perf_playbook.md roofline). Ships off by
    # default until tools/perf_sweep.py rn50_fused_bn measures the win
    # on-chip (the fused_adamw honesty contract).
    fused_bn: bool = False


@dataclass(frozen=True)
class ViTConfig:
    family: str = "vit"
    image_size: int = 224
    patch_size: int = 16
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    num_classes: int = 1000
    dropout: float = 0.0
    pool: str = "cls"  # cls | mean


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block config (SURVEY C9). ``num_experts = 0``
    disables MoE.

    Two ROUTINGS, which are two models and not two implementations of one
    (``routing``): ``capacity`` is GShard top-k with a per-group capacity
    and DROPS, softmax scores and the auxiliary losses — what every
    training recipe of this repo uses (``gpt2_moe*``); ``dropless`` is
    top-k with no capacity, so no token is ever dropped, sigmoid or softmax
    scores, an optional shared expert and a scaling of the routed sum —
    what the published sparse decoders state and what serving runs
    (models/moe.py computes it grouped by expert). ``dispatch`` below is
    NOT that choice: it picks between two formulations of the ``capacity``
    routing's token exchange with identical semantics (ROADMAP C6)."""

    num_experts: int = 0
    top_k: int = 2
    routing: str = "capacity"  # capacity | dropless
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01
    # ST-MoE router z-loss coefficient (mean log²-sum-exp of router
    # logits); 0 disables.
    router_z_loss: float = 1e-3
    # Routing groups (GShard GSEC layout): dispatch/combine memory scales
    # with 1/G and capacity is enforced per group. 0 = auto (the mesh's
    # batch-shard count, so each data shard routes its own tokens).
    num_groups: int = 0
    # Token->expert exchange formulation of the ``capacity`` routing,
    # identical routing/drop semantics (seating comes from the same
    # slot-major cumsum either way):
    #   einsum — one-hot [G,S,E,C] dispatch/combine einsums (GShard); the
    #            exchange is MACs against mostly-zero one-hots, costing
    #            O(S*E*C*D) — comparable to the expert FFN itself at
    #            audited shapes (docs/perf_playbook.md).
    #   sort   — scatter/gather (ragged) exchange: seat indices are
    #            scattered into the [E*C] slot table and tokens gathered
    #            by index; ~zero exchange MACs.
    dispatch: str = "einsum"  # einsum | sort
    # What a published sparse decoder's config.json states (``dropless``
    # routing reads these; ``capacity`` keeps GPT-2's 4x GELU experts):
    # the width of a routed expert's gated feed-forward
    # (``moe_intermediate_size``), the shared experts every token passes
    # through and their width, the router's score function, whether the
    # chosen scores are normalised to sum to one (``norm_topk_prob``), and
    # the factor on the routed sum (``moe_routed_scaling_factor``).
    expert_dim: int = 0
    num_shared_experts: int = 0
    shared_expert_dim: int = 0
    score_func: str = "softmax"  # softmax | sigmoid
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0


@dataclass(frozen=True)
class RopeConfig:
    """Rotary position parameters of ONE layer type, as a published
    ``rope_parameters`` entry states them. ``rope_type`` ``default`` is
    plain rotary; ``yarn`` (Peng et al. 2023, arXiv:2309.00071) blends
    interpolated and extrapolated frequencies between the ``beta_fast``
    and ``beta_slow`` rotation counts over ``original_max_position_embeddings``
    and scales cos and sin by ``attention_factor`` (0 = yarn's own
    ``0.1 ln(factor) + 1``). ``partial_rotary_factor``: the leading share
    of a head's dimensions that rotates."""

    rope_type: str = "default"  # default | yarn
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0


@dataclass(frozen=True)
class GPTConfig:
    family: str = "gpt"
    vocab_size: int = 50257
    num_layers: int = 24
    num_heads: int = 16
    hidden_dim: int = 1024
    seq_len: int = 1024
    mlp_ratio: int = 4
    dropout: float = 0.0
    # GPT-2's LayerNorm epsilon (flax's default is 1e-6; HF checkpoints
    # are trained with 1e-5 — keeping it makes HF imports numerically
    # exact, see tools/import_hf_gpt2.py).
    layer_norm_epsilon: float = 1e-5
    # Attention implementation: "dense" | "ring" | "ulysses" | "flash"
    attention: str = "dense"
    # KV-cache decode attention: "flash" routes single-token steps through
    # the fused split-KV Pallas kernel (ops/decode_attention.py; on
    # non-TPU backends it silently takes the identical-numerics dense
    # path, same contract as attention="flash"), "dense" forces the
    # masked-dense reference. Orthogonal to ``attention`` — the training
    # kernels are pointless at one-token query shapes.
    decode_attention: str = "flash"
    # Quantized KV cache ("none" | "int8" | "fp8_e4m3"): decode stores
    # K/V in the 1-byte format with per-(row, position, head) bf16 scales
    # carried alongside (each written token quantizes once, over its own
    # head vector, and is never re-quantized) — cache HBM per slot drops
    # ~2x vs bf16 at matched decode tolerance, which is what caps
    # servable concurrent slots (serving/engine.py accounting,
    # tools/serve_bench.py int8 arms). The flash-decode kernel
    # dequantizes per split-KV chunk in VMEM; the dense fallback
    # dequantizes in bounded chunks — no full-precision full-context
    # tensor materializes in a decode step (graft-lint pinned).
    kv_cache_quant: str = "none"
    # Chunked-vocab LM loss: compute the weight-tied head + cross-entropy
    # in sequence chunks of this many tokens (rematerialized in backward),
    # so the [B, T, vocab] logits tensor never materializes — for
    # GPT-2-medium at T=1024 that is ~400 MB of bf16 logits (plus their
    # cotangents) traded for a scan. 0 = off (dense head). If the sequence
    # length is not divisible by the chunk, the loss warns and falls back
    # to the dense head (the knob is a memory optimization, not a
    # correctness switch).
    lm_loss_chunk: int = 0
    moe: MoEConfig = field(default_factory=MoEConfig)
    # ---- The architecture, as a published config.json describes it. The
    # defaults are GPT-2's (LayerNorm, learned positions, full multi-head
    # attention with biases, 4x GELU, tied head): its parameter tree and
    # programs are what they were before these fields existed.
    norm: str = "layernorm"  # layernorm | rmsnorm (eps: layer_norm_epsilon)
    position: str = "learned"  # learned (wpe) | rope
    # Rotary parameters by layer type: ``rope`` for full-attention layers,
    # ``rope_sliding`` for sliding-window ones.
    rope: RopeConfig = field(default_factory=RopeConfig)
    rope_sliding: RopeConfig = field(default_factory=RopeConfig)
    bias: bool = True  # biases on the attention and feed-forward maps
    # Grouped KV heads and a head size apart from hidden / heads (0 = as
    # GPT-2: num_heads KV heads of hidden_dim // num_heads).
    num_kv_heads: int = 0
    head_dim: int = 0
    # One entry a layer, ``full_attention`` or ``sliding_attention``
    # (empty: every layer full). A sliding layer's position p attends to
    # positions p - sliding_window < j <= p and has ``num_heads_sliding``
    # query heads (0 = num_heads). A model that states ``layer_types`` has
    # layers of unlike shape: its stack is a loop over layers kept apart
    # (``layer_<i>`` subtrees), not the one scanned ``blocks`` stack.
    layer_types: tuple[str, ...] = ()
    num_heads_sliding: int = 0
    sliding_window: int = 0
    # Per-head sigmoid gate on the attention output, a linear map of the
    # layer's normed input, applied before the out projection.
    attention_gate: bool = False
    mlp: str = "gelu"  # gelu (fc_in, fc_out) | swiglu (gated, silu)
    mlp_dim: int = 0  # dense feed-forward width (0 = hidden_dim * mlp_ratio)
    # With experts: the layers that keep the dense feed-forward.
    dense_layers: tuple[int, ...] = ()
    tie_embeddings: bool = True  # False: an output head of its own (lm_head)
    # Pipeline parallelism (SURVEY C7): >1 stages the block stack over the
    # ``pipe`` mesh axis. ``pipeline_microbatches`` = 0 means "same as
    # stages" (the minimum that keeps every stage busy outside the bubble).
    pipeline_stages: int = 1
    pipeline_microbatches: int = 0
    # Pipeline backend (ISSUE 14):
    #   "spmd" — the stage-vmap GPipe schedule (parallel/pipeline.py): the
    #            whole timeline is ONE compiled GSPMD program; all M
    #            microbatch activations stay live across the tick scan.
    #   "mpmd" — per-stage programs (parallel/mpmd_pipeline.py, the MPMD
    #            pipeline-parallelism formulation of arXiv 2412.14374):
    #            each stage is its own jitted program on its pipe-slice
    #            submesh with stage-local params/optimizer shards (no
    #            leading [S, ...] vmap dim), driven by a host-side 1F1B
    #            scheduler with EXPLICIT inter-stage activation/gradient
    #            transfers — steady-state holds only min(S, M) in-flight
    #            microbatch activations instead of M, there is no
    #            vmap(spmd_axis_name) lowering (so sequence-parallel
    #            ring/ulysses attention composes — BACKLOG R8-2), and the
    #            per-stage-program shape is the multi-slice scale-out
    #            substrate. ``pipeline_stages``/``pipeline_microbatches``
    #            keep their meaning (``effective_microbatches`` is still
    #            the one resolution rule); grad accumulation folds into
    #            the same 1F1B run as additional microbatches.
    pipeline_impl: str = "spmd"  # spmd | mpmd
    # Circular (interleaved) schedule: each physical stage holds this many
    # non-adjacent layer groups ("virtual stages"), cutting the GPipe bubble
    # from (S-1)/(M+S-1) to (S-1)/(repeat*M + S-1) at the price of rotating
    # activations through the stages ``repeat`` times. 1 = plain GPipe.
    pipeline_circular_repeat: int = 1
    # Stage-granular rematerialization — 1F1B's activation residency in the
    # one-program GSPMD schedule: the backward saves only per-tick stage
    # BOUNDARY activations and recomputes stage internals (one extra stage
    # forward each, the usual remat trade). Finer-grained than
    # trainer.remat=full (which recomputes the whole pipeline timeline
    # inside the backward); composes with either schedule above.
    pipeline_stage_remat: bool = False
    # Per-block (per-layer) rematerialization on the nn.scan stack — the
    # selective policy tier between trainer.remat=dots (saves every matmul
    # output: O(L·B·T·D·(9+mlp_ratio)) residuals) and trainer.remat=full
    # (whole-loss checkpoint: low forward residency but the backward's
    # recompute materializes the full scan residual set at once). Each
    # scanned Block is checkpointed individually, so the backward holds the
    # L carry boundaries [B,T,D] plus ONE block's internals at a time:
    #   "full"      — save only the scan carry per layer (max memory cut,
    #                 one extra block-forward per layer of recompute);
    #   "save_attn" — additionally save each block's attention-sublayer
    #                 output ([B,T,D]/layer, checkpoint_name-tagged), so
    #                 the recompute pass skips re-running attention — the
    #                 quadratic part of the block — for ~2x the (tiny)
    #                 boundary residuals;
    #   "none"      — off.
    # Residual accounting across these modes: tools/pp_memory_audit.py
    # --flagship. Ignored by the pipeline path (pipeline_stage_remat is
    # that path's equivalent) and by decode.
    block_remat: str = "none"


@dataclass(frozen=True)
class VideoConfig:
    """Video-clip classifier (BASELINE config 5): ViT over tubelet embeddings
    of a frame stack — the TPU-native stand-in for the Ego4D recipe."""

    family: str = "video"
    image_size: int = 224
    num_frames: int = 8
    tubelet_size: tuple[int, int, int] = (2, 16, 16)  # (t, h, w)
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    num_classes: int = 400
    dropout: float = 0.0


# --------------------------------------------------------------------------
# Top-level experiment
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ExperimentConfig:
    name: str = "experiment"
    model: Any = field(default_factory=MLPConfig)
    data: DataConfig = field(default_factory=DataConfig)
    trainer: TrainerConfig = field(default_factory=TrainerConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)
    workdir: str = "/tmp/frl_tpu_runs"

    def replace(self, **kw) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)
