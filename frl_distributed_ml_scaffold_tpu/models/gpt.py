"""GPT-2 transformer LM (BASELINE config 4: GPT-2-medium, ZeRO-1 + accum).

The flagship model and the carrier for every task-required parallelism
(SURVEY C6–C9):

- **TP**: q/k/v/fc_in kernels column-split, out/fc_out row-split over the
  ``model`` axis — Megatron layout, expressed purely as ``gpt_tp_rules()``
  regex → PartitionSpec (the model code itself is strategy-free; GSPMD
  inserts the per-layer allreduces).
- **SP**: ``attention="ring"`` routes through the ring-attention op
  (ops/ring_attention.py) for sequence-sharded long context;
  ``"ulysses"`` does the all_to_all head↔seq reshard around dense attention.
- **EP**: ``moe.num_experts > 0`` swaps the MLP for the expert-parallel MoE
  block (models/moe.py).

TPU-first details: layers stacked with ``nn.scan`` (one compiled block body
regardless of depth — compile time stays flat at 24 layers), softmax and
LayerNorm in fp32, everything else in the policy compute dtype (bf16 on the
MXU), weight-tied LM head.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.config.schema import GPTConfig
from frl_distributed_ml_scaffold_tpu.parallel.partition import PartitionRules
from frl_distributed_ml_scaffold_tpu.precision import Policy


def gpt_tp_rules(pipelined: bool = False, circular: bool = False) -> PartitionRules:
    """Megatron column/row sharding (SURVEY C6). Kernels carry a leading
    layer dim from nn.scan stacking, hence the extra ``None``; under
    pipeline parallelism they carry ``[stage, layer_in_stage, ...]`` and the
    stage dim shards over ``pipe`` (SURVEY C7). The circular schedule adds a
    leading virtual-repeat dim: ``[repeat, stage, layer_in_group, ...]``."""
    if circular:
        pre: tuple = (None, "pipe", None)
    elif pipelined:
        pre = ("pipe", None)
    else:
        pre = (None,)
    rules: tuple = (
        (r"blocks/attn/(query|key|value)/kernel", P(*pre, None, "model")),
        (r"blocks/attn/(query|key|value)/bias", P(*pre, "model")),
        (r"blocks/attn/out/kernel", P(*pre, "model", None)),
        (r"blocks/mlp/fc_in/kernel", P(*pre, None, "model")),
        (r"blocks/mlp/fc_in/bias", P(*pre, "model")),
        (r"blocks/mlp/fc_out/kernel", P(*pre, "model", None)),
        (r"blocks/moe/wi", P(*pre, "expert", None, "model")),
        (r"blocks/moe/wo", P(*pre, "expert", "model", None)),
        (r"blocks/moe/router/kernel", P(*pre, None, None)),
        (r"wte/embedding", P("model", None)),
    )
    if circular:
        # Everything else inside the stacked blocks (LayerNorm scales etc.)
        # still lives on its stage. Placed last — first match wins.
        rules = rules + ((r"blocks/", P(None, "pipe")),)
    elif pipelined:
        rules = rules + ((r"blocks/", P("pipe")),)
    return PartitionRules(rules=rules)


def _train_block_stack(cfg: GPTConfig, *, length: int, hooks=None):
    """The scanned TRAINING-mode Block stack: blockwise param-gather hook
    (``nn.map_variables``) + per-block remat wrap + ``nn.scan``, shared by
    the monolithic ``GPT`` and the per-stage ``GptStage`` (MPMD pipeline,
    ISSUE 14) so the two paths cannot drift. Returns the transformed
    CLASS; the caller instantiates it with ``name="blocks"`` (decode
    builds its own plain scan — caches/hooks never mix)."""
    block_cls = Block
    if hooks is not None:
        # Gather INSIDE the scan body (one layer's slice per iteration —
        # the blockwise schedule) and inside the remat region below (so
        # recompute re-gathers instead of saving full params).
        # map_variables(init=False): param creation still sees the raw
        # sharded tree, keeping init and checkpoint layouts identical to
        # the unhooked model.
        block_cls = nn.map_variables(
            block_cls,
            "params",
            trans_in_fn=hooks.block_hook,
            init=False,
        )
    if cfg.block_remat != "none" or hooks is not None:
        # Per-layer remat (config 3's activation checkpointing at the
        # granularity that matters under nn.scan): checkpoint each
        # scanned body so the backward re-derives one block's internals
        # at a time instead of holding all L layers'. prevent_cse=False
        # is the documented setting under scan — the scan boundary
        # already stops the CSE that remat's default guards against, and
        # leaving it True blocks XLA optimizations for nothing.
        if hooks is not None:
            # Same three modes, with gathered params always excluded
            # from the saved set (GATHER_NAME tag).
            from frl_distributed_ml_scaffold_tpu.parallel.fsdp_overlap import (
                overlap_remat_policy,
            )

            policy = overlap_remat_policy(cfg.block_remat)
        elif cfg.block_remat == "full":
            policy = None
        elif cfg.block_remat == "save_attn":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out"
            )
        else:
            raise KeyError(
                f"unknown model.block_remat={cfg.block_remat!r} "
                "(none | full | save_attn)"
            )
        block_cls = nn.remat(block_cls, prevent_cse=False, policy=policy)
    return nn.scan(
        block_cls,
        length=length,
        variable_axes={"params": 0, "cache": 0},
        split_rngs={"params": True, "dropout": True},
    )


def mpmd_stage_params(cfg: GPTConfig, params, num_stages: int):
    """Slice a PLAIN-layout GPT params tree into per-stage trees for the
    MPMD pipeline backend (ISSUE 14): ``{"stage_j": ...}`` where stage
    ``j`` owns ``blocks`` leaves ``[L/S, ...]`` (rows ``[j*L/S,
    (j+1)*L/S)`` of the plain ``[L, ...]`` stack — a pure slice, no
    transpose), the FIRST stage additionally owns the embedding tables
    (``wte``/``wpe`` — and with them the weight-tied LM head's master
    copy), and the LAST stage owns ``ln_f``. Inverse:
    ``mpmd_merge_params``; ``unstack_pipeline_params`` accepts either
    stacked layout so decode/export paths need no config surgery."""
    if "blocks" not in params:
        raise ValueError(
            "mpmd_stage_params expects the PLAIN-layout params tree "
            "(blocks leaves [L, ...]); restack pipeline-trained params "
            "via unstack_pipeline_params first"
        )
    L, s = cfg.num_layers, num_stages
    if s < 2:
        raise ValueError(f"MPMD stage slicing needs >= 2 stages, got {s}")
    if L % s:
        raise ValueError(f"{L} layers not divisible by {s} stages")
    lps = L // s
    head_keys = {"ln_f"}
    out = {}
    for j in range(s):
        tree = {
            "blocks": jax.tree.map(
                lambda l, _j=j: l[_j * lps : (_j + 1) * lps],
                params["blocks"],
            )
        }
        if j == 0:
            # Everything outside the block stack that is not the final
            # norm feeds the input side (wte/wpe today; future input-side
            # params land here by default).
            for k, v in params.items():
                if k not in ("blocks", *head_keys):
                    tree[k] = v
        if j == s - 1:
            for k in head_keys:
                if k in params:
                    tree[k] = params[k]
        out[f"stage_{j}"] = tree
    return out


def mpmd_merge_params(cfg: GPTConfig, stage_params):
    """Merge MPMD per-stage trees (``mpmd_stage_params`` layout) back to
    the plain-stack params tree — blocks leaves concatenate along the
    layer dim in stage order; wte/wpe come from stage 0, ln_f from the
    last stage."""
    stages = sorted(
        (k for k in stage_params if k.startswith("stage_")),
        key=lambda k: int(k.split("_", 1)[1]),
    )
    if len(stages) < 2 or stages != [f"stage_{j}" for j in range(len(stages))]:
        raise ValueError(
            f"not an MPMD stage-params tree (keys: {sorted(stage_params)})"
        )
    out = {}
    for k, v in stage_params[stages[0]].items():
        if k != "blocks":
            out[k] = v
    for k, v in stage_params[stages[-1]].items():
        if k != "blocks":
            out[k] = v
    out["blocks"] = jax.tree.map(
        lambda *ls: jnp.concatenate(ls, axis=0),
        *[stage_params[k]["blocks"] for k in stages],
    )
    return out


class GptStage(nn.Module):
    """One MPMD pipeline stage as a standalone per-stage program body
    (ISSUE 14): a contiguous run of ``num_layers`` Blocks, with the
    embedding front (``wte``/``wpe`` + dropout) on the FIRST stage and
    the final ``ln_f`` on the LAST. Param names match the monolithic
    ``GPT`` exactly, so per-stage trees are pure slices of the plain
    stack (``mpmd_stage_params``) and checkpoints restack losslessly.

    The weight-tied LM head is deliberately NOT applied here: the last
    stage returns ``ln_f``'d FEATURES, and the loss program receives the
    first stage's embedding table as an explicit cross-stage input — the
    tied-embedding transfer every MPMD system carries (its gradient
    rides the reverse transfer back to stage 0's master copy).

    ``param_hooks``/``tp_overlap`` take the same overlap-schedule hooks
    as ``GPT`` (parallel/schedule.py ``hooked_model`` clones either
    attribute): the fsdp block gathers and TP rings lower INSIDE the
    stage program, where they compose exactly as in the monolithic scan
    body — per-stage programs have no stage vmap for them to collide
    with."""

    config: GPTConfig
    policy: Policy
    num_layers: int
    first: bool = False
    last: bool = False
    param_hooks: Any = None
    tp_overlap: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False):
        cfg = self.config
        dtype = self.policy.compute_dtype
        if self.first:
            # Same modules, names, initializers, and dtype flow as GPT's
            # embedding front — stage 0's subtree IS the plain tree's.
            wte = nn.Embed(
                cfg.vocab_size,
                cfg.hidden_dim,
                dtype=dtype,
                embedding_init=nn.initializers.normal(stddev=0.02),
                name="wte",
            )
            wpe = self.param(
                "wpe",
                nn.initializers.normal(stddev=0.02),
                (cfg.seq_len, cfg.hidden_dim),
            )
            t = x.shape[1]
            x = wte(x) + wpe[:t].astype(dtype)
            x = nn.Dropout(cfg.dropout, deterministic=not train)(x)
        stack_cls = _train_block_stack(
            cfg, length=self.num_layers, hooks=self.param_hooks
        )
        blocks = stack_cls(
            cfg, dtype, train, False, self.tp_overlap, 0, 0, 0,
            name="blocks",
        )
        (x, _aux), _ = blocks((x, jnp.zeros((), jnp.float32)), None)
        if self.last:
            x = nn.LayerNorm(
                dtype=jnp.float32, epsilon=cfg.layer_norm_epsilon,
                name="ln_f",
            )(x)
        return x


def unstack_pipeline_params(cfg: GPTConfig, params):
    """Restack pipeline-trained block params into the plain-stack layout.

    Pipeline training stores block weights stage-stacked — GPipe as
    ``pipeline/ticks/blocks`` leaves ``[S, L/S, ...]``, circular as
    ``pipeline/blocks`` leaves ``[v, S, L/(S*v), ...]`` — while the decode
    path's ``nn.scan`` stack expects ``blocks`` leaves ``[L, ...]``. Both
    stacked layouts enumerate layers in row-major order of their leading
    dims (stage j holds contiguous layers; circular virtual stage
    ``r*S + j`` is row ``[r, j]``), so the restack is a pure reshape per
    leaf — no transpose, no new compute path. Returns a params tree a
    ``pipeline_stages=1`` model of the same config applies directly.
    """
    if "pipeline" not in params:
        if "stage_0" in params:
            # MPMD per-stage layout (ISSUE 14): merge, don't reshape —
            # stage trees are plain-stack slices by construction.
            return mpmd_merge_params(cfg, params)
        raise ValueError(
            "params carry no 'pipeline' subtree — already plain-stacked?"
        )
    pipe = params["pipeline"]
    # GPipe nests under the scanned tick module; circular owns the stacked
    # pytree directly.
    blocks = pipe["ticks"]["blocks"] if "ticks" in pipe else pipe["blocks"]
    lead = 2 if "ticks" in pipe else 3
    L = cfg.num_layers

    def restack(leaf):
        import numpy as np

        if int(np.prod(leaf.shape[:lead])) != L:
            raise ValueError(
                f"stacked leaf {leaf.shape} does not fold into "
                f"{L} layers ({lead} leading dims)"
            )
        return leaf.reshape((L,) + leaf.shape[lead:])

    out = {k: v for k, v in params.items() if k != "pipeline"}
    out["blocks"] = jax.tree.map(restack, blocks)
    return out


def _masked_dense_attention(q, k, v, mask):
    """Dense attention with an explicit mask ([Tq, Tk] shared or
    [B, Tq, Tk] per-row), fp32 softmax — the same numerics as
    ops.dense_attention, used by the KV-cache decode path where causality
    is against *absolute* positions in the cache, not positions within the
    query window. The per-row form carries ragged-prompt occupancy."""
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    mask = mask[:, None] if mask.ndim == 3 else mask[None, None]
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd",
        probs.astype(q.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


# ------------------------------------------------- layers of unlike shape
#
# A config that states ``layer_types`` describes a stack whose layers differ:
# full and sliding-window attention with their own head counts and rotary
# parameters, a dense feed-forward before sparse ones. The helpers below are
# the one place that reads those fields; the attention of such a stack is
# ``GroupedAttention`` and its layers are kept apart (``GPT.__call__``).

#: Short names of the two layer kinds: the suffix of a kind's pool leaves
#: and block table in the paged cache (``key_pool_full`` ...).
KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def layer_kind(cfg: GPTConfig, i: int) -> str:
    """``full`` or ``sliding``: the kind of layer ``i``."""
    if not cfg.layer_types:
        return "full"
    if len(cfg.layer_types) != cfg.num_layers:
        raise ValueError(
            f"layer_types has {len(cfg.layer_types)} entries for "
            f"num_layers={cfg.num_layers}"
        )
    return KINDS[cfg.layer_types[i]]


def kind_layers(cfg: GPTConfig) -> dict[str, list[int]]:
    """kind -> its layers' indices in order (kinds with no layer left out):
    a layer's place in this list is its row in the kind's pool."""
    out: dict[str, list[int]] = {}
    for i in range(cfg.num_layers):
        out.setdefault(layer_kind(cfg, i), []).append(i)
    return out


def attn_geometry(cfg: GPTConfig, kind: str):
    """(query heads, KV heads, head size, window, rotary parameters) of a
    layer of ``kind``; window 0 = unbounded."""
    sliding = kind == "sliding"
    h = (cfg.num_heads_sliding or cfg.num_heads) if sliding else cfg.num_heads
    h_kv = cfg.num_kv_heads or h
    hd = cfg.head_dim or cfg.hidden_dim // cfg.num_heads
    if h % h_kv:
        raise ValueError(f"{h} query heads do not group over {h_kv} KV heads")
    if sliding and cfg.sliding_window < 1:
        raise ValueError("sliding_attention layers need sliding_window >= 1")
    return (h, h_kv, hd, cfg.sliding_window if sliding else 0,
            cfg.rope_sliding if sliding else cfg.rope)


def window_table_blocks(cfg: GPTConfig, block_size: int) -> int:
    """Places in a sliding layer's block table: the blocks a window can
    touch, ``ceil(W / bs) + 1`` (a window that does not start on a block
    boundary reaches into one more). The table is a RING: logical block j
    sits at place ``j % places``, and a block the window has left gives its
    place to the block that many further on."""
    return -(-cfg.sliding_window // block_size) + 1


def window_pool_blocks(cfg: GPTConfig, block_size: int, batch: int) -> int:
    """Blocks of the sliding layers' pool: a ring's worth for each of
    ``batch`` slot rows, what they can hold at once, and the trash block 0.
    Worked out, not configured: no slot can hold more, so a smaller pool is
    the only other size that means anything, and nothing sizes one yet."""
    return batch * window_table_blocks(cfg, block_size) + 1


def rope_inv_freq(rope, head_dim: int):
    """(inverse frequencies ``[rot / 2]``, factor on cos and sin, rotating
    dimensions) of one layer type's rotary parameters. ``yarn``: Peng et
    al. 2023 as the published configs state it — dimensions that turn more
    than ``beta_fast`` times over the original context keep their frequency,
    those under ``beta_slow`` turns are interpolated by ``factor``, a linear
    ramp between; cos and sin carry ``attention_factor``."""
    import math

    import numpy as np

    rot = int(head_dim * rope.partial_rotary_factor)
    rot -= rot % 2
    inv = 1.0 / (rope.rope_theta ** (np.arange(0, rot, 2) / rot))
    if rope.rope_type == "default":
        return inv, 1.0, rot
    if rope.rope_type != "yarn":
        raise ValueError(f"unknown rope_type {rope.rope_type!r} (default | yarn)")
    orig = rope.original_max_position_embeddings

    def turns_at(n):  # the dimension that turns n times over `orig` positions
        return rot * math.log(orig / (n * 2 * math.pi)) / (
            2 * math.log(rope.rope_theta))

    low = max(math.floor(turns_at(rope.beta_fast)), 0)
    high = min(math.ceil(turns_at(rope.beta_slow)), rot - 1)
    ramp = np.clip(
        (np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0
    )
    inv = inv / rope.factor * ramp + inv * (1.0 - ramp)
    mscale = rope.attention_factor or 0.1 * math.log(rope.factor) + 1.0
    return inv, mscale, rot


def apply_rope(x, positions, rope, head_dim: int):
    """Rotate the leading ``rot`` dimensions of every head of ``x``
    ``[B, T, H, hd]`` by its position ``[B, T]`` (halves paired: dimension i
    with i + rot/2); fp32 inside."""
    inv, mscale, rot = rope_inv_freq(rope, head_dim)
    if rot == 0:
        return x
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32
    )  # [B, T, rot/2]
    cos = (jnp.cos(ang) * mscale)[:, :, None, :]
    sin = (jnp.sin(ang) * mscale)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    a, b_ = x32[..., : rot // 2], x32[..., rot // 2 : rot]
    out = jnp.concatenate(
        [a * cos - b_ * sin, b_ * cos + a * sin, x32[..., rot:]], axis=-1
    )
    return out.astype(x.dtype)


#: Query rows a block of ``grouped_attention``: the score strip of one block
#: is ``[B, H, rows, keys]`` in fp32 (64 heads x 256 x 4096: 268 MB), never
#: ``[B, H, T, T]``.
_QUERY_BLOCK = 256


def grouped_attention(q, k, v, qpos, *, window: int = 0):
    """Causal attention of ``q [B, T, Hq, hd]`` over keys ``k, v
    [B, S, Hkv, hd]`` that sit at positions ``0 .. S-1``: query head i reads
    KV head ``i // (Hq / Hkv)``, the query at position ``qpos [B, T]``
    attends positions ``qpos - window < j <= qpos`` (``window`` 0:
    unbounded). fp32 softmax. Queries go in blocks of ``_QUERY_BLOCK`` rows,
    and a windowed block reads only the keys its rows can reach, so the
    scores are never held whole. ``qpos`` must not decrease along a row
    (left-pad columns clip to 0)."""
    b, t, hq, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)

    def attend(qc, qp, kc, vc, kp):
        c = qc.shape[1]
        sc = jnp.einsum(
            "bckgd,bjkd->bkgcj", qc.reshape(b, c, hkv, g, hd), kc,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = kp[:, None, :] <= qp[:, :, None]  # [B, C, K]
        if window:
            mask &= kp[:, None, :] > qp[:, :, None] - window
        sc = jnp.where(mask[:, None, None], sc, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum(
            "bkgcj,bjkd->bckgd", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, c, hq, hd).astype(q.dtype)

    all_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if t <= _QUERY_BLOCK or t % _QUERY_BLOCK:
        return attend(q, qpos, k, v, all_pos)
    c = _QUERY_BLOCK
    kw = c + window  # keys a windowed block can reach

    def block(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * c, c, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(qpos, i * c, c, axis=1)
        if not window or kw >= s:
            return attend(qc, qp, k, v, all_pos)
        start = jnp.clip(qp[:, 0] - window + 1, 0, s - kw)  # [B]
        cut = jax.vmap(
            lambda x, at: jax.lax.dynamic_slice_in_dim(x, at, kw, axis=0)
        )
        return attend(qc, qp, cut(k, start), cut(v, start),
                      start[:, None] + jnp.arange(kw)[None])

    out = jax.lax.map(block, jnp.arange(t // c))  # [n, B, C, Hq, hd]
    return jnp.swapaxes(out, 0, 1).reshape(b, t, hq, hd)


def make_norm(cfg: GPTConfig, name: str):
    """The config's normalisation, fp32: LayerNorm (GPT-2) or RMSNorm."""
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(
            dtype=jnp.float32, epsilon=cfg.layer_norm_epsilon, name=name
        )
    if cfg.norm != "layernorm":
        raise ValueError(f"unknown norm {cfg.norm!r} (layernorm | rmsnorm)")
    return nn.LayerNorm(
        dtype=jnp.float32, epsilon=cfg.layer_norm_epsilon, name=name
    )


def _constrain_kv_pool(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """Pin a PAGED cache leaf — the stacked lane-dense K/V pools
    ``[L, N, bs, H*hd]`` or their scale pools ``[L, N, H*bs]`` —
    model-sharded over the mesh's ``model`` axis (heads are the MAJOR
    part of every pool leaf's last dimension, so splitting that
    dimension is the same Megatron head split as ``_constrain_kv_cache``)
    and REPLICATED over the batch axes: pool blocks are shared across
    slot rows (that is what multiplies concurrency), so a batch-sharded
    pool would scatter a row's blocks across data shards and every table
    lookup would become a cross-shard gather."""
    from frl_distributed_ml_scaffold_tpu.dist.mesh import current_mesh_env

    env = current_mesh_env()
    if env is None or env.axis_size("model") <= 1:
        return x
    if heads % env.axis_size("model") != 0:
        return x
    from jax.sharding import NamedSharding

    spec = P(*([None] * (x.ndim - 1)), "model")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(env.mesh, spec)
    )


def paged_cache_leaves(
    cfg: GPTConfig, dtype: Any, *, block_size: int, pool_blocks: int,
    batch: int,
) -> dict[str, tuple[tuple[int, ...], Any]]:
    """Name -> (shape, dtype) of the attention's PAGED cache leaves, all
    layers stacked — the one place their shapes are written down
    (``CausalSelfAttention`` reads its variables by it and
    ``init_paged_cache`` builds the empty cache from it).

    The K/V pools are LANE-DENSE, ``[L, N, bs, H*hd]``: a token's K row
    is H*hd contiguous values (heads major), so the minor dimension is a
    multiple of the 128-lane tile and the device keeps the leaf in the
    plain row-major layout that the in-place scatter and the Pallas
    kernel both read. (With a ``[..., H, hd]`` minor pair and hd = 64,
    half a lane tile, the TPU's compiler moved the block index into the
    lanes instead and every program that touched the pool transposed it
    there and back: PERF.md §6, PR 28.) A quantized pool's scales are
    ``[L, N, H*bs]`` for the same reason: a block's per-(position, head)
    scales as one row, heads major."""
    if cfg.layer_types:
        raise ValueError(
            "a model with layer_types keeps a pool for each layer kind: "
            "kind_pool_leaves"
        )
    h = cfg.num_heads
    hd = cfg.hidden_dim // h
    layers = cfg.num_layers
    quant = cfg.kv_cache_quant != "none"
    if quant:
        from frl_distributed_ml_scaffold_tpu.ops.quantization import lowp_dtype

        dtype = lowp_dtype(cfg.kv_cache_quant)
    pool = ((layers, pool_blocks, block_size, h * hd), dtype)
    leaves = {"key_pool": pool, "value_pool": pool}
    if quant:
        scale = ((layers, pool_blocks, h * block_size), jnp.bfloat16)
        leaves.update(key_pool_scale=scale, value_pool_scale=scale)
    leaves["cache_index"] = ((layers, batch), jnp.int32)
    return leaves


def kind_pool_leaves(
    cfg: GPTConfig, dtype: Any, *, block_size: int, pool_blocks: int,
    batch: int,
) -> dict[str, tuple[tuple[int, ...], Any]]:
    """Name -> (shape, dtype) of the PAGED cache of a model with
    ``layer_types``: for each layer kind a K and a V pool, lane-dense
    ``[layers of the kind, blocks, bs, Hkv*hd]`` (``paged_cache_leaves``
    says why), and its block table. A full layer keeps every position of a
    request, so its table has ``ceil(seq_len / bs)`` places; a sliding
    layer keeps a window's worth, so its table is a ring of
    ``window_table_blocks`` places whatever the context, and its pool
    holds a ring for each row (``window_pool_blocks``). One write cursor
    for all layers (``pos_index``), and the expert layers' counts of the
    last step (``moe_stats``: experts touched, pairs)."""
    _, h_kv, hd, _, _ = attn_geometry(cfg, "full")
    row = h_kv * hd
    counts = {k: len(v) for k, v in kind_layers(cfg).items()}
    blocks = {"full": pool_blocks,
              "sliding": window_pool_blocks(cfg, block_size, batch)
              if "sliding" in counts else 0}
    places = {"full": -(-cfg.seq_len // block_size),
              "sliding": window_table_blocks(cfg, block_size)
              if "sliding" in counts else 0}
    leaves = {}
    for kind, n in counts.items():
        pool = ((n, blocks[kind], block_size, row), dtype)
        leaves[f"key_pool_{kind}"] = leaves[f"value_pool_{kind}"] = pool
        leaves["block_tables" + ("" if kind == "full" else f"_{kind}")] = (
            (batch, places[kind]), jnp.int32)
    leaves["pos_index"] = ((batch,), jnp.int32)
    leaves["moe_stats"] = ((2,), jnp.int32)
    return leaves


def init_paged_cache(model: "GPT", batch: int) -> Any:
    """The EMPTY paged decode cache of ``model`` (a ``GPT`` cloned with
    ``kv_block_size`` / ``kv_pool_blocks``) for ``batch`` slot rows:
    zero pools, cursors at 0, all-zero block tables (every row on the
    reserved trash block 0). Unlike the contiguous cache, which a
    prefill creates on the fly, the paged cache has to EXIST before the
    first step: the layer loop carries the pools whole
    (``GPT.__call__``), and a loop cannot carry what its first
    iteration has yet to create. Traceable: wrap in ``jax.jit`` or
    ``jax.eval_shape`` as needed."""
    cfg = model.config
    if cfg.layer_types:
        return {
            n: jnp.zeros(shape, dt) for n, (shape, dt) in kind_pool_leaves(
                cfg, model.policy.compute_dtype,
                block_size=model.kv_block_size,
                pool_blocks=model.kv_pool_blocks, batch=batch,
            ).items()
        }
    attn = paged_cache_leaves(
        cfg, model.policy.compute_dtype, block_size=model.kv_block_size,
        pool_blocks=model.kv_pool_blocks, batch=batch,
    )
    return {
        "blocks": {
            "attn": {n: jnp.zeros(s, d) for n, (s, d) in attn.items()}
        },
        "pos_index": jnp.zeros((batch,), jnp.int32),
        "block_tables": jnp.zeros(
            (batch, -(-cfg.seq_len // model.kv_block_size)), jnp.int32
        ),
    }


def live_rows(full_tables: jnp.ndarray) -> jnp.ndarray:
    """``[B]`` bool: the slot rows that hold a request, read from the block
    tables of the layers that keep every position. A row whose table
    starts at the trash block is DEAD: the engine hands block 0 to no
    request, and a retired or never-used slot's row is all zeros. Nothing
    resets such a row's cursor — a dead row is not short — so its death is
    read here and nowhere else."""
    return full_tables[:, 0] != 0


def paged_attend(
    q, k, v, pools, tables, row, idx, full_tables, *, window: int = 0,
    quant: str = "none", impl: str, name: str,
):
    """One layer's step over the PAGED cache: write the step's K/V into
    the carried pools in place, then attend — the one place that decides
    where a token's K/V row goes and which rows a step attends.

    ``q [B, T, Hq, hd]`` and ``k, v [B, T, Hkv, hd]`` are the tokens at
    logical positions ``idx[b] .. idx[b] + T - 1``. ``pools``: ``key_pool``
    and ``value_pool`` ``[rows, N, bs, Hkv*hd]`` (quantized: 1-byte, with
    ``key_pool_scale`` / ``value_pool_scale`` ``[rows, N, Hkv*bs]``), of
    which this layer owns row ``row`` (traced in the scanned stack).
    ``tables [B, places]``: position p of a row sits in pool block
    ``tables[b, p // bs]`` at offset ``p % bs`` — the block's place CLAMPED
    to the table for layers that keep every position (a dead row's cursor
    runs on, and a verify tile's DRAFT positions beyond the blocks the
    engine appended land in the trash block: padding whose scores are never
    accepted), and taken ``% places`` under a ``window``, whose table is a
    ring. ``full_tables``: the tables that say which rows are dead
    (``live_rows``; ``tables`` itself but for a sliding layer). A dead row
    is told length 0, so the kernel reads no block of it and returns zeros,
    where its cursor alone would have it walk the trash block for as long
    as the row once was.
    Returns the attention output ``[B, T, Hq, hd]`` and the updated pools:
    nothing pool-sized is cut out, copied or written back (the compiled HLO
    is pinned in tests/test_chip_compile.py)."""
    from frl_distributed_ml_scaffold_tpu.ops.decode_attention import (
        paged_verify_attention,
    )

    b, t, h_kv, hd = k.shape
    bs, places = pools["key_pool"].shape[2], tables.shape[1]
    offs = idx[:, None] + jnp.arange(t)[None, :]  # [B, t]
    blk = offs // bs
    phys = jnp.take_along_axis(
        tables.astype(jnp.int32),
        blk % places if window else jnp.minimum(blk, places - 1),
        axis=1,
    )  # [B, t]
    off = offs % bs
    pools, rows = dict(pools), {"key_pool": k, "value_pool": v}
    if quant != "none":
        from frl_distributed_ml_scaffold_tpu.ops.quantization import quantize

        # Quantize ONCE per written token over its own head vector:
        # per-(row, pos, head) scales over hd, identical to the contiguous
        # path's scale at the same position. A block's scales are one row,
        # heads major: head i of offset o sits at lane i * bs + o.
        lanes = jnp.arange(h_kv) * bs + off[..., None]
        for n, x in rows.items():
            rows[n], sc = quantize(x, quant, channel_axes=(0, 1, 2))
            pools[n + "_scale"] = _constrain_kv_pool(
                pools[n + "_scale"].at[row, phys[..., None], lanes].set(
                    sc[..., 0].astype(jnp.bfloat16)),
                h_kv,
            )
    for n, x in rows.items():
        pools[n] = _constrain_kv_pool(
            pools[n].at[row, phys, off].set(
                x.reshape(b, t, h_kv * hd).astype(pools[n].dtype)),
            h_kv,
        )
    y = paged_verify_attention(
        q, pools["key_pool"], pools["value_pool"],
        jnp.where(live_rows(full_tables), idx + t, 0), tables, row,
        window=window,
        k_scale=pools.get("key_pool_scale"),
        v_scale=pools.get("value_pool_scale"), impl=impl, name=name,
    )
    return y, pools


def _constrain_kv_cache(x: jnp.ndarray) -> jnp.ndarray:
    """Pin a cache leaf — [B, S, H, hd] K/V values or their [B, S, H]
    quantization scales — model-sharded over the mesh's ``model`` axis
    (heads split on axis 2 either way — the Megatron layout the
    projection kernels already carry), batch over the batch axes when
    divisible.

    This is what keeps multi-chip serving from silently running the cache
    replicated: prefill EMITS the cache in this layout and every decode
    step consumes and re-emits it in the same layout, so no monolithic
    reshard appears at the prefill->decode handoff (jaxpr-pinned in
    tests/test_serving.py, the tp_overlap pin style)."""
    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        BATCH_AXES,
        current_mesh_env,
    )

    env = current_mesh_env()
    if env is None or env.axis_size("model") <= 1:
        return x
    if x.ndim < 3 or x.shape[2] % env.axis_size("model") != 0:
        return x
    batch = BATCH_AXES if x.shape[0] % env.batch_axis_size == 0 else None
    from jax.sharding import NamedSharding

    spec = P(batch, None, "model", *([None] * (x.ndim - 3)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(env.mesh, spec)
    )


class CausalSelfAttention(nn.Module):
    config: GPTConfig
    dtype: Any
    # Collective-matmul TP schedule (parallel/tp_overlap.py TpHooks): when
    # set, QKV share one bidirectional all-gather-matmul ring (the first
    # projection streams the sequence shards in under its own compute and
    # hands the assembled copy to its siblings) and the out projection is
    # a matmul-reduce-scatter ring instead of matmul+allreduce. Params are
    # untouched — the hooks ride nn.Dense's injectable dot_general.
    tp: Any = None
    # Decode KV-cache capacity (0 = config.seq_len): serving buckets the
    # cache to a power of two covering prompt+budget so short requests
    # stop paying full-context cache traffic (serving/engine.py policy).
    cache_len: int = 0
    # Paged decode cache (ISSUE 10; 0 = contiguous per-row cache): K/V
    # live in a shared pool of kv_pool_blocks fixed-size blocks instead
    # of [B, S] stacks; the per-row block table arrives via the scan
    # carry (serving/engine.py owns allocation and the tables) and the
    # layer's index beside it (the pool holds all layers).
    kv_block_size: int = 0
    kv_pool_blocks: int = 0

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        train: bool,
        decode: bool = False,
        lengths: jnp.ndarray | None = None,
        block_tables: jnp.ndarray | None = None,
        layer: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        cfg = self.config
        d = cfg.hidden_dim
        h = cfg.num_heads
        hd = d // h
        tp = None if decode else self.tp
        qkv_dg = tp.qkv_context().dot_general if tp is not None else None
        out_dg = tp.mrs_dot_general if tp is not None else None
        if tp is not None:
            # Pre-cast to the compute dtype so flax's per-Dense
            # promote_dtype is an identity: the shared-QKV ring cache keys
            # on input-object identity, and under bf16_mixed the fp32
            # LayerNorm output would otherwise become THREE distinct cast
            # tracers — three gather rings instead of one. Numerically a
            # no-op (Dense performs this exact cast internally).
            x = x.astype(self.dtype)
        q = nn.Dense(d, dtype=self.dtype, name="query", dot_general=qkv_dg)(x)
        k = nn.Dense(d, dtype=self.dtype, name="key", dot_general=qkv_dg)(x)
        v = nn.Dense(d, dtype=self.dtype, name="value", dot_general=qkv_dg)(x)
        b, t, _ = x.shape
        q = q.reshape(b, t, h, hd)
        k = k.reshape(b, t, h, hd)
        v = v.reshape(b, t, h, hd)

        if decode:
            # Incremental decoding: append this call's K/V at each row's
            # write position and attend over the occupied cache prefix.
            # The flash/ring/ulysses training kernels are pointless at
            # decode shapes (q is one token), so every attention mode
            # shares this path; single-token steps route through
            # ops/decode_attention (flash-decode kernel or its
            # identical-numerics dense fallback, per cfg.decode_attention).
            s = self.cache_len or cfg.seq_len
            # Quantized cache (cfg.kv_cache_quant): K/V live in the 1-byte
            # format with per-(row, position, head) bf16 scales in sibling
            # cache vars. Each written token quantizes ONCE, over its own
            # head vector — cache entries are never re-quantized, so the
            # values a position contributes are identical at every later
            # step and in every bucket size.
            quant = cfg.kv_cache_quant != "none"
            if quant:
                from frl_distributed_ml_scaffold_tpu.ops.quantization import (
                    lowp_dtype,
                )

                cache_dtype = lowp_dtype(cfg.kv_cache_quant)
            else:
                cache_dtype = self.dtype
            if self.kv_block_size > 0:
                # PAGED cache (ISSUE 10): K/V live in a POOL of
                # fixed-size blocks shared by every row; this row's
                # logical block j is physical pool block
                # block_tables[b, j]. Only single-token steps run paged —
                # prefill stays contiguous (serving/engine.py grafts the
                # prefilled blocks into the pool, moving exactly the
                # blocks that change owner). Shared-prefix blocks are
                # immutable by construction: a row's writes land at
                # positions >= its private suffix, and the engine's
                # copy-on-write admission never maps a shared block
                # there.
                if block_tables is None or layer is None:
                    raise ValueError(
                        "kv_block_size set but no block_tables / layer "
                        "index reached the attention cache — the decode "
                        "loop must thread them"
                    )
                # The pools hold ALL layers ([L, N, bs, H*hd], lane-dense:
                # ``paged_cache_leaves``) and the layer loop CARRIES them
                # whole (GPT.__call__): this layer writes its tokens' rows
                # at ``[layer, block, offset]`` in place and the kernel
                # reads the stack where it lies (``paged_attend``). ONE
                # kernel for the decode step and the speculative VERIFY
                # tile: all t positions score against the paged cache in
                # one forward, causal inside the tile, so greedy acceptance
                # against these logits is exact; the decode step is its
                # t = 1 tile, under its own name in a device trace.
                cache = {
                    name: self.variable("cache", name, jnp.zeros, shape, dt)
                    for name, (shape, dt) in paged_cache_leaves(
                        cfg, self.dtype, block_size=self.kv_block_size,
                        pool_blocks=self.kv_pool_blocks, batch=b,
                    ).items()
                }
                ci = cache.pop("cache_index")
                idx = ci.value[layer]  # [B]
                y, pools = paged_attend(
                    q, k.astype(self.dtype), v.astype(self.dtype),
                    {n: c.value for n, c in cache.items()}, block_tables,
                    layer, idx, block_tables,
                    quant=cfg.kv_cache_quant, impl=cfg.decode_attention,
                    name="attn_paged_decode" if t == 1
                    else "attn_paged_verify",
                )
                for n, c in cache.items():
                    c.value = pools[n]
                ci.value = ci.value.at[layer].set(idx + t)
                y = y.reshape(b, t, d)
                y = nn.Dense(
                    d, dtype=self.dtype, name="out", dot_general=out_dg
                )(y)
                y = nn.Dropout(cfg.dropout, deterministic=not train)(y)
                return y
            # Cache vars are created lazily on first use: flax permits
            # variable creation during apply when the collection is mutable.
            ck = self.variable(
                "cache", "cached_key", jnp.zeros, (b, s, h, hd), cache_dtype
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros, (b, s, h, hd), cache_dtype
            )
            if quant:
                ksc = self.variable(
                    "cache", "key_scale", jnp.zeros, (b, s, h), jnp.bfloat16
                )
                vsc = self.variable(
                    "cache", "value_scale", jnp.zeros, (b, s, h), jnp.bfloat16
                )
            # Per-ROW write index: serving slots decode at different
            # occupancies (continuous batching), so the index is [B], the
            # write is a batched scatter, and the mask is per-row.
            ci = self.variable(
                "cache", "cache_index", jnp.zeros, (b,), jnp.int32
            )
            idx = ci.value  # [B]
            lens = (
                jnp.full((b,), t, jnp.int32)
                if lengths is None
                else lengths.astype(jnp.int32)
            )
            pad = t - lens  # [B] left-pad widths (0 when not ragged)
            k_w, v_w = k.astype(self.dtype), v.astype(self.dtype)
            if t > 1:
                # Ragged prefill: prompts arrive LEFT-padded ([pad | real]
                # columns). Roll each row so its real tokens land at cache
                # slots [0, len) — the cache is stored densely by absolute
                # position, which is what lets the decode kernel read only
                # the occupied prefix. The trailing t-len written slots
                # hold wrapped pad garbage; they sit at positions >= len,
                # masked now and overwritten by later decode steps.
                roll_cols = (jnp.arange(t)[None, :] + pad[:, None]) % t
                k_w = jnp.take_along_axis(
                    k_w, roll_cols[:, :, None, None], axis=1
                )
                v_w = jnp.take_along_axis(
                    v_w, roll_cols[:, :, None, None], axis=1
                )
            rows = jnp.arange(b)[:, None]
            # Columns past the cache capacity are DROPPED, not clipped:
            # a seeded suffix prefill (serving shared-prefix admission,
            # cache_index starting at the prefix length) can push its
            # trailing wrapped-pad garbage columns past ``s`` — clipping
            # would pile them onto position s-1, clobbering a real
            # token's K/V. The same drop also silences retired serving
            # rows whose index has advanced past capacity.
            write_cols = idx[:, None] + jnp.arange(t)[None, :]
            if quant:
                from frl_distributed_ml_scaffold_tpu.ops.quantization import (
                    dequantize,
                    quantize,
                )

                qk, sk = quantize(k_w, cfg.kv_cache_quant,
                                  channel_axes=(0, 1, 2))
                qv, sv = quantize(v_w, cfg.kv_cache_quant,
                                  channel_axes=(0, 1, 2))
                k_w, v_w = qk, qv  # [B, t, H, hd] 1-byte payloads
                ksc.value = _constrain_kv_cache(
                    ksc.value.at[rows, write_cols].set(
                        sk[..., 0].astype(ksc.value.dtype), mode="drop"
                    )
                )
                vsc.value = _constrain_kv_cache(
                    vsc.value.at[rows, write_cols].set(
                        sv[..., 0].astype(vsc.value.dtype), mode="drop"
                    )
                )
            ck.value = _constrain_kv_cache(
                ck.value.at[rows, write_cols].set(k_w, mode="drop")
            )
            cv.value = _constrain_kv_cache(
                cv.value.at[rows, write_cols].set(v_w, mode="drop")
            )
            if t == 1:
                from frl_distributed_ml_scaffold_tpu.ops.decode_attention import (
                    decode_attention,
                )

                y = decode_attention(
                    q[:, 0], ck.value, cv.value, idx + 1,
                    k_scale=ksc.value if quant else None,
                    v_scale=vsc.value if quant else None,
                    impl=cfg.decode_attention,
                )[:, None]
            else:
                # Query at column j has absolute position idx + j - pad
                # (pad columns clip to 0: their outputs are never read,
                # but the softmax must stay finite).
                qpos = jnp.maximum(
                    idx[:, None] + jnp.arange(t)[None, :] - pad[:, None], 0
                )  # [B, t]
                kpos = jnp.arange(s)
                mask = kpos[None, None, :] <= qpos[:, :, None]  # [B, t, S]
                if quant:
                    # Prefill attends over the dequantized bucket — a
                    # [B, bucket, H, hd] widening is the prefill program's
                    # own working-set class (its score tensor is bigger);
                    # the per-STEP no-wide-cache pin applies to t == 1.
                    k_att = dequantize(
                        ck.value, ksc.value[..., None], self.dtype
                    )
                    v_att = dequantize(
                        cv.value, vsc.value[..., None], self.dtype
                    )
                else:
                    k_att, v_att = ck.value, cv.value
                y = _masked_dense_attention(q, k_att, v_att, mask)
            ci.value = idx + lens
        elif cfg.attention == "ring":
            from frl_distributed_ml_scaffold_tpu.ops.ring_attention import (
                ring_attention,
            )

            y = ring_attention(q, k, v, axis_name="seq", causal=True)
        elif cfg.attention == "ulysses":
            from frl_distributed_ml_scaffold_tpu.ops.ulysses import (
                ulysses_attention,
            )

            y = ulysses_attention(q, k, v, axis_name="seq", causal=True)
        elif cfg.attention == "flash":
            from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
                flash_attention,
            )

            y = flash_attention(q, k, v, causal=True)
        else:
            from frl_distributed_ml_scaffold_tpu.ops import dense_attention

            # Same op (and the same fp32-softmax numerics) as the trivial-axis
            # path of ring/ulysses — dense vs. sharded attention differ only
            # in communication, never in math.
            y = dense_attention(q, k, v, causal=True)

        y = y.reshape(b, t, d)
        y = nn.Dense(d, dtype=self.dtype, name="out", dot_general=out_dg)(y)
        y = nn.Dropout(cfg.dropout, deterministic=not train)(y)
        return y


class GroupedAttention(nn.Module):
    """The attention of a stack with ``layer_types``: query heads grouped
    over fewer KV heads, a head size of its own, rotary positions by layer
    kind, an optional sliding window and per-head output gate. Three ways
    in, one set of weights: the plain causal forward; the CONTIGUOUS cache
    (``generate`` and the engine's prefill: every position kept, the window
    a mask); and the PAGED decode step over the pools of the layer's kind,
    which are handed in and handed back (the layer loop of ``GPT.__call__``
    carries them; one layer's row of the pool is updated in place)."""

    config: GPTConfig
    dtype: Any
    kind: str  # full | sliding
    row: int  # this layer's row in its kind's pool
    cache_len: int = 0

    @nn.compact
    def __call__(self, x, *, train: bool, decode: bool, ctx: dict):
        cfg = self.config
        h, h_kv, hd, window, rope = attn_geometry(cfg, self.kind)
        b, t, _ = x.shape
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=cfg.bias, dtype=self.dtype, name=name
        )
        q = dense(h * hd, "query")(x).reshape(b, t, h, hd)
        k = dense(h_kv * hd, "key")(x).reshape(b, t, h_kv, hd)
        v = dense(h_kv * hd, "value")(x).reshape(b, t, h_kv, hd)
        positions = ctx["positions"]  # [B, T] absolute
        if cfg.position == "rope":
            q = apply_rope(q, positions, rope, hd)
            k = apply_rope(k, positions, rope, hd)
        pools = ctx.get("pools")
        if not decode:
            y = grouped_attention(q, k, v, positions, window=window)
        elif pools is None:
            # Contiguous cache [B, S, Hkv, hd] by absolute position, as
            # CausalSelfAttention keeps it: left-padded prompts are rolled
            # so that real tokens land at [0, len); what wraps lands past
            # the row's length, masked now and overwritten later.
            s = self.cache_len or cfg.seq_len
            ck = self.variable(
                "cache", "cached_key", jnp.zeros, (b, s, h_kv, hd), self.dtype)
            cv = self.variable(
                "cache", "cached_value", jnp.zeros, (b, s, h_kv, hd),
                self.dtype)
            idx, pad = ctx["idx"], t - ctx["lengths"]
            k_w, v_w = k.astype(self.dtype), v.astype(self.dtype)
            if t > 1:
                roll = (jnp.arange(t)[None, :] + pad[:, None]) % t
                k_w = jnp.take_along_axis(k_w, roll[:, :, None, None], axis=1)
                v_w = jnp.take_along_axis(v_w, roll[:, :, None, None], axis=1)
            rows = jnp.arange(b)[:, None]
            cols = idx[:, None] + jnp.arange(t)[None, :]
            ck.value = ck.value.at[rows, cols].set(k_w, mode="drop")
            cv.value = cv.value.at[rows, cols].set(v_w, mode="drop")
            y = grouped_attention(q, ck.value, cv.value, positions,
                                  window=window)
        else:
            y, kind_pools = paged_attend(
                q, k, v, pools[self.kind], ctx["tables"][self.kind],
                self.row, ctx["idx"], ctx["tables"]["full"], window=window,
                impl=cfg.decode_attention,
                name=f"attn_mixed_decode_{self.kind}",
            )
            pools = {**pools, self.kind: kind_pools}
        if cfg.attention_gate:
            gate = nn.Dense(h, use_bias=False, dtype=self.dtype, name="gate")(x)
            y = y * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                y.dtype)[..., None]
        y = dense(cfg.hidden_dim, "out")(y.reshape(b, t, h * hd))
        y = nn.Dropout(cfg.dropout, deterministic=not train)(y)
        return y, pools


class GptMlp(nn.Module):
    config: GPTConfig
    dtype: Any
    tp: Any = None  # collective-matmul hooks (see CausalSelfAttention)

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool) -> jnp.ndarray:
        cfg = self.config
        tp = self.tp
        ag_dg = tp.ag_dot_general if tp is not None else None
        mrs_dg = tp.mrs_dot_general if tp is not None else None
        width = cfg.mlp_dim or cfg.hidden_dim * cfg.mlp_ratio
        if cfg.mlp == "swiglu":
            from frl_distributed_ml_scaffold_tpu.models.moe import gated_ffn

            y = gated_ffn(x, width, cfg.hidden_dim, cfg.bias, self.dtype)
            return nn.Dropout(cfg.dropout, deterministic=not train)(y)
        if cfg.mlp != "gelu":
            raise ValueError(f"unknown mlp {cfg.mlp!r} (gelu | swiglu)")
        y = nn.Dense(
            width,
            use_bias=cfg.bias,
            dtype=self.dtype,
            name="fc_in",
            dot_general=ag_dg,
        )(x)
        y = nn.gelu(y)
        y = nn.Dense(
            cfg.hidden_dim, use_bias=cfg.bias, dtype=self.dtype,
            name="fc_out", dot_general=mrs_dg,
        )(y)
        y = nn.Dropout(cfg.dropout, deterministic=not train)(y)
        return y


class Block(nn.Module):
    config: GPTConfig
    dtype: Any
    train: bool  # static per-trace; bound at GPT.__call__ construction time
    decode: bool = False  # KV-cache incremental decoding
    tp: Any = None  # collective-matmul TP hooks (parallel/tp_overlap.py)
    cache_len: int = 0  # decode cache bucket (0 = config.seq_len)
    kv_block_size: int = 0  # paged decode pool (0 = contiguous cache)
    kv_pool_blocks: int = 0
    # A layer kept APART (a stack with ``layer_types``): its index in the
    # model, which says its kind, its row in the kind's pool and whether
    # its feed-forward is dense. -1: a layer of the scanned uniform stack.
    index: int = -1

    def _apart(self, carry):
        """One layer of a stack with ``layer_types``. The carry is ``(x,
        aux loss, ctx)``; ``ctx`` holds what every layer reads (positions,
        write cursor, prompt lengths, block tables, the mask of tokens that
        are real) and what the layers hand on: the pools by kind and the
        expert layers' counts."""
        x, aux_loss, ctx = carry
        cfg, train, i = self.config, self.train, self.index
        kind = layer_kind(cfg, i)
        y = make_norm(cfg, "ln1")(x)
        attn_out, pools = GroupedAttention(
            cfg, self.dtype, kind, kind_layers(cfg)[kind].index(i),
            cache_len=self.cache_len, name="attn",
        )(y, train=train, decode=self.decode, ctx=ctx)
        x = x + attn_out
        y = make_norm(cfg, "ln2")(x)
        if cfg.moe.num_experts > 0 and i not in cfg.dense_layers:
            from frl_distributed_ml_scaffold_tpu.models.moe import MoEMlp

            mlp_out, extra = MoEMlp(cfg, self.dtype, name="moe")(
                y, train=train, token_mask=ctx.get("token_mask"))
            if cfg.moe.routing == "dropless":
                ctx = {**ctx, "moe_stats": ctx["moe_stats"] + extra}
            else:
                aux_loss = aux_loss + extra
        else:
            mlp_out = GptMlp(cfg, self.dtype, name="mlp")(y, train=train)
        return (x + mlp_out, aux_loss, {**ctx, "pools": pools}), None

    @nn.compact
    def __call__(self, carry, layer):
        if self.index >= 0:
            return self._apart(carry)
        # Decode mode threads the per-row prompt lengths through the scan
        # carry (a traced array cannot be a module attribute); they are
        # loop-invariant. Paged decode additionally threads the per-row
        # block tables the same way (every layer reads the same tables)
        # and scans over ``layer``, this block's index: the pools hold
        # all layers and are carried whole, as the ``cache`` collection
        # (None on every other path).
        tables = None
        if self.decode and self.kv_block_size > 0:
            x, aux_loss, lengths, tables = carry
        elif self.decode:
            x, aux_loss, lengths = carry
        else:
            (x, aux_loss), lengths = carry, None
        cfg, train, tp = self.config, self.train, self.tp
        y = make_norm(cfg, "ln1")(x)
        attn_out = CausalSelfAttention(
            cfg, self.dtype, tp=tp, cache_len=self.cache_len,
            kv_block_size=self.kv_block_size,
            kv_pool_blocks=self.kv_pool_blocks, name="attn"
        )(y, train=train, decode=self.decode, lengths=lengths,
          block_tables=tables, layer=layer)
        # Named for block_remat="save_attn": saving this one [B,T,D] tensor
        # per layer lets the per-block recompute skip the attention sublayer
        # (the quadratic part). A no-op unless a checkpoint policy asks.
        attn_out = checkpoint_name(attn_out, "attn_out")
        x = x + attn_out
        if tp is not None:
            # Keep the residual stream sequence-sharded over the model axis
            # between the reduce-scatter that produced attn_out and the
            # gather ring that will consume ln2's output: the add and the
            # LayerNorms are per-token, so anchoring here keeps the whole
            # inter-matmul segment local.
            x = tp.constrain_stream(x)
        y = make_norm(cfg, "ln2")(x)
        if cfg.moe.num_experts > 0:
            from frl_distributed_ml_scaffold_tpu.models.moe import MoEMlp

            mlp_out, layer_aux = MoEMlp(cfg, self.dtype, name="moe")(y, train=train)
            aux_loss = aux_loss + layer_aux
        else:
            mlp_out = GptMlp(cfg, self.dtype, tp=tp, name="mlp")(y, train=train)
        x = x + mlp_out
        if tp is not None:
            x = tp.constrain_stream(x)
        if self.decode and self.kv_block_size > 0:
            return (x, aux_loss, lengths, tables), None
        if self.decode:
            return (x, aux_loss, lengths), None
        return (x, aux_loss), None


class GPT(nn.Module):
    config: GPTConfig
    policy: Policy
    # Blockwise param-gather apply hook (fsdp_overlap.OverlapHooks —
    # lowered from the declared OverlapSchedule's gather(fsdp,block) rule
    # by parallel/schedule.py's executor): when set, each scanned Block's
    # param slice is explicitly all-gathered inside the scan body
    # (nn.map_variables) and the block is rematted with a policy that
    # refuses to save the gathered full params, so the backward
    # re-gathers (reduce-scatter of grads is the gather's transpose).
    # Attached by the Trainer AFTER partition specs exist; init/decode
    # always run unhooked — the params tree is identical either way.
    param_hooks: Any = None
    # Collective-matmul ring hooks (tp_overlap.TpHooks — lowered from the
    # schedule's gather(model,ring_chunk)/scatter(model) pair, with any
    # declared ``lowp`` riding as a transfer attribute): replaces the
    # four GSPMD TP matmuls per block (QKV, attn-out, fc_in, fc_out)
    # with latency-hiding ppermute rings and keeps the residual stream
    # sequence-sharded over the model axis. Attached by the Trainer like
    # param_hooks; init/decode always run unhooked.
    tp_overlap: Any = None
    # Decode KV-cache capacity (0 = config.seq_len). generate()/the
    # serving engine clone the model with the active bucket so the cache
    # arrays — and everything that reads them — are sized to the request
    # window, not the model's maximum context.
    cache_len: int = 0
    # Paged decode cache (ISSUE 10; engine-set via clone, like cache_len):
    # kv_block_size > 0 stores K/V in a shared pool of kv_pool_blocks
    # fixed-size blocks addressed through a per-row ``block_tables``
    # cache var ([B, ceil(seq_len/block_size)] int32, engine-owned) —
    # single-token decode steps and verify tiles only; prefill stays
    # contiguous and the engine grafts it into the pool block-wise. The
    # cache is built by ``init_paged_cache`` before the first step.
    kv_block_size: int = 0
    kv_pool_blocks: int = 0
    # (A model with ``layer_types`` keeps a pool for each layer kind:
    # ``kv_pool_blocks`` is the full kind's, the sliding kind's follows
    # from the slot rows, ``window_pool_blocks``.)

    def _layers_apart(self, x, *, train, decode, paged, positions, idx, lens):
        """The stack of a model with ``layer_types``: layers of unlike shape
        (head counts by kind, a dense feed-forward before sparse ones) cannot
        share one scanned body, so each is a module of its own,
        ``layer_<i>``, called in turn. In a paged decode step the loop
        carries the pools of both kinds whole — each layer updates its row
        of its kind's pool in place and hands the pools on — so nothing
        pool-sized is cut out or copied (tests/test_chip_compile.py)."""
        cfg = self.config
        if cfg.pipeline_stages > 1 or self.param_hooks or self.tp_overlap:
            raise NotImplementedError(
                "a model with layer_types runs on the plain layer loop: no "
                "pipeline stages, no overlap hooks"
            )
        t = x.shape[1]
        ctx = {"positions": positions, "idx": idx, "lengths": lens,
               "moe_stats": jnp.zeros((2,), jnp.int32)}
        kinds = list(kind_layers(cfg))
        if paged:
            table_of = lambda k: "block_tables" + (  # noqa: E731
                "" if k == "full" else f"_{k}")
            ctx["tables"] = {
                k: self.get_variable("cache", table_of(k)) for k in kinds}
            ctx["pools"] = {
                k: {n: self.get_variable("cache", f"{n}_{k}")
                    for n in ("key_pool", "value_pool")}
                for k in kinds}
            # A slot row with no request attends nothing, and its token
            # goes to no expert (it would cost an expert's read).
            ctx["token_mask"] = live_rows(ctx["tables"]["full"])[:, None]
        elif decode:
            ctx["token_mask"] = (
                jnp.arange(t)[None, :] >= (t - lens)[:, None])  # not padding
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            (x, aux, ctx), _ = Block(
                cfg, self.policy.compute_dtype, train, decode, None,
                self.cache_len if decode else 0,
                self.kv_block_size if paged else 0, 0, index=i,
                name=f"layer_{i}",
            )((x, aux, ctx), None)
        if paged:
            for k, kind_pools in ctx["pools"].items():
                for n, pool in kind_pools.items():
                    self.put_variable("cache", f"{n}_{k}", pool)
            self.put_variable("cache", "moe_stats", ctx["moe_stats"])
        return x, aux

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        *,
        train: bool = False,
        decode: bool = False,
        return_features: bool = False,
        lengths: jnp.ndarray | None = None,
    ):
        cfg = self.config
        dtype = self.policy.compute_dtype
        b, t = tokens.shape
        apart = bool(cfg.layer_types)
        if lengths is not None and not decode:
            raise ValueError(
                "lengths (ragged left-padded prompts) is a decode-mode "
                "argument; training/eval batches are dense"
            )
        if decode and self.kv_block_size > 0 and t > 1 and lengths is not None:
            raise NotImplementedError(
                "paged multi-token decode is the dense VERIFY tile "
                "(speculative decoding, ISSUE 11) — ragged lengths do "
                "not apply; prefill stays contiguous and the engine "
                "grafts it block-wise into the pool"
            )
        paged = decode and self.kv_block_size > 0
        if paged and not self.has_variable("cache", "block_tables"):
            raise ValueError(
                "paged decode needs its cache to exist before the first "
                "step: the layer loop carries the KV pools whole and "
                "cannot carry what its first iteration would create — "
                "build the empty cache with "
                "models.gpt.init_paged_cache(model, batch)"
            )

        wte = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_dim,
            dtype=dtype,
            embedding_init=nn.initializers.normal(stddev=0.02),
            name="wte",
        )
        if cfg.position not in ("learned", "rope"):
            raise ValueError(
                f"unknown position {cfg.position!r} (learned | rope)")
        learned = cfg.position == "learned"
        if learned:
            wpe = self.param(
                "wpe", nn.initializers.normal(stddev=0.02),
                (cfg.seq_len, cfg.hidden_dim),
            )
        idx0 = lens = None
        if decode:
            # Positions are absolute and PER ROW: offset by how much of
            # each row's cache this call's tokens come after (tracked here
            # so the embedding and the per-layer attention caches advance
            # together; rows diverge under ragged prompts and continuous
            # batching). Left-pad columns clip to position 0 — their
            # embeddings feed garbage lanes that the attention mask and
            # the right-aligned logit read both ignore.
            pos = self.variable(
                "cache", "pos_index", jnp.zeros, (b,), jnp.int32
            )
            # Canonical per-row lengths, computed ONCE for the whole
            # decode trace: the position offsets here and the cache
            # writes/masks in every scanned block (via the scan carry)
            # must advance from the same array.
            lens = (
                jnp.full((b,), t, jnp.int32)
                if lengths is None
                else lengths.astype(jnp.int32)
            )
            pos_ids = jnp.clip(
                pos.value[:, None] + jnp.arange(t)[None, :] - (t - lens)[:, None],
                0,
                cfg.seq_len - 1,
            )  # [B, t]
            idx0 = pos.value  # the write cursor before this call
            pos.value = pos.value + lens
        else:
            pos_ids = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        x = wte(tokens)
        if learned:
            pe = jnp.take(wpe, pos_ids, axis=0) if decode else wpe[:t]
            x = x + pe.astype(dtype)  # [B, t, D] / [t, D]
        x = nn.Dropout(cfg.dropout, deterministic=not train)(x)

        if decode and cfg.pipeline_stages > 1:
            raise NotImplementedError(
                "KV-cache decoding runs on the plain layer stack (pipeline "
                "parallelism is a training-throughput schedule). "
                "models.generation.generate/beam_search restack pipeline "
                "params automatically (unstack_pipeline_params); only a "
                "direct apply(decode=True) needs pipeline_stages=1"
            )
        if apart:
            x, aux_loss = self._layers_apart(
                x, train=train, decode=decode, paged=paged,
                positions=pos_ids, idx=idx0, lens=lens,
            )
        elif cfg.pipeline_stages > 1:
            # flash/ring/ulysses open their own shard_map regions; the
            # pipeline's stage vmap names its axis (spmd_axis_name="pipe"),
            # so those regions batch over the stage dim and compose — no
            # mode exclusions.
            from frl_distributed_ml_scaffold_tpu.parallel.pipeline import (
                CircularSpmdPipeline,
                SpmdPipeline,
                circular_repeat,
                effective_microbatches,
            )

            v = circular_repeat(cfg)
            cls = CircularSpmdPipeline if v > 1 else SpmdPipeline
            pipe = cls(
                Block,
                (cfg, dtype, train),
                num_layers=cfg.num_layers,
                num_stages=cfg.pipeline_stages,
                num_microbatches=effective_microbatches(cfg),
                stage_remat=cfg.pipeline_stage_remat,
                name="pipeline",
                **({"repeat": v} if v > 1 else {}),
            )
            x, aux_loss = pipe(x, jnp.zeros((), jnp.float32))
        else:
            if decode:
                # Decode keeps its own plain scan: hooks/remat are
                # training-path rewrites and never mix with the caches.
                # The contiguous cache is scanned with the layers (each
                # layer owns its [B, S, H, hd] slice). The PAGED cache
                # is CARRIED: one stacked pool that every layer updates
                # in place at [layer, block, offset] — scanned in and out,
                # each layer's pool would be sliced out of the stack,
                # copied, and written back (gigabytes a decode step).
                stack_cls = nn.scan(
                    Block,
                    length=cfg.num_layers,
                    variable_axes=(
                        {"params": 0} if paged else {"params": 0, "cache": 0}
                    ),
                    variable_carry="cache" if paged else False,
                    split_rngs={"params": True, "dropout": True},
                )
            else:
                # Shared with the MPMD per-stage programs (GptStage):
                # blockwise param-gather hook + per-block remat + scan.
                stack_cls = _train_block_stack(
                    cfg, length=cfg.num_layers, hooks=self.param_hooks
                )
            blocks = stack_cls(
                cfg,
                dtype,
                train,
                decode,
                None if decode else self.tp_overlap,
                self.cache_len if decode else 0,
                self.kv_block_size if decode else 0,
                self.kv_pool_blocks if decode else 0,
                name="blocks",
            )
            if paged:
                # Paged decode: the block tables are a MODEL-level cache
                # var (one copy, not per-layer — every layer reads the
                # same row→block mapping), threaded to the scanned blocks
                # through the carry like `lens`. The engine writes them
                # host-side between steps; the model only reads.
                tbl = self.get_variable("cache", "block_tables")
                carry0 = (x, jnp.zeros((), jnp.float32), lens, tbl)
                (x, aux_loss, _, _), _ = blocks(
                    carry0, jnp.arange(cfg.num_layers, dtype=jnp.int32)
                )
            elif decode:
                # `lens` from the position block above — one defaulting
                # site for the whole decode trace.
                carry0 = (x, jnp.zeros((), jnp.float32), lens)
                (x, aux_loss, _), _ = blocks(carry0, None)
            else:
                (x, aux_loss), _ = blocks(
                    (x, jnp.zeros((), jnp.float32)), None
                )

        x = make_norm(cfg, "ln_f")(x)
        if return_features:
            # Pre-head features for the chunked-vocab LM loss (the weight-
            # tied head lives at params['wte']['embedding']; the loss
            # reproduces wte.attend chunk by chunk so the [B, T, vocab]
            # logits tensor never materializes).
            feats = x.astype(dtype)
            if cfg.moe.num_experts > 0:
                return feats, aux_loss
            return feats
        if cfg.tie_embeddings:
            logits = wte.attend(x.astype(dtype))  # weight-tied LM head
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=dtype, name="lm_head"
            )(x.astype(dtype))
        if cfg.moe.num_experts > 0:
            return logits, aux_loss
        return logits
