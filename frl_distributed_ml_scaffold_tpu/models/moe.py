"""Expert-parallel MoE MLP (SURVEY C9): GShard-style top-k capacity routing.

TPU-native formulation: experts live in a single stacked parameter
(E, D, H) sharded over the ``expert`` mesh axis; token dispatch/combine are
einsums against one-hot dispatch tensors, so GSPMD lowers the expert
exchange to ``all_to_all`` on ICI — no manual send/recv.

**Grouped dispatch** (the GShard paper's GSEC layout): the token stream is
split into G independent routing groups, each with its own capacity
``C_g = capacity_factor * (N/G) * k / E``. The dispatch/combine tensors are
``[G, S, E, C_g]`` — total memory ``N * E * C_g``, i.e. **G× smaller** than
the ungrouped ``[N, E, C]`` formulation (at GPT-2-medium MoE shapes,
N=4096 / E=64 / cf=1.25 / k=2 → C=160: the ungrouped bf16 dispatch +
fp32 combine pair is ~252 MB per layer, G=8 cuts it to ~31 MB; measured
deltas in docs/perf_playbook.md). Groups default to the mesh's
batch-shard count, so each data shard routes its own tokens and the group
dim stays batch-sharded through every einsum. Per-group capacity is the
standard practice trade: a token can be dropped because *its group* is
over capacity even if another group has room (residual carries it, as with
any capacity drop).

Router math in fp32. Load-balance aux loss per GShard/Switch over ALL k
assignment slots, plus the ST-MoE router z-loss (mean log²-sum-exp of the
router logits) that keeps logits from drifting into bf16-hostile ranges.

**Which routing a recipe uses** (``moe.routing``; two models, not two
implementations): everything above is the ``capacity`` routing — GShard
top-k with a per-group capacity and DROPS — which every training recipe of
this repo uses (``gpt2_moe*``); ``moe.dispatch`` (einsum | sort) picks
between two formulations of ITS token exchange and nothing else. The
``dropless`` routing (``MoEMlp._dropless``) is what the published sparse
decoders state and what serving runs: the top-k of sigmoid (or softmax)
scores with no capacity, so no token is ever dropped; the chosen scores
normalised and scaled; a shared expert every token passes through; every
expert a gated feed-forward. Its products are computed grouped by expert
(ops/grouped_experts.py: pairs sorted by expert, one pass over row tiles,
only the experts that got a pair are read), and it reports how many experts
it touched and how many pairs it computed, for the serving engine's
``decode`` span.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from frl_distributed_ml_scaffold_tpu.config.schema import GPTConfig
from frl_distributed_ml_scaffold_tpu.dist.mesh import (
    BATCH_AXES,
    current_mesh_env,
)


def _num_groups(moe, n: int, b: int, train: bool) -> int:
    """Routing-group count for ``n`` tokens (batch dim ``b``).

    Explicit config must divide the token count in the TRAINING path —
    a silent gcd snap there would change per-group capacity semantics
    (different drop boundaries) with no signal, so it raises instead. In
    the decode path (train=False, tiny n = batch at one token per
    sequence) ``gcd`` snaps to the nearest divisor: a hard divisibility
    error would make every grouped-MoE checkpoint un-generatable.

    Auto (0) follows the mesh's batch sharding so each data shard routes
    its own tokens — snapped to ``gcd(b, shards)`` so the group dim always
    aligns with the batch dim (never cuts a group mid-sequence) and stays
    batch-sharded through every einsum; since g | b and n = b*t, g | n."""
    if moe.num_groups > 0:
        if train and b % moe.num_groups != 0:
            # Divide the BATCH dim, not merely n=b*t: a group that cuts a
            # sequence breaks the batch alignment the einsum sharding
            # relies on (same invariant as the auto path below); g | b
            # also gives g | n since n = b*t.
            raise ValueError(
                f"moe.num_groups={moe.num_groups} does not divide the "
                f"training batch dim b={b} (token count n={n}); a silent "
                "snap would change per-group capacity/drop semantics, and "
                "groups must align with the batch dim to stay "
                "batch-sharded. Pick a divisor of the batch size or use "
                "num_groups=0 (auto)."
            )
        # The gcd snap only serves decode (train=False, tiny n).
        return moe.num_groups if train else math.gcd(n, moe.num_groups)
    env = current_mesh_env()
    if env is None:
        return 1
    shards = 1
    for a in BATCH_AXES:
        shards *= env.mesh.shape.get(a, 1)
    return math.gcd(b, shards)


class MoEMlp(nn.Module):
    config: GPTConfig
    dtype: Any

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, *, train: bool, token_mask=None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """``capacity`` routing: ``(y, aux loss)``. ``dropless`` routing:
        ``(y, stats)`` with ``stats`` int32 ``[2]`` — distinct experts that
        got a pair, and pairs — over the tokens ``token_mask [B, T]`` keeps
        (padding columns and dead slot rows route nowhere)."""
        cfg = self.config
        moe = cfg.moe
        if moe.routing == "dropless":
            return self._dropless(x, token_mask)
        if moe.routing != "capacity":
            raise ValueError(
                f"moe.routing={moe.routing!r}: expected 'capacity' or "
                "'dropless'"
            )
        d = cfg.hidden_dim
        hidden = d * cfg.mlp_ratio
        e, k = moe.num_experts, moe.top_k
        if moe.dispatch not in ("einsum", "sort"):
            raise ValueError(
                f"moe.dispatch={moe.dispatch!r}: expected 'einsum' or 'sort'"
            )
        b, t, _ = x.shape
        n = b * t
        g = _num_groups(moe, n, b, train)
        s = n // g
        capacity = max(1, int(moe.capacity_factor * s * k / e))
        # Cast to the compute dtype here (the dense MLP gets this implicitly
        # from nn.Dense(dtype=...)); expert math below runs in this dtype so
        # the residual sum keeps the block's carry dtype stable under scan.
        xf = x.reshape(g, s, d).astype(self.dtype)

        # Router (fp32): probabilities over experts per token.
        router_logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32)
        )
        probs = jax.nn.softmax(router_logits, axis=-1)  # (G, S, E)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (G, S, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        # Position-in-expert via per-group cumulative counts, slot by slot
        # (slot-major: every token's first choice is seated before any
        # second choice, per GShard). The seating is SHARED by both
        # dispatch formulations, so routing/drop semantics are identical
        # and `test_moe_sorted_matches_einsum` can pin exact equivalence.
        pos_toks, keeps = [], []
        prev_counts = jnp.zeros((g, e), jnp.int32)
        for slot in range(k):
            onehot = jax.nn.one_hot(gate_idx[..., slot], e, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=1) - 1 + prev_counts[:, None, :]
            prev_counts = prev_counts + onehot.sum(axis=1)
            pos_tok = (pos * onehot).sum(-1)  # (G, S)
            pos_toks.append(pos_tok)
            keeps.append(pos_tok < capacity)

        # Expert computation: stacked params, expert axis shardable. The
        # group dim rides the batch sharding; the E dim the expert axis.
        wi = self.param(
            "wi", nn.initializers.normal(stddev=0.02), (e, d, hidden)
        )
        wo = self.param(
            "wo", nn.initializers.normal(stddev=0.02), (e, hidden, d)
        )

        if moe.dispatch == "sort":
            # Ragged (scatter/gather) exchange: seat indices scattered
            # into the [E*C] slot table, tokens gathered by index —
            # ~zero exchange MACs vs the einsum pair's O(S*E*C*D), which
            # at audited shapes costs as much as the expert FFN itself
            # (docs/perf_playbook.md "Dispatch FLOPs"). Sentinel row s /
            # slot e*c catches drops and empty seats (gathered as zeros,
            # scattered into the void via mode='drop').
            gi = jnp.arange(g)[:, None]
            token_idx = jnp.broadcast_to(jnp.arange(s)[None, :], (g, s))
            src = jnp.full((g, e * capacity), s, jnp.int32)
            for slot in range(k):
                dest = jnp.where(
                    keeps[slot],
                    gate_idx[..., slot] * capacity + pos_toks[slot],
                    e * capacity,
                )
                src = src.at[gi, dest].set(token_idx, mode="drop")
            x_pad = jnp.concatenate(
                [xf, jnp.zeros((g, 1, d), self.dtype)], axis=1
            )
            expert_in = (
                x_pad[gi, src]  # (G, E*C, D)
                .reshape(g, e, capacity, d)
                .transpose(1, 0, 2, 3)  # (E, G, C, D)
            )
            h = jax.nn.gelu(
                jnp.einsum("egcd,edh->egch", expert_in, wi.astype(self.dtype))
            )
            expert_out = jnp.einsum("egch,ehd->egcd", h, wo.astype(self.dtype))
            out_pad = jnp.concatenate(
                [
                    expert_out.transpose(1, 0, 2, 3).reshape(
                        g, e * capacity, d
                    ),
                    jnp.zeros((g, 1, d), self.dtype),
                ],
                axis=1,
            )
            y = jnp.zeros((g, s, d), self.dtype)
            for slot in range(k):
                idx = jnp.where(
                    keeps[slot],
                    gate_idx[..., slot] * capacity + pos_toks[slot],
                    e * capacity,
                )
                w = jnp.where(
                    keeps[slot], gate_vals[..., slot], 0.0
                ).astype(self.dtype)
                y = y + out_pad[gi, idx] * w[..., None]
        else:
            # One-hot einsum exchange (GShard): GSPMD turns the
            # dispatch/combine einsums into all_to_all on ICI.
            dispatch = jnp.zeros((g, s, e, capacity), self.dtype)
            combine = jnp.zeros((g, s, e, capacity), jnp.float32)
            for slot in range(k):
                onehot = jax.nn.one_hot(
                    gate_idx[..., slot], e, dtype=jnp.int32
                )
                pos_oh = jax.nn.one_hot(
                    pos_toks[slot], capacity, dtype=self.dtype
                )
                slot_dispatch = (
                    onehot.astype(self.dtype)[..., None]
                    * pos_oh[..., None, :]
                    * keeps[slot].astype(self.dtype)[..., None, None]
                )
                dispatch = dispatch + slot_dispatch
                combine = combine + slot_dispatch.astype(
                    jnp.float32
                ) * gate_vals[..., slot].astype(jnp.float32)[..., None, None]
            expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xf)
            h = jax.nn.gelu(
                jnp.einsum("egcd,edh->egch", expert_in, wi.astype(self.dtype))
            )
            expert_out = jnp.einsum(
                "egch,ehd->egcd", h, wo.astype(self.dtype)
            )
            y = jnp.einsum(
                "gsec,egcd->gsd", combine.astype(self.dtype), expert_out
            )  # and back

        # GShard load-balance loss, E * sum_e(frac_tokens_e * mean_prob_e),
        # with frac counting ALL k assignment slots (each slot contributes
        # 1/k so a perfectly uniform router scores frac_e = 1/E exactly as
        # in the top-1 form). prev_counts already holds the slot-summed
        # per-expert counts; gate_idx is integer so frac carries no
        # gradient either way — aux gradients flow through mean_prob.
        frac = prev_counts.sum(0).astype(jnp.float32) / (g * s * k)
        mean_prob = jnp.mean(probs, axis=(0, 1))
        aux = moe.router_aux_loss * e * jnp.sum(frac * mean_prob)
        # ST-MoE router z-loss: penalizes large router logits (bf16-unsafe
        # and softmax-saturating) without touching the routing decision.
        if moe.router_z_loss > 0.0:
            z = jax.nn.logsumexp(router_logits, axis=-1)  # (G, S)
            aux = aux + moe.router_z_loss * jnp.mean(z * z)

        return y.reshape(b, t, d), aux

    def _dropless(self, x, token_mask):
        """Top-k without drops, grouped by expert (module docstring)."""
        from frl_distributed_ml_scaffold_tpu.ops.grouped_experts import (
            expert_ffn_grouped,
            grouped_layout,
            tile_rows,
        )

        cfg, moe = self.config, self.config.moe
        d, e, k, f = cfg.hidden_dim, moe.num_experts, moe.top_k, moe.expert_dim
        b, t, _ = x.shape
        n = b * t
        xf = x.reshape(n, d).astype(self.dtype)
        # Router in fp32, from the norm's fp32 output and at full matmul
        # precision (the TPU's default would round both operands to bf16):
        # the scores decide which experts a token gets, and the eighth and
        # ninth of 256 lie closer than bf16 resolves.
        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, precision="highest",
            name="router",
        )(x.reshape(n, d).astype(jnp.float32))
        if moe.score_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif moe.score_func == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(
                f"moe.score_func={moe.score_func!r}: expected 'sigmoid' or "
                "'softmax'"
            )
        top, idx = jax.lax.top_k(scores, k)  # [N, k]
        if moe.norm_topk_prob:
            top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-20)
        top = top * moe.routed_scaling_factor

        init = nn.initializers.normal(stddev=0.02)
        w1 = self.param("w1", init, (e, d, f)).astype(self.dtype)
        w3 = self.param("w3", init, (e, d, f)).astype(self.dtype)
        # The down-projections lie flat, expert after expert: the sparse
        # layer is a feed-forward of width E x F of which a token uses k x F
        # rows (the layout of block-sparse expert libraries). The kernel's
        # DMA addresses expert e's rows e*F .. (e+1)*F - 1 where they lie.
        w2 = self.param("w2", init, (e * f, d)).astype(self.dtype)

        live = (
            jnp.ones((n,), bool) if token_mask is None
            else token_mask.reshape(n)
        )
        pair_expert = jnp.where(live[:, None], idx, e).reshape(n * k)
        tm = tile_rows(n * k)
        dest, src, tile_expert, n_used, counts = grouped_layout(
            pair_expert.astype(jnp.int32), e, tm
        )
        # Row r of the layout is pair src[r]'s token (none: a row of zeros).
        x_rows = jnp.concatenate([xf, jnp.zeros((1, d), self.dtype)])[
            jnp.minimum(src // k, n)
        ]
        y_rows = expert_ffn_grouped(
            x_rows, w1, w3, w2, tile_expert, n_used, tm=tm
        )
        pair_live = jnp.repeat(live, k)
        y_pairs = jnp.where(
            pair_live[:, None], y_rows[dest].astype(jnp.float32), 0.0
        ).reshape(n, k, d)
        y = jnp.einsum("nk,nkd->nd", top.astype(jnp.float32), y_pairs)
        if moe.num_shared_experts:
            y = y + _GatedMlp(
                moe.shared_expert_dim * moe.num_shared_experts, d, False,
                self.dtype, name="shared",
            )(xf).astype(jnp.float32)
        stats = jnp.stack([(counts > 0).sum(), counts.sum()]).astype(jnp.int32)
        return y.astype(self.dtype).reshape(b, t, d), stats


class _GatedMlp(nn.Module):
    """``(silu(x W1) * (x W3)) W2``: the gated feed-forward of the dense
    layers and of the shared expert."""

    width: int
    out: int
    bias: bool
    dtype: Any

    @nn.compact
    def __call__(self, x):
        return gated_ffn(x, self.width, self.out, self.bias, self.dtype)


def gated_ffn(x, width: int, out: int, bias: bool, dtype):
    """``(silu(x W1) * (x W3)) W2`` as three ``nn.Dense`` of the calling
    module (``w1``, ``w3``, ``w2``): the gated feed-forward of a dense layer
    (``GptMlp``) and of the shared expert."""
    dense = lambda n, name: nn.Dense(  # noqa: E731
        n, use_bias=bias, dtype=dtype, name=name
    )
    h = jax.nn.silu(dense(width, "w1")(x)) * dense(width, "w3")(x)
    return dense(out, "w2")(h)
