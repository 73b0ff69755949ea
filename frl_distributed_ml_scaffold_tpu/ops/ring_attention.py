"""Ring attention: sequence-parallel causal attention over the ``seq`` axis.

SURVEY C8 / §5. The sequence dimension is sharded across the ``seq`` mesh
axis; each shard keeps its queries resident while the K/V shards rotate
around the ring via ``ppermute`` (one neighbor hop per step — this is what
rides the ICI torus links). Each hop's compute is the fused Pallas flash
kernel (ops/flash_attention.py) on TPU — per-hop VMEM stays O(block·D) and
no shard ever materializes even its local [T_local, T_local] score matrix —
so context length is bounded by HBM across the ring, not by any quadratic
buffer. Off-TPU the hops use the identical-numerics dense-with-lse path.

Hop results merge exactly by per-row logsumexp: each hop returns its block
output normalized by its own (o, lse); ``logaddexp`` combines them into the
running global (o, lse). Hops strictly above the causal diagonal skip their
compute entirely (``lax.cond`` — only the ppermute runs).

Backward is a custom VJP (the memory win would otherwise be lost to saved
per-hop K/V residuals): only the LOCAL (q, k, v, o, lse) are saved; the
backward re-rotates K/V around the ring together with traveling dK/dV
accumulators, each hop calling the flash backward kernels with the global
lse (``p = exp(s - lse)`` is exact per block). Accumulators travel in fp32.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from frl_distributed_ml_scaffold_tpu.dist.mesh import BATCH_AXES, current_mesh_env

_NEG_INF = -1.0e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "seq",
    causal: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, T, H, D) attention with T sharded over ``axis_name``.

    Called from model code tracing under the GSPMD jit; wraps its own
    shard_map region over the current mesh. Falls back to single-device
    blockwise math when the seq axis is trivial. ``interpret`` forces the
    per-hop Pallas kernels into interpreter mode (tests on CPU); ``None``
    picks pallas-on-TPU / dense-elsewhere automatically.
    """
    env = current_mesh_env()
    if env is None or env.axis_size(axis_name) == 1:
        return dense_attention(q, k, v, causal=causal)

    spec = P(BATCH_AXES, axis_name, "model", None)
    inner = partial(
        _ring_shard_fn, axis_name=axis_name, causal=causal, interpret=interpret
    )
    from frl_distributed_ml_scaffold_tpu.dist.mesh import shard_map_unchecked

    return shard_map_unchecked(
        inner,
        mesh=env.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)


def _ring_shard_fn(q, k, v, *, axis_name: str, causal: bool, interpret):
    # Flash kernels run in (B, H, T, D); these transposes sit against the
    # projection reshapes outside and fuse in XLA.
    qT, kT, vT = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o = _ring(qT, kT, vT, axis_name, causal, interpret)
    return o.transpose(0, 2, 1, 3)


def _merge(o_run, lse_run, o_blk, lse_blk):
    """Exact combine of two self-normalized partial attentions (fp32)."""
    lse_new = jnp.logaddexp(lse_run, lse_blk)
    w_run = jnp.exp(lse_run - lse_new)
    w_blk = jnp.exp(lse_blk - lse_new)
    o_new = o_run * w_run + o_blk.astype(jnp.float32) * w_blk
    return o_new, lse_new


def _ring_fwd_loop(q, k, v, axis_name, causal, interpret):
    """``lax.fori_loop`` over hops 1..n-1 (hop 0, the diagonal, is special)
    so traced program size stays O(1) in the ring size."""
    from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
        block_attention_fwd,
    )

    from frl_distributed_ml_scaffold_tpu.dist.collectives import axis_size

    idx = lax.axis_index(axis_name)
    n = axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Hop 0: the diagonal block (q and k share a position origin).
    o0, lse0 = block_attention_fwd(q, k, v, causal=causal, interpret=interpret)

    def body(s, carry):
        k_blk, v_blk, o, lse = carry
        k_blk, v_blk = lax.ppermute((k_blk, v_blk), axis_name, perm)
        if causal:
            # After s rotations this shard holds the block from idx - s.
            src = (idx - s) % n
            o_s, lse_s = lax.cond(
                src < idx,  # blocks from the future contribute nothing
                lambda a, b, c: block_attention_fwd(
                    a, b, c, causal=False, interpret=interpret
                ),
                lambda a, b, c: (
                    jnp.zeros_like(o0),
                    jnp.full_like(lse0, _NEG_INF),
                ),
                q,
                k_blk,
                v_blk,
            )
        else:
            o_s, lse_s = block_attention_fwd(
                q, k_blk, v_blk, causal=False, interpret=interpret
            )
        o, lse = _merge(o, lse, o_s, lse_s)
        return (k_blk, v_blk, o, lse)

    _, _, o, lse = lax.fori_loop(
        1, n, body, (k, v, o0.astype(jnp.float32), lse0)
    )
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring(q, k, v, axis_name, causal, interpret):
    o, _ = _ring_fwd_loop(q, k, v, axis_name, causal, interpret)
    return o


def _ring_fwd_rule(q, k, v, axis_name, causal, interpret):
    o, lse = _ring_fwd_loop(q, k, v, axis_name, causal, interpret)
    return o, (q, k, v, o, lse)


def _ring_bwd_rule(axis_name, causal, interpret, res, do):
    from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
        block_attention_bwd,
    )

    q, k, v, o, lse = res
    from frl_distributed_ml_scaffold_tpu.dist.collectives import axis_size

    idx = lax.axis_index(axis_name)
    n = axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Hop 0: diagonal. dK/dV accumulators then TRAVEL with their block
    # around the ring (fp32), so each visiting device adds its contribution
    # in place; after the final rotation they arrive back home complete.
    dq0, dk0, dv0 = block_attention_bwd(
        q, k, v, o, lse, do, causal=causal, interpret=interpret
    )

    def _live(args):
        q_, k_, v_, o_, lse_, do_ = args
        return block_attention_bwd(
            q_, k_, v_, o_, lse_, do_, causal=False, interpret=interpret
        )

    def _dead(args):
        q_, k_, v_, _o, _l, _d = args
        return jnp.zeros_like(q_), jnp.zeros_like(k_), jnp.zeros_like(v_)

    def body(s, carry):
        k_blk, v_blk, dq, dk_acc, dv_acc = carry
        k_blk, v_blk, dk_acc, dv_acc = lax.ppermute(
            (k_blk, v_blk, dk_acc, dv_acc), axis_name, perm
        )
        if causal:
            src = (idx - s) % n
            dq_s, dk_s, dv_s = lax.cond(
                src < idx, _live, _dead, (q, k_blk, v_blk, o, lse, do)
            )
        else:
            dq_s, dk_s, dv_s = _live((q, k_blk, v_blk, o, lse, do))
        return (
            k_blk,
            v_blk,
            dq + dq_s.astype(jnp.float32),
            dk_acc + dk_s.astype(jnp.float32),
            dv_acc + dv_s.astype(jnp.float32),
        )

    _, _, dq, dk_acc, dv_acc = lax.fori_loop(
        1,
        n,
        body,
        (k, v, dq0.astype(jnp.float32), dk0.astype(jnp.float32),
         dv0.astype(jnp.float32)),
    )
    # n-1 rotations have happened; one more brings each block's dK/dV home.
    dk_acc, dv_acc = lax.ppermute((dk_acc, dv_acc), axis_name, perm)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


_ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def dense_attention(q, k, v, *, causal: bool = True):
    """(B, T, H, D) dense attention — the numerics contract all sharded
    paths reduce to when their axis is trivial.

    MXU-friendly mixed precision: einsum operands stay in the input dtype
    (bf16 under the mixed policy) with fp32 accumulation
    (``preferred_element_type``) — the MXU's native bf16-multiply /
    fp32-accumulate mode — and the softmax itself is fp32.
    """
    t, d = q.shape[1], q.shape[3]
    scale = 1.0 / np.sqrt(d)
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


# Backwards-compat private alias (pre-public-export importers).
_single_shard_attention = dense_attention
