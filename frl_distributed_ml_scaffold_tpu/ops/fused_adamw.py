"""Fused AdamW update — the BACKLOG-5 bandwidth experiment (off by default).

The optax chain expresses one optimizer step as several tree_maps
(moment update → bias correction → decay → LR scale → apply), each a
param-sized elementwise pass XLA must fuse back together; the RN50 trace
shows ~7 ms/step in the optimizer+casts segment. This module fuses the
whole AdamW update for one leaf into ONE Pallas pass: 4 reads (g, m, v, p)
and 3 writes (m', v', p') at fp32 — the HBM floor for Adam-family state.

Honesty contract (the pool_grad=mask precedent): this is an EXPERIMENT.
``optimizer.name=fused_adamw`` is opt-in, numerically pinned to
``optax.adamw`` by tests, and ships as default only if the on-chip sweep
(tools/perf_sweep.py rn50_fused_opt) measures a win. Sharding note: a
pallas_call is opaque to GSPMD, so the kernel path is for
replicated-state configs (DDP / single chip — exactly the RN50 headline);
the trainer refuses ZeRO/FSDP configs (trainer/loop.py) because the
opaque call would silently all-gather the sharded state every step.

Non-TPU backends run the identical math as plain jnp (exact, fast) so CI
and sim meshes never touch Mosaic; the kernel itself is covered in
interpret mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

_LANES = 128
_BLOCK_ROWS = 512  # 512x128 fp32 = 256 KB per operand; 7 operands < 2 MB VMEM


class FusedAdamWState(NamedTuple):
    count: jax.Array  # int32 scalar
    mu: optax.Updates
    nu: optax.Updates


def _adamw_math(g, m, v, p, lr, bc1, bc2, *, b1, b2, eps, wd):
    """The update formula — single source shared by kernel and fallback.
    Matches optax.adamw exactly: scale_by_adam (bias-corrected) +
    add_decayed_weights + scale_by_learning_rate."""
    g = g.astype(jnp.float32)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * (g * g)
    mhat = m / bc1
    vhat = v / bc2
    p_new = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    return p_new, m, v


def _kernel(lr_ref, bc1_ref, bc2_ref, g_ref, m_ref, v_ref, p_ref,
            pn_ref, mn_ref, vn_ref, *, b1, b2, eps, wd):
    p_new, m_new, v_new = _adamw_math(
        g_ref[...], m_ref[...], v_ref[...], p_ref[...],
        lr_ref[0, 0], bc1_ref[0, 0], bc2_ref[0, 0],
        b1=b1, b2=b2, eps=eps, wd=wd,
    )
    pn_ref[...] = p_new
    mn_ref[...] = m_new
    vn_ref[...] = v_new


def _update_leaf(g, m, v, p, lr, bc1, bc2, *, b1, b2, eps, wd, interpret):
    """One leaf through the fused kernel: ravel → pad to a 2D lane grid →
    pallas_call → unpad. Padding lanes carry zeros (sqrt(0) is fine) and
    are sliced away."""
    from jax.experimental import pallas as pl

    shape, dtype = p.shape, p.dtype
    n = p.size
    per_block = _BLOCK_ROWS * _LANES
    padded = max(per_block, ((n + per_block - 1) // per_block) * per_block)
    rows = padded // _LANES

    def prep(x):
        flat = jnp.ravel(x).astype(jnp.float32)
        return jnp.pad(flat, (0, padded - n)).reshape(rows, _LANES)

    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    block_spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    out2d = jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)
    to2 = lambda s: jnp.asarray(s, jnp.float32).reshape(1, 1)
    pn, mn, vn = pl.pallas_call(
        functools.partial(_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[scalar_spec] * 3 + [block_spec] * 4,
        out_specs=[block_spec] * 3,
        out_shape=[out2d] * 3,
        interpret=interpret,
        name="fused_adamw",
    )(to2(lr), to2(bc1), to2(bc2), prep(g), prep(m), prep(v), prep(p))

    unpad = lambda x: x.reshape(-1)[:n].reshape(shape).astype(dtype)
    return unpad(pn), unpad(mn), unpad(vn)


def fused_adamw(
    learning_rate: optax.ScalarOrSchedule,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    interpret: bool | None = None,
) -> optax.GradientTransformation:
    """AdamW as one fused pass per leaf; optax-compatible.

    The returned transformation also carries ``fused_apply(grads, state,
    params) -> (new_params, new_state)`` — the train step uses it to skip
    the separate ``apply_updates`` pass; the standard ``update`` contract
    (returning deltas) stays available for generic callers at the cost of
    one extra subtraction pass.
    """

    def _lr(count):
        return (
            learning_rate(count)
            if callable(learning_rate)
            else jnp.asarray(learning_rate)
        )

    def init_fn(params):
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        return FusedAdamWState(
            count=jnp.zeros((), jnp.int32), mu=zeros(), nu=zeros()
        )

    def _apply(grads, state, params):
        if params is None:
            raise ValueError("fused_adamw requires params")
        t = optax.safe_int32_increment(state.count)
        # optax.adamw's scale_by_learning_rate evaluates the schedule at
        # the PRE-increment count while scale_by_adam bias-corrects with
        # the incremented one — match both exactly.
        lr = _lr(state.count)
        tf = t.astype(jnp.float32)
        bc1 = 1.0 - b1**tf
        bc2 = 1.0 - b2**tf

        backend = jax.default_backend()
        use_interpret = (
            interpret if interpret is not None else backend != "tpu"
        )
        use_fallback = use_interpret and backend != "tpu" and interpret is None

        def math_leaf(g, m, v, p):
            # Identical update without Mosaic; restores the param dtype
            # exactly like the kernel path's unpad (fp32 promotion would
            # otherwise flip a bf16 params tree to fp32 after one step —
            # retrace, donation mismatch, unrestorable checkpoints).
            pn, mn, vn = _adamw_math(
                g, m, v, p.astype(jnp.float32), lr, bc1, bc2,
                b1=b1, b2=b2, eps=eps, wd=weight_decay,
            )
            return pn.astype(p.dtype), mn, vn

        def leaf(g, m, v, p):
            # Sub-block leaves (BatchNorm scales, biases) skip the kernel:
            # padding them to the 512x128 tile would amplify their HBM
            # traffic ~1000x and pay a launch each — the plain math fuses
            # fine at that size.
            if use_fallback or p.size < _BLOCK_ROWS * _LANES:
                return math_leaf(g, m, v, p)
            return _update_leaf(
                g, m, v, p, lr, bc1, bc2,
                b1=b1, b2=b2, eps=eps, wd=weight_decay,
                interpret=use_interpret,
            )

        triples = jax.tree.map(leaf, grads, state.mu, state.nu, params)
        is_triple = lambda x: isinstance(x, tuple) and len(x) == 3
        pick = lambda i: jax.tree.map(
            lambda tr: tr[i], triples, is_leaf=is_triple
        )
        new_params = pick(0)
        new_state = FusedAdamWState(count=t, mu=pick(1), nu=pick(2))
        return new_params, new_state

    def update_fn(updates, state, params=None):
        new_params, new_state = _apply(updates, state, params)
        deltas = jax.tree.map(
            lambda np_, p: (np_ - p.astype(jnp.float32)).astype(p.dtype),
            new_params, params,
        )
        return deltas, new_state

    tx = optax.GradientTransformation(init_fn, update_fn)
    # Attach the direct path (GradientTransformation is a NamedTuple —
    # subclass-free attachment via __dict__ is unavailable, so wrap).
    return _FusedTransform(tx.init, tx.update, _apply)


class _FusedTransform(optax.GradientTransformation):
    """GradientTransformation + ``fused_apply`` (params/state in one step)."""

    def __new__(cls, init, update, fused_apply):
        self = super().__new__(cls, init, update)
        return self

    def __init__(self, init, update, fused_apply):
        self.fused_apply = fused_apply
