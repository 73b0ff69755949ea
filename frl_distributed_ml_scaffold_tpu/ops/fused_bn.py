"""Fused BatchNorm-backward Pallas TPU kernel — the priced RN50 HBM fix.

The v5e trace (docs/perf_playbook.md "Where the remaining RN50 gap lives")
pins ~150 ms of the 227 ms headline step in bandwidth-bound BN/ReLU-backward
fusions: the autodiff BN backward materializes dx̂ and the stat-gradient
intermediates, so each BN layer's activation is read and its gradient
written several times around the statistics reductions. This module replaces
ONLY the backward of train-mode BatchNorm with a two-kernel Pallas chain at
the exact-math HBM floor:

  1. **reduction pass** — one stream over (x, dy) producing the per-channel
     sums ``dβ = Σ dy`` and ``dγ = Σ dy·x̂``, with x̂ RECOMPUTED in-register
     from (x, μ, σ) rather than saved by the forward;
  2. **dx pass** — one stream over (x, dy) producing
     ``dx = (γ/σ)·(dy − dβ/M − x̂·dγ/M)`` directly, no dx̂ / no broadcasted
     stat-grad tensors ever touching HBM.

Total HBM traffic: x and dy read twice each, dx written once — the floor
for the exact (non-approximated) BN backward, since dx depends on full-batch
reductions of dy. The forward is byte-identical to ``flax.linen.BatchNorm``
(same fp32 fast-variance stats, same promote-then-cast normalize), swapped
in via ``jax.custom_vjp`` — so ``model.fused_bn=true`` changes backward
scheduling, never training math.

Sharding (the fused_adamw honesty-contract lesson, solved rather than
refused this time): a ``pallas_call`` is opaque to GSPMD, but BN backward is
**sync-BN** — the sums span the global batch. Under a mesh with a populated
batch axis the backward shard_maps over ``("data", "fsdp")``: each shard
runs the reduction kernel on its local rows, one ``lax.psum`` merges the
per-channel sums (the same collective autodiff's sync-BN backward needs),
and the dx kernel runs shard-local. Off-mesh the kernels run directly.

Non-TPU backends run the identical math as plain jnp (exact, fast) so CI
and sim meshes never touch Mosaic by default; the kernels themselves are
covered in interpreter mode (``interpret=True`` / ``FORCE_INTERPRET``),
mirroring the ``fused_adamw.py`` / ``flash_attention.py`` pattern.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_LANES = 128

#: Test hook: force the Pallas interpreter through call paths that do not
#: expose an ``interpret`` argument (the Trainer → ResNet → FusedBatchNorm
#: chain). None = route by backend (TPU: compiled kernel; else: jnp math).
FORCE_INTERPRET: bool | None = None


def _rows_per_block(c_pad: int) -> int:
    """Row-block size for a (rows, C) grid: ~1 MB of fp32 per operand block,
    power of two, sublane-aligned. At RN50's widest BN (C=2048) this is 128
    rows; at the stem (C=64 → padded 128) it is 1024."""
    target = 256 * 1024  # fp32 elements per operand block
    return int(max(8, min(1024, 2 ** int(np.log2(max(8, target // c_pad))))))


def _use_kernel(interpret: bool | None) -> tuple[bool, bool]:
    """(run_pallas, interpret_flag) — same routing contract as fused_adamw:
    TPU compiles the kernel, non-TPU defaults to the identical jnp math,
    and tests opt into the interpreter explicitly."""
    if interpret is None:
        interpret = FORCE_INTERPRET
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        return on_tpu, False
    return True, bool(interpret)


# ------------------------------------------------------------------ forward


def _bn_train_forward(x, scale, bias, eps, out_dtype):
    """Train-mode BN forward, mirroring flax ``_compute_stats`` (fp32
    fast-variance, clipped non-negative) + ``_normalize`` (promoted math,
    single final cast) op for op — the numerics the tests pin against
    ``nn.BatchNorm``. Returns (y, mean, var); stats are fp32 (C,)."""
    x32 = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = x32.mean(axes)
    mean2 = jnp.square(x32).mean(axes)
    var = jnp.maximum(0.0, mean2 - jnp.square(mean))
    y = x32 - mean
    mul = lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    y = y * mul + bias.astype(jnp.float32)
    return y.astype(out_dtype), mean, var


# ----------------------------------------------------------------- backward


def _fallback_bwd(x, dy, scale, mean, var, eps):
    """The backward formula as plain jnp — the identical-math non-TPU path
    (XLA fuses it fine at CI scale) and the reference the kernels mirror."""
    axes = tuple(range(x.ndim - 1))
    m = float(np.prod([x.shape[a] for a in axes]))
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    inv = lax.rsqrt(var + eps)
    xhat = (x32 - mean) * inv
    dbeta = dy32.sum(axes)
    dgamma = (dy32 * xhat).sum(axes)
    gi = scale.astype(jnp.float32) * inv
    dx = gi * (dy32 - dbeta * (1.0 / m) - xhat * (dgamma * (1.0 / m)))
    return dx.astype(x.dtype), dgamma, dbeta


def _sums_kernel(x_ref, dy_ref, mean_ref, inv_ref, db_ref, dg_ref,
                 acc_b, acc_g):
    """Pass 1: per-channel Σdy and Σdy·x̂ over the row grid. VMEM scratch
    accumulators persist across the sequential TPU grid; x̂ is recomputed
    from the resident (x, μ, 1/σ) tiles — it never exists in HBM."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_b[...] = jnp.zeros_like(acc_b)
        acc_g[...] = jnp.zeros_like(acc_g)

    dy32 = dy_ref[...].astype(jnp.float32)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    acc_b[...] += dy32.sum(axis=0, keepdims=True)
    acc_g[...] += (dy32 * xhat).sum(axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        db_ref[...] = acc_b[...]
        dg_ref[...] = acc_g[...]


def _dx_kernel(x_ref, dy_ref, mean_ref, inv_ref, gi_ref, k1_ref, k2_ref,
               dx_ref):
    """Pass 2: dx = (γ/σ)·(dy − dβ/M − x̂·dγ/M), one streamed read of
    (x, dy) and one write of dx. k1 = dβ/M, k2 = dγ/M precomputed (C,)."""
    dy32 = dy_ref[...].astype(jnp.float32)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    dx = gi_ref[...] * (dy32 - k1_ref[...] - xhat * k2_ref[...])
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _pad_2d(a2d, rows_pad, c_pad):
    r, c = a2d.shape
    return jnp.pad(a2d, ((0, rows_pad - r), (0, c_pad - c)))


def _vec(v, c_pad):
    return jnp.pad(v.astype(jnp.float32), (0, c_pad - v.shape[0])).reshape(1, -1)


def _kernel_sums(x2d, dy2d, mean, var, eps, interpret):
    """(Σdy, Σdy·x̂) over local rows via the pass-1 kernel. Row/channel
    padding is zero-filled on dy, so padded positions contribute nothing."""
    import jax.experimental.pallas as pl

    r, c = x2d.shape
    c_pad = max(_LANES, -(-c // _LANES) * _LANES)
    rb = _rows_per_block(c_pad)
    rows_pad = max(rb, -(-r // rb) * rb)
    blk = pl.BlockSpec((rb, c_pad), lambda i: (i, 0))
    vec = pl.BlockSpec((1, c_pad), lambda i: (0, 0))
    out = jax.ShapeDtypeStruct((1, c_pad), jnp.float32)
    from jax.experimental.pallas import tpu as pltpu

    inv = lax.rsqrt(var + eps)
    db, dg = pl.pallas_call(
        _sums_kernel,
        grid=(rows_pad // rb,),
        in_specs=[blk, blk, vec, vec],
        out_specs=[vec, vec],
        out_shape=[out, out],
        scratch_shapes=[
            pltpu.VMEM((1, c_pad), jnp.float32),
            pltpu.VMEM((1, c_pad), jnp.float32),
        ],
        interpret=interpret,
        name="bn_bwd_reduce",
    )(
        _pad_2d(x2d, rows_pad, c_pad),
        _pad_2d(dy2d, rows_pad, c_pad),
        _vec(mean, c_pad),
        _vec(inv, c_pad),
    )
    return db[0, :c], dg[0, :c]


def _kernel_dx(x2d, dy2d, scale, mean, var, dgamma, dbeta, eps, m, interpret):
    """dx over local rows via the pass-2 kernel; ``m`` is the GLOBAL count."""
    import jax.experimental.pallas as pl

    r, c = x2d.shape
    c_pad = max(_LANES, -(-c // _LANES) * _LANES)
    rb = _rows_per_block(c_pad)
    rows_pad = max(rb, -(-r // rb) * rb)
    blk = pl.BlockSpec((rb, c_pad), lambda i: (i, 0))
    vec = pl.BlockSpec((1, c_pad), lambda i: (0, 0))
    inv = lax.rsqrt(var + eps)
    gi = scale.astype(jnp.float32) * inv
    dx = pl.pallas_call(
        _dx_kernel,
        grid=(rows_pad // rb,),
        in_specs=[blk, blk, vec, vec, vec, vec, vec],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows_pad, c_pad), x2d.dtype),
        interpret=interpret,
        name="bn_bwd_dx",
    )(
        _pad_2d(x2d, rows_pad, c_pad),
        _pad_2d(dy2d, rows_pad, c_pad),
        _vec(mean, c_pad),
        _vec(inv, c_pad),
        _vec(gi, c_pad),
        _vec(dbeta * (1.0 / m), c_pad),
        _vec(dgamma * (1.0 / m), c_pad),
    )
    return dx[:r, :c]


def _pallas_bwd_local(x, dy, mean, var, eps, interpret):
    """Pass-1 kernel on LOCAL rows; the caller psums the returned partial
    sums when sharded. NHWC→(rows, C) reshapes are free (row-major,
    feature axis last)."""
    c = x.shape[-1]
    x2d = x.reshape(-1, c)
    dy2d = dy.reshape(-1, c)
    dbeta, dgamma = _kernel_sums(x2d, dy2d, mean, var, eps, interpret)
    return x2d, dy2d, dgamma, dbeta


def _bn_bwd_dispatch(x, dy, scale, mean, var, eps, interpret):
    """Route the backward: jnp math off-TPU (unless interpret is forced),
    else the Pallas chain — shard_mapped over the batch axes when the
    ambient mesh shards the batch, with one psum merging the channel sums
    (sync-BN, matching the forward's global statistics)."""
    run_pallas, interp = _use_kernel(interpret)
    if not run_pallas:
        return _fallback_bwd(x, dy, scale, mean, var, eps)

    m_global = float(np.prod(x.shape[:-1]))

    def local(x_l, dy_l, scale_r, mean_r, var_r, *, axis_names):
        x2d, dy2d, dgamma, dbeta = _pallas_bwd_local(
            x_l, dy_l, mean_r, var_r, eps, interp
        )
        if axis_names:
            dgamma = lax.psum(dgamma, axis_names)
            dbeta = lax.psum(dbeta, axis_names)
        dx2d = _kernel_dx(
            x2d, dy2d, scale_r, mean_r, var_r, dgamma, dbeta, eps,
            m_global, interp,
        )
        return dx2d.reshape(x_l.shape), dgamma, dbeta

    from frl_distributed_ml_scaffold_tpu.dist.mesh import (
        BATCH_AXES,
        current_mesh_env,
        shard_map_unchecked,
    )

    env = current_mesh_env()
    if env is None or env.batch_axis_size <= 1:
        return local(x, dy, scale, mean, var, axis_names=())
    if x.shape[0] % env.batch_axis_size != 0:
        # shard_map needs exact divisibility; GSPMD-padded odd batches take
        # the identical-math jnp path rather than silently all-gathering
        # around an opaque kernel.
        return _fallback_bwd(x, dy, scale, mean, var, eps)
    from jax.sharding import PartitionSpec as P

    batch = P(BATCH_AXES, *([None] * (x.ndim - 1)))
    rep = P()
    return shard_map_unchecked(
        functools.partial(local, axis_names=BATCH_AXES),
        mesh=env.mesh,
        in_specs=(batch, batch, rep, rep, rep),
        out_specs=(batch, rep, rep),
    )(x, dy, scale, mean, var)


# ----------------------------------------------------------- custom-vjp tie


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bn_train(eps, out_dtype, interpret, x, scale, bias):
    return _bn_train_forward(x, scale, bias, eps, out_dtype)


def _bn_train_fwd(eps, out_dtype, interpret, x, scale, bias):
    y, mean, var = _bn_train_forward(x, scale, bias, eps, out_dtype)
    # Residuals: x̂ is NOT saved — the backward recomputes it from
    # (x, mean, var), which is the whole HBM win.
    return (y, mean, var), (x, scale, bias, mean, var)


def _bn_train_bwd(eps, out_dtype, interpret, res, cts):
    x, scale, bias, mean, var = res
    dy, _, _ = cts
    # The mean/var outputs exist ONLY to feed the (non-differentiated)
    # running-average update; the module below stop_gradients them, so
    # their cotangents are structurally zero and the backward covers y
    # alone. This function is private to FusedBatchNorm for that reason.
    dx, dgamma, dbeta = _bn_bwd_dispatch(
        x, dy, scale, mean, var, eps, interpret
    )
    return dx.astype(x.dtype), dgamma.astype(scale.dtype), dbeta.astype(bias.dtype)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def fused_bn_train(x, scale, bias, *, eps=1e-5, out_dtype=None,
                   interpret: bool | None = None):
    """Train-mode BatchNorm with the fused Pallas backward.

    Returns ``(y, mean, var)``; ``mean``/``var`` are the fp32 batch stats
    for running-average updates and must not be differentiated through
    (wrap them in ``stop_gradient``, as ``FusedBatchNorm`` does). Forward
    numerics match ``nn.BatchNorm`` exactly; ``out_dtype=None`` applies the
    flax promotion rule (promote of x/scale/bias dtypes).
    """
    if out_dtype is None:
        out_dtype = jnp.promote_types(
            jnp.promote_types(x.dtype, scale.dtype), bias.dtype
        )
    return _bn_train(eps, jnp.dtype(out_dtype), interpret, x, scale, bias)


# ------------------------------------------------------------------ module


class FusedBatchNorm(nn.BatchNorm):
    """``nn.BatchNorm`` drop-in: identical params/variables/forward, the
    train-mode backward replaced by the fused kernel chain.

    Configurations outside the kernel's contract (non-trailing feature
    axis, pmap-style ``axis_name`` stats, masking, slow variance, disabled
    scale/bias) delegate wholesale to ``nn.BatchNorm`` — as does eval mode,
    whose running-stat normalize has no reduction chain to fuse.
    """

    interpret: bool | None = None

    @nn.compact
    def __call__(self, x, use_running_average: bool | None = None, *,
                 mask=None):
        use_running_average = nn.merge_param(
            "use_running_average",
            self.use_running_average,
            use_running_average,
        )
        fusable = (
            not use_running_average
            and mask is None
            and self.axis == -1
            and self.axis_name is None
            and self.axis_index_groups is None
            and self.use_fast_variance
            and self.force_float32_reductions
            and self.use_bias
            and self.use_scale
        )
        if not fusable:
            # merge_param refuses a value given both at construction and at
            # call time — forward the call-time value only when the
            # constructor left it unset.
            ura = None if self.use_running_average is not None else use_running_average
            return super().__call__(x, use_running_average=ura, mask=mask)

        feature_shape = (x.shape[-1],)
        # Same variable/param names and creation order as nn.BatchNorm —
        # checkpoints and partition rules see an identical tree.
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), feature_shape,
        )
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), feature_shape,
        )
        scale = self.param(
            "scale", self.scale_init, feature_shape, self.param_dtype
        )
        bias = self.param(
            "bias", self.bias_init, feature_shape, self.param_dtype
        )
        from flax.linen import dtypes as _dtypes

        out_dtype = _dtypes.canonicalize_dtype(x, scale, bias, dtype=self.dtype)
        y, mean, var = fused_bn_train(
            x, scale, bias, eps=self.epsilon, out_dtype=out_dtype,
            interpret=self.interpret,
        )
        if not self.is_initializing():
            mean = lax.stop_gradient(mean)
            var = lax.stop_gradient(var)
            ra_mean.value = (
                self.momentum * ra_mean.value + (1 - self.momentum) * mean
            )
            ra_var.value = (
                self.momentum * ra_var.value + (1 - self.momentum) * var
            )
        return y
