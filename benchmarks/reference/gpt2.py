"""Plain reference for GPT-2: forward, loss, gradients and the AdamW step.

Straightforward jax.numpy in float32 with every matrix product at 'highest'
precision; no kernels, no cache, no batching tricks. It follows Radford et
al. 2019 as the published config.json states it (pre-LayerNorm blocks, learned
positions, tanh-GELU, tied output head). It imports nothing of the program and
is handed the weights the benchmark made (lib/weights.py), as a flat
{name: array} dict whose names are the checkpoint layout (`param_shapes`):

  wte/embedding [V,D]  wpe [T,D]  ln_f/{scale,bias} [D]
  blocks/{ln1,ln2}/{scale,bias} [L,D]
  blocks/attn/{query,key,value,out}/{kernel [L,D,D], bias [L,D]}
  blocks/mlp/fc_in/{kernel [L,D,4D], bias [L,4D]}
  blocks/mlp/fc_out/{kernel [L,4D,D], bias [L,D]}

`lowp=True` is the control of the comparison that decides `correct`: the same
mathematics with both operands of every matrix product rounded to float8
under a per-tensor scale (e4m3 forward, e5m2 for the cotangents that the
backward products take), the nearest precision below the bfloat16 the
configurations state. Memory: layers are scanned under jax.checkpoint
and rows are taken in blocks, so the full width fits beside nothing else.

The drivers call `param_shapes`, `train_steps`, `features` and `head`, and
hand each the configuration file's `model` group whole (README.md, "The
reference's interface"); of it this module reads `num_layers`, `hidden_dim`,
`num_heads`, `seq_len`, `vocab_size`, `mlp_ratio` (4 where absent) and
`layer_norm_epsilon` (1e-5 where absent).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn
F8_GRAD_MAX = 57344.0  # largest finite float8_e5m2


def _q8(x, whole=None):
    """Round to float8_e4m3 under a per-tensor scale; gradient passes through.
    `whole` is the tensor the scale is taken from where `x` is a part of it."""
    scale = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x if whole is None else whole)) / F8_MAX + 1e-30)
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(y - x)


@jax.custom_vjp
def _q_cotangent(x):
    """Identity whose cotangent is rounded to float8_e5m2 under a per-tensor
    scale, so that the backward products take float8 operands too."""
    return x


def _q_cotangent_fwd(x):
    return x, None


def _q_cotangent_bwd(_, g):
    scale = jnp.max(jnp.abs(g)) / F8_GRAD_MAX + 1e-30
    return ((g / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale,)


_q_cotangent.defvjp(_q_cotangent_fwd, _q_cotangent_bwd)


def _mm(a, b, lowp, b_whole=None):
    if lowp:
        return _q_cotangent(jnp.matmul(_q8(a), _q8(b, b_whole), precision="highest"))
    return jnp.matmul(a, b, precision="highest")


def param_shapes(model):
    """The checkpoint layout as a tree of shapes, from the sizes alone."""
    l, d, v, t = (model[k] for k in ("num_layers", "hidden_dim", "vocab_size", "seq_len"))
    f = d * model.get("mlp_ratio", 4)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dense = lambda i, o: {"bias": s(l, o), "kernel": s(l, i, o)}
    ln = lambda *lead: {"bias": s(*lead, d), "scale": s(*lead, d)}
    return {
        "blocks": {
            "attn": {n: dense(d, d) for n in ("key", "out", "query", "value")},
            "ln1": ln(l), "ln2": ln(l),
            "mlp": {"fc_in": dense(d, f), "fc_out": dense(f, d)},
        },
        "ln_f": ln(),
        "wpe": s(t, d),
        "wte": {"embedding": s(v, d)},
    }


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _block(x, p, heads, eps, lowp):
    b, t, d = x.shape
    hd = d // heads
    y = _layer_norm(x, p["ln1/scale"], p["ln1/bias"], eps)
    q, k, v = (
        (_mm(y, p[f"attn/{n}/kernel"], lowp) + p[f"attn/{n}/bias"])
        .reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
        for n in ("query", "key", "value")
    )
    scores = _mm(q, k.transpose(0, 1, 3, 2), lowp) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = _mm(jax.nn.softmax(scores, axis=-1), v, lowp)
    att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _mm(att, p["attn/out/kernel"], lowp) + p["attn/out/bias"]
    y = _layer_norm(x, p["ln2/scale"], p["ln2/bias"], eps)
    y = _gelu_tanh(_mm(y, p["mlp/fc_in/kernel"], lowp) + p["mlp/fc_in/bias"])
    return x + _mm(y, p["mlp/fc_out/kernel"], lowp) + p["mlp/fc_out/bias"]


def features(params, tokens, model, lowp=False):
    """Final-LayerNorm features [B,T,D] for token ids [B,T]."""
    heads, eps = model["num_heads"], model.get("layer_norm_epsilon", 1e-5)
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    t = tokens.shape[1]
    x = params["wte/embedding"][tokens] + params["wpe"][:t]
    stack = {k[len("blocks/"):]: v for k, v in params.items()
             if k.startswith("blocks/")}

    @jax.checkpoint
    def body(x, layer):
        return _block(x, layer, heads, eps, lowp), None

    x, _ = jax.lax.scan(body, x, stack)
    return _layer_norm(x, params["ln_f/scale"], params["ln_f/bias"], eps)


def head(params, feats, lo, n, model, lowp=False):
    """Logits [..., n] of the vocabulary's rows lo .. lo + n - 1 (the tied
    output head) for features [..., D]: the serving comparison takes the head
    in such blocks, so that no [T, V] array is made. `n` is static, `lo` may
    be traced; under `lowp` the scale is the whole matrix's."""
    w = params["wte/embedding"].astype(jnp.float32)
    rows = jax.lax.dynamic_slice_in_dim(w, lo, n, axis=0)
    return _mm(feats, rows.T, lowp, b_whole=w)


def logits(params, tokens, model, lowp=False):
    f = features(params, tokens, model, lowp)
    return _mm(f, params["wte/embedding"].astype(jnp.float32).T, lowp)


def loss_sum(params, tokens, model, lowp=False):
    """Summed next-token cross-entropy over tokens [B,T+1]."""
    lg = logits(params, tokens[:, :-1], model, lowp)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.sum()


def loss_and_grad(params, tokens, model, lowp=False, rows_per_block=1):
    """Mean loss and its gradient over the whole batch, rows in blocks."""
    b, t1 = tokens.shape
    blocks = tokens.reshape(b // rows_per_block, rows_per_block, t1)
    grad_fn = jax.value_and_grad(lambda p, tk: loss_sum(p, tk, model, lowp))
    zero = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32), params)

    def body(acc, tk):
        l, g = grad_fn(params, tk)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    (total, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
    n = b * (t1 - 1)
    return total / n, jax.tree.map(lambda g: g / n, grads)


def learning_rate(count, opt):
    """Linear warm-up from 0, then cosine to 0 at total_steps."""
    w, total, base = opt["warmup_steps"], opt["total_steps"], opt["learning_rate"]
    count = jnp.asarray(count, jnp.float32)
    warm = base * count / max(w, 1)
    frac = jnp.clip((count - w) / max(total - w, 1), 0.0, 1.0)
    cos = base * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(count < w, warm, cos) if w > 0 else cos


def adamw_step(params, grads, mu, nu, count, opt):
    """Clip by global norm, Adam moments with bias correction, decoupled
    weight decay on every leaf, times minus the learning rate. Returns the
    new (params, mu, nu) and the gradient as the moments received it."""
    clip = opt.get("grad_clip_norm")
    if clip is not None:
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        factor = jnp.where(gnorm < clip, 1.0, clip / gnorm)
        grads = {k: g * factor for k, g in grads.items()}
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr = learning_rate(count, opt)
    c = jnp.asarray(count, jnp.float32) + 1.0
    new_p, new_mu, new_nu = {}, {}, {}
    for k, p in params.items():
        m = b1 * mu[k] + (1.0 - b1) * grads[k]
        v = b2 * nu[k] + (1.0 - b2) * grads[k] ** 2
        update = (m / (1.0 - b1**c)) / (jnp.sqrt(v / (1.0 - b2**c)) + eps)
        new_p[k] = p - lr * (update + opt["weight_decay"] * p)
        new_mu[k], new_nu[k] = m, v
    return new_p, new_mu, new_nu, grads


def train_steps(params, batches, opt, model, lowp=False, fault=None,
                rows_per_block=1):
    """Follow the first len(batches) optimizer steps from `params`.

    Returns per-step losses, the first gradient as the moments got it (on the
    host) with its per-leaf norm, and the per-leaf norm of the parameters'
    change after the last step. `fault`
    plants one of the faults the comparison must catch: "half_batch" takes
    the mean over the first half of the rows only."""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}

    @jax.jit
    def step(p, mu, nu, count, tokens):
        loss, g = loss_and_grad(p, tokens, model, lowp, rows_per_block)
        new_p, mu, nu, seen = adamw_step(p, g, mu, nu, count, opt)
        return new_p, mu, nu, loss, seen, {k: jnp.sqrt(jnp.sum(v * v)) for k, v in seen.items()}

    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    p, mu, nu = params, zeros, dict(zeros)
    losses, first, norms = [], None, None
    for i, tokens in enumerate(batches):
        tokens = jnp.asarray(tokens)
        if fault == "half_batch":
            tokens = tokens[: tokens.shape[0] // 2]
        p, mu, nu, loss, seen, seen_norms = step(p, mu, nu, i, tokens)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(seen)
            norms = {k: float(v) for k, v in seen_norms.items()}
        del seen, seen_norms
    change = {k: float(jnp.sqrt(jnp.sum((p[k] - params[k]) ** 2))) for k in p}
    return {"loss": losses, "grad_norm": norms, "update_norm": change, "first_grad": first}
