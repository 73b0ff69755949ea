"""Plain reference for Laguna-XS.2 (poolside): the forward pass, served only.

Straightforward jax.numpy in float32 with every matrix product at 'highest'
precision; no kernel, no cache, no batching, nothing sorted or grouped. It
follows the published config.json (huggingface.co/poolside/Laguna-XS.2) as
ISSUE 31 wrote the layer equations down from it, and imports nothing of the
program. For a layer of type t with H_t query heads, x [T, D]:

  h = RMSNorm(x);  q = h Wq [T, H_t, hd],  k = h Wk,  v = h Wv [T, Hkv, hd]
  rotary of type t on q and k (full: yarn over the first half of a head's
  dimensions, cos and sin times attention_factor; sliding: plain, all of them)
  head i reads KV head i // (H_t / Hkv); position p attends p - W < j <= p
  (W = sliding_window in a sliding layer, unbounded in a full one);
  scores * hd ** -0.5, softmax in float32
  g = sigmoid(h Wg) [T, H_t] multiplies each head's output
  x = x + concat(heads) Wo
  h = RMSNorm(x)
  dense layer:   x = x + (silu(h W1) * (h W3)) W2
  sparse layer:  s = sigmoid(h Wr) [T, E]; the k largest chosen; their
                 weights s_e / sum(chosen s) * routed_scaling_factor;
                 x = x + shared(h) + sum_e w_e expert_e(h), every expert and
                 the shared one the same gated feed-forward. No token dropped.
  final RMSNorm; logits = features Whead (an output head of its own).

Departures from the published description: NONE in the mathematics. Five
things the config.json has no key for are ASSUMED, here and in the program:
(1) `gating: true` is a gate per head, a sigmoid of a linear map [D, H_t] of
the layer's normed input, applied before Wo (the sibling row Laguna-S-2.1
spells it "per-head", and the published 33.4 B parameters are met by this
count, where an element-wise gate would add 0.6 B); (2) silu in the gated
feed-forward (no `hidden_act` key); (3) sigmoid router scores normalised over
the chosen, no groups, a correction bias of zero (the convention the 256 / 8 /
2.5 triple comes from; the sibling's `norm_topk_prob: true`); (4) no
normalisation of q and k (no key names one); (5) the window counts the
position itself (p - j < W).

It is handed the weights the benchmark made (lib/weights.py) in the program's
own checkpoint layout, a flat {path: array} dict, in bf16 — 7.74 GB at the
published widths cut to layers 0-4 — and must fit beside them. So the tree is NEVER upcast: a layer's
attention matrices, one expert's three matrices and one block of the head's
columns are upcast where they are used; the experts are taken ONE AT A TIME
over all the tokens with the tokens not routed to them masked to nought (plain,
and 256 / 8 times the work of the program's grouped product); the scores are
taken in blocks of query rows.

  wte/embedding [V, D]   lm_head/kernel [D, V]   ln_f/scale [D]
  layer_<i>/{ln1,ln2}/scale [D]
  layer_<i>/attn/{query [D, H_t*hd], key, value [D, Hkv*hd], gate [D, H_t],
                  out [H_t*hd, D]}/kernel
  layer_<i>/mlp/{w1, w3 [D, F], w2 [F, D]}/kernel                (dense layers)
  layer_<i>/moe/router/kernel [D, E]   layer_<i>/moe/{w1, w3 [E, D, Fe],
      w2 [E * Fe, D]: the down-projections flat, expert after expert}
  layer_<i>/moe/shared/{w1, w3, w2}/kernel                      (sparse layers)

`lowp=True` is the control of the comparison that decides `correct`: the same
mathematics with both operands of every matrix product rounded to float8_e4m3
under a per-tensor scale (a stacked leaf's scale is the whole leaf's), the
nearest precision below the bfloat16 the configuration states.

Of the configuration file's `model` group (keys named as the program's model
config names them) this module reads `num_layers`, `hidden_dim`, `num_heads`,
`num_heads_sliding`, `num_kv_heads`, `head_dim`, `layer_types`,
`sliding_window`, `rope`, `rope_sliding`, `mlp_dim`, `dense_layers`,
`layer_norm_epsilon`, `vocab_size` and the `moe` group.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn
ROW_BLOCK = 128  # query rows a block of scores


def _scale(whole):
    """The per-tensor float8 scale of `whole`."""
    return jnp.max(jnp.abs(whole)).astype(jnp.float32) / F8_MAX + 1e-30


def _q8(x, scale=None):
    """Round to float8_e4m3 under a per-tensor scale (`scale`: that of the
    tensor `x` is a part of, where it is one)."""
    scale = _scale(x) if scale is None else scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, lowp, b_scale=None):
    b = b.astype(jnp.float32)
    if lowp:
        a, b = _q8(a), _q8(b, b_scale)
    return jnp.matmul(a, b, precision="highest")


def _sliding(model, i):
    return model["layer_types"][i] == "sliding_attention"


def _heads(model, i):
    return model["num_heads_sliding"] if _sliding(model, i) else model["num_heads"]


def _sparse(model, i):
    return model["moe"]["num_experts"] > 0 and i not in model.get("dense_layers", ())


def param_shapes(model):
    """The checkpoint layout as a tree of shapes, from the sizes alone."""
    d, v, hd, hkv = (model[k] for k in ("hidden_dim", "vocab_size", "head_dim", "num_kv_heads"))
    moe = model["moe"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    w = lambda i, o: {"kernel": s(i, o)}
    ffn = lambda f: {"w1": w(d, f), "w2": w(f, d), "w3": w(d, f)}
    tree = {"lm_head": w(d, v), "ln_f": {"scale": s(d)}, "wte": {"embedding": s(v, d)}}
    for i in range(model["num_layers"]):
        h = _heads(model, i)
        layer = {
            "attn": {"gate": w(d, h), "key": w(d, hkv * hd), "out": w(h * hd, d),
                     "query": w(d, h * hd), "value": w(d, hkv * hd)},
            "ln1": {"scale": s(d)}, "ln2": {"scale": s(d)},
        }
        if _sparse(model, i):
            e, f = moe["num_experts"], moe["expert_dim"]
            layer["moe"] = {"router": w(d, e), "shared": ffn(moe["shared_expert_dim"]),
                            "w1": s(e, d, f), "w2": s(e * f, d), "w3": s(e, d, f)}
        else:
            layer["mlp"] = ffn(model["mlp_dim"])
        tree[f"layer_{i}"] = layer
    return tree


def train_steps(*args, **kwargs):
    raise NotImplementedError(
        "the Laguna-XS.2 configuration is served only: its reference has the forward pass "
        "(`features`, `head`) and no loss, gradients or optimizer step")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def rotary_tables(rope, head_dim, positions):
    """cos and sin [T, rot / 2] of one layer type's rotary parameters, and the
    number of dimensions that rotate. `yarn` as the published configs define it
    (Peng et al. 2023): a dimension that turns more than `beta_fast` times over
    the original context keeps its frequency, one that turns fewer than
    `beta_slow` times has it divided by `factor`, a linear ramp over the
    dimensions between; cos and sin carry `attention_factor`."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    half = rot // 2
    theta = float(rope.get("rope_theta", 10000.0))
    freq = jnp.asarray([theta ** (-2.0 * i / rot) for i in range(half)], jnp.float32)
    mscale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor, orig = float(rope["factor"]), rope["original_max_position_embeddings"]
        dim_of = lambda turns: rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
        freq = freq * (1.0 - ramp) + freq / factor * ramp
        mscale = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    return jnp.cos(ang) * mscale, jnp.sin(ang) * mscale, rot


def _rotate(x, cos, sin, rot):
    """x [T, H, hd]: dimension i < rot/2 pairs with i + rot/2; the rest pass."""
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, x[..., rot:]], axis=-1)


def _attention(x, p, model, i, lowp):
    t = x.shape[0]
    h, hkv, hd = _heads(model, i), model["num_kv_heads"], model["head_dim"]
    window = model["sliding_window"] if _sliding(model, i) else 0
    rope = model["rope_sliding"] if _sliding(model, i) else model["rope"]
    y = _rms_norm(x, p["ln1/scale"], model["layer_norm_epsilon"])
    q = _mm(y, p["attn/query/kernel"], lowp).reshape(t, h, hd)
    k = _mm(y, p["attn/key/kernel"], lowp).reshape(t, hkv, hd)
    v = _mm(y, p["attn/value/kernel"], lowp).reshape(t, hkv, hd)
    cos, sin, rot = rotary_tables(rope, hd, jnp.arange(t))
    q, k = _rotate(q, cos, sin, rot), _rotate(k, cos, sin, rot)
    # Head i reads KV head i // (h / hkv): repeat each KV head over its group.
    k = jnp.repeat(k, h // hkv, axis=1).transpose(1, 2, 0)  # [H, hd, T]
    v = jnp.repeat(v, h // hkv, axis=1).transpose(1, 0, 2)  # [H, T, hd]
    q = q.transpose(1, 0, 2)  # [H, T, hd]
    cols = jnp.arange(t)

    def rows_block(r0, n):  # scores of query rows r0 .. r0 + n - 1 only
        qb = jax.lax.dynamic_slice_in_dim(q, r0, n, axis=1)
        sc = _mm(qb, k, lowp) * hd ** -0.5  # [H, n, T]
        rows = r0 + jnp.arange(n)
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen &= cols[None, :] > rows[:, None] - window
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return _mm(jax.nn.softmax(sc, axis=-1), v, lowp)  # [H, n, hd]

    if t > ROW_BLOCK and t % ROW_BLOCK == 0:
        out = jax.lax.map(lambda r0: rows_block(r0, ROW_BLOCK), jnp.arange(0, t, ROW_BLOCK))
        out = out.transpose(1, 0, 2, 3).reshape(h, t, hd)
    else:
        out = rows_block(0, t)
    gate = jax.nn.sigmoid(_mm(y, p["attn/gate/kernel"], lowp))  # [T, H]
    out = out.transpose(1, 0, 2) * gate[:, :, None]
    return x + _mm(out.reshape(t, h * hd), p["attn/out/kernel"], lowp)


def _ffn(y, w1, w3, w2, lowp, scales=(None, None, None)):
    gate = _mm(y, w1, lowp, scales[0])
    up = _mm(y, w3, lowp, scales[1])
    return _mm(jax.nn.silu(gate) * up, w2, lowp, scales[2])


def _feed_forward(x, p, model, i, lowp):
    y = _rms_norm(x, p["ln2/scale"], model["layer_norm_epsilon"])
    if not _sparse(model, i):
        return x + _ffn(y, p["mlp/w1/kernel"], p["mlp/w3/kernel"], p["mlp/w2/kernel"], lowp)
    moe = model["moe"]
    e, k = moe["num_experts"], moe["top_k"]
    if moe.get("score_func", "sigmoid") != "sigmoid" or not moe.get("norm_topk_prob", True):
        raise NotImplementedError("this reference scores with a sigmoid, normalised over the chosen")
    s = jax.nn.sigmoid(_mm(y, p["moe/router/kernel"], lowp))  # [T, E]
    top, chosen = jax.lax.top_k(s, k)
    top = top / top.sum(-1, keepdims=True) * moe.get("routed_scaling_factor", 1.0)
    # [T, E]: the weight of expert e for each token, nought where not chosen.
    weight = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(top)
    w1, w3, w2 = p["moe/w1"], p["moe/w3"], p["moe/w2"]
    f = w1.shape[-1]  # expert j's down-projection is rows j*f .. (j+1)*f - 1 of w2
    # A stacked leaf's float8 scale is the whole leaf's, taken once.
    scales = tuple(_scale(w) for w in (w1, w3, w2)) if lowp else (None, None, None)

    def one_expert(acc, j):  # every token through expert j, masked by its weight
        out = _ffn(y, w1[j], w3[j], jax.lax.dynamic_slice_in_dim(w2, j * f, f), lowp, scales)
        return acc + out * jax.lax.dynamic_slice_in_dim(weight, j, 1, axis=1), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(e))
    shared = _ffn(y, p["moe/shared/w1/kernel"], p["moe/shared/w3/kernel"],
                  p["moe/shared/w2/kernel"], lowp)
    return x + shared + routed


def features(params, tokens, model, lowp=False):
    """Final-RMSNorm features [B, T, D] for token ids [B, T]; position i sees
    positions 0..i only (within the window, in a sliding layer)."""
    def one(row):
        x = params["wte/embedding"][row].astype(jnp.float32)
        for i in range(model["num_layers"]):
            pre = f"layer_{i}/"
            p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
            x = _attention(x, p, model, i, lowp)
            x = _feed_forward(x, p, model, i, lowp)
        return _rms_norm(x, params["ln_f/scale"], model["layer_norm_epsilon"])

    return jnp.stack([one(row) for row in tokens])


def head(params, feats, lo, n, model, lowp=False):
    """Logits [..., n] of the vocabulary's rows lo .. lo + n - 1 (the output
    head's columns) for features [..., D]. `n` is static, `lo` may be traced;
    under `lowp` the scale is the whole matrix's. Only the block is upcast."""
    w = params["lm_head/kernel"]
    cols = jax.lax.dynamic_slice_in_dim(w, lo, n, axis=1)
    return _mm(feats, cols, lowp, b_scale=_scale(w) if lowp else None)
