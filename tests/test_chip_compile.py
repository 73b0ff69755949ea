"""Real-size compiles for a DESCRIBED TPU v5e (no chip attached).

The Pallas interpreter accepts kernels Mosaic refuses: all four single-token
decode kernels passed every interpret-mode test and failed their first real
compile (a batched mat-vec whose left operand has no free dimension). The
TPU's compiler is installed in the sandbox and compiles for a chip that is
described, not attached — about a second per kernel — so the kernels of the
train and serve paths are compiled here at GPT-2-medium / RN50 widths on
every test run. A compile that passes is NOT a chip run: nothing executes,
so this says nothing about results or times (chip_smoke.py does).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, pytest-xdist workers each import
every test file, and a module that decided at import whether its tests
exist would give the workers different collections. Keep these tests in
this ONE file (a second file could land on another worker, whose fixture
would then skip).
"""

from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from frl_distributed_ml_scaffold_tpu.ops.flash_attention import flash_attention
from frl_distributed_ml_scaffold_tpu.ops.fused_adamw import fused_adamw
from frl_distributed_ml_scaffold_tpu.ops.fused_bn import fused_bn_train

# ops/__init__ re-exports the function under the module's own name.
da = importlib.import_module(
    "frl_distributed_ml_scaffold_tpu.ops.decode_attention"
)

# GPT-2-medium: 16 heads of 64, context 1024; the bench operating point's
# batch of 8; pool blocks of 16 and 64; a k=3 speculative verify tile.
B, H, D, S, T_VERIFY = 8, 16, 64, 1024, 4


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one described v5e chip. The persistent compile cache is
    off while this module runs: an entry compiled for a described chip is
    written but cannot be read back without one, and the next run would
    warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile_has_kernel(one_chip, fn, *shapes, names, attention=True) -> None:
    """Compile ``fn`` for the described chip — raises what the chip's
    compiler would raise — and require each Pallas kernel in the program
    under its own name: a device trace calls a kernel by the name of its
    custom-call instruction (``%attn_flash_fwd.1 = ... custom-call(``), and
    the benchmark's kernel metrics find it by that. Every attention kernel's
    name starts with ``attn_`` (the two accepted rooflines match
    ``^%attn[\\w.]*``), and no other kernel's does."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    calls = re.findall(
        r"^\s*(?:ROOT )?%([\w.\-]+) = .*custom-call\(.*tpu_custom_call",
        text, re.M,
    )
    found = {re.sub(r"\.\d+$", "", c) for c in calls}
    assert found == set(names), (found, names)
    assert all(n.startswith("attn_") == attention for n in found), found


def _scoped(fn, scope="attn"):
    """``fn`` inside a named scope, as a kernel sits inside its flax module
    (GPT's is called ``attn``, which is what the kernels were called on the
    device before they had names). A transform wraps the outermost scope it
    finds — ``jvp(attn)/attn_flash_fwd`` — so the kernel's own name comes
    through ``jax.grad`` whole; with no scope around it, it would not."""
    def scoped(*args):
        with jax.named_scope(scope):
            return fn(*args)

    return scoped


BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
QKV = ((B, S, H, D), BF16)


@_scoped
def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def test_flash_attention_forward(one_chip):
    _compile_has_kernel(
        one_chip, _flash, QKV, QKV, QKV, names=["attn_flash_fwd"]
    )


def test_flash_attention_backward(one_chip):
    grad = jax.grad(
        lambda q, k, v: _flash(q, k, v).astype(F32).sum(), argnums=(0, 1, 2)
    )
    _compile_has_kernel(
        one_chip, grad, QKV, QKV, QKV,
        names=["attn_flash_fwd", "attn_flash_dq", "attn_flash_dkv"],
    )


@pytest.mark.parametrize(
    "seq,dtype",
    [(256, BF16), (1024, BF16), (1024, F32)],
    ids=["s256-bf16", "s1024-bf16", "s1024-fp32"],
)
def test_decode_attention(one_chip, seq, dtype):
    """Single-token decode over a contiguous cache — generate()'s path.
    fp32 is the serving benchmark's policy: at 512-position chunks its K
    and V overflow VMEM, so the chunk is sized in bytes."""
    kv = ((B, seq, H, D), dtype)
    _compile_has_kernel(
        one_chip,
        _scoped(lambda q, k, v, n: da.decode_attention(
            q, k, v, n, impl="flash", interpret=False)),
        ((B, H, D), dtype), kv, kv, ((B,), I32),
        names=["attn_decode"],
    )


def test_decode_attention_int8_cache(one_chip):
    kv, sc = ((B, S, H, D), I8), ((B, S, H), BF16)
    _compile_has_kernel(
        one_chip,
        _scoped(lambda q, k, v, n, ks, vs: da.decode_attention(
            q, k, v, n, k_scale=ks, v_scale=vs, impl="flash",
            interpret=False)),
        ((B, H, D), BF16), kv, kv, ((B,), I32), sc, sc,
        names=["attn_decode_quant"],
    )


def _pool_shapes(block: int, dtype):
    """Stacked lane-dense pools of two layers, as models/gpt.py stores them."""
    n_blocks, table = B * S // block + 1, S // block
    return (
        ((2, n_blocks, block, H * D), dtype),
        ((B,), I32),
        ((B, table), I32),
        ((), I32),  # the layer index, traced inside the layer loop
        ((2, n_blocks, H * block), BF16),
    )


@pytest.mark.parametrize("block", [16, 64])
def test_paged_decode_attention(one_chip, block):
    """Single-token decode over the block pool — the serving engine's path."""
    pool, lens, tables, layer, _ = _pool_shapes(block, BF16)
    _compile_has_kernel(
        one_chip,
        _scoped(lambda q, k, v, n, t, l: da.paged_decode_attention(
            q, k, v, n, t, l, impl="flash", interpret=False)),
        ((B, H, D), BF16), pool, pool, lens, tables, layer,
        names=["attn_paged_decode"],
    )


@pytest.mark.parametrize("block", [16, 64])
def test_paged_decode_attention_int8_pool(one_chip, block):
    pool, lens, tables, layer, scales = _pool_shapes(block, I8)
    _compile_has_kernel(
        one_chip,
        _scoped(lambda q, k, v, n, t, l, ks, vs: da.paged_decode_attention(
            q, k, v, n, t, l, k_scale=ks, v_scale=vs, impl="flash",
            interpret=False)),
        ((B, H, D), BF16), pool, pool, lens, tables, layer, scales, scales,
        names=["attn_paged_decode_quant"],
    )


@pytest.mark.parametrize("block", [16, 64])
def test_paged_verify_attention(one_chip, block):
    """The speculative verify tile (k=3 drafts + the last accepted token)."""
    pool, lens, tables, layer, _ = _pool_shapes(block, BF16)
    _compile_has_kernel(
        one_chip,
        _scoped(lambda q, k, v, n, t, l: da.paged_verify_attention(
            q, k, v, n, t, l, impl="flash", interpret=False)),
        ((B, T_VERIFY, H, D), BF16), pool, pool, lens, tables, layer,
        names=["attn_paged_verify"],
    )


# ----------------------------------------------- the KV pool stays in place
#
# The serving engine's three programs that hold the paged KV pool, compiled
# as the engine builds them at the serving benchmark's size, and read as the
# chip would run them. graft-lint's serving:* programs and the cache-copy
# budget of analysis/materialization.py read the JAXPR; they passed while
# the chip moved gigabytes a step, because the passes over the pool were put
# in by the TPU's compiler: a [.., H, hd] minor pair with hd = 64 made it
# keep the block index in the lanes, and every program transposed the pool
# there and back (PERF.md section 6, PR 28).

POOL_LAYERS, POOL_SLOTS, POOL_BLOCKS, POOL_BS = 24, 48, 1281, 16
#: Elements of ONE layer's slice of a K/V pool: no op may write that many.
POOL_SLICE = POOL_BLOCKS * POOL_BS * H * D
#: Ops that move nothing. (The compiler's own prefetch of a weight, the
#: embedding, into faster memory is let through below: no pass over the pool.)
_MOVES_NOTHING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}
_UPDATES = ("scatter", "dynamic-update-slice")
_UPDATE_ROOT = re.compile(
    r"ROOT %[\w.\-]+ = .+? (?:" + "|".join(_UPDATES) + r")\("
)


def _pool_sized_ops(
    text: str, pool_slice: int = POOL_SLICE, pool_blocks: int = POOL_BLOCKS
) -> list[str]:
    """Instructions of the optimised HLO, outside fused computations, whose
    output holds an array of ``pool_slice`` elements or more and that are
    neither free nor the in-place update itself (a scatter /
    dynamic-update-slice, or the fusion whose root is one)."""
    bodies = dict(re.findall(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?\{\n(.*?)^\}", text, re.M | re.S
    ))
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found = []
    for name, body in bodies.items():
        if name in fused:
            continue
        for line in body.splitlines():
            m = re.match(
                r"\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(", line
            )
            if m is None:
                continue
            out, op = m.groups()
            sizes = [
                int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
                for dims in re.findall(r"\w+\[([\d,]*)\]", out)
            ]
            if max(sizes, default=0) < pool_slice or op in _MOVES_NOTHING:
                continue
            if op in _UPDATES:
                continue
            called = re.search(r"calls=%([\w.\-]+)", line)
            if op == "fusion" and called and _UPDATE_ROOT.search(
                bodies[called.group(1)]
            ):
                continue
            if op in ("copy-start", "copy-done") and (
                f",{pool_blocks}," not in out
            ):
                continue  # the prefetch of a weight: no array of pool blocks
            found.append(f"{op} -> {out[:60]}")
    return found


@pytest.fixture(scope="module")
def pool_programs(one_chip):
    """quant -> {program: compiled}: ``serve_paged_decode``,
    ``serve_verify`` and ``serve_paged_graft`` from the engine's own
    builders (a real ``ServingEngine`` over abstract GPT-2-medium weights;
    nothing runs), compiled for the described chip. The host is a CPU, so
    the attention router would take its dense route: the kernel route is
    forced, as on the chip."""
    from frl_distributed_ml_scaffold_tpu.config.schema import GPTConfig
    from frl_distributed_ml_scaffold_tpu.models.gpt import (
        GPT,
        init_paged_cache,
    )
    from frl_distributed_ml_scaffold_tpu.precision import get_policy
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree,
        )

    def build(quant):
        policy = get_policy("bf16")
        model = GPT(
            GPTConfig(
                num_layers=POOL_LAYERS, hidden_dim=H * D, num_heads=H,
                seq_len=S, vocab_size=50257, dropout=0.0,
                decode_attention="flash", kv_cache_quant=quant,
            ),
            policy,
        )
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, policy.param_dtype),
            jax.eval_shape(
                lambda: model.init(
                    {"params": jax.random.key(0)},
                    jnp.zeros((1, 8), I32), train=False,
                )["params"]
            ),
        )
        eng = ServingEngine(
            model, params, num_slots=POOL_SLOTS, temperature=0.0,
            kv_block_size=POOL_BS, kv_pool_blocks=POOL_BLOCKS,
            speculate="ngram", speculate_k=T_VERIFY - 1,
        )
        cache = jax.eval_shape(
            lambda: init_paged_cache(eng._paged_model(), POOL_SLOTS)
        )
        # A 150-token prompt: a slot cache of 256 positions, ten blocks.
        slot_model, n_priv = eng._model_at(256), 10
        slot_cache = jax.eval_shape(
            lambda p, t: slot_model.apply(
                {"params": p}, t, decode=True, mutable=["cache"]
            )[1]["cache"],
            params, jax.ShapeDtypeStruct((1, 8), I32),
        )
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, I32)  # noqa: E731
        programs = {
            "serve_paged_decode": (
                eng._paged_decode_fn(),
                (params, cache, i32(POOL_SLOTS),
                 jax.eval_shape(lambda: jax.random.key(0))),
            ),
            "serve_verify": (
                eng._verify_fn(), (params, cache, i32(POOL_SLOTS, T_VERIFY))
            ),
            "serve_paged_graft": (
                eng._paged_graft_fn(256, n_priv),
                (cache, slot_cache, i32(n_priv), i32(), i32()),
            ),
        }
        return {
            name: fn.lower(*on_chip(args)).compile()
            for name, (fn, args) in programs.items()
        }

    was = da.FORCE_INTERPRET
    da.FORCE_INTERPRET = False
    try:
        yield {quant: build(quant) for quant in ("none", "int8")}
    finally:
        da.FORCE_INTERPRET = was


@pytest.mark.parametrize("quant", ["none", "int8"], ids=["bf16", "int8-pool"])
@pytest.mark.parametrize(
    "program", ["serve_paged_decode", "serve_verify", "serve_paged_graft"]
)
def test_pool_program_leaves_the_pool_in_place(pool_programs, program, quant):
    """No op writes a layer's slice of the pool or more, other than the
    in-place update; the K/V pools arrive in the plain row-major layout
    that the scatter and the kernel read; the program's temporaries are a
    few megabytes (2.64 GB and 2.03 GB before PR 28)."""
    compiled = pool_programs[quant][program]
    text = compiled.as_text()
    assert _pool_sized_ops(text) == []
    dtype = "s8" if quant == "int8" else "bf16"
    shape = f"{dtype}[{POOL_LAYERS},{POOL_BLOCKS},{POOL_BS},{H * D}]"
    pools = re.findall(
        re.escape(shape) + r"\{([\d,]+)", text.split("\n", 1)[0]
    )
    assert pools and set(pools) == {"3,2,1,0"}, pools
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    if program != "serve_paged_graft":
        suffix = "_quant" if quant == "int8" else ""
        kernel = {"serve_paged_decode": "attn_paged_decode",
                  "serve_verify": "attn_paged_verify"}[program] + suffix
        assert re.search(
            rf"%{kernel}[\w.]* = .*custom-call\(.*tpu_custom_call", text
        ), f"{kernel} is not in {program}"


# Laguna-XS.2 (huggingface.co/poolside/Laguna-XS.2 config.json) at its
# published widths, cut in depth alone to layers 0-4 (the dense full layer,
# then one whole period: sliding, sliding, sliding, full): 3.87 B parameters,
# 7.74 GB in bf16. What PERF.md section 4 says of it was measured at these
# sizes.
LAGUNA_XS2_LAYERS_0_4 = {
    "family": "gpt", "vocab_size": 100352, "num_layers": 5, "hidden_dim": 2048,
    "seq_len": 5120, "num_heads": 48, "num_heads_sliding": 64,
    "num_kv_heads": 8, "head_dim": 128,
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "sliding_window": 512, "norm": "rmsnorm", "layer_norm_epsilon": 1e-06,
    "position": "rope",
    "rope": {"rope_type": "yarn", "rope_theta": 500000.0,
             "partial_rotary_factor": 0.5, "factor": 64.0,
             "original_max_position_embeddings": 4096, "beta_fast": 64.0,
             "beta_slow": 1.0, "attention_factor": 1.4158883083359672},
    "rope_sliding": {"rope_type": "default", "rope_theta": 10000.0,
                     "partial_rotary_factor": 1.0},
    "bias": False, "attention_gate": True, "mlp": "swiglu", "mlp_dim": 8192,
    "dense_layers": [0], "tie_embeddings": False,
    "moe": {"num_experts": 256, "top_k": 8, "routing": "dropless",
            "expert_dim": 512, "num_shared_experts": 1,
            "shared_expert_dim": 512, "score_func": "sigmoid",
            "norm_topk_prob": True, "routed_scaling_factor": 2.5},
    "dropout": 0.0, "decode_attention": "flash",
}
# 64 slots over blocks of 128: 576 usable blocks of the full kind (1 MiB
# each), and a ring of 5 for each slot in the sliding kind.
LAGUNA_XS2_ENGINE = {"num_slots": 64, "kv_block_size": 128,
                     "kv_pool_blocks": 577, "prefix_cache": False}


@pytest.fixture(scope="module")
def kind_pool_programs(one_chip):
    """{program: (compiled, engine)}: the paged decode program and the
    admission graft of a model with two kinds of layer in the pool and
    expert layers, at published widths (``LAGUNA_XS2_LAYERS_0_4``: window
    and full attention mixed, 48 / 64 query heads over 8 KV heads of 128,
    256 routed experts), from the engine's own builders over abstract
    weights."""
    from frl_distributed_ml_scaffold_tpu.config import (
        ExperimentConfig,
        config_from_dict,
    )
    from frl_distributed_ml_scaffold_tpu.models import create_model
    from frl_distributed_ml_scaffold_tpu.models.gpt import init_paged_cache
    from frl_distributed_ml_scaffold_tpu.precision import get_policy
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    ge = importlib.import_module(
        "frl_distributed_ml_scaffold_tpu.ops.grouped_experts"
    )
    policy = get_policy("bf16")
    model = create_model(
        config_from_dict(
            ExperimentConfig, {"model": LAGUNA_XS2_LAYERS_0_4}
        ).model,
        policy,
    )
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, policy.param_dtype),
        jax.eval_shape(
            lambda: model.init(
                {"params": jax.random.key(0)}, jnp.zeros((1, 8), I32),
                train=False,
            )["params"]
        ),
    )
    eng = ServingEngine(model, params, **LAGUNA_XS2_ENGINE)
    cache = jax.eval_shape(
        lambda: init_paged_cache(eng._paged_model(), eng.num_slots)
    )
    # A 1000-token prompt: a slot cache of 1024 positions, eight blocks of
    # 128, of which the sliding layers' pool takes the last five.
    s_c, n_g = 1024, 8
    slot_model = eng._model_at(s_c)
    slot_cache = jax.eval_shape(
        lambda p, t: slot_model.apply(
            {"params": p}, t, decode=True, mutable=["cache"]
        )[1]["cache"],
        params, jax.ShapeDtypeStruct((1, 8), I32),
    )
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, I32)  # noqa: E731
    programs = {
        "serve_paged_decode": (
            eng._paged_decode_fn(),
            (params, cache, i32(eng.num_slots),
             jax.eval_shape(lambda: jax.random.key(0))),
        ),
        "serve_paged_graft": (
            eng._paged_graft_fn(s_c, n_g),
            (cache, slot_cache, i32(n_g), i32(eng.window_places), i32()),
        ),
    }
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree,
    )
    was = da.FORCE_INTERPRET, ge.FORCE_INTERPRET
    da.FORCE_INTERPRET = ge.FORCE_INTERPRET = False
    try:
        yield {
            name: (fn.lower(*on_chip(args)).compile(), eng)
            for name, (fn, args) in programs.items()
        }
    finally:
        da.FORCE_INTERPRET, ge.FORCE_INTERPRET = was


@pytest.mark.parametrize("program", ["serve_paged_decode", "serve_paged_graft"])
def test_pool_program_leaves_the_pools_of_both_kinds_in_place(
    kind_pool_programs, program
):
    """``test_pool_program_leaves_the_pool_in_place`` for a model that keeps
    a pool for each layer kind: the layer loop (layers apart, not a scan)
    hands both pools on whole, so no op writes a layer's slice of either
    pool other than the in-place update, both arrive row-major, the
    program's temporaries stay small, and the decode program holds the
    grouped attention kernel of each kind and the expert kernel under their
    names."""
    compiled, eng = kind_pool_programs[program]
    text = compiled.as_text()
    row = 8 * 128  # 8 KV heads of 128: a token's K row
    for blocks in (eng.pool_blocks, eng.window_pool_blocks):
        assert _pool_sized_ops(
            text, blocks * eng.block_size * row, blocks) == []
    for layers, blocks in ((2, eng.pool_blocks), (3, eng.window_pool_blocks)):
        shape = f"bf16[{layers},{blocks},{eng.block_size},{row}]"
        layouts = re.findall(
            re.escape(shape) + r"\{([\d,]+)", text.split("\n", 1)[0])
        assert layouts and set(layouts) == {"3,2,1,0"}, (shape, layouts)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6
    assert mem.alias_size_in_bytes >= sum(
        2 * layers * blocks * eng.block_size * row * 2
        for layers, blocks in (
            (2, eng.pool_blocks), (3, eng.window_pool_blocks))
    )
    if program == "serve_paged_decode":
        calls = set(re.findall(
            r"%([a-z_]+)[\w.]* = .*custom-call\(.*tpu_custom_call", text))
        assert calls == {
            "attn_mixed_decode_full", "attn_mixed_decode_sliding",
            "moe_expert_ffn",
        }, calls


@pytest.mark.parametrize(
    "shape",
    [(32, 56, 56, 256), (32, 112, 112, 64)],
    ids=["rn50-56x56x256", "rn50-stem"],
)
def test_fused_bn_backward(one_chip, shape):
    """RN50's widest-traffic BatchNorm and its stem, backward (two Pallas
    passes behind a custom VJP)."""
    c = shape[-1]
    grad = jax.grad(
        _scoped(lambda x, scale, bias: fused_bn_train(  # -> (y, mean, var)
            x, scale, bias, interpret=False)[0].astype(F32).sum(), "bn"),
        argnums=(0, 1, 2),
    )
    _compile_has_kernel(
        one_chip, grad, (shape, BF16), ((c,), F32), ((c,), F32),
        names=["bn_bwd_reduce", "bn_bwd_dx"], attention=False,
    )


def test_fused_adamw(one_chip):
    """One fused pass over a GPT-2-medium MLP weight."""
    tx = fused_adamw(1e-3, weight_decay=0.01, interpret=False)
    w = ((1024, 4096), F32)

    def apply(g, mu, nu, p):
        state = tx.init({"w": p})._replace(mu={"w": mu}, nu={"w": nu})
        return tx.fused_apply({"w": g}, state, {"w": p})

    _compile_has_kernel(
        one_chip, apply, w, w, w, w, names=["fused_adamw"], attention=False
    )
