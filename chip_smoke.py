#!/usr/bin/env python
"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py             # one chip: train, serve, kernels
    python chip_smoke.py --chips 4   # four chips: the sharded step, and
                                     # the one-device step it is compared with

One process (a chip belongs to one process at a time), which drives the
trainer and the serving engine through the entry points a user calls, at
the full width of GPT-2-medium with random weights made from a seed, and
checks what comes out by the repo's own means. The first phase that fails
raises: nothing is caught to carry on, the exit code is then non-zero and
no result line is printed. With no TPU it stops at the device check — the
CPU is never passed off as the chip.

Each phase prints one JSON line (compile seconds are what jax's own
monitoring events report for lowering and backend compile or cache
retrieval; run seconds are the rest of the phase's wall time). The
LAST line of stdout is the result the driver reads::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases are plain functions that take their sizes as arguments, so the
CPU rehearsal (tests/test_chip_smoke.py) drives the same code at a tiny
size. Run outputs go under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

#: GPT-2-medium at full width (the published config: 24 layers, hidden
#: 1024, 16 heads, vocab 50257, context 1024).
GPT2_MEDIUM = dict(
    vocab_size=50257, num_layers=24, num_heads=16, hidden_dim=1024,
    seq_len=1024,
)
#: The operating point bench.py runs ``gpt2_medium_zero1`` at on one v5e.
TRAIN_POINT = (
    "data.global_batch_size=8", "trainer.grad_accum=1",
    "model.attention=flash", "model.lm_loss_chunk=128",
    "trainer.remat=none", "model.block_remat=full",
)
#: The attention of Laguna-XS.2's two layer kinds (the configuration
#: ``laguna-xs2-serve``): kind -> (query heads, window), over 8 KV heads of
#: 128 in pool blocks of 128.
LAGUNA_KINDS = dict(
    kv_heads=8, head_dim=128, block=128,
    layers={"full": (48, 0), "sliding": (64, 512)},
)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# ------------------------------------------------------------ bookkeeping


class CompileClock:
    """Sums jax's own compile-time events, so each phase can report compile
    seconds apart from run seconds, and how the persistent cache fared.
    Lowering and backend compile (or cache retrieval) count; tracing does
    not — its events nest (an outer jit's trace contains the inner ones),
    so their sum can exceed the wall clock."""

    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, seconds, **_):
        if event in self._COMPILE_EVENTS:
            self.compile_s += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return self.compile_s, self.cache_hits, self.cache_misses


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Time one phase and print its line. ``fields`` (yielded) carries what
    the phase wants on that line. An exception passes straight through."""
    import jax

    fields: dict = {}
    c0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    yield fields
    wall = time.perf_counter() - t0
    c1, h1, m1 = clock.snapshot()
    # Drop whatever the phase left on the device before the next starts:
    # the chip has 16 GB and the train phase alone fills most of it.
    gc.collect()
    jax.clear_caches()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    emit(
        name, ok=True, compile_s=round(c1 - c0, 2),
        run_s=round(wall - (c1 - c0), 2), cache_hits=h1 - h0,
        cache_misses=m1 - m0,
        device_bytes_in_use_after=stats.get("bytes_in_use"),
        device_peak_bytes=stats.get("peak_bytes_in_use"), **fields,
    )


def _max_abs_err(a, b) -> float:
    import jax.numpy as jnp

    return float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    )


def _has_kernel(compiled_or_lowered) -> bool:
    return "tpu_custom_call" in compiled_or_lowered.as_text()


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


# --------------------------------------------------------------- preamble


def check_device(chips: int) -> dict:
    """The device JAX found, as the result line reports it. Anything but
    ``chips`` TPU devices ends the run here, non-zero, with no result."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (devices: {dev}); this script "
            "proves the system on the chip and does not run on a CPU"
        )
    if dev["count"] != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but JAX sees {dev['count']} "
            "device(s)"
        )
    return dev


def preamble(dev: dict) -> None:
    """Versions, the native data core, the compile cache, and the two ways
    of waiting for the device timed once."""
    import jax
    import jax.numpy as jnp
    import jaxlib

    from frl_distributed_ml_scaffold_tpu.data.native import native_available

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # libtpu is not always a package
        libtpu = "unknown"
    cache_dir = jax.config.jax_compilation_cache_dir
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    emit(
        "preamble", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, device=dev,
        data_core="native" if native_available() else "numpy",
        compile_cache_dir=cache_dir,
        compile_cache_placed_by=(
            "JAX_COMPILATION_CACHE_DIR"
            if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "checkout"
        ),
        compile_cache_entries_at_start=entries,
        compile_cache_warm=entries > 0,
    )

    # utils/timing.py waits with device_get of a scalar; block_until_ready
    # waits for the same event. Time both once, on the same program.
    @jax.jit
    def work(x):
        for _ in range(64):
            x = jnp.tanh(x @ x) * 0.5
        return x.sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.device_get(work(x))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(work(x))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.device_get(work(x))
    t_get = time.perf_counter() - t0
    t0 = time.perf_counter()
    work(x)  # enqueue only: what a timing without a sync would read
    t_enqueue = time.perf_counter() - t0
    emit("sync", block_until_ready_s=round(t_block, 5),
         device_get_scalar_s=round(t_get, 5),
         enqueue_only_s=round(t_enqueue, 5))


# ------------------------------------------------------------------ train


def _read_metrics(run_dir: str) -> list[dict]:
    path = os.path.join(run_dir, "metrics.jsonl")
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def phase_train(fields: dict, *, device: str = "tpu",
                overrides: tuple[str, ...] = TRAIN_POINT,
                steps: int = 4) -> None:
    """``launch.main`` — the function launch.py calls — trains
    ``gpt2_medium_zero1`` for ``steps`` steps and saves with Orbax; a
    second ``main`` call resumes from that save and takes one more step."""
    import math

    import jax

    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.launcher import launch
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    workdir = os.path.join(OUT_DIR, "train")
    shutil.rmtree(workdir, ignore_errors=True)  # a stale save would resume
    run_dir = os.path.join(workdir, "gpt2_medium_zero1")
    common = [
        *overrides, f"workdir={workdir}", "trainer.log_every=1",
        "checkpoint.enabled=true", f"checkpoint.save_every={steps}",
    ]

    def run(total: int) -> None:
        rc = launch.main([
            "--config=gpt2_medium_zero1", f"--device={device}", *common,
            f"trainer.total_steps={total}",
        ])
        if rc != 0:
            raise RuntimeError(f"launch.main returned {rc}")

    run(steps)
    first = _read_metrics(run_dir)
    if [r["step"] for r in first] != list(range(1, steps + 1)):
        raise AssertionError(f"steps logged: {[r['step'] for r in first]}")
    run(steps + 1)
    resumed = _read_metrics(run_dir)[len(first):]
    if [r["step"] for r in resumed] != [steps + 1]:
        raise AssertionError(
            f"resume should take exactly step {steps + 1}; it logged "
            f"{[r['step'] for r in resumed]} (a restore that fell back to "
            "a fresh init starts from step 1)"
        )
    losses = [r["loss"] for r in first + resumed]
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite loss: {losses}")

    # The program that ran: compile the same step again (a persistent-
    # cache hit) and look for the flash kernel — T=1024 tiles, so the
    # dense route must not have been taken silently.
    cfg = apply_overrides(
        get_config("gpt2_medium_zero1"),
        [*overrides, f"workdir={workdir}", "data.prefetch=0"],
    )
    trainer = Trainer(cfg)
    batch = trainer.pipeline.global_batch(0)
    compiled = trainer.lower_train_step(trainer.state_shapes, batch).compile()
    mem = compiled.memory_analysis()
    cost = trainer.step_cost_analysis(trainer.state_shapes, batch)
    # What the backend itself counts in the optimized program, beside the
    # count MFU uses (it sees no FLOPs inside a Pallas custom call).
    xla_flops = (compiled.cost_analysis() or {}).get("flops")
    kernel = _has_kernel(compiled)
    if _on_tpu() and not kernel:
        raise AssertionError(
            "no tpu_custom_call in the compiled train step: "
            "model.attention=flash took the dense route"
        )
    shutil.rmtree(os.path.join(run_dir, "ckpt"))  # ~4 GB at full width
    fields.update(
        config="gpt2_medium_zero1", overrides=list(overrides),
        steps=steps, resumed_at=steps, losses=[round(l, 4) for l in losses],
        step_time_median_s=resumed[-1].get("step_time_median_s")
        or first[-1].get("step_time_median_s"),
        flash_kernel_in_step=kernel,
        step_flops=cost["flops"], step_flops_source=cost["flops_source"],
        step_flops_compiled_program=xla_flops,
        step_temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        step_argument_bytes=getattr(mem, "argument_size_in_bytes", None),
    )


# ------------------------------------------------------------------ serve


def phase_serve(fields: dict, *, model_kw: dict = GPT2_MEDIUM,
                prompt_lens: tuple[int, ...] = (5, 17, 33, 9, 64, 3),
                max_new: int = 12, num_slots: int = 4,
                block_size: int = 16, seed: int = 0) -> None:
    """``ServingEngine`` over a paged pool with the flash decode kernel
    answers a few ragged requests; its tokens must equal ``generate()``'s
    greedy tokens, with the kernel and with the dense reference."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        PrecisionConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.generation import generate
    from frl_distributed_ml_scaffold_tpu.models.gpt import GPT
    from frl_distributed_ml_scaffold_tpu.precision import get_policy
    from frl_distributed_ml_scaffold_tpu.serving import ServingEngine

    cfg = GPTConfig(**model_kw, dropout=0.0, decode_attention="flash")
    policy = get_policy(PrecisionConfig(policy="fp32"))
    model = GPT(cfg, policy)
    dense = GPT(dataclasses.replace(cfg, decode_attention="dense"), policy)
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        for n in prompt_lens
    ]
    width = max(prompt_lens)
    padded = np.zeros((len(prompts), width), np.int32)  # left-padded
    for i, p in enumerate(prompts):
        padded[i, width - len(p):] = p
    lengths = jnp.asarray(prompt_lens, jnp.int32)

    # Token identity needs exact arithmetic: at the MXU's default precision
    # two kernels that sum in different orders differ by ~1e-3 in the
    # logits, enough to flip an argmax between near-tied random-weight
    # tokens. fp32 weights at 'highest' (which the kernels' dots inherit
    # at trace time) leave ~1e-6, so equal tokens mean equal programs.
    with jax.default_matmul_precision("highest"):
        params = jax.jit(
            lambda: model.init(
                {"params": jax.random.key(seed)},
                jnp.zeros((1, 8), jnp.int32), train=False,
            )["params"]
        )()
        eng = ServingEngine(
            model, params, num_slots=num_slots, temperature=0.0,
            kv_block_size=block_size,
        )
        ids = [eng.submit(p, max_new) for p in prompts]
        done = {c.id: c for c in eng.run()}
        decode_kernel = _has_kernel(eng.lower_decode_step())
        stats = dict(eng.stats)
        eng.close()

        def greedy(m):
            out = jax.jit(
                lambda p, x, l: generate(
                    m, p, x, max_new_tokens=max_new, temperature=0.0,
                    prompt_lengths=l,
                )
            )(params, jnp.asarray(padded), lengths)
            return np.asarray(jax.device_get(out))[:, width:]

        ref_flash, ref_dense = greedy(model), greedy(dense)

    if sorted(done) != sorted(ids) or not all(c.ok for c in done.values()):
        raise AssertionError(
            f"requests not all served: "
            f"{[(c.id, c.finish_reason) for c in done.values()]}"
        )
    served = np.stack([done[i].tokens[done[i].prompt_len:] for i in ids])
    for name, ref in (("generate(flash)", ref_flash),
                      ("generate(dense)", ref_dense)):
        if not np.array_equal(served, ref):
            bad = np.argwhere(served != ref)[0].tolist()
            raise AssertionError(
                f"engine tokens != {name} tokens, first at (request, "
                f"position) {bad}: {served[bad[0]].tolist()} vs "
                f"{ref[bad[0]].tolist()}"
            )
    if not (0 <= served.min() and served.max() < cfg.vocab_size):
        raise AssertionError("token out of the vocabulary")
    if _on_tpu() and not decode_kernel:
        raise AssertionError(
            "no tpu_custom_call in the engine's decode program: "
            "decode_attention=flash took the dense route"
        )
    if not stats.get("decode_paged"):
        raise AssertionError(f"no paged decode step ran: {stats}")
    fields.update(
        model={**model_kw, "policy": "fp32", "matmul_precision": "highest"},
        requests=len(prompts), prompt_lens=list(prompt_lens),
        max_new=max_new, num_slots=num_slots, kv_block_size=block_size,
        tokens_equal_generate_flash=True, tokens_equal_generate_dense=True,
        decode_kernel_in_program=decode_kernel,
        decode_steps=stats.get("decode_steps"),
        block_appends=stats.get("block_append"),
        first_request_tokens=served[0].tolist(),
    )


# ---------------------------------------------------------------- kernels


def phase_kernels(fields: dict, *, batch: int = 8, heads: int = 16,
                  head_dim: int = 64, seq: int = 1024,
                  block_sizes: tuple[int, ...] = (16, 64),
                  verify_len: int = 4, kinds: dict = LAGUNA_KINDS,
                  adamw_shape=(1024, 4096),
                  interpret: bool | None = None) -> None:
    """Every Pallas kernel of the train and serve paths, compiled for real
    at the width it has there and compared with its dense reference.
    ``interpret`` is None on the chip (Mosaic compiles); the CPU rehearsal
    passes True."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from frl_distributed_ml_scaffold_tpu.models.generation import (
        slot_blocks_to_pool,
    )
    from frl_distributed_ml_scaffold_tpu.models.gpt import grouped_attention
    from frl_distributed_ml_scaffold_tpu.ops.flash_attention import (
        flash_attention,
    )
    from frl_distributed_ml_scaffold_tpu.ops.fused_adamw import fused_adamw
    from frl_distributed_ml_scaffold_tpu.ops.quantization import quantize
    from frl_distributed_ml_scaffold_tpu.ops.ring_attention import (
        dense_attention,
    )

    # ops/__init__ re-exports the function under the module's own name.
    da = importlib.import_module(
        "frl_distributed_ml_scaffold_tpu.ops.decode_attention"
    )
    errs: dict[str, float] = {}

    def check(name, got, want, tol):
        errs[name] = err = _max_abs_err(got, want)
        if not err < tol:  # also catches NaN
            raise AssertionError(f"kernel {name}: max|err| {err} >= {tol}")

    def jit_checked(name, fn, *args):
        """Compile ``fn`` and, on the chip, refuse a program with no
        kernel in it (every shape here tiles)."""
        compiled = jax.jit(fn).lower(*args).compile()
        if _on_tpu() and not _has_kernel(compiled):
            raise AssertionError(f"kernel {name}: no tpu_custom_call")
        return compiled(*args)

    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(0), 32))

    # Flash attention forward and backward, [B, T, H, D] bf16, causal. The
    # dense reference runs at 'highest' so the tolerance measures the
    # kernel (bf16 inputs, fp32 accumulation), not the reference.
    q, k, v = (
        jax.random.normal(next(keys), (batch, seq, heads, head_dim), bf16)
        for _ in range(3)
    )
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))

    def loss(att):
        return lambda q, k, v: (att(q, k, v).astype(jnp.float32) * w).sum()

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=interpret
    )
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)  # noqa: E731
    out = jit_checked("flash_fwd", flash, q, k, v)
    g = jit_checked(
        "flash_bwd", jax.grad(loss(flash), argnums=(0, 1, 2)), q, k, v
    )
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(dense)(q, k, v)
        g_ref = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    check("flash_fwd", out, ref, 3e-2)
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        check(f"flash_bwd_{name}", a, b, 6e-2)
    del out, g, ref, g_ref, w

    # Single-token decode over a contiguous cache (generate()'s path),
    # ragged occupancy, bf16 and int8.
    qd = jax.random.normal(next(keys), (batch, heads, head_dim), bf16)
    kv_len = jnp.asarray(
        [1 + (i * 131) % seq for i in range(batch)], jnp.int32
    ).at[0].set(seq)
    out = jit_checked(
        "decode",
        lambda q, k, v, l: da.decode_attention(
            q, k, v, l, impl="flash", interpret=interpret),
        qd, k, v, kv_len,
    )
    check("decode", out, da.dense_decode_attention(qd, k, v, kv_len), 2e-2)
    # Per-(row, position, head) scales in bf16, as models/gpt.py stores them.
    k8, ks = quantize(k, "int8", channel_axes=(0, 1, 2), scale_dtype=bf16)
    v8, vs = quantize(v, "int8", channel_axes=(0, 1, 2), scale_dtype=bf16)
    ks, vs = ks[..., 0], vs[..., 0]
    out = jit_checked(
        "decode_int8",
        lambda q, k, v, l, a, b: da.decode_attention(
            q, k, v, l, k_scale=a, v_scale=b, impl="flash",
            interpret=interpret),
        qd, k8, v8, kv_len, ks, vs,
    )
    check("decode_int8", out, da.dense_decode_attention_quant(
        qd, k8, v8, kv_len, ks, vs), 2e-2)

    # Paged decode and the verify tile (the engine's path): the same K/V
    # cut into pool blocks behind a shuffled block table, stored as the
    # model stores them — all layers stacked, lane-dense [L, N, bs, H*D],
    # a block's int8 scales one row [L, N, H*bs] — and read at layer 1 of
    # 2 (layer 0 holds zeros).
    layer = 1
    # Row 1 is DEAD (a slot with no request): length 0, zeros out.
    lens_p = kv_len.at[1].set(0)
    live = (lens_p > 0)[:, None, None]
    for bs in block_sizes:
        m = seq // bs
        perm = jax.random.permutation(next(keys), batch * m) + 1  # 0=trash
        tables = perm.reshape(batch, m).astype(jnp.int32)

        def pool(name, x):
            rows = slot_blocks_to_pool(
                name, x.reshape(batch * m, bs, *x.shape[2:])
            )
            out = jnp.zeros((2, batch * m + 1, *rows.shape[1:]), x.dtype)
            return out.at[layer, perm].set(rows)

        kp, vp = pool("key_pool", k), pool("value_pool", v)
        out = jit_checked(
            f"paged_decode_bs{bs}",
            lambda q, k, v, l, t: da.paged_decode_attention(
                q, k, v, l, t, layer, impl="flash", interpret=interpret),
            qd, kp, vp, lens_p, tables,
        )
        check(f"paged_decode_bs{bs}", out, da.dense_paged_decode_attention(
            qd, kp, vp, lens_p, tables, layer), 2e-2)
        # ...which is also what the contiguous reference gives.
        check(f"paged_vs_contiguous_bs{bs}", out, jnp.where(
            live, da.dense_decode_attention(qd, k, v, kv_len), 0), 2e-2)
        kp8, vp8 = pool("key_pool", k8), pool("value_pool", v8)
        ksp, vsp = pool("key_pool_scale", ks), pool("value_pool_scale", vs)
        out = jit_checked(
            f"paged_decode_int8_bs{bs}",
            lambda q, k, v, l, t, a, b: da.paged_decode_attention(
                q, k, v, l, t, layer, k_scale=a, v_scale=b, impl="flash",
                interpret=interpret),
            qd, kp8, vp8, lens_p, tables, ksp, vsp,
        )
        check(f"paged_decode_int8_bs{bs}", out,
              da.dense_paged_decode_attention(
                  qd, kp8, vp8, lens_p, tables, layer, ksp, vsp), 2e-2)
        qv = jax.random.normal(
            next(keys), (batch, verify_len, heads, head_dim), bf16
        )
        lens_v = jnp.where(lens_p > 0, jnp.maximum(kv_len, verify_len), 0)
        out = jit_checked(
            f"paged_verify_bs{bs}",
            lambda q, k, v, l, t: da.paged_verify_attention(
                q, k, v, l, t, layer, impl="flash", interpret=interpret),
            qv, kp, vp, lens_v, tables,
        )
        check(f"paged_verify_bs{bs}", out, da.dense_paged_verify_attention(
            qv, kp, vp, lens_v, tables, layer), 2e-2)
        del kp, vp, kp8, vp8, ksp, vsp, out

    # The same paged kernel over the pools of a layer KIND: query heads
    # grouped over fewer KV heads and, under a window, the table a ring
    # (block j at place j % places) that holds a row's newest blocks — the
    # longer rows have wrapped it, the shorter stay under the window. Held
    # to its plain twin and to plain grouped attention over the contiguous
    # K/V.
    h_kv, hd, bs = kinds["kv_heads"], kinds["head_dim"], kinds["block"]
    kk, vk = (
        jax.random.normal(next(keys), (batch, seq, h_kv, hd), bf16)
        for _ in range(2)
    )
    for kind, (h_q, window) in kinds["layers"].items():
        places = -(-window // bs) + 1 if window else seq // bs
        tables = np.asarray(
            jax.random.permutation(next(keys), batch * places) + 1, np.int32
        ).reshape(batch, places)
        pools = np.zeros((2, 2, batch * places + 1, bs, h_kv * hd), bf16)
        for r, n in enumerate(np.asarray(lens_p)):
            end = -(-int(n) // bs)
            for j in range(max(end - places, 0), end):
                for pool, x in zip(pools, (kk, vk)):
                    pool[layer, tables[r, j % places]] = np.asarray(
                        x[r, j * bs:(j + 1) * bs]).reshape(bs, h_kv * hd)
        kp, vp = jnp.asarray(pools[0]), jnp.asarray(pools[1])
        qk = jax.random.normal(next(keys), (batch, h_q, hd), bf16)
        out = jit_checked(
            f"mixed_decode_{kind}",
            lambda q, k, v, l, t: da.paged_verify_attention(
                q[:, None], k, v, l, t, layer, window=window, impl="flash",
                interpret=interpret, name=f"attn_mixed_decode_{kind}")[:, 0],
            qk, kp, vp, lens_p, jnp.asarray(tables),
        )
        check(f"mixed_decode_{kind}", out, da.dense_paged_decode_attention(
            qk, kp, vp, lens_p, jnp.asarray(tables), layer, window=window),
            2e-2)
        check(f"mixed_vs_contiguous_{kind}", out, jnp.where(
            live, grouped_attention(
                qk[:, None], kk, vk, kv_len[:, None] - 1, window=window
            )[:, 0], 0), 2e-2)
        del kp, vp, pools, out

    # Fused AdamW vs optax.adamw at an MLP weight's shape.
    params = {"w": jax.random.normal(next(keys), adamw_shape)}
    grads = jax.tree.map(jnp.cos, params)
    kw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    tx_f = fused_adamw(1e-3, interpret=interpret, **kw)
    tx_r = optax.adamw(1e-3, **kw)
    p_f, s_f = jit_checked(
        "fused_adamw", tx_f.fused_apply, grads, tx_f.init(params), params
    )
    u_r, _ = tx_r.update(grads, tx_r.init(params), params)
    check("fused_adamw", p_f["w"], optax.apply_updates(params, u_r)["w"],
          1e-5)
    if int(jax.device_get(s_f.count)) != 1:
        raise AssertionError("fused_adamw did not advance its step count")
    fields.update(
        widths=dict(batch=batch, heads=heads, head_dim=head_dim, seq=seq,
                    block_sizes=list(block_sizes), verify_len=verify_len,
                    kinds=kinds,
                    adamw_shape=list(adamw_shape)),
        kernels_in_program=_on_tpu(),
        max_abs_err={k_: float(f"{e:.3g}") for k_, e in errs.items()},
    )


# ------------------------------------------- the two cheap chip-only checks


def phase_offload(fields: dict) -> None:
    """Optimizer state in ``pinned_host`` memory (a TPU-only memory kind):
    the state reports it and the model still learns."""
    import jax

    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(
        get_config("mnist_mlp"),
        ["data.global_batch_size=256", "data.prefetch=0",
         "trainer.log_every=1000000", "checkpoint.enabled=false",
         "trainer.offload_opt_state=true",
         f"workdir={os.path.join(OUT_DIR, 'offload')}"],
    )
    trainer = Trainer(cfg)
    state = trainer.init_state()
    kinds = sorted(
        {l.sharding.memory_kind for l in jax.tree.leaves(state.opt_state)}
    )
    batch = trainer.pipeline.global_batch(0)
    l0 = None
    for step in range(20):
        state, metrics = trainer.train_step(state, batch)
        if step == 0:
            l0 = float(jax.device_get(metrics["loss"]))
    l_last = float(jax.device_get(metrics["loss"]))
    if kinds != ["pinned_host"] or not l_last < l0:
        raise AssertionError(
            f"opt-state offload: memory kinds {kinds}, loss {l0} -> {l_last}"
        )
    fields.update(memory_kinds=kinds, loss0=round(l0, 4),
                  loss_last=round(l_last, 4))


def phase_moe_dispatch(fields: dict) -> None:
    """MoE sort-vs-einsum dispatch: CI pins exact equivalence on the CPU;
    the chip check is that the scatter/gather formulation compiles for the
    TPU and agrees there too (its lowering differs materially)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from frl_distributed_ml_scaffold_tpu.config.schema import (
        GPTConfig,
        MoEConfig,
    )
    from frl_distributed_ml_scaffold_tpu.models.moe import MoEMlp

    gcfg = GPTConfig(
        hidden_dim=128, num_heads=4, seq_len=64,
        moe=MoEConfig(num_experts=8, top_k=2, num_groups=1),
    )
    x = jax.random.normal(jax.random.key(0), (4, 64, 128), jnp.float32)
    outs = {}
    # Highest matmul precision: the einsum path's exchange runs on the MXU
    # while sort's gathers are exact, so default-precision error would not
    # cancel between the two paths and could false-fail the check.
    with jax.default_matmul_precision("highest"):
        for dispatch in ("einsum", "sort"):
            m = MoEMlp(
                dataclasses.replace(
                    gcfg, moe=dataclasses.replace(gcfg.moe, dispatch=dispatch)
                ),
                jnp.float32,
            )
            variables = jax.jit(
                lambda v, _m=m: _m.init(jax.random.key(1), v, train=True)
            )(x)
            outs[dispatch] = jax.jit(
                lambda v, xx, _m=m: _m.apply(v, xx, train=True)
            )(variables, x)
    err = _max_abs_err(outs["einsum"][0], outs["sort"][0])
    if not err < 1e-4:
        raise AssertionError(f"moe sort vs einsum dispatch: max|err| {err}")
    fields.update(max_abs_err=float(f"{err:.3g}"))


# ------------------------------------------------------- four chips only


def _run_steps(name: str, overrides: list[str], devices, steps: int):
    """``steps`` train steps of ``name`` on a mesh over ``devices``: the
    losses, the parameters' bytes and memory_stats' bytes in use per
    device, the mesh, and whether the step holds a Pallas kernel."""
    import jax

    from frl_distributed_ml_scaffold_tpu.config import (
        apply_overrides,
        get_config,
    )
    from frl_distributed_ml_scaffold_tpu.dist.mesh import build_mesh
    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    cfg = apply_overrides(get_config(name), overrides)
    env = build_mesh(cfg.mesh, devices=devices)
    trainer = Trainer(cfg, mesh_env=env)
    state = trainer.init_state()
    batch = trainer.pipeline.global_batch(0)
    kernel = _has_kernel(trainer.lower_train_step(state, batch))
    param_bytes = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(state.params):
        for shard in leaf.addressable_shards:
            param_bytes[shard.device.id] += shard.data.nbytes
    losses = []
    for _ in range(steps):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    in_use = {
        d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    }
    return dict(
        config=name, mesh=dict(env.mesh.shape), losses=losses,
        param_bytes_per_device=param_bytes, bytes_in_use_per_device=in_use,
        flash_kernel_in_step=kernel,
    )


def phase_sharded(fields: dict, *, devices=None,
                  overrides: tuple[str, ...] = TRAIN_POINT, steps: int = 3,
                  arms: tuple[str, ...] = ("gspmd", "overlap")) -> None:
    """The sharded step on an ``fsdp=2 x model=2`` mesh against the same
    steps on a one-device mesh, in this process: the GSPMD rules of
    parallel/partition.py + gpt_tp_rules, then the shard_map ppermute
    rings of the ``gpt2_medium_fsdp_tp_overlap`` recipe. Losses must agree
    to the tolerance the virtual-device dry run uses, and every device
    must hold its share of the parameters."""
    import math

    import jax

    from __graft_entry__ import _PARITY_TOL

    devices = list(jax.devices() if devices is None else devices)
    base = [*overrides, "data.prefetch=0", "checkpoint.enabled=false",
            f"workdir={os.path.join(OUT_DIR, 'sharded')}"]
    recipes = {
        "gspmd": ("gpt2_medium_zero1",
                  ["mesh.data=1", "mesh.fsdp=2", "mesh.model=2",
                   "parallel.param_sharding=fsdp",
                   "parallel.opt_sharding=like_params"]),
        "overlap": ("gpt2_medium_fsdp_tp_overlap", []),
    }
    single = _run_steps(
        "gpt2_medium_zero1", base, devices[:1], steps
    )["losses"]
    gc.collect()
    fields.update(steps=steps, tol=_PARITY_TOL,
                  one_device_losses=[round(l, 4) for l in single])
    for arm in arms:
        name, extra = recipes[arm]
        fields[arm] = run = _run_steps(name, base + extra, devices, steps)
        gc.collect()
        losses, param_bytes = run["losses"], run["param_bytes_per_device"]
        if not all(math.isfinite(l) for l in losses):
            raise AssertionError(f"{arm}: non-finite loss {losses}")
        for a, b in zip(losses, single):
            if not abs(a - b) <= _PARITY_TOL * max(1.0, abs(b)):
                raise AssertionError(
                    f"{arm}: sharded losses {losses} != one-device losses "
                    f"{single} (tol {_PARITY_TOL})"
                )
        total = sum(param_bytes.values())
        # fsdp=2 x model=2 cuts every large parameter four ways; only the
        # small replicated leaves (norms, biases) are held whole. One
        # device holding more than a third is a placement fault.
        worst = max(param_bytes.values())
        if len(devices) > 1 and not (
            min(param_bytes.values()) > 0 and worst <= total / 3
        ):
            raise AssertionError(
                f"{arm}: parameters are not spread over the mesh: "
                f"{param_bytes}"
            )
        if _on_tpu() and not run["flash_kernel_in_step"]:
            raise AssertionError(f"{arm}: no tpu_custom_call in the step")
        run["losses"] = [round(l, 4) for l in losses]


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the sharded step and its one-device comparison",
    )
    args = ap.parse_args(argv)

    dev = check_device(args.chips)  # no TPU: exits non-zero, here
    from frl_distributed_ml_scaffold_tpu.launcher.launch import (
        enable_compile_cache,
    )

    enable_compile_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    clock = CompileClock()
    preamble(dev)
    if args.chips == 4:
        with phase("sharded", clock) as f:
            phase_sharded(f)
    else:
        with phase("train", clock) as f:
            phase_train(f)
        with phase("serve", clock) as f:
            phase_serve(f)
        with phase("kernels", clock) as f:
            phase_kernels(f)
        with phase("opt_state_offload", clock) as f:
            phase_offload(f)
        with phase("moe_dispatch", clock) as f:
            phase_moe_dispatch(f)
    sys.stderr.flush()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
