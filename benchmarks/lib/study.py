#!/usr/bin/env python3
"""Readings from which the limits of `correct` are set, many seeds in one
process: the program against the reference (the lower reading), the reference
in the precision below put in the program's place (the control), and for
training the half-batch fault planted in the reference (a state returned
unchanged reads 1 by the measure and needs no run).

    python3 benchmarks/lib/study.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--first-seed N] [--seconds S] [--out FILE]

Prints one JSON line per seed, with what `correct.decide` says of each
reading under the cell's committed limits (`program_correct`,
`control_fp8_correct`, ...: the control has to come out false). Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import common, correct  # noqa: E402

FAULT_SEEDS = 3  # of the control's seeds, those on which half the batch is left out too


def study_train(cell, args, emit):
    import gc

    from frl_distributed_ml_scaffold_tpu.trainer.loop import Trainer

    from lib import train

    cfg_file = cell["config_file"]
    steps = cfg_file["correct"]["steps"]
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cfg = train.build_config(cell, seed, args.rehearse)
        trainer = Trainer(cfg)
        prog, state = train.program_readings(trainer, train.fresh_state(trainer, seed), seed,
                                             steps, cfg_file["optimizer"]["b1"])
        del state, trainer
        gc.collect()
        ref = train.reference_readings(cell, cfg, seed, steps, args.rehearse)
        limits = common.limits(cfg_file, args.rehearse)
        numbers = correct.train_numbers(prog, ref)
        row = {"seed": seed, "program": numbers,
               "program_correct": correct.decide(numbers, limits)[0],
               "loss": {"program": prog["loss"], "reference": ref["loss"]},
               "reference_grad_norm": ref["grad_norm"]}
        del prog
        if i < args.control_seeds:
            low = train.reference_readings(cell, cfg, seed, steps, args.rehearse, lowp=True)
            row["control_fp8"] = correct.train_numbers(low, ref)
            row["control_fp8_correct"] = correct.decide(row["control_fp8"], limits)[0]
            del low
        if i < min(args.control_seeds, FAULT_SEEDS):
            half = train.reference_readings(cell, cfg, seed, steps, args.rehearse,
                                            fault="half_batch")
            row["fault_half_batch"] = correct.train_numbers(half, ref)
            row["fault_half_batch_correct"] = correct.decide(row["fault_half_batch"], limits)[0]
            del half
        emit(row)


def study_serve(cell, args, emit):
    from lib import serve
    from lib.traffic import serve_schedule
    from lib.weights import make_params

    cfg_file, mix = cell["config_file"], cell["traffic_file"]
    ref = common.reference_of(cfg_file)
    engine, tracer, params, sizes, eng_kw = serve.build_engine(
        cell, args.first_seed, args.rehearse, False)
    if args.rehearse:
        mix = serve.rehearsal_mix(mix, sizes)
    serve.warm_up(engine, mix, sizes, eng_kw["kv_block_size"], args.first_seed)
    import jax

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    mix = dict(mix, ramp_s=0.0)  # every request of the short window is finished and sampled
    seconds = args.seconds or cfg_file["correct"]["sample_requests"] / mix["rate_per_s"]
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        params = make_params(shapes, seed)
        engine.params = params
        tracer.drain()
        schedule = serve_schedule(mix, seconds, seed, sizes["vocab_size"])
        res = serve.drive(engine, schedule, seconds, float(mix.get("drain_s", 60.0)))
        w = serve.window_metrics(res, seconds)
        sample = serve.pick_sample(res, seconds, cfg_file["correct"]["sample_requests"], seed)
        gap, n = serve.served_gap(ref, sizes, params, sample)
        row = {"seed": seed, "program": {"logit_gap": gap}, "tokens": n,
               "attempted": w["attempted"], "failed": w["failed"],
               "why_failed": w["why_failed"], "drained_by_s": res["t_end"]}
        limits = common.limits(cfg_file, args.rehearse)
        row["program_correct"] = correct.decide(row["program"], limits,
                                                extra_ok=w["failed"] == 0 and n > 0)[0]
        if i < args.control_seeds:
            row["control_fp8"] = {
                "logit_gap": serve.served_gap(ref, sizes, params, sample, lowp=True)[0]}
            row["control_fp8_correct"] = correct.decide(row["control_fp8"], limits)[0]
        emit(row)
    engine.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_484_001)
    ap.add_argument("--seconds", type=float, default=None,
                    help="serving: the short window; by default as long as it takes "
                    "for as many requests as a run compares to fall due")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    common.check_devices(cell["chips"], args.rehearse)
    common.place_compile_cache()
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    {"train": study_train, "serve": study_serve}[cell["kind"]](cell, args, emit)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
