#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures, checks the timed path against the plain reference
and prints one JSON object as the last line of standard output. The cell's
configuration and traffic mix are files found by name; the window driver is
picked by their `kind`; the per-layer metrics are the files under metrics/
that apply to what the cell's files state. See README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import common  # noqa: E402

common.T0 = T_START


def applies(spec: dict, cell: dict) -> bool:
    """A metric applies by what the cell's own files state, never by name."""
    rule = spec.get("applies", {})
    if "kind" in rule and rule["kind"] != cell["kind"]:
        return False
    kernels = set(cell["config_file"].get("kernels", ()))
    return set(rule.get("kernels", ())) <= kernels


def metric_specs(cell: dict) -> list[dict]:
    mdir = os.path.join(BENCH_DIR, "metrics")
    specs = []
    for fname in sorted(os.listdir(mdir)):
        if fname.endswith(".json"):
            with open(os.path.join(mdir, fname)) as fh:
                spec = json.load(fh)
            if applies(spec, cell):
                specs.append(spec)
    return specs


def reader_for(spec: dict):
    """The `read(ctx, spec)` of metrics/<reader_file>.py where the metric's
    file names one (several metrics share a reader so), else that of
    metrics/<name>.py where there is one, else the function in lib/readers.py
    that the file names as `reader`."""
    own = os.path.join(BENCH_DIR, "metrics", spec.get("reader_file", spec["name"]) + ".py")
    if "reader_file" in spec or os.path.exists(own):
        mod_spec = importlib.util.spec_from_file_location("metric_" + spec["name"], own)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read
    from lib import readers

    return getattr(readers, spec["reader"])


def per_layer(cell: dict, ctx: dict) -> dict:
    out = {}
    for spec in metric_specs(cell):
        value = reader_for(spec)(ctx, spec)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU; prints no device metric")
    ap.add_argument("--sweep", default=None,
                    help="serving cells: comma-separated rates to offer in turn; "
                    "prints the table of backlog and tails and no result line")
    args = ap.parse_args(argv)

    bench = common.load_benchmark()
    cell = common.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    dev = common.check_devices(cell["chips"], args.rehearse)
    common.place_compile_cache()
    common.phase("imports and reaching the chip")
    peaks = None if args.rehearse else common.peaks_for(dev["kind"])
    if args.rehearse:
        print("REHEARSAL on", dev, "- no number below is a device metric", flush=True)

    driver = importlib.import_module("lib." + cell["kind"])
    if args.sweep:
        driver.sweep(cell, args, dev, [float(r) for r in args.sweep.split(",")])
        return 0
    res = driver.run(cell, args, dev, T_START, peaks)

    tr = res["ctx"].get("trace")
    if args.trace:
        t_read = time.perf_counter()
        metrics = per_layer(cell, res["ctx"])
        if tr:
            read_s = time.perf_counter() - t_read
            common.log(f"trace reduction: {tr['reduce_s'] + read_s:.2f} s (parse and reduce "
                       f"{tr['reduce_s']:.2f} s, the per-layer readers {read_s:.2f} s)")
    else:
        # The driver of the cell's kind reads every end-to-end number it can;
        # the line carries those that BENCHMARK.json lists for this cell.
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if cell["name"] in m.get("workloads", [cell["name"]])}
    if args.rehearse:
        # The walk-through's numbers go to stderr only: a CPU timing is never
        # written under a device metric's name.
        common.log(f"rehearsal readings (not device metrics): {json.dumps(metrics)}")
        metrics = {}
    line = {
        "correct": bool(res["correct"]),
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": res["device"],
    }
    if args.trace and tr:
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["compared"] = res["compared"]
    common.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
