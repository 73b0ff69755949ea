"""What every window driver shares: the benchmark's files, the device check,
the peaks table, the exact percentile, seeds, and the result line."""

from __future__ import annotations

import importlib
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

T0 = time.perf_counter()  # run.py moves this back to its own first line


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def phase(name: str) -> None:
    """Where set-up's time goes: seconds since the process began."""
    log(f"phase {name}: done at {time.perf_counter() - T0:.2f} s")


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as fh:
        return json.load(fh)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_cell(name: str) -> dict:
    """One entry of BENCHMARK.json's workloads, with its two files."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = dict(cells[name])
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as fh:
        cell["config_file"] = json.load(fh)
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")
    kinds = {cell["config_file"]["kind"], cell["traffic_file"]["kind"]}
    if len(kinds) != 1:
        raise SystemExit(f"{name}: config and traffic are of different kinds {kinds}")
    cell["kind"] = kinds.pop()
    return cell


def sized(cfg_file: dict, key: str, rehearse: bool) -> dict:
    """A group of the configuration's file (`model`, `optimizer`, `engine`),
    with the rehearsal preset's changes laid over it in a rehearsal."""
    over = cfg_file["rehearse"].get(key, {}) if rehearse else {}
    return dict(cfg_file.get(key, {}), **over)


def reference_of(cfg_file: dict):
    """The configuration's plain reference: the module under reference/ that
    its file names. There is no default: which equations a configuration is
    held to is the file's to say."""
    name = cfg_file.get("reference")
    if not name:
        raise SystemExit(
            "the configuration's file has no `reference` key: it has to name its plain "
            "reference, a module under benchmarks/reference/ (README.md, \"The reference's "
            "interface\")")
    return importlib.import_module("reference." + name)


def limits(cfg_file: dict, rehearse: bool) -> dict:
    """The limits of `correct`: the cell's own, set from chip readings at its
    own size; a rehearsal's tiny model may state its own beside its sizes."""
    over = cfg_file["rehearse"].get("limits", {}) if rehearse else {}
    return dict(cfg_file["correct"]["limits"], **over)


def percentile(values, q: float) -> float:
    """Exact percentile with linear interpolation between order statistics
    (numpy's default definition), on a plain list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(
            f"no peaks known for device kind {device_kind!r}: add it to "
            "benchmarks/peaks.json with the source of the figures"
        )
    return table[device_kind]


def check_devices(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Outside a rehearsal anything but the
    cell's number of TPU chips ends the run with no result."""
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if rehearse:
        return dev
    if dev["platform"] != "tpu" or dev["count"] != chips:
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX reports {dev}. "
            "A measurement never falls back to the CPU (use --rehearse "
            "for a CPU walk-through that prints no device metric)."
        )
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps none)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def seed_halves(seed: int):
    """The seed as two uint32 values (seeds run past 32 bits), to be passed
    into jitted code as arguments: a seed closed over would be a constant of
    the program, and every new seed would compile anew."""
    import numpy as np

    return np.uint32(seed % (2**32)), np.uint32(seed // (2**32))


def seed_key(lo, hi, stream: int = 0):
    """A jax key from the two halves; works on traced values."""
    import jax

    key = jax.random.fold_in(jax.random.key(lo), hi)
    return jax.random.fold_in(key, stream)


def place_compile_cache() -> None:
    """The program's own helper places the cache (JAX_COMPILATION_CACHE_DIR
    when set, else the fixed <checkout>/.jax_cache); sub-second programs are
    cached too so that a second run of a cell compiles nothing."""
    import jax
    from frl_distributed_ml_scaffold_tpu.launcher.launch import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def print_result(result: dict) -> None:
    """The compared numbers last on stderr, then the one result line last on
    stdout, with the compared numbers as its last key."""
    compared = result.pop("compared", {})
    sys.stdout.flush()
    for name, pair in compared.items():
        print(f"[bench] compared {name}: value={pair['value']!r} limit={pair['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = compared
    print(json.dumps(result), flush=True)
