#!/usr/bin/env python
"""graft-lint CLI: enforce the repo's performance invariants statically.

Lints every registered recipe's train step (trace-only: jaxpr + lowered
StableHLO, no XLA compile), the ``schedule:`` program family (every
overlap recipe's train step re-checked against the expectations DERIVED
from its declared ``parallel/schedule.py`` OverlapSchedule — ISSUE 13),
the serving decode step, and the traced modules' Python source, then
emits a JSON report and exits non-zero on any ``severity:error``
finding.  CPU-sim safe: forces JAX_PLATFORMS=cpu with 8 virtual
devices, the same harness as the test suite.

    python tools/graft_lint.py --all-recipes            # the CI gate
    python tools/graft_lint.py --recipe gpt2_medium_tp_overlap
    python tools/graft_lint.py --all-recipes --json report.json
    python tools/graft_lint.py --all-recipes --budget-mb 256
    python tools/graft_lint.py --all-recipes --save-census census.json
    python tools/graft_lint.py --all-recipes --against census.json

Passes and their error conditions are cataloged in
docs/static_analysis.md; per-recipe shrink shapes live in
``analysis.runner.RECIPE_OVERRIDES`` (a recipe without an entry is itself
a lint error — the gate must never trace production shapes on the sim).

``--save-census`` / ``--against`` persist and diff the per-recipe
collective censuses: the promoted form of "this refactor didn't change
the step's communication".  A diff is reported as a warning (visible,
not blocking) because census changes are sometimes the point of a PR —
refresh the baseline in the same commit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Platform pins BEFORE jax imports (the conftest.py discipline): the lint
# is a CPU-sim analysis and never takes the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _apply_census_diff(reports, against_path):
    from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
        census_diff,
    )

    with open(against_path) as fh:
        baseline = json.load(fh)
    for rep in reports:
        rows = rep.meta.get("collective_census")
        if rows is None or rep.program not in baseline:
            continue
        old = [_record_from_dict(d) for d in baseline[rep.program]]
        new = [_record_from_dict(d) for d in rows]
        diff = census_diff(old, new)
        for kind in ("added", "removed"):
            for entry in diff[kind]:
                rep.add(
                    "collective_census", "warning", f"census-{kind}",
                    f"{entry['count']}x {entry['primitive']} "
                    f"{entry['shapes']} on axes {entry['axes']} "
                    f"{kind} vs baseline",
                    **entry,
                )


def _record_from_dict(d):
    from frl_distributed_ml_scaffold_tpu.analysis.collectives import (
        CollectiveRecord,
    )

    return CollectiveRecord(
        primitive=d["primitive"],
        axes=tuple(d["axes"]),
        shapes=tuple(tuple(s) for s in d["shapes"]),
        dtype=d["dtype"],
        bytes_per_call=d["bytes_per_call"],
        trip_count=d["trip_count"],
        path=tuple(d["path"]),
    )


#: The pass families --only selects from (argparse refuses anything
#: else — a typo'd pass name must fail loudly, not lint nothing).
_FAMILIES = (
    "recipes", "serving", "reshard", "hygiene", "robustness", "concurrency",
)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--all-recipes", action="store_true",
        help="lint every registered recipe (plus serving + hygiene + "
        "robustness)",
    )
    ap.add_argument(
        "--recipe", action="append", default=[],
        help="lint one recipe (repeatable)",
    )
    ap.add_argument(
        "--no-serving", action="store_true",
        help="skip the serving decode-step lint",
    )
    ap.add_argument(
        "--no-reshard", action="store_true",
        help="skip the redistribution executor (reshard:*) lint",
    )
    ap.add_argument(
        "--no-hygiene", action="store_true",
        help="skip the AST hygiene lint",
    )
    ap.add_argument(
        "--no-robustness", action="store_true",
        help="skip the failure-semantics robustness lint",
    )
    ap.add_argument(
        "--no-concurrency", action="store_true",
        help="skip the lock-discipline concurrency lint",
    )
    ap.add_argument(
        "--only", action="append", default=[], metavar="PASS",
        choices=sorted(_FAMILIES),
        help="run ONLY the named pass families (repeatable; one of: "
        + ", ".join(sorted(_FAMILIES))
        + "). Unknown names are refused. 'recipes' still needs "
        "--all-recipes or --recipe.",
    )
    ap.add_argument(
        "--budget-mb", type=float, default=None,
        help="materialization budget per intermediate, in MiB (error "
        "above; default: census only)",
    )
    ap.add_argument("--json", help="write the full JSON report here")
    ap.add_argument(
        "--save-census", help="write per-program collective censuses here"
    )
    ap.add_argument(
        "--against", help="diff censuses against a --save-census file"
    )
    ap.add_argument(
        "--workdir", default="/tmp/graft_lint",
        help="scratch workdir for recipe construction",
    )
    ap.add_argument(
        "-q", "--quiet", action="store_true",
        help="only print failing programs and the final summary",
    )
    args = ap.parse_args(argv)
    only = set(args.only)
    if only:
        if (
            args.no_serving or args.no_reshard or args.no_hygiene
            or args.no_robustness or args.no_concurrency
        ):
            ap.error("--only cannot be combined with --no-* flags")
        if "recipes" in only and not (args.all_recipes or args.recipe):
            ap.error("--only recipes needs --all-recipes or --recipe NAME")
    elif not args.all_recipes and not args.recipe:
        ap.error("pass --all-recipes or at least one --recipe NAME")

    run_recipes = "recipes" in only if only else True

    def _family(name: str, no_flag: bool) -> bool:
        return (name in only) if only else not no_flag

    from frl_distributed_ml_scaffold_tpu.analysis.runner import lint_all

    budget = (
        int(args.budget_mb * 1024 * 1024)
        if args.budget_mb is not None
        else None
    )

    def progress(rep):
        if not args.quiet or not rep.ok:
            for line in rep.summary_lines():
                print(line, flush=True)

    reports = lint_all(
        recipes=(
            (None if args.all_recipes else args.recipe)
            if run_recipes
            else []
        ),
        serving=_family("serving", args.no_serving),
        reshard=_family("reshard", args.no_reshard),
        hygiene=_family("hygiene", args.no_hygiene),
        robustness=_family("robustness", args.no_robustness),
        concurrency=_family("concurrency", args.no_concurrency),
        workdir=args.workdir,
        budget_bytes=budget,
        on_report=progress if args.against is None else None,
    )
    if args.against:
        _apply_census_diff(reports, args.against)
        for rep in reports:
            progress(rep)

    if args.save_census:
        censuses = {
            r.program: r.meta["collective_census"]
            for r in reports
            if "collective_census" in r.meta
        }
        with open(args.save_census, "w") as fh:
            json.dump(censuses, fh, indent=1)
        print(f"wrote censuses for {len(censuses)} programs to "
              f"{args.save_census}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump([r.to_dict() for r in reports], fh, indent=1)
        print(f"wrote JSON report to {args.json}")

    n_err = sum(len(r.errors()) for r in reports)
    n_warn = sum(len(r.warnings()) for r in reports)
    n_fail = sum(1 for r in reports if not r.ok)
    print(
        f"graft-lint: {len(reports)} programs, {n_fail} failing, "
        f"{n_err} error(s), {n_warn} warning(s)"
    )
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
