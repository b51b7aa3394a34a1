"""Benchmark harness — one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (one line per measurement):
  queues.py           — SPSC vs lock queue op cost (substrate of Fig. 6),
                        in-process and across a spawn boundary (shm ring
                        vs multiprocessing.Queue — the Fig. 5 analogue)
  farm_overhead.py    — Fig. 6: farm overhead vs grain, derived speedup model
  farm_composition.py — graph runtime: pipeline-of-farms + feedback overhead
  skeleton_parity.py  — skeleton IR: same skeleton on both backends
  sched_policies.py   — scheduling policies × grain on a skewed farm + fusion
  proc_farm.py        — threads-vs-procs farm speedup over grain (the
                        GIL-escape curve of the procs backend)
  a2a_shuffle.py      — all-to-all hand-off cost vs nleft×nright matrix
                        shape, threads vs procs
  ooc_aggregation.py  — out-of-core keyed aggregation: wall time + peak RSS
                        per scale tier, budgeted spill path vs the
                        single-process in-memory baseline
  autotune.py         — profile-guided re-lowering (tune=True) vs the best
                        hand-tuned grain and vs the static default lowering
  smith_waterman.py   — Fig. 7 + Table 1: SW database search GCUPS
  roofline.py         — EXPERIMENTS §Roofline terms from the dry-run artifacts

``--json PATH`` additionally writes the rows machine-readable (schema:
``{"schema": "bench-rows/2", "meta": {host, cpus, python, jax, run_id},
"results": {benchmark: [{"config", "us_per_item", "derived"}]}}``) so
the perf trajectory is recorded run over run — CI uploads
``BENCH_results.json`` as an artifact.  The ``meta`` block stamps where
a number came from (bench-rows/1 files lack it; the baseline gate reads
both).  ``--only a,b`` restricts the run to the named modules (smoke
configs stay the caller's job: set module attributes before calling
:func:`main`).

``--check-baseline PATH`` is the perf-regression gate: after the run,
every (benchmark, config) row present in both the fresh results and the
committed baseline JSON (bench-rows/1 or /2 schema) is compared on
``us_per_item``, and the process exits non-zero if any row got slower
than ``baseline × (1 + tolerance)`` (``--tolerance``, default 0.35 —
generous because CI machines are noisy and smoke tiers are small).
Rows on only one side are reported and skipped, so adding a benchmark
never breaks the gate before the baseline is re-recorded.  To re-record
``benchmarks/baseline.json`` after an intended perf change, run the CI
smoke invocation (the module-attribute overrides in the bench-JSON step
of ``.github/workflows/ci.yml``) with ``--json benchmarks/baseline.json``
and commit the result.

Skeleton API
------------
The streaming modules all build the same IR (``repro.core.skeleton``): a
declarative ``Pipeline`` / ``Farm`` / ``Feedback`` / ``AllToAll``
expression, executed by ``lower(skel, backend=...)``.  The ``threads``
backend lowers to the thread/SPSC-ring graph runtime (what
``farm_overhead`` / ``farm_composition`` cost out, hand-off by hand-off);
the ``mesh`` backend lowers the *whole* skeleton to one ``shard_map``
program (``pipeline_apply`` of ``farm_map`` stages — no host hop between
farms).  ``skeleton_parity.py`` runs one skeleton both ways, asserts
identical ordered outputs, and reports the per-item hand-off overhead vs
the fused lowering — the measured input to the ROADMAP's fusion-policy
item.
"""
from __future__ import annotations

import argparse
import importlib
import json
import time
from typing import List, Optional, Tuple

MODULES = ("queues", "farm_overhead", "farm_composition", "skeleton_parity",
           "sched_policies", "proc_farm", "a2a_shuffle", "ooc_aggregation",
           "autotune", "smith_waterman", "roofline")


def _emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


def run_meta() -> dict:
    """The bench-rows/2 provenance block: enough to tell two uploaded
    artifacts apart (which host, how many cores, which toolchain) and a
    monotonic run id to order same-host runs."""
    import os
    import platform

    meta = {
        "host": platform.node(),
        "cpus": os.cpu_count(),
        "python": platform.python_version(),
        "run_id": f"{time.time_ns():x}",
    }
    try:
        from importlib import metadata as _ilmd
        meta["jax"] = _ilmd.version("jax")
    except Exception:          # jax absent: the host-only rows still record
        meta["jax"] = None
    return meta


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as machine-readable JSON "
                         "(BENCH_results.json schema)")
    ap.add_argument("--only", metavar="MODS", default=None,
                    help="comma-separated benchmark modules to run "
                         f"(default: all of {','.join(MODULES)})")
    ap.add_argument("--check-baseline", metavar="PATH", default=None,
                    help="compare rows against a committed bench-rows/1 "
                         "baseline and exit non-zero on a regression past "
                         "--tolerance (the CI perf gate)")
    ap.add_argument("--tolerance", type=float, default=0.35,
                    help="allowed fractional slowdown per row before the "
                         "baseline check fails (default 0.35)")
    args = ap.parse_args(argv)

    names = MODULES if args.only is None else tuple(
        m.strip() for m in args.only.split(",") if m.strip())
    unknown = sorted(set(names) - set(MODULES))
    if unknown:
        ap.error(f"unknown benchmark modules {unknown} (have {list(MODULES)})")
    if not names:
        # "--only , " would otherwise run nothing and exit 0 — a CI
        # invocation typo silently uploading an empty BENCH_results.json
        ap.error(f"--only selected no benchmark modules "
                 f"(have {list(MODULES)})")
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows: List[Tuple[str, str, float, str]] = []
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        mod = importlib.import_module(f"{__package__ or 'benchmarks'}.{name}")

        def emit(row_name: str, us: float, derived: str = "",
                 _bench: str = name) -> None:
            rows.append((_bench, row_name, us, derived))
            _emit(row_name, us, derived)

        mod.run(emit)
    _emit("total_bench_wall", (time.time() - t0) * 1e6, "")

    if args.json:
        results: dict = {}
        for bench, config, us, derived in rows:
            results.setdefault(bench, []).append(
                {"config": config, "us_per_item": us, "derived": derived})
        with open(args.json, "w") as f:
            json.dump({"schema": "bench-rows/2", "meta": run_meta(),
                       "results": results}, f, indent=2, sort_keys=True)
        print(f"# wrote {sum(map(len, results.values()))} rows "
              f"from {len(results)} benchmarks to {args.json}", flush=True)

    if args.check_baseline:
        check_baseline(rows, args.check_baseline, args.tolerance)


def check_baseline(rows: List[Tuple[str, str, float, str]], path: str,
                   tolerance: float) -> None:
    """The perf-regression gate: raise ``SystemExit(1)`` if any row shared
    with the baseline regressed past ``baseline × (1 + tolerance)``."""
    with open(path) as f:
        base = json.load(f)
    if base.get("schema") not in ("bench-rows/1", "bench-rows/2"):
        raise SystemExit(f"baseline {path} is not bench-rows/1 or /2 "
                         f"(schema={base.get('schema')!r})")
    baseline = {(bench, r["config"]): float(r["us_per_item"])
                for bench, rs in base.get("results", {}).items()
                for r in rs}
    fresh = {(bench, config): us for bench, config, us, _ in rows}
    regressions = []
    compared = 0
    for key in sorted(set(fresh) & set(baseline)):
        compared += 1
        was, now = baseline[key], fresh[key]
        if was > 0 and now > was * (1.0 + tolerance):
            regressions.append((key, was, now))
    skipped = sorted(set(fresh) ^ set(baseline))
    for key in skipped:
        side = "baseline-only" if key in baseline else "new"
        print(f"# baseline: skipping {key[0]}/{key[1]} ({side} row)")
    print(f"# baseline: {compared} rows compared against {path} "
          f"(tolerance {tolerance:+.0%})", flush=True)
    if regressions:
        for (bench, config), was, now in regressions:
            print(f"# REGRESSION {bench}/{config}: {was:.3f} -> {now:.3f} "
                  f"us/item ({now / was - 1.0:+.0%})", flush=True)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
