"""Benchmark harness: one module per paper claim (the paper is a theory
paper — no experimental tables — so benchmarks validate its equations and
complexity claims; see DESIGN.md §1 "Validation targets").

    PYTHONPATH=src python -m benchmarks.run [--only collision,...] [--skip recall,...]

Prints ``name,us_per_call,derived`` CSV.

The kernel rows are additionally snapshotted to ``BENCH_kernels.json``,
the mutable-lifecycle rows to ``BENCH_updates.json``, the planner
adherence rows to ``BENCH_planner.json``, the serving-broker rows
(trace latency/throughput, degradation recall, chaos coverage) to
``BENCH_serving.json``, and the autotuner rows (prior-vs-calibrated
plan speedup + adherence) to ``BENCH_tuner.json``, and the adaptive-probing
rows (tables probed + streamed-vs-monolithic speedup) to
``BENCH_earlyexit.json`` (cwd) — one record per row plus
backend/device metadata — so successive PRs leave a machine-readable perf
trajectory.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

MODULES = [
    "collision",  # Eq 25/27 Monte-Carlo validation
    "rho_tables",  # Thm 4/5 rho < 1 tables
    "odtrick",  # §4.2.3 O(d) trick equivalence + speedup
    "sublinear_fit",  # empirical n^rho_hat scaling
    "recall",  # recall@10 vs exact scan
    "multiprobe_bench",  # beyond-paper: probes-for-tables trade
    "planner_bench",  # declarative planning: recall-target adherence + cost
    "kernels_bench",  # kernel microbenchmarks
    "update_bench",  # mutable lifecycle: insert/query-vs-fill/compact
    "serving_bench",  # broker: traces, degradation recall, chaos coverage
    "tuner_bench",  # offline autotuner: prior-vs-calibrated speedup + adherence
    "quant_bench",  # quantized tier: memory ratio, latency, recall delta
    "earlyexit_bench",  # adaptive probing: tables probed + speedup vs full L
    "analysis_bench",  # static-analysis gate: lint/trace cost + budget numbers
]

# convenience aliases accepted by --only/--skip
ALIASES = {"quant": "quant_bench", "analysis": "analysis_bench",
           "earlyexit": "earlyexit_bench"}

# benchmark modules whose rows also snapshot to a machine-readable artifact
SNAPSHOTS = {
    "kernels_bench": "BENCH_kernels.json",
    "update_bench": "BENCH_updates.json",
    "planner_bench": "BENCH_planner.json",
    "serving_bench": "BENCH_serving.json",
    "tuner_bench": "BENCH_tuner.json",
    "quant_bench": "BENCH_quant.json",
    "earlyexit_bench": "BENCH_earlyexit.json",
    "analysis_bench": "BENCH_analysis.json",
}


def select_modules(only: str | None, skip: str | None) -> list:
    """Apply ``--only`` then ``--skip``; unknown names fail fast (a typo'd
    filter silently running the full suite costs minutes)."""
    mods = only.split(",") if only else list(MODULES)
    skipped = skip.split(",") if skip else []
    mods = [ALIASES.get(m, m) for m in mods]
    skipped = [ALIASES.get(m, m) for m in skipped]
    unknown = [m for m in [*mods, *skipped] if m not in MODULES]
    if unknown:
        raise SystemExit(
            f"unknown benchmark module(s) {unknown}; known: {', '.join(MODULES)}"
        )
    return [m for m in mods if m not in skipped]


def _write_kernels_json(rows, path: str = "BENCH_kernels.json") -> None:
    import jax

    payload = {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "rows": [
            {"name": name, "us_per_call": round(us, 2), "derived": str(derived)}
            for name, us, derived in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module list")
    ap.add_argument("--skip", default=None,
                    help="comma-separated modules to exclude from the run")
    args = ap.parse_args()
    mods = select_modules(args.only, args.skip)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run()
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.1f},{derived}")
            sys.stdout.flush()
            if name in SNAPSHOTS:
                _write_kernels_json(rows, path=SNAPSHOTS[name])
        except Exception as e:
            failed.append(name)
            print(f"{name},NaN,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()
