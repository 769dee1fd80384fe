"""Benchmark driver: one function per paper table (+ TPU extensions).

Each table module's ``run()`` is timed with warmup + repeated runs; the
MEDIAN wall time is reported (robust to first-call JIT compilation and
scheduler noise).  Besides the human-readable CSV on stdout, the driver
writes a ``BENCH_<timestamp>.json`` artifact (name, median_us, derived
metrics per table) so the perf trajectory stays machine-readable across PRs:
compare any two artifacts field-by-field to see what moved.

When ``REPRO_AUTOTUNE_MEASURE=1``, the LSTM block-size winners are refined
EMPIRICALLY before any bench runs: the autotuner's analytic top-3
candidates for every shape ``benchmarks/paper_lstm.bench_shapes`` will
execute are re-ranked by real kernel timing (``bench.make_measure_fn``) and
the measured winner is cached — step 3 of the paper's Generator methodology
(analytical pruning, then measurement of survivors), previously an unused
hook.  The CI ``lstm-bench-smoke`` step exercises this in interpret mode.

Usage:
  python benchmarks/run.py [--warmup 1] [--repeats 3] [--only NAME ...]
                           [--out DIR] [--quick]
"""
import argparse
import inspect
import json
import os
import statistics
import sys
import time
from datetime import datetime, timezone
from pathlib import Path

# Make ``from benchmarks import ...`` work when invoked as a script
# (``python benchmarks/run.py`` puts benchmarks/ itself on sys.path, not
# the repo root).
_ROOT = str(Path(__file__).resolve().parent.parent)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from repro.kernels.runtime import enable_compile_cache  # noqa: E402


def _run(mod, quick: bool):
    """Call ``mod.run()``, forwarding ``quick`` when the bench supports it."""
    if quick and "quick" in inspect.signature(mod.run).parameters:
        return mod.run(quick=True)
    return mod.run()


def time_module(mod, warmup: int, repeats: int, quick: bool = False):
    """Median wall-time (µs) of ``mod.run()`` plus its derived metrics."""
    for _ in range(warmup):
        _run(mod, quick)
    times, derived = [], {}
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        derived = _run(mod, quick) or {}
        times.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(times), derived


def autotune_measure_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE_MEASURE", "").strip().lower() in (
        "1", "true", "yes", "on",
    )


def refine_lstm_autotune(quick: bool = False, *, top_k: int = 3) -> list[dict]:
    """Empirically re-rank the analytic top-k block candidates for every
    LSTM shape the benchmarks will run (the autotuner's ``measure_fn``
    hook).  Winners land in the shared autotune cache, so the subsequent
    ``block_b="auto"`` bench calls pick them up.  Returns the refined
    entries for logging/tests."""
    from benchmarks.paper_lstm import bench_shapes
    from repro.kernels.autotune import autotune
    from repro.kernels.bench import make_measure_fn

    refined = []
    for kernel, problem, dtype in bench_shapes(quick):
        best = autotune(
            kernel, problem, dtype=dtype,
            measure_fn=make_measure_fn(kernel, problem, dtype=dtype),
            top_k=top_k,
        )
        shape = ",".join(f"{k}={v}" for k, v in sorted(problem.items()))
        print(f"  measured {kernel}[{dtype}] {shape} -> {best}")
        refined.append({"kernel": kernel, "problem": dict(problem),
                        "dtype": dtype, "best": dict(best)})
    return refined


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--only", nargs="*", help="run only benches whose name contains any of these")
    ap.add_argument("--out", default=".", help="directory for the BENCH_*.json artifact")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / short streams for benches that support it")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from benchmarks import (
        activation_variants,
        adaptive_threshold,
        generator_fpga,
        generator_tpu,
        paper_lstm,
        roofline_report,
        serve_bench,
        workload_strategies,
    )

    benches = [
        ("paper_lstm_C1_C2", paper_lstm),
        ("workload_strategies_C3", workload_strategies),
        ("adaptive_threshold_C4", adaptive_threshold),
        ("activation_variants_RQ1", activation_variants),
        ("generator_fpga_RQ3", generator_fpga),
        ("generator_tpu_beyond", generator_tpu),
        ("roofline_report", roofline_report),
        ("serve_continuous_batching", serve_bench),
    ]
    if args.only:
        benches = [(n, m) for n, m in benches if any(s in n for s in args.only)]
        if not benches:
            ap.error(f"--only {args.only} matches no benchmark")

    # Refinement only pays off when the LSTM bench actually runs (its
    # winners are what the measured candidates feed).
    if autotune_measure_enabled() and any(m is paper_lstm for _, m in benches):
        print("REPRO_AUTOTUNE_MEASURE=1: refining LSTM block winners empirically")
        refine_lstm_autotune(args.quick)

    results = []
    for name, mod in benches:
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        median_us, derived = time_module(mod, args.warmup, args.repeats,
                                         quick=args.quick)
        results.append({
            "name": name,
            "median_us": median_us,
            "derived": {k: float(v) for k, v in derived.items()},
        })

    print("\nname,median_us,derived")
    for r in results:
        headline = next(iter(r["derived"].items()), ("", float("nan")))
        print(f"{r['name']},{r['median_us']:.0f},{headline[0]}={headline[1]:.4g}")

    stamp = datetime.now(timezone.utc).strftime("%Y%m%d-%H%M%S")
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    artifact = out_dir / f"BENCH_{stamp}.json"
    # schema v2: both drivers share the version + meta block shape that
    # scripts/check_bench.py validates (driver knobs live under "meta")
    artifact.write_text(json.dumps({
        "schema_version": 2,
        "timestamp_utc": stamp,
        "meta": {
            "driver": "run",
            "quick": bool(args.quick),
            "warmup": args.warmup,
            "repeats": args.repeats,
        },
        "results": results,
    }, indent=1, sort_keys=True))
    print(f"\nwrote {artifact}")


if __name__ == "__main__":
    main()
