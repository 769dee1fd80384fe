"""Run one cell of the benchmark once, on the machine it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the repository root. Prints progress and the
numbers compared for ``correct`` (each beside its limit) on standard error,
and as the last line of standard output one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``compared``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. Exits non-zero, printing no result, when JAX
finds no TPU, fewer chips than the cell needs, or a device kind that
``bench/peaks.json`` lacks.

``--control int8`` serves with the program's int8 path instead, int8
weights and int8 KV pages (the lower-precision control of the correctness
check), and ``--poisson-rate R``
serves the cell's lengths as Poisson arrivals at R per second (the sweep
that finds the knee); neither is part of a measured run.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--poisson-rate", type=float, default=None,
                    help="serve the cell's lengths as Poisson arrivals at this "
                         "rate (the sweep that finds the knee; not a measured run)")
    args = ap.parse_args(argv)
    try:
        from bench import harness
        from repro.kernels.runtime import enable_compile_cache
    except ImportError as e:
        print(f"bench: cannot import the program under test: {e}", file=sys.stderr)
        return 2
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_process=T_PROCESS, control=args.control,
                          poisson_rate=args.poisson_rate)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
