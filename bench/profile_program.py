"""One traced run of a cell, read for the serving program's own spans and
device scopes (``program_trace``), beside the numbers ``run.py`` reports.

    python3 bench/profile_program.py --workload <name> --seed <n> --seconds <s> \
        [--out result.json]

Runs the cell as ``run.py --trace 1`` does (``harness.run``) and reduces
the trace with ``program_trace`` before the harness deletes it, the decode
program's compiled HLO text giving each op its scope. Prints one JSON
object, and writes it to ``--out``: the run's own result (``run``) and the
reduction (``program``). The compile cache keys this run's programs on
their op_name metadata too, so the first such run compiles them anew.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _decode_hlo(engine, pool) -> str:
    """The compiled text of the paged decode program at the pool's shapes:
    the program the window ran."""
    import jax.numpy as jnp

    host = (pool.tok, pool.positions(), pool.decode_mask(), pool.table)
    low = engine._paged_decode.lower(engine.params, pool.cache, *map(jnp.asarray, host))
    return low.compile().as_text()


def profile(cell: str, seed: int, seconds: float, **run_kw) -> dict:
    """{"run": ``harness.run``'s traced result, "program":
    ``program_trace.reduce``'s}. Two of the harness's names are wrapped for
    the run: ``build``, to keep the engine whose decode program gives the
    scopes, and ``_reduce_trace``, the one place the trace exists."""
    from bench import harness, program_trace, trace

    seen: dict = {}
    build0, reduce0 = harness.build, harness._reduce_trace

    def build(*a, **k):
        seen["engine"], sched = build0(*a, **k)
        seen["pool"] = sched.pool
        return seen["engine"], sched

    def reduce_trace(trace_dir, log):
        tr = program_trace.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        seen["program"] = program_trace.reduce(tr, _decode_hlo(seen["engine"], seen["pool"]))
        return trace.reduce(tr)

    harness.build, harness._reduce_trace = build, reduce_trace
    try:
        out = harness.run(cell, seed, seconds, True, **run_kw)
    finally:
        harness.build, harness._reduce_trace = build0, reduce0
    return {"run": out, "program": seen["program"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    from bench import harness  # noqa: F401  (puts the program's src on the path)
    from repro.kernels.runtime import enable_compile_cache

    def log(s):
        print(s, file=sys.stderr, flush=True)

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the compile cache's key leaves op_name metadata out, so without this a
    # program could load an executable built from another commit's text of
    # the same program, whose metadata lacks the scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    result = profile(args.workload, args.seed, args.seconds, t_process=T_PROCESS, log=log)
    text = json.dumps(result)
    if args.out:
        Path(args.out).write_text(text)
    p = result["program"]
    log("profile: " + json.dumps({k: p[k] for k in (
        "tick_self_ms", "decode_host_ms", "decode_attn_ms", "decode_kv_ms",
        "scopes", "idle_gaps", "idle_by_gap_name", "idle_by_span")}))
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
