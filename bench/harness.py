"""One run of one cell: set-up, the measured window, the metrics, the check.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its metrics are the entries of
``end_to_end`` and ``per_layer`` that apply to it, each read by
``bench/metrics/<name>.py``. Nothing here names a cell, a configuration or
a metric.

The system under test is built as the serving launcher builds it:
``InferenceEngine`` over a paged slot pool, and the launcher's
``make_scheduler`` for a ``ContinuousBatchingScheduler`` with chunked
admission. The benchmark supplies the weights, the calibration and the idle
policy (``wallclock``), so the scheduler runs on the wall clock, and it
records spans around the engine calls (``spans``). The window drives
``ContinuousBatchingScheduler.run``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import check, costs, traffic  # noqa: E402
from bench.metrics import reader  # noqa: E402


FILL_LIMIT_S = 180  # a backlog's pool must fill within this


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device the peaks table lacks."""


# -- the cell -----------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict        # the configuration file
    mix: dict         # the traffic file
    end_to_end: list  # metric entries of BENCHMARK.json that apply
    per_layer: list


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, its files found by name
    under ``root/bench``."""
    bj = json.loads((root / "BENCHMARK.json").read_text())
    w = next((w for w in bj["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    conf = json.loads((root / "bench" / "configs" / f"{w['config']}.json").read_text())
    e2e = [m for m in bj["end_to_end"] if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    pl = [m for m in bj["per_layer"] if _applies(m, name, reported)]
    mix = traffic.load_mix(w["traffic"], root / "bench" / "traffic")
    return Cell(name, w["chips"], conf, mix, e2e, pl)


def at_size(cell: Cell, size: str) -> tuple[dict, dict, dict]:
    """(model, serving, mix) at the cell's size: "full" as run on the chip,
    or "cpu_test", the configuration file's small stand-in for tests."""
    model, serving, mix = cell.conf["model"], cell.conf["serving"], cell.mix
    if size == "cpu_test":
        t = cell.conf["cpu_test"]
        model = {**model, **t["model"]}
        if "ssm" in t:
            model["ssm"] = {**model["ssm"], **t["ssm"]}
        serving = {**serving, **t["serving"]}
        mix = {**mix, **t["traffic"]}
    return model, serving, mix


# -- the program's side ---------------------------------------------------------
def program_config(conf: dict, model: dict, quant: str | None = None):
    """The program's ArchConfig for ``model``, checked field by field."""
    from repro.configs import get_config
    from repro.configs.base import SSMConfig

    base = get_config(conf["arch"])
    fields = {k: model[k] for k in ("num_layers", "d_model", "num_heads",
                                    "num_kv_heads", "d_ff", "vocab_size",
                                    "norm_eps") if k in model}
    fields["tie_embeddings"] = model["tie_embeddings"]
    if model["family"] == "dense":
        fields["head_dim"] = model["head_dim"]
        fields["rope_theta"] = model["rope_theta"]
    else:
        s = model["ssm"]
        fields["ssm"] = SSMConfig(state_size=s["state_size"], head_dim=s["head_dim"],
                                  expand=s["expand"], conv_width=s["conv_width"],
                                  chunk_size=s["chunk_size"])
    cfg = dataclasses.replace(base, **fields, quant=quant)
    if cfg.family != model["family"]:
        raise ValueError(f"{conf['arch']} is family {cfg.family}, the file says "
                         f"{model['family']}")
    return cfg


def check_layout(params_abstract, cfg) -> None:
    """The benchmark's weight tree must be the program's parameter layout."""
    import jax
    from repro.models.model import init_model

    want = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    got_l, got_t = jax.tree.flatten(params_abstract)
    want_l, want_t = jax.tree.flatten(want)
    if got_t != want_t:
        raise ValueError(f"weight layout differs from the program's:\n{got_t}\n{want_t}")
    for g, w in zip(got_l, want_l):
        if (g.shape, g.dtype) != (w.shape, w.dtype):
            raise ValueError(f"leaf {g.shape}/{g.dtype} != program {w.shape}/{w.dtype}")


def build(arch: str, cfg, params: list, serving: dict, clock, seed: int,
          kv_quant: str | None = None):
    """(engine, scheduler) by the serving launcher's construction. The
    weights come in a list that the engine empties, so that under the int8
    control the bf16 tree is freed before the pool is allocated."""
    from repro.launch import serve
    from repro.serving.engine import InferenceEngine, ServeConfig
    from bench.wallclock import WallCalibration, WallIdlePolicy

    args = serve.build_parser().parse_args([
        "--arch", arch, "--mode", "chunked", "--paged",
        "--batch", str(serving["max_batch"]), "--max-len", str(serving["max_len"]),
        "--page-size", str(serving["page_size"]),
        "--prefill-chunk", str(serving["prefill_chunk"]), "--seed", str(seed)])
    # ServeConfig as launch.serve.make_engine builds it for these flags;
    # make_engine itself draws its own weights, so it is not called
    engine = InferenceEngine(cfg, params=params.pop(), sc=ServeConfig(
        max_batch=args.batch, max_len=args.max_len, paged=True,
        page_size=args.page_size, kv_quant=kv_quant))
    args.policy = WallIdlePolicy(clock)
    sched = serve.make_scheduler(args, engine, WallCalibration(clock),
                                 prefill_chunk=args.prefill_chunk)
    return engine, sched


def warm_up(engine, pool, groups, chunk: int) -> None:
    """Compile and run every program shape the window can use: a prefill
    group of each (size, prompt length) in ``groups`` (its zeroed cache, its
    chunk step, its landing in the pool, its first-token readback) and the
    decode step. Only the last two chunks of each group are computed.

    A program's first call on a freshly made array and its later calls on
    an array that an earlier call returned are compiled apart, so the decode
    step runs twice before anything lands in the pool, and each group runs
    a chunk on its fresh cache and one on the cache the first returned."""
    engine.masked_decode_step(pool)
    engine.masked_decode_step(pool)
    for k, n in groups:
        slots = pool.free_slots()[:k]
        st = engine.begin_chunked_prefill(
            pool, slots, np.zeros((k, n), np.int32),
            rids=[-1 - j for j in range(k)], budgets=[2] * k)
        st.pos = max(n - 2 * chunk, 0)
        while not st.done:
            engine.chunked_prefill_step(st, chunk)
        engine.finish_chunked_prefill(pool, st)
        engine.masked_decode_step(pool)
        for s in slots:
            pool.retire(s)


def free_program_state(engine, sched) -> None:
    """Drop the pool and the engine's weights before the reference runs."""
    sched.pool.cache = None
    engine.params = None
    gc.collect()


# -- the run --------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    rec: object
    items: list
    start_s: float    # the window, in seconds since the run began
    seconds: float
    end_s: float
    waits: list
    setup_s: float
    m: dict
    peaks: dict | None
    trace: dict | None


def _compile_counter():
    import jax

    n = [0]

    def listen(event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: n[0]


def device_info(require_tpu: bool, chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu:
        if d.platform != "tpu":
            raise NoChip(f"JAX finds no TPU (default backend {d.platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
        pk = costs.peaks(d.device_kind)
    else:
        pk = None
    return d, devs, pk


def _stop_rule(rec, clock, items, seconds, mix, cc):
    """When the run ends. The window opens at the first arrival, or (a
    backlog) once every slot decodes: filling an empty pool is a transient
    no deployment sees. Past the close the run goes on, unmeasured, until
    every request due in the window has its first token (open loops) and
    the requests served in full hold the tokens the check needs, or until
    the wait runs out. Returns the window's start (None until it opens)."""
    from_full = mix["measure_from"] == "pool_full"
    drain = float(mix["drain_first_tokens_s"])
    wait = max(drain, float(cc["max_wait_s"]))

    def window_start():
        return rec.full_at if from_full else 0.0

    def stop_when(now):
        ws = window_start()
        if ws is None:
            if now > FILL_LIMIT_S:
                raise RuntimeError(f"the pool was not full after {FILL_LIMIT_S} s")
            return False
        end = ws + seconds
        if now < end:
            return False
        firsts = drain <= 0 or len(rec.tokens) >= len(items) or now >= end + drain
        return (firsts and rec.finished_tokens >= cc["min_tokens"]) or now >= end + wait

    clock.stop_when = stop_when
    return window_start


def _window(sched, reqs, clock, trace: bool):
    """Drive the scheduler until the clock ends the run; with ``trace``,
    under the profiler. Returns the reduced trace or None."""
    import jax
    from bench.wallclock import WindowClosed

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
        on_gap = sched.policy.on_gap

        def annotated_gap(gap_s):
            with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                return on_gap(gap_s)
        sched.policy.on_gap = annotated_gap
    clock.start()
    try:
        if trace:
            with jax.profiler.TraceAnnotation("bench.window"):
                sched.run(reqs)
        else:
            sched.run(reqs)
    except WindowClosed:
        pass
    if trace:
        jax.profiler.stop_trace()
    return trace_dir


def _reduce_trace(trace_dir, log):
    from bench import trace as trace_mod

    extracted = trace_mod.extract(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"trace planes: {json.dumps(extracted['planes'])[:1500]}")
    return trace_mod.reduce(extracted)


def _check(rec, items, params, model, mix, cc, ref, seed, log) -> tuple[bool, dict]:
    """The served tokens of a sample of finished requests against the
    reference (``check``); returns (correct, the numbers compared)."""
    by_rid = {it.rid: it for it in items}
    finished, bad = [], []
    for rid in rec.tokens:
        served = rec.served(rid)
        if (len(served) > by_rid[rid].new_tokens or (served < 0).any()
                or (served >= model["vocab_size"]).any()):
            bad.append(rid)
        elif len(served) == by_rid[rid].new_tokens:
            finished.append((rid, by_rid[rid].prompt, served))
    sampled = check.sample(finished, seed=seed, min_tokens=cc["min_tokens"],
                           max_requests=cc["max_requests"])
    pad = max(mix["prompt_tokens"]["values"]) + mix["output_tokens"]["max"]
    t0 = time.perf_counter()
    got = check.compare(sampled, lambda t, p: ref.logits(params, t, model, p), pad,
                        mix["output_tokens"]["max"])
    compared = {k: {"value": got[k], "limit": v["limit"]} for k, v in cc.items()
                if isinstance(v, dict) and "limit" in v}
    compared["bad_tokens"] = {"value": len(bad), "limit": 0}
    log(f"check: {got['requests']} finished requests, {got['tokens']} served "
        f"tokens against the f32 reference in {time.perf_counter() - t0:.1f} s "
        f"({len(finished)} finished in all)")
    for k, v in compared.items():
        log(f"compared {k}: {v['value']!r} (limit {v['limit']!r})")
    return bool(sampled) and all(v["value"] <= v["limit"] for v in compared.values()), compared


def _log_queue(rec, items, seconds, end_s, log):
    """Median time to first token per third of the window, and what still
    waited at the close: a backlog that grows shows here (the knee sweep)."""
    first = rec.first_token_s()
    due = [it for it in items if it.due_s < seconds]
    thirds = [[first.get(it.rid, end_s) - it.due_s for it in due
               if k * seconds / 3 <= it.due_s < (k + 1) * seconds / 3] for k in range(3)]
    log("time to first token by thirds of the window (median ms): "
        + " / ".join(f"{np.median(t) * 1e3:.1f}" if t else "-" for t in thirds)
        + "; due in the window but no first token by its close: "
        f"{sum(1 for it in due if first.get(it.rid, end_s) > seconds)}")


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, size: str = "full", require_tpu: bool = True,
        root: Path = ROOT, control: str | None = None, fault=None,
        poisson_rate: float | None = None,
        log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    cell = load_cell(cell_name, root)
    model, serving, mix = at_size(cell, size)
    cc = cell.conf["cpu_test"]["check"] if size == "cpu_test" else cell.conf["check"]
    if poisson_rate:
        mix = {**mix, "arrivals": "poisson", "rate_hz": poisson_rate,
               "drain_first_tokens_s": max(mix["drain_first_tokens_s"], 30)}

    import jax

    dev, devs, pk = device_info(require_tpu, cell.chips)
    compiles = _compile_counter()
    from repro.serving.load import Request
    from bench import reference, weights
    from bench.spans import Recorder
    from bench.wallclock import WallClock

    # -- set-up: weights, the server, every compiled shape ---------------------
    cfg = program_config(cell.conf, model, quant=control)
    check_layout(weights.abstract(model), cfg)
    clock = WallClock(seconds)
    # the control serves as the launcher's --quant-weights --quant-kv does
    engine, sched = build(cell.conf["arch"], cfg, [weights.make(model, seed, dev)],
                          serving, clock, seed, kv_quant=control)
    params = None if control else engine.params
    # ``fault(engine)`` breaks the timed path underneath (tests only); it may
    # return a hook that alters tokens as the engine hands them over
    fault_obj = fault(engine) if fault else None
    items = traffic.generate(mix, seed=seed, seconds=seconds, vocab_size=model["vocab_size"])
    groups = traffic.prefill_groups(
        items, serving.get("max_prefill_group", serving["max_batch"]))
    log(f"warm-up: {len(groups)} prefill groups (size, prompt length): {groups}")
    warm_up(engine, sched.pool, groups, serving["prefill_chunk"])
    jax.block_until_ready(sched.pool.cache)
    reqs = [Request(it.rid, it.due_s, it.prompt, it.new_tokens) for it in items]
    rec = Recorder(clock, annotate=trace, fault=fault_obj)
    rec.vocab, rec.pool_size = model["vocab_size"], serving["max_batch"]
    rec.budget = {it.rid: it.new_tokens for it in items}
    rec.install(engine)
    window_start = _stop_rule(rec, clock, items, seconds, mix, cc)

    # -- the window ---------------------------------------------------------------
    c0 = compiles()
    trace_dir = _window(sched, reqs, clock, trace)
    end_s = clock.now()
    in_run = compiles() - c0
    start_s = window_start()
    if start_s is None:
        raise RuntimeError("the run ended before every slot decoded; no window opened")
    setup_s = clock.origin + start_s - t_process
    reduced = _reduce_trace(trace_dir, log) if trace else None
    stats = dev.memory_stats() if require_tpu else None
    peak = int(stats["peak_bytes_in_use"]) if stats else None
    log(f"window: {seconds} s from {start_s:.3f} s, run ended at {end_s:.3f} s; "
        f"compiles in the run: {in_run}")
    if end_s < start_s + seconds:
        log(f"the run ran out of requests at {end_s:.3f} s, before the window closed")
    log(f"spans: {len(rec.of('decode'))} decode, {len(rec.of('chunk'))} chunk, "
        f"{len(rec.of('finish'))} groups (largest "
        f"{max((s.rows for s in rec.of('begin')), default=0)}); requests with a first token "
        f"{len(rec.tokens)} of {len(items)}")
    drain = mix["drain_first_tokens_s"] > 0
    if drain:
        _log_queue(rec, items, seconds, end_s, log)
    if reduced:
        log(f"trace: window {reduced['window_s']!r} s, busy {reduced['busy_s']!r} s, "
            f"module runs {json.dumps(reduced['kinds'])}")

    rd = Run(rec, items, start_s, float(seconds), end_s, clock.waits, setup_s, model, pk,
             reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"], root / "bench" / "metrics")(rd)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- correct: once the program's state is freed --------------------------
    free_program_state(engine, sched)
    del engine, sched
    if control:
        params = weights.make(model, seed, dev)
    correct, compared = _check(rec, items, params, model, mix, cc,
                               reference.module(cell.conf["reference"]), seed, log)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    on_device = reduced is not None and reduced["busy_s"] is not None
    if on_device:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    if drain:
        first = rec.first_token_s()
        attempted = len(items)
        failed = sum(1 for it in items if it.rid not in first)
    else:
        attempted = sum(1 for t in rec.admitted.values()
                        if start_s <= t <= start_s + seconds)
        failed = 0
    out = {"correct": correct, "attempted": attempted,
           "failed": failed + compared["bad_tokens"]["value"],
           "metrics": metrics, "device": device}
    if on_device:
        out["breakdown"] = reduced["breakdown"]
    out["compared"] = compared
    return out
