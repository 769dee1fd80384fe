"""The serving path's profiler spans and device scopes: under
``jax.profiler`` a chunked paged run shows one ``serve.tick`` per loop
iteration with the engine's calls nested in it, and the compiled decode and
chunk programs carry the sublayer scopes in their HLO metadata."""
import gc
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_reduced_config
from repro.models.params import init_params
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.kv_cache import cache_defs
from repro.serving.load import Request, bursty_stream
from repro.serving.scheduler import (ContinuousBatchingScheduler, FixedCalibration,
                                     _gc_spans)

SCOPES = ("kv_pages", "proj", "attention", "mlp", "logits")


def _paged_engine():
    cfg = get_reduced_config("granite-3-8b")
    return InferenceEngine(cfg, sc=ServeConfig(max_batch=3, max_len=48, paged=True,
                                               page_size=4))


def _profiled(fn, tmp_path):
    """(fn's result, [(name, start_ns, end_ns, stats)] of the host spans
    named serve.* or engine.*) with ``fn`` run under the profiler."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0])
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith(("serve.", "engine."))]
    return out, sorted(spans, key=lambda s: s[1])


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_chunked_paged_run_spans(tmp_path):
    eng = _paged_engine()
    reqs = bursty_stream(6, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=3,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 9),
                         new_tokens=(1, 6))
    # one late arrival: the pool drains and the scheduler waits for it
    reqs.append(Request(len(reqs), 60.0, np.arange(1, 6, dtype=np.int32), 2))
    steps = []
    decode0 = eng.masked_decode_step

    def counted(pool):
        steps.append(len(pool.decoding_slots()))
        return decode0(pool)

    eng.masked_decode_step = counted
    # a preset calibration: a measured one would time probe decode steps
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001, prefill_per_tok_s=0.001)
    sched = ContinuousBatchingScheduler(eng, policy="adaptive", prefill_chunk=3,
                                        calibration=cal)
    rep, spans = _profiled(lambda: sched.run(reqs), tmp_path)
    assert rep.chunks > 0 and steps

    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert ticks
    triple = ("engine.decode.prepare", "engine.decode.dispatch", "engine.decode.readback")
    n_decode = 0
    for tick in ticks:
        inner = [s for s in spans if s[0] != "serve.tick" and _inside(s, tick)]
        dec = [s for s in inner if s[0].startswith("engine.decode.")]
        # a tick makes at most one decode call: its three spans, in order
        assert [s[0] for s in dec] in ([], list(triple))
        assert all(a[2] <= b[1] for a, b in zip(dec, dec[1:]))
        n_decode += bool(dec)
    assert n_decode == len(steps)
    # every engine span lies in some tick
    assert all(any(_inside(s, t) for t in ticks)
               for s in spans if s[0].startswith("engine."))

    # the spans of a prefill group carry its size
    group = [s for s in spans if s[0] in ("engine.begin", "engine.chunk.dispatch",
                                          "engine.chunk.readback", "engine.land")]
    assert {s[0] for s in group} == {"engine.begin", "engine.chunk.dispatch",
                                     "engine.chunk.readback", "engine.land"}
    assert all(s[3].get("rows", 0) >= 1 for s in group)
    for name in ("engine.begin", "engine.land"):
        assert sum(s[3]["rows"] for s in group if s[0] == name) == len(reqs)
    # each chunk of a group carries the size its begin gave
    size = None
    for s in group:
        if s[0] == "engine.begin":
            size = s[3]["rows"]
        else:
            assert s[3]["rows"] == size
    # the wait for the late arrival is a serve.idle inside its tick
    idle = [s for s in spans if s[0] == "serve.idle"]
    assert idle and all(any(_inside(s, t) for t in ticks) for s in idle)


def test_gc_passes_are_spans_while_serving(tmp_path):
    def collect():
        with _gc_spans():
            n = len(gc.callbacks)
            gc.collect()
        return n

    n, spans = _profiled(collect, tmp_path)
    assert [s[0] for s in spans] == ["serve.gc"]
    assert len(gc.callbacks) == n - 1  # the callback leaves with the block


def test_programs_are_named_and_scoped():
    eng = _paged_engine()
    pool = eng.make_pool()
    host = (pool.tok, pool.positions(), pool.decode_mask(), pool.table)
    decode = eng._paged_decode.lower(eng.params, pool.cache, *map(jnp.asarray, host))
    cache = init_params(cache_defs(eng.cfg, batch=2, max_len=pool.virtual_len),
                        jax.random.PRNGKey(0))
    chunk = eng._chunk.lower(eng.params, cache, jnp.zeros((2, 4), jnp.int32),
                             jnp.int32(0), None)
    toks = jnp.zeros((1, 4), jnp.int32)
    named = {
        "jit__paged_decode_impl": decode,
        "jit__prefill_impl": eng._prefill.lower(eng.params, toks, None),
        "jit__decode_impl": eng._decode.lower(
            eng.params, init_params(cache_defs(eng.cfg, batch=1, max_len=8),
                                    jax.random.PRNGKey(0)),
            toks[:, :1], jnp.int32(0)),
    }
    for name, low in named.items():
        assert low.as_text(dialect="hlo").startswith(f"HloModule {name},")
    # the chunk step is not named yet: its rename is pending

    for low in (decode, chunk):
        text = low.compile().as_text()
        for scope in SCOPES:
            assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope


@pytest.mark.parametrize("paged", (False, True))
def test_spans_leave_tokens_unchanged(paged):
    """The spans wrap the same calls: the engine's decode still returns the
    greedy token and the guard for every slot, paged or contiguous."""
    cfg = get_reduced_config("granite-3-8b")
    eng = InferenceEngine(cfg, sc=ServeConfig(max_batch=2, max_len=32, paged=paged,
                                              page_size=4))
    pool = eng.make_pool()
    prompt = np.arange(1, 6, dtype=np.int32)
    first = eng.prefill_into_slot(pool, 0, prompt, rid=0, budget=4)
    nxt, fin = eng.masked_decode_step(pool)
    ref = eng.generate(prompt[None], 2)[0]
    assert first == ref[0] and nxt[0] == ref[1] and fin[0]
