"""Memory-pressure-robust paged serving: typed exhaustion (never a crash),
preempt-and-restore exactness, and SLO-tiered victim selection.

ACCEPTANCE: on an over-committed paged pool under the seeded page-pressure
fault profile, every preempted-and-restored request emits token-for-token
what an undisturbed run emits (exact in f32 — swap restores the identical
bytes, recompute replays the greedy prefix), across blocking / chunked /
speculative scheduling and composed with NaN-fault quarantine; and no run
ever dies with the crash-era RuntimeError.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.model import init_model
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.faults import FaultProfile, make_profile
from repro.serving.load import poisson_stream
from repro.serving.scheduler import (
    ContinuousBatchingScheduler,
    FixedCalibration,
    PreemptionPolicy,
    ServeReport,
)

FAMILY_ARCHS = ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                "zamba2-7b", "whisper-tiny", "granite-4.0-h-small")

CAL = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                       prefill_per_tok_s=0.001, verify_per_tok_s=0.0001)

# every decode/verify tick pins 2 free pages out — pressure is the rule,
# not the exception, and the sequence is seeded-deterministic
PRESS = FaultProfile(seed=3, press_rate=0.5, press_pages=2)


def _engines_f32(arch, *, max_batch=3, max_len=32, page_size=4,
                 num_pages=6, **sc_kw):
    """A reference paged engine at parity sizing (exhaustion impossible) and
    a TIGHT engine over-committed to ``num_pages``, over identical f32
    params — greedy chains are exact, so token identity is meaningful."""
    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(cfg, jax.random.PRNGKey(0)))
    ref = InferenceEngine(cfg, params=params, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, **sc_kw))
    tight = InferenceEngine(cfg, params=params, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=num_pages, **sc_kw))
    return ref, tight


def _stream(eng, n=6, seed=1, new_tokens=(2, 8), prompt_lens=(4, 6),
            rate_hz=40.0, **kw):
    return poisson_stream(n, rate_hz=rate_hz, seed=seed,
                          vocab_size=eng.cfg.vocab_size,
                          prompt_lens=prompt_lens, new_tokens=new_tokens, **kw)


def _tokens(rep):
    return {r.rid: r.tokens for r in rep.records if not r.shed and not r.failed}


def _drained(sched):
    pool = sched.pool
    assert pool.active_count == 0 and not pool._press_pins
    assert pool.pages.free_count == pool.num_pages - 1 - len(pool._prefix)


def _run(eng, reqs, **kw):
    sched = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        calibration=CAL, **kw)
    rep = sched.run(reqs)
    _drained(sched)
    return rep


# ---------------------------------------------------------------------------
# ACCEPTANCE: preempt+restore identity, every family, pressure every tick
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_pressure_run_token_identical_every_family(arch):
    ref, tight = _engines_f32(arch)
    reqs = _stream(ref)
    base = _run(ref, reqs)
    rep = _run(tight, reqs, preempt="tiered", faults=PRESS)
    assert rep.failed == 0 and rep.shed == 0
    assert rep.quarantined == 0  # preemption never burns the retry budget
    assert all(r.retries == 0 for r in rep.records)
    assert _tokens(rep) == _tokens(base)
    # pressure costs energy (swap transfers / restore re-prefills), never
    # correctness; on the tight pool the watermark really fired
    assert rep.preempted > 0
    assert rep.preempt_wasted_j > 0
    assert rep.energy_j > base.energy_j


@pytest.mark.parametrize("swap", (True, False))
def test_speculative_pressure_identity_swap_and_recompute(swap):
    ref, tight = _engines_f32("granite-3-8b")
    reqs = _stream(ref, seed=2, prompt_period=3)
    base = _run(ref, reqs, speculate_k=3)
    rep = _run(tight, reqs, speculate_k=3, preempt="tiered", swap=swap,
               faults=PRESS)
    assert rep.failed == 0 and rep.preempted > 0
    assert _tokens(rep) == _tokens(base)
    if swap:
        # short contexts at reload bandwidth: the cost model picks swap
        assert rep.swapped > 0
        assert rep.swapped + rep.recomputed == rep.preempted
    else:
        assert rep.swapped == 0 and rep.recomputed == rep.preempted


def test_chunked_pressure_identity():
    ref, tight = _engines_f32("granite-3-8b")
    reqs = _stream(ref, seed=4, prompt_lens=(6,), rate_hz=60.0)
    base = _run(ref, reqs, prefill_chunk=2)
    rep = _run(tight, reqs, prefill_chunk=2, preempt="tiered", faults=PRESS)
    assert rep.failed == 0
    assert _tokens(rep) == _tokens(base)


def test_nan_quarantine_composes_with_preemption():
    """Both restore paths at once: NaN faults quarantine (charged to the
    retry budget) while pressure preempts (not charged) — output stays the
    undisturbed greedy chain."""
    ref, tight = _engines_f32("granite-3-8b")
    reqs = _stream(ref, seed=5)
    base = _run(ref, reqs)
    prof = FaultProfile(seed=9, nan_rate=0.15, press_rate=0.5, press_pages=2,
                        max_faults=12)
    rep = _run(tight, reqs, preempt="tiered", faults=prof)
    assert rep.failed == 0
    assert _tokens(rep) == _tokens(base)
    assert rep.retried == sum(r.retries for r in rep.records)


def test_overcommitted_speculative_cow_never_raises_runtime_error():
    """Regression pin for the crash era: speculative verify tails plus COW
    shared-prefix forks on a pool too small for the worst case used to die
    in ``_alloc_page``'s RuntimeError. Now the run COMPLETES — exhaustion
    is typed, caught, and preempted around — even with pressure faults."""
    ref, tight = _engines_f32("granite-3-8b", num_pages=7, share_prefix=True)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, ref.cfg.vocab_size, 4).astype(np.int32)
    reqs = _stream(ref, n=6, seed=6, prompt_lens=(6,), rate_hz=80.0)
    for r in reqs:  # shared 4-token prefix (one full block), random tails
        r.prompt = np.concatenate([prefix, r.prompt[4:]])
    base = _run(ref, reqs, prefill_chunk=2, speculate_k=3)
    rep = _run(tight, reqs, prefill_chunk=2, speculate_k=3,
               preempt="tiered", faults=PRESS)  # must not raise
    assert rep.failed == 0
    assert _tokens(rep) == _tokens(base)


def test_emergency_path_keeps_tierless_runs_alive():
    """No preemption policy configured: mid-tick exhaustion is still typed
    and recovered by the emergency preempt — the scheduler never crashes,
    only spends more energy."""
    ref, tight = _engines_f32("granite-3-8b")
    reqs = _stream(ref, seed=7, rate_hz=80.0)
    base = _run(ref, reqs)
    rep = _run(tight, reqs, faults=PRESS)  # preempt=None
    assert rep.failed == 0
    assert _tokens(rep) == _tokens(base)


# ---------------------------------------------------------------------------
# SLO tiers: latency-tier wins, batch tier never starves
# ---------------------------------------------------------------------------
def _tier_lat(rep, reqs, tier, q=99):
    tiers = {r.rid: r.tier for r in reqs}
    lats = [r.latency_s for r in rep.records
            if tiers[r.rid] == tier and not r.shed and not r.failed]
    assert lats, f"no completed {tier}-tier requests"
    return float(np.percentile(lats, q))


def test_latency_tier_beats_tierless_and_batch_completes():
    ref, tight = _engines_f32("granite-3-8b", max_batch=2, num_pages=6)
    reqs = _stream(ref, n=10, seed=8, rate_hz=300.0, tier_mix=0.5)
    assert {r.tier for r in reqs} == {"latency", "batch"}
    tiered = _run(tight, reqs, preempt="tiered", faults=PRESS)
    tierless = _run(tight, reqs, faults=PRESS)
    # everyone completes both ways — tiering REORDERS, it does not starve
    for rep in (tiered, tierless):
        assert rep.failed == 0 and rep.shed == 0
        assert len(_tokens(rep)) == len(reqs)
    assert (_tier_lat(tiered, reqs, "latency")
            <= _tier_lat(tierless, reqs, "latency"))
    assert _tokens(tiered) == _tokens(tierless)  # same greedy chains


def test_preempt_and_shed_stay_deadline_correct():
    """Deadlines + shedding under pressure: every request lands in exactly
    one terminal state, a restored request that can no longer make its
    deadline is shed at retry, and ``missed`` marks exactly the completed-
    late records."""
    ref, tight = _engines_f32("granite-3-8b", max_batch=2, num_pages=6)
    reqs = _stream(ref, n=10, seed=9, rate_hz=300.0, tier_mix=0.5,
                   deadline_s=0.12)
    rep = _run(tight, reqs, preempt="tiered", shed=True, faults=PRESS)
    assert rep.items + rep.shed + rep.failed == len(reqs)
    for r in rep.records:
        if r.shed or r.failed:
            assert np.isnan(r.finish_s)
        else:
            assert r.missed == (r.latency_s > 0.12)
    assert rep.missed == sum(r.missed for r in rep.records)


# ---------------------------------------------------------------------------
# policy plumbing + report surface
# ---------------------------------------------------------------------------
def test_preemption_policy_orders():
    cands = [
        {"slot": 0, "tier": "latency", "slack": 0.1, "pages": 5, "progress": 0.9},
        {"slot": 1, "tier": "batch", "slack": 0.2, "pages": 2, "progress": 0.5},
        {"slot": 2, "tier": "batch", "slack": 9.0, "pages": 4, "progress": 0.1},
    ]
    # tiered: batch before latency, most slack first, biggest footprint
    assert [c["slot"] for c in PreemptionPolicy("tiered").rank(cands)][0] == 2
    assert [c["slot"] for c in PreemptionPolicy("footprint").rank(cands)][0] == 0
    assert [c["slot"] for c in PreemptionPolicy("slack").rank(cands)][0] == 2
    with pytest.raises(ValueError, match="preemption order"):
        PreemptionPolicy("bogus")


def test_preempt_requires_real_paged_pool():
    cfg = dataclasses.replace(get_reduced_config("granite-3-8b"),
                              dtype=jnp.float32)
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg = cfg
    eng.sc = ServeConfig(max_batch=2, max_len=32)  # contiguous
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingScheduler(eng, execute=False, calibration=CAL,
                                    preempt="tiered")


def test_summary_surfaces_preemption_counters():
    rep = ServeReport("continuous", [], 1.0, 1.0, 0, 0, preempted=3,
                      swapped=2, recomputed=1, preempt_wasted_j=0.5,
                      evictions=4)
    s = rep.summary()
    assert "preempt=3" in s and "swap=2" in s and "recomp=1" in s
    assert "evict=4" in s
