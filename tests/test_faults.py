"""Overload-robust serving: seeded fault injection, quarantine-and-retry,
deadline-aware shedding, and graceful degradation.

ACCEPTANCE: under a seeded fault profile, every request that is not shed
completes token-for-token identical to a fault-free run (exact in f32 —
greedy resume from committed tokens), within the retry budget.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.retry as core_retry
import repro.training.fault as training_fault
from repro.configs import get_reduced_config
from repro.core.retry import RestartPolicy, StragglerDetector
from repro.models.model import init_model
from repro.serving.draft import SpecThrottle
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.faults import FAULT_PROFILES, FaultInjector, FaultProfile, make_profile
from repro.serving.load import Request, flash_crowd_stream, poisson_stream
from repro.serving.scheduler import ContinuousBatchingScheduler, FixedCalibration

FAMILY_ARCHS = ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                "zamba2-7b", "whisper-tiny", "granite-4.0-h-small")

CAL = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                       prefill_per_tok_s=0.001, verify_per_tok_s=0.0001)


def _engine_f32(arch, max_batch=2, max_len=32, slack=0):
    """f32 everywhere: resume-from-committed-context equivalence is exact
    modulo float reassociation, and in f32 an argmax tie inside that noise
    is measure-zero (bf16 quantizes coarsely enough to flip near-ties)."""
    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(cfg, jax.random.PRNGKey(0)))
    return InferenceEngine(cfg, params=params,
                           sc=ServeConfig(max_batch=max_batch, max_len=max_len,
                                          spec_slack=slack))


def _virtual_sched(**kw):
    """Engine-free scheduler (virtual pool + fixed costs): the robustness
    control flow without any device work."""
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg = get_reduced_config("granite-3-8b")
    eng.sc = ServeConfig(max_batch=kw.pop("max_batch", 4),
                         max_len=kw.pop("max_len", 64))
    return ContinuousBatchingScheduler(eng, execute=False, calibration=CAL,
                                       policy="on_off", **kw)


# ---------------------------------------------------------------------------
# shared fault-handling core (satellite: training/serving share one module)
# ---------------------------------------------------------------------------
def test_training_fault_reexports_shared_core():
    """training.fault keeps its historical API, but the implementations ARE
    the shared core objects — no forked copies to drift."""
    assert training_fault.RestartPolicy is core_retry.RestartPolicy
    assert training_fault.StragglerDetector is core_retry.StragglerDetector
    assert training_fault.WorkerFailure is core_retry.WorkerFailure
    assert training_fault.run_with_restarts is core_retry.run_with_restarts


# ---------------------------------------------------------------------------
# fault profiles + injector
# ---------------------------------------------------------------------------
def test_make_profile_names_and_kv_spec():
    assert make_profile("none") is None
    light = make_profile("light", seed=3)
    assert light == dataclasses.replace(FAULT_PROFILES["light"], seed=3)
    p = make_profile("nan=0.1,stall=0.2,stallx=4,chunk=0.3,max=7", seed=1)
    assert p == FaultProfile(seed=1, nan_rate=0.1, stall_rate=0.2,
                             stall_factor=4.0, chunk_fault_rate=0.3,
                             max_faults=7)
    with pytest.raises(ValueError, match="bad fault spec"):
        make_profile("bogus=1")


def test_make_profile_parses_page_pressure():
    p = make_profile("press=0.2,pressn=3", seed=2)
    assert p == FaultProfile(seed=2, press_rate=0.2, press_pages=3)
    assert p.enabled  # the press axis alone makes the profile active
    assert isinstance(p.press_pages, int)
    # press-free profiles stay disabled and keep returning None
    assert make_profile("none") is None


def test_press_draws_only_when_enabled():
    """The press axis must not consume RNG draws when off — enabling it
    cannot perturb the nan/stall/chunk sequences of historical profiles."""
    base = FaultProfile(seed=11, nan_rate=0.3, stall_rate=0.3)

    def drive(inj, with_press):
        out = []
        for _ in range(40):
            if with_press:
                inj.press()
            out.append((tuple(inj.poison_victims([0, 1])), inj.stall()))
        return out

    assert (drive(FaultInjector(base), with_press=True)
            == drive(FaultInjector(base), with_press=False))

    pressed = dataclasses.replace(base, press_rate=0.5, press_pages=2)
    inj_a, inj_b = FaultInjector(pressed), FaultInjector(pressed)
    seq = [inj_a.press() for _ in range(60)]
    assert seq == [inj_b.press() for _ in range(60)]  # seeded-deterministic
    assert set(seq) == {0, 2}  # events pin exactly press_pages pages
    assert inj_a.events == sum(1 for s in seq if s)


def test_injector_deterministic_and_budget_capped():
    prof = FaultProfile(seed=5, nan_rate=0.3, stall_rate=0.3,
                        chunk_fault_rate=0.3, max_faults=6)

    def drive(inj):
        out = []
        for _ in range(50):
            out.append((tuple(inj.poison_victims([0, 1, 2])), inj.stall(),
                        inj.chunk_fails()))
        return out

    a, b = drive(FaultInjector(prof)), drive(FaultInjector(prof))
    assert a == b  # same seed, same draw order -> identical fault sequence
    inj = FaultInjector(prof)
    drive(inj)
    assert inj.events == 6  # max_faults caps total injected events
    c = drive(FaultInjector(dataclasses.replace(prof, seed=6)))
    assert c != a  # the seed matters


# ---------------------------------------------------------------------------
# engine finiteness guard + poison/resume primitives
# ---------------------------------------------------------------------------
def test_poison_slot_flags_only_that_slot():
    eng = _engine_f32("granite-3-8b")
    rng = np.random.default_rng(0)
    pool = eng.make_pool()
    for slot in (0, 1):
        prompt = rng.integers(0, eng.cfg.vocab_size, 4).astype(np.int32)
        eng.prefill_into_slot(pool, slot, prompt, rid=slot, budget=8)
    nxt, fin = eng.masked_decode_step(pool)
    assert fin[0] and fin[1]  # healthy pool: guard passes everywhere
    eng.poison_slot(pool, 0)
    nxt, fin = eng.masked_decode_step(pool)
    assert not fin[0] and fin[1]  # per-slot isolation: slot 1 unaffected


def test_resume_into_slot_continues_exact_greedy_chain():
    """Quarantine mid-decode, resume from committed tokens: the continuation
    must be token-for-token the uninterrupted greedy chain."""
    eng = _engine_f32("granite-3-8b", max_len=48)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, eng.cfg.vocab_size, 6).astype(np.int32)
    ref = eng.generate(prompt[None], 8)[0].tolist()
    pool = eng.make_pool()
    toks = [eng.prefill_into_slot(pool, 0, prompt, rid=0, budget=8)]
    for _ in range(3):
        nxt, fin = eng.masked_decode_step(pool)
        assert fin[0]
        pool.advance(0, 1, int(nxt[0]))
        toks.append(int(nxt[0]))
    eng.poison_slot(pool, 0)  # fault strikes after 4 committed tokens
    _, fin = eng.masked_decode_step(pool)
    assert not fin[0]
    pool.retire(0)
    context = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    eng.resume_into_slot(pool, 0, context, rid=0, budget=8,
                         emitted=len(toks), next_tok=toks[-1])
    while len(toks) < 8:
        nxt, fin = eng.masked_decode_step(pool)
        assert fin[0]
        pool.advance(0, 1, int(nxt[0]))
        toks.append(int(nxt[0]))
    assert toks == ref


# ---------------------------------------------------------------------------
# ACCEPTANCE: faulted run == fault-free run, token for token, every family
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_faulted_run_token_identical_every_family(arch):
    eng = _engine_f32(arch)
    reqs = poisson_stream(6, rate_hz=40.0, seed=1, vocab_size=eng.cfg.vocab_size,
                          prompt_lens=(4, 6), new_tokens=(2, 6))
    clean = ContinuousBatchingScheduler(
        eng, policy="idle_waiting", calibration=CAL).run(reqs)
    prof = FaultProfile(seed=7, nan_rate=0.2, stall_rate=0.1, max_faults=4)
    sched = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        calibration=CAL, faults=prof)
    faulted = sched.run(reqs)
    assert faulted.quarantined > 0  # the profile actually struck
    assert faulted.failed == 0 and faulted.shed == 0
    assert faulted.retried <= faulted.quarantined
    assert all(r.retries <= sched.retry.max_restarts for r in faulted.records)
    clean_toks = {r.rid: r.tokens for r in clean.records}
    for rec in faulted.records:
        assert rec.tokens == clean_toks[rec.rid]
    # faults cost energy and wall-time, never correctness
    assert faulted.energy_j > clean.energy_j
    assert faulted.wasted_energy_j > 0


def test_speculative_faulted_run_token_identical():
    eng = _engine_f32("granite-3-8b", max_len=40, slack=4)
    reqs = poisson_stream(6, rate_hz=40.0, seed=2, vocab_size=eng.cfg.vocab_size,
                          prompt_lens=(4, 6), new_tokens=(2, 8), prompt_period=3)
    clean = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        calibration=CAL, speculate_k=4).run(reqs)
    prof = FaultProfile(seed=5, nan_rate=0.25, max_faults=3)
    faulted = ContinuousBatchingScheduler(
        eng, policy="idle_waiting", calibration=CAL, speculate_k=4,
        spec_throttle=True, faults=prof).run(reqs)
    assert faulted.quarantined > 0 and faulted.failed == 0
    clean_toks = {r.rid: r.tokens for r in clean.records}
    for rec in faulted.records:
        assert rec.tokens == clean_toks[rec.rid]


def test_chunk_fault_degrades_to_blocking_token_identical():
    """Every chunk tick fails -> the group exhausts its retry budget,
    falls back to blocking admission, and still emits identical tokens."""
    eng = _engine_f32("granite-3-8b", max_len=40)
    reqs = poisson_stream(5, rate_hz=60.0, seed=3, vocab_size=eng.cfg.vocab_size,
                          prompt_lens=(8,), new_tokens=(2, 5))
    clean = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        calibration=CAL, prefill_chunk=4).run(reqs)
    deg = ContinuousBatchingScheduler(
        eng, policy="idle_waiting", calibration=CAL, prefill_chunk=4,
        faults=FaultProfile(seed=1, chunk_fault_rate=1.0)).run(reqs)
    assert deg.degraded == 1
    assert deg.chunk_faults == deg.chunks  # every chunk tick was lost
    assert deg.items == len(reqs) and deg.failed == 0
    clean_toks = {r.rid: r.tokens for r in clean.records}
    for rec in deg.records:
        assert rec.tokens == clean_toks[rec.rid]
    assert deg.wasted_energy_j > 0  # the lost chunk ticks burned energy


# ---------------------------------------------------------------------------
# retry budget, backpressure, shedding, stragglers (virtual: control flow)
# ---------------------------------------------------------------------------
def test_retry_budget_exhaustion_fails_request():
    """nan_rate=1.0 poisons every tick: no request can ever commit a second
    token, so every request burns its whole retry budget and fails."""
    reqs = poisson_stream(3, rate_hz=50.0, seed=0, new_tokens=(4, 8))
    retry = RestartPolicy(max_restarts=2, backoff_s=0.001)
    sched = _virtual_sched(faults=FaultProfile(seed=0, nan_rate=1.0), retry=retry)
    rep = sched.run(reqs)
    assert rep.failed == 3 and rep.items == 0
    assert all(r.failed and r.retries == retry.max_restarts for r in rep.records)
    # every joule of a failed request is wasted
    assert rep.wasted_energy_j == pytest.approx(
        sum(r.energy_j for r in rep.records))


def test_fault_determinism_same_profile_same_report():
    reqs = poisson_stream(12, rate_hz=60.0, seed=4, new_tokens=(2, 8))
    prof = FaultProfile(seed=9, nan_rate=0.1, stall_rate=0.2)

    def go():
        rep = _virtual_sched(faults=prof).run(reqs)
        return (rep.quarantined, rep.retried, rep.failed, rep.stragglers,
                rep.energy_j, rep.wasted_energy_j,
                [tuple(r.tokens) for r in rep.records])

    assert go() == go()


def test_queue_limit_backpressure_sheds_at_ingress():
    flood = flash_crowd_stream(50, base_rate_hz=5.0, spike_rate_hz=500.0,
                               spike_start_s=0.5, spike_len_s=0.2, seed=2)
    rep = _virtual_sched(queue_limit=4).run(flood)
    assert rep.shed > 0
    assert rep.items + rep.shed == 50
    shed_recs = [r for r in rep.records if r.shed]
    # shed at ingress: no admission, no tokens, no energy
    assert all(not r.tokens and r.energy_j == 0 for r in shed_recs)


def test_deadline_shedding_beats_serve_everything_goodput():
    """The overload gate in miniature: under a flash crowd with deadlines,
    shedding must convert energy into MORE on-time completions per joule
    than serving everything late."""
    flood = flash_crowd_stream(60, base_rate_hz=5.0, spike_rate_hz=400.0,
                               spike_start_s=1.0, spike_len_s=0.5, seed=2,
                               deadline_s=0.3)
    noshed = _virtual_sched(shed=False).run(flood)
    shedr = _virtual_sched(shed=True).run(flood)
    assert noshed.missed > 0  # serve-everything is drowning
    assert shedr.shed > 0
    # the cost model is per-request (it can't see future admissions' prefill
    # stalls), so a few admitted requests may still miss — but shedding must
    # cut misses sharply and win on on-time completions per joule
    assert shedr.missed < 0.2 * noshed.missed
    assert shedr.goodput_per_joule >= noshed.goodput_per_joule


def test_straggler_detector_counts_persistent_stalls():
    # moderate stall rate: the detector needs a healthy baseline EMA before
    # a 25x outlier stands out (back-to-back stalls in warmup would prime
    # the mean high and hide everything)
    reqs = poisson_stream(16, rate_hz=100.0, seed=1, new_tokens=(16, 32))
    prof = FaultProfile(seed=3, stall_rate=0.15, stall_factor=25.0)
    sched = _virtual_sched(
        faults=prof,
        detector=StragglerDetector(patience=1, warmup=2, z_threshold=3.0))
    rep = sched.run(reqs)
    assert rep.stragglers > 0
    assert rep.quarantined == 0  # stalls slow ticks, they don't corrupt


# ---------------------------------------------------------------------------
# speculation auto-throttle
# ---------------------------------------------------------------------------
def test_spec_throttle_shrinks_and_regrows():
    th = SpecThrottle(8, lo=0.2, hi=0.5, alpha=0.5, probe_every=3)
    th.begin(0)
    assert th.window(0) == 8
    for _ in range(6):  # acceptance collapses -> window halves to 0
        th.observe(0, 0, th.window(0) or 1)
    assert th.window(0) == 0
    # throttled-to-0 probes with a 1-draft window every probe_every ticks
    probes = [th.window(0) for _ in range(6)]
    assert probes.count(1) == 2 and probes.count(0) == 4
    # a run of perfect probes re-opens and regrows the window
    for _ in range(12):
        k = th.window(0)
        th.observe(0, k, k)
    assert th.window(0) == 8


def test_spec_throttle_requires_speculation():
    with pytest.raises(ValueError, match="spec_throttle"):
        _virtual_sched(spec_throttle=True)


def test_throttle_falls_back_to_plain_decode_on_hostile_stream():
    """Random prompts + fresh random continuations: n-gram drafts rarely
    match, the EMA collapses, and the pool runs plain decode ticks (cheaper
    than burning k-token verify windows on 0-acceptance drafts)."""
    eng = _engine_f32("granite-3-8b", max_len=48, slack=4)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, eng.cfg.vocab_size, 6).astype(np.int32)
    reqs = [Request(rid=0, arrival_s=0.0, prompt=prompt, new_tokens=24)]
    sched = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        calibration=CAL, speculate_k=4,
                                        spec_throttle=True)
    rep = sched.run(reqs)
    # output still exact greedy regardless of throttle state
    assert rep.records[0].tokens == eng.generate(prompt[None], 24)[0].tolist()
    assert rep.throttled_ticks > 0  # the window did hit 0 and fell back
