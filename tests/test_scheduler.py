"""Continuous-batching scheduler: slot invariants, exact token accounting,
chunked-prefill equivalence, online streaming-τ convergence, vectorized
traces."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core.fpga import optimized_template, paper_workload
from repro.core.workload import (
    AccelProfile,
    break_even_tau,
    bursty_trace,
    irregular_trace,
    learn_tau,
    simulate,
)
from repro.serving.engine import InferenceEngine, ServeConfig, WorkloadAwareServer
from repro.serving.load import bursty_stream, diurnal_stream, poisson_stream
from repro.serving.policy import StreamingTauPolicy, make_policy
from repro.serving.scheduler import (
    ContinuousBatchingScheduler,
    FixedCalibration,
    run_static_batches,
)

# one representative per architecture family (dense / MLA-MoE / SSM / hybrid
# / audio) — the masked decode path must hold for every cache layout
FAMILY_ARCHS = ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                "zamba2-7b", "whisper-tiny", "granite-4.0-h-small")


def _engine(arch, max_batch=2, max_len=32):
    return InferenceEngine(get_reduced_config(arch),
                           sc=ServeConfig(max_batch=max_batch, max_len=max_len))


def _engine_f32(arch, max_batch=2, max_len=32):
    """Engine with everything float32: the chunked-vs-blocking equivalence is
    exact modulo float reassociation at chunk boundaries, and in f32 an
    argmax tie within that reassociation noise is measure-zero — bf16
    quantizes logits coarsely enough that near-ties flip."""
    from repro.models.model import init_model

    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(cfg, jax.random.PRNGKey(0)))
    return InferenceEngine(cfg, params=params,
                           sc=ServeConfig(max_batch=max_batch, max_len=max_len))


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_scheduler_invariants_every_family(arch):
    eng = _engine(arch)
    reqs = poisson_stream(6, rate_hz=40.0, seed=1, vocab_size=eng.cfg.vocab_size,
                          prompt_lens=(4, 6), new_tokens=(1, 4))
    sched = ContinuousBatchingScheduler(eng, policy="adaptive")
    rep = sched.run(reqs)
    # no slot leaks: everything admitted finished and freed its slot
    assert sched.admitted == sched.completed == len(reqs)
    assert sched.pool.active_count == 0
    assert rep.items == len(reqs)
    # per-request token counts exact, ordering/latency sane
    by_rid = {rec.rid: rec for rec in rep.records}
    for r in reqs:
        rec = by_rid[r.rid]
        assert len(rec.tokens) == r.new_tokens
        assert all(0 <= t < eng.cfg.vocab_size for t in rec.tokens)
        assert rec.admit_s >= r.arrival_s
        assert rec.finish_s > rec.admit_s or r.new_tokens == 1
    assert rep.energy_j > 0 and rep.time_s > 0


def test_scheduler_matches_lockstep_generate_greedy():
    """A request served alone through the slot pool must reproduce the
    lockstep ``generate`` continuation token-for-token."""
    eng = _engine("granite-3-8b", max_batch=2, max_len=48)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, eng.cfg.vocab_size, 7).astype(np.int32)
    from repro.serving.load import Request

    reqs = [Request(rid=0, arrival_s=0.0, prompt=prompt, new_tokens=6)]
    rep = ContinuousBatchingScheduler(eng, policy="idle_waiting").run(reqs)
    ref = eng.generate(prompt[None], 6)[0].tolist()
    assert rep.records[0].tokens == ref


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_masked_decode_exact_under_staggered_occupancy(arch):
    """The masked decode path must match lockstep ``generate`` token-for-
    token for EVERY cache layout, including a second request admitted
    MID-DECODE of the first (mixed per-slot positions)."""
    eng = _engine(arch)
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, eng.cfg.vocab_size, 6).astype(np.int32)
    p2 = rng.integers(0, eng.cfg.vocab_size, 4).astype(np.int32)
    pool = eng.make_pool()
    toks1 = [eng.prefill_into_slot(pool, 0, p1, rid=1, budget=5)]
    toks2 = []
    for step in range(4):
        if step == 2:  # admit request 2 while request 1 is mid-decode
            toks2.append(eng.prefill_into_slot(pool, 1, p2, rid=2, budget=5))
        nxt, _ = eng.masked_decode_step(pool)
        for s in pool.active_slots():
            info = pool.slots[s]
            info.pos += 1
            info.emitted += 1
            pool.tok[s] = nxt[s]
            (toks1 if s == 0 else toks2).append(int(nxt[s]))
    assert toks1 == eng.generate(p1[None], 5)[0].tolist()
    ref2 = eng.generate(p2[None], 5)[0].tolist()
    assert toks2 == ref2[: len(toks2)] and len(toks2) == 3


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_chunked_scheduler_token_identical_every_family(arch):
    """ACCEPTANCE: chunked admission must emit token-for-token identical
    outputs to the blocking-prefill scheduler for every cache layout — the
    decode step is per-slot independent, so tokens depend only on each
    request's own prefilled cache, and the chunked cache must equal the
    blocking one."""
    eng = _engine_f32(arch, max_batch=3, max_len=48)
    reqs = bursty_stream(8, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=3,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 9),
                         new_tokens=(1, 4))
    block = ContinuousBatchingScheduler(eng, policy="adaptive").run(reqs)
    sched = ContinuousBatchingScheduler(eng, policy="adaptive", prefill_chunk=4)
    chunk = sched.run(reqs)
    assert chunk.mode == "chunked" and chunk.chunks > 0
    assert sched.admitted == sched.completed == len(reqs)
    assert sched.pool.active_count == 0 and not sched.pool.admitting.any()
    tb = {r.rid: r.tokens for r in block.records}
    tc = {r.rid: r.tokens for r in chunk.records}
    assert tb == tc


def test_chunked_partial_and_oversized_chunks():
    """Chunk sizes that don't divide the prompt (final partial chunk) and
    chunks larger than the whole prompt must both reproduce blocking."""
    eng = _engine_f32("granite-3-8b", max_batch=2, max_len=48)
    from repro.serving.load import Request

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, eng.cfg.vocab_size, 11).astype(np.int32)
    reqs = [Request(rid=0, arrival_s=0.0, prompt=prompt, new_tokens=5)]
    ref = ContinuousBatchingScheduler(eng, policy="idle_waiting").run(reqs)
    for chunk in (4, 32):
        rep = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                          prefill_chunk=chunk).run(reqs)
        assert rep.records[0].tokens == ref.records[0].tokens
        assert rep.chunks == -(-11 // chunk)


def test_chunked_same_length_group_admission():
    """A burst of same-prompt-length arrivals must admit as ONE batched
    group: ceil(s0/chunk) chunk calls total, identical admit times."""
    eng = _engine("whisper-tiny", max_batch=4, max_len=64)
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=5e-4)
    from repro.serving.load import Request

    reqs = [Request(rid=i, arrival_s=0.0, prompt=np.zeros(16, np.int32),
                    new_tokens=4) for i in range(3)]
    sched = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        execute=False, calibration=cal,
                                        prefill_chunk=8)
    rep = sched.run(reqs)
    assert rep.chunks == 2  # one group of 3, 16 tokens in chunks of 8
    assert len({r.admit_s for r in rep.records}) == 1


def test_chunked_admission_fifo_across_bursts():
    """Admission order is FIFO in arrival order, across bursts and in both
    admission paths — same-length batching only groups CONSECUTIVE waiting
    requests, it never reorders past a different-length arrival."""
    eng = _engine("whisper-tiny", max_batch=4, max_len=64)
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=5e-4)
    reqs = bursty_stream(48, fast_rate_hz=400.0, slow_rate_hz=3.0, seed=7,
                         vocab_size=64, prompt_lens=(4, 8, 16),
                         new_tokens=(2, 8))
    for chunk in (None, 8):
        rep = ContinuousBatchingScheduler(eng, policy="adaptive",
                                          execute=False, calibration=cal,
                                          prefill_chunk=chunk).run(reqs)
        admits = [r.admit_s for r in sorted(rep.records, key=lambda r: r.rid)]
        assert all(a <= b for a, b in zip(admits, admits[1:]))  # FIFO


def test_slot_pool_free_list_and_admitting_state():
    """The explicit free-slot list stays the exact complement of active
    slots through reserve/activate/retire cycles, and admitting slots are
    excluded from the decode mask until activation."""
    from repro.serving.slots import SlotPool

    pool = SlotPool(get_reduced_config("whisper-tiny"), max_batch=4,
                    max_len=32, virtual=True)
    assert pool.free_slots() == [0, 1, 2, 3] and pool.free_count == 4
    pool.admit_virtual(0, rid=10, pos=4, budget=2)
    pool.reserve(1, rid=11)
    assert pool.free_slots() == [2, 3]
    assert pool.active_count == 2 and pool.decoding_count == 1
    assert pool.decoding_slots() == [0]
    pool.activate(1, None, rid=11, pos=8, budget=3, first_tok=0)
    assert pool.decoding_count == 2 and not pool.admitting.any()
    pool.retire(0)
    assert pool.free_slots() == [2, 3, 0]  # FIFO reuse: retired goes last
    assert pool.next_free() == 2
    with pytest.raises(AssertionError):
        pool.activate(2, None, rid=9, pos=1, budget=1, first_tok=0)  # not reserved


def test_policy_busy_hook_sees_mixed_ticks():
    """Duty-cycle policies observe every busy tick: with chunked admission
    the busy ledger splits into prefill and decode components."""
    eng = _engine("whisper-tiny", max_batch=2, max_len=64)
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=5e-4)
    reqs = poisson_stream(10, rate_hz=50.0, seed=0, vocab_size=64,
                          prompt_lens=(8, 16), new_tokens=(2, 6))
    sched = ContinuousBatchingScheduler(eng, policy="adaptive", execute=False,
                                        calibration=cal, prefill_chunk=8)
    sched.run(reqs)
    busy = sched.policy.busy_s
    assert busy["prefill"] > 0 and busy["decode"] > 0
    # at least one chunk-sized prefill tick per chunk at the calibrated floor
    assert busy["prefill"] >= sched.chunks * cal.prefill_s(1, 1)


def test_scheduler_queue_pressure_and_deadlines():
    """Burst far beyond pool capacity: requests queue, all complete, and the
    deadline accounting flows into the SimResult-compatible report."""
    eng = _engine("granite-3-8b", max_batch=2, max_len=32)
    reqs = bursty_stream(10, fast_rate_hz=5000.0, slow_rate_hz=50.0, seed=0,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4,),
                         new_tokens=(2, 5), deadline_s=1e-4)
    sched = ContinuousBatchingScheduler(eng, policy="adaptive")
    rep = sched.run(reqs)
    assert rep.items == 10 and sched.pool.active_count == 0
    sim = rep.to_sim_result()
    assert sim.items == rep.items and sim.energy_j == rep.energy_j
    assert sim.missed_deadlines == sum(r.missed for r in rep.records)
    # an impossibly tight deadline under queue pressure must register misses
    assert sim.missed_deadlines > 0


def test_deadline_exactly_at_completion_is_on_time():
    """Boundary semantics: a request that finishes EXACTLY on its deadline
    is on time — ``missed`` uses strict >, and the admission feasibility
    estimate uses strict > too, so shedding leaves it alone. Power-of-two
    calibration costs make every sum in the virtual ledger exact, so the
    equality is bit-for-bit, not approximate."""
    from repro.serving.load import Request

    eng = _engine("whisper-tiny", max_batch=2, max_len=64)
    cal = FixedCalibration(step_s=2.0 ** -8, prefill_base_s=2.0 ** -10,
                           prefill_per_tok_s=2.0 ** -10)
    s0, nt = 8, 4
    exact = cal.prefill_s(1, s0) + (nt - 1) * cal.step_s()
    req = lambda d: [Request(rid=0, arrival_s=0.0,
                             prompt=np.zeros(s0, np.int32), new_tokens=nt,
                             deadline_s=d)]
    for shed in (False, True):
        rep = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                          execute=False, calibration=cal,
                                          shed=shed).run(req(exact))
        rec = rep.records[0]
        assert rec.latency_s == exact  # exact ledger arithmetic
        assert not rec.missed and not rec.shed
        assert rep.missed == 0 and rep.shed == 0 and rep.items == 1
        # one ulp tighter flips the verdict: shed up front when admission
        # control is on, a missed completion when it is off
        tight = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                            execute=False, calibration=cal,
                                            shed=shed).run(
            req(float(np.nextafter(exact, 0.0))))
        if shed:
            assert tight.shed == 1 and tight.items == 0
        else:
            assert tight.missed == 1 and tight.items == 1


def test_deadline_below_minimum_prefill_shed_vs_missed():
    """A deadline shorter than the bare prefill cost is infeasible for ANY
    schedule: admission control sheds it for zero tokens and zero request
    energy, while shed=False serves it anyway and books the miss — the two
    policies must agree it cannot be on time."""
    from repro.serving.load import Request

    eng = _engine("whisper-tiny", max_batch=2, max_len=64)
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=0.001)
    s0 = 8
    reqs = [Request(rid=0, arrival_s=0.0, prompt=np.zeros(s0, np.int32),
                    new_tokens=4, deadline_s=0.5 * cal.prefill_s(1, s0))]
    shed_rep = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                           execute=False, calibration=cal,
                                           shed=True).run(reqs)
    assert shed_rep.shed == 1 and shed_rep.items == 0
    rec = shed_rep.records[0]
    assert rec.shed and rec.tokens == [] and rec.energy_j == 0.0
    assert shed_rep.wasted_energy_j == 0.0  # shed pre-admission: nothing sunk
    serve = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                        execute=False, calibration=cal,
                                        shed=False).run(reqs)
    assert serve.shed == 0 and serve.missed == 1 and serve.items == 1
    assert len(serve.records[0].tokens) == 4  # served to completion anyway
    # serving the doomed request burns energy shedding saves
    assert serve.energy_j > shed_rep.energy_j
    assert serve.wasted_energy_j == serve.records[0].energy_j


def test_missed_accounting_consistent_across_modes():
    """One overloaded deadline stream through blocking, chunked, and
    speculative scheduling: in every mode a record is missed iff its latency
    exceeds its deadline, the report's ``missed`` matches the per-record
    count, and with shed=False nothing is ever dropped."""
    eng = InferenceEngine(get_reduced_config("whisper-tiny"),
                          sc=ServeConfig(max_batch=4, max_len=64, spec_slack=4))
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=5e-4)
    reqs = bursty_stream(24, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=5,
                         vocab_size=64, prompt_lens=(8, 16),
                         new_tokens=(4, 12), deadline_s=0.05)
    for mode_kw in (dict(), dict(prefill_chunk=8), dict(speculate_k=4)):
        rep = ContinuousBatchingScheduler(eng, policy="adaptive",
                                          execute=False, calibration=cal,
                                          **mode_kw).run(reqs)
        assert rep.items == 24 and rep.shed == 0 and rep.failed == 0
        assert rep.missed == sum(r.missed for r in rep.records)
        for r in rep.records:
            assert r.missed == (r.latency_s > 0.05)
        assert rep.missed > 0  # the burst genuinely overloads the pool


def test_virtual_scheduler_deterministic_and_continuous_wins():
    """Engine-free virtual run with fixed calibration: deterministic ledger,
    and continuous batching beats static batches on items/J AND p50 on a
    bursty stream (the benchmark's claim, in miniature)."""
    eng = _engine("whisper-tiny", max_batch=4, max_len=64)
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.003,
                           prefill_per_tok_s=2e-4)
    service = 0.003 + 12 * 0.004
    reqs = bursty_stream(60, fast_rate_hz=2.0 / service,
                         slow_rate_hz=0.02 / service, seed=2,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 8),
                         new_tokens=(4, 24))
    run = lambda: ContinuousBatchingScheduler(
        eng, policy="adaptive", execute=False, calibration=cal).run(reqs)
    a, b = run(), run()
    assert a.energy_j == b.energy_j and a.p50_s == b.p50_s  # deterministic
    stat = run_static_batches(eng, reqs, policy="adaptive", execute=False,
                              calibration=cal, flush_s=16 * service)
    assert stat.items == a.items == 60
    assert a.items_per_joule > stat.items_per_joule
    assert a.p50_s < stat.p50_s


def test_online_tau_within_10pct_of_offline_learn_tau():
    """Acceptance: the streaming-τ policy on a stationary irregular
    (bimodal) stream lands within 10% of the offline learn_tau items/J."""
    prof = AccelProfile.from_template(optimized_template(), paper_workload())
    gaps = irregular_trace(prof, n=1200, seed=3)
    pol = StreamingTauPolicy(prof, window=400, refit_every=150, refit_steps=150)
    online_gap_e = sum(pol.on_gap(g).energy_j for g in gaps)
    online_e = prof.e_cfg_j + prof.p_active_w * prof.t_inf_s * gaps.size + online_gap_e
    online_ipj = gaps.size / online_e
    offline = simulate(gaps, "adaptive", prof, tau=learn_tau(gaps, prof))
    assert pol.refits > 0
    assert online_ipj >= 0.9 * offline.items_per_joule


def test_streaming_tau_adapts_to_regime_change():
    """τ must MOVE when the gap regime shifts across the break-even point."""
    prof = AccelProfile.from_template(optimized_template(), paper_workload())
    tau_be = break_even_tau(prof)
    pol = StreamingTauPolicy(prof, window=120, refit_every=60, refit_steps=120)
    rng = np.random.default_rng(0)
    for g in rng.uniform(0.05 * tau_be, 0.3 * tau_be, 120):  # short-gap regime
        pol.on_gap(float(g))
    tau_short = pol.tau
    for g in rng.uniform(5 * tau_be, 12 * tau_be, 240):  # long-gap regime
        pol.on_gap(float(g))
    assert pol.tau != tau_short  # the estimator tracked the shift


def test_policies_match_offline_gap_energies():
    """Each online policy's per-gap charge equals the offline simulate()
    ledger for its strategy (same AccelProfile, same gaps)."""
    prof = AccelProfile.from_template(optimized_template(), paper_workload())
    gaps = bursty_trace(prof, n=300, seed=1)
    e_inf = prof.p_active_w * prof.t_inf_s * gaps.size
    for name in ("on_off", "idle_waiting", "slow_down"):
        pol = make_policy(name, prof)
        total = prof.e_cfg_j + e_inf + sum(pol.on_gap(g).energy_j for g in gaps)
        ref = simulate(gaps, name, prof)
        assert total == pytest.approx(ref.energy_j, rel=1e-9), name


def test_run_trace_vectorized_matches_simulate():
    """WorkloadAwareServer.run_trace is now ONE simulate call — its ledger
    must equal the direct vectorized simulation, and compare_strategies with
    an explicit t_inf must not touch the server's measured-latency state."""
    eng = _engine("whisper-tiny")
    srv = WorkloadAwareServer(eng, strategy="adaptive")
    prof = srv.profile(0.01)
    gaps = bursty_trace(prof, n=500, seed=4)
    tau = break_even_tau(prof)
    stats = srv.run_trace(gaps, t_inf=0.01)
    ref = simulate(gaps, "adaptive", prof, tau=tau)
    assert stats.energy_j == pytest.approx(ref.energy_j)
    assert stats.items == ref.items
    assert stats.missed == ref.missed_deadlines
    assert stats.reloads == int(np.count_nonzero(gaps > tau))

    res = srv.compare_strategies(gaps, t_inf=0.01)
    assert srv._measured_t is None  # no side-channel mutation
    assert set(res) == {"on_off", "idle_waiting", "slow_down", "adaptive"}
    again = srv.compare_strategies(gaps, t_inf=0.01)
    for k in res:
        assert res[k].energy_j == again[k].energy_j  # stateless → reproducible


def test_bursty_trace_vectorized_statistics():
    """The numpy bursty trace keeps the Markov chain's distribution: mostly
    short burst gaps with a heavy quiet tail, deterministic per seed."""
    prof = AccelProfile.from_template(optimized_template(), paper_workload())
    tau_be = break_even_tau(prof)
    g = bursty_trace(prof, n=20000, seed=0)
    assert g.shape == (20000,) and (g > 0).all()
    np.testing.assert_array_equal(g, bursty_trace(prof, n=20000, seed=0))
    # busy fraction ~ 10/(10 + 1/0.7) ≈ 0.875 -> P(gap < tau_be) ≈ 0.89
    short_frac = np.mean(g < tau_be)
    assert 0.80 < short_frac < 0.95
    # mean ≈ 0.875·0.2τ + 0.125·5τ ≈ 0.8τ
    assert 0.5 * tau_be < g.mean() < 1.1 * tau_be


def test_load_generators_shapes_and_rates():
    for gen, kw in (
        (poisson_stream, dict(rate_hz=100.0)),
        (bursty_stream, dict(fast_rate_hz=200.0, slow_rate_hz=2.0)),
        (diurnal_stream, dict(base_rate_hz=10.0, peak_rate_hz=100.0, period_s=5.0)),
    ):
        reqs = gen(50, seed=0, vocab_size=64, prompt_lens=(4, 8),
                   new_tokens=(2, 6), **kw)
        assert len(reqs) == 50
        arr = np.asarray([r.arrival_s for r in reqs])
        assert (np.diff(arr) >= 0).all()  # timestamps sorted
        assert {len(r.prompt) for r in reqs} <= {4, 8}
        assert all(2 <= r.new_tokens <= 6 for r in reqs)
        assert all((r.prompt >= 0).all() and (r.prompt < 64).all() for r in reqs)
        assert [r.rid for r in reqs] == list(range(50))
