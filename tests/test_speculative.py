"""Speculative multi-token decode: exact greedy equivalence per family,
acceptance edge cases (accept-0 / accept-all / budget boundary / mixed
pools), drafter behaviour, and report accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.serving.draft import NgramDrafter
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.load import Request, bursty_stream, poisson_stream
from repro.serving.scheduler import ContinuousBatchingScheduler, FixedCalibration

FAMILY_ARCHS = ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                "zamba2-7b", "whisper-tiny", "granite-4.0-h-small")


def _engine_f32(arch, max_batch=2, max_len=32, slack=4):
    """f32 engine: speculative-vs-plain equivalence is exact modulo float
    reassociation (verify scores a K+1 window through the chunk path where
    plain decode steps one token at a time), and in f32 an argmax tie
    within that noise is measure-zero."""
    from repro.models.model import init_model

    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(cfg, jax.random.PRNGKey(0)))
    return InferenceEngine(cfg, params=params,
                           sc=ServeConfig(max_batch=max_batch, max_len=max_len,
                                          spec_slack=slack))


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_speculative_token_identical_every_family(arch):
    """ACCEPTANCE: the speculative scheduler must emit token-for-token
    identical output to plain masked decode for every cache layout —
    including the SSM/hybrid recurrent-state rollback to the last accepted
    token, which a positional KV cache gets for free."""
    eng = _engine_f32(arch, max_batch=3, max_len=48)
    reqs = bursty_stream(8, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=3,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 9),
                         new_tokens=(1, 6))
    block = ContinuousBatchingScheduler(eng, policy="adaptive").run(reqs)
    sched = ContinuousBatchingScheduler(eng, policy="adaptive", speculate_k=4)
    spec = sched.run(reqs)
    assert spec.mode == "speculative" and spec.verify_ticks > 0
    assert sched.admitted == sched.completed == len(reqs)
    assert sched.pool.active_count == 0
    assert {r.rid: r.tokens for r in block.records} == \
           {r.rid: r.tokens for r in spec.records}
    # every verify-committed token is accounted, and never fewer than one
    # token per tick per decoding slot (the accept-0 floor)
    assert spec.accepted_tokens == sum(len(r.tokens) - 1 for r in spec.records)
    assert spec.accepted_per_tick >= 1.0


def test_speculative_composes_with_chunked_admission():
    """Mixed decoding/admitting pools: chunked admission reserves slots
    whose prefill is in flight; the verify mask must exclude them and the
    combined scheduler still reproduces blocking output exactly."""
    eng = _engine_f32("granite-3-8b", max_batch=3, max_len=48)
    reqs = bursty_stream(8, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=3,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 9),
                         new_tokens=(1, 6))
    block = ContinuousBatchingScheduler(eng, policy="adaptive").run(reqs)
    sched = ContinuousBatchingScheduler(eng, policy="adaptive",
                                        prefill_chunk=4, speculate_k=4)
    spec = sched.run(reqs)
    assert spec.mode == "speculative"
    assert spec.chunks > 0 and spec.verify_ticks > 0
    assert not sched.pool.admitting.any() and sched.pool.active_count == 0
    assert {r.rid: r.tokens for r in block.records} == \
           {r.rid: r.tokens for r in spec.records}


def test_verify_accept_all_and_accept_0():
    """Engine-level edges: perfect drafts accept all K (and the bonus token
    extends the chain); always-wrong drafts accept 0 and still commit
    exactly the plain-decode token each tick — never slower than plain
    decode in tokens emitted."""
    eng = _engine_f32("granite-3-8b", max_batch=2, max_len=48, slack=3)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, eng.cfg.vocab_size, 6).astype(np.int32)
    ref = eng.generate(prompt[None], 8)[0].tolist()

    pool = eng.make_pool()
    toks = [eng.prefill_into_slot(pool, 0, prompt, rid=0, budget=8)]
    toks += [eng.prefill_into_slot(pool, 1, prompt, rid=1, budget=8)]
    assert toks == ref[:1] * 2
    t_good, t_bad = [toks[0]], [toks[1]]
    ticks = 0
    while len(t_bad) < 8:
        drafts = np.zeros((2, 3), np.int32)
        i = len(t_good)
        drafts[0] = (ref[i:i + 3] + [0] * 3)[:3]          # oracle drafts
        drafts[1] = [(t + 1) % eng.cfg.vocab_size          # always wrong
                     for t in (ref[len(t_bad):len(t_bad) + 3] + [0] * 3)[:3]]
        out, acc, _ = eng.masked_speculative_step(pool, drafts)
        ticks += 1
        assert acc[1] == 0  # wrong drafts never accepted
        if len(t_good) < 8:
            n = min(int(acc[0]) + 1, 8 - len(t_good))
            t_good.extend(out[0, :n].tolist())
            pool.advance(0, n, int(out[0, n - 1]))
        t_bad.append(int(out[1, 0]))
        pool.advance(1, 1, int(out[1, 0]))
    assert t_good == ref and t_bad == ref
    # oracle drafts finish in ceil(7/4) ticks; accept-0 takes all 7
    assert ticks == 7
    # drafted surplus: tick 1 commits 3 drafts + bonus, tick 2 truncates at
    # the budget (2 drafts + 1); the accept-0 slot adds none
    assert pool.committed == 7 + 7 and pool.drafted == 5


def test_speculative_budget_boundary_no_overshoot():
    """A slot whose remaining budget is smaller than the accepted window
    retires mid-verify with EXACTLY its budget — acceptance past the budget
    is truncated, len(tokens) == new_tokens."""
    eng = _engine_f32("whisper-tiny", max_batch=2, max_len=32, slack=6)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, eng.cfg.vocab_size, 4).astype(np.int32)
    for budget in (1, 2, 3):
        reqs = [Request(rid=0, arrival_s=0.0, prompt=prompt, new_tokens=budget)]
        sched = ContinuousBatchingScheduler(eng, policy="idle_waiting",
                                            speculate_k=6)
        rep = sched.run(reqs)
        assert len(rep.records[0].tokens) == budget
        assert rep.records[0].tokens == eng.generate(prompt[None], budget)[0].tolist()
        assert sched.pool.active_count == 0


def test_speculative_requires_slack():
    """Engine/scheduler refuse a verify window larger than the spare cache
    rows — otherwise tail writes would clamp onto live positions."""
    eng = _engine_f32("granite-3-8b", max_batch=2, max_len=32, slack=2)
    with pytest.raises(ValueError, match="spec_slack"):
        ContinuousBatchingScheduler(eng, policy="adaptive", speculate_k=4)
    pool = eng.make_pool()
    with pytest.raises(AssertionError, match="spec_slack"):
        eng.masked_speculative_step(pool, np.zeros((2, 4), np.int32))


def test_ngram_drafter_suffix_and_fallback():
    d = NgramDrafter(3)
    d.begin(7, [1, 2, 3, 4, 1, 2])
    # suffix [1, 2] recurs at the start → replay what followed: 3, 4, 1
    assert d.propose(7).tolist() == [3, 4, 1]
    d.observe(7, [9])
    # no suffix ending in 9 recurs → period-1 fallback
    assert d.propose(7).tolist() == [9, 9, 9]
    d.forget(7)
    assert d.propose(7).tolist() == [0, 0, 0]  # unknown rid → zeros
    with pytest.raises(ValueError):
        NgramDrafter(0)


def test_virtual_speculative_ledger_deterministic():
    """Engine-free speculative run: the virtual model's greedy chain is all
    zeros, so the n-gram drafter locks on after one tick and the ledger is
    deterministic with verify ticks charged at step + K·per-candidate."""
    eng = InferenceEngine(get_reduced_config("whisper-tiny"),
                          sc=ServeConfig(max_batch=4, max_len=64, spec_slack=4))
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=5e-4, verify_per_tok_s=2e-4)
    assert cal.verify_s(4) == pytest.approx(0.004 + 4 * 2e-4)
    reqs = poisson_stream(12, rate_hz=50.0, seed=0, vocab_size=64,
                          prompt_lens=(8,), new_tokens=(4, 8))
    run = lambda: ContinuousBatchingScheduler(
        eng, policy="adaptive", execute=False, calibration=cal,
        speculate_k=4).run(reqs)
    a, b = run(), run()
    assert a.energy_j == b.energy_j and a.p50_s == b.p50_s
    assert a.verify_ticks > 0 and a.accepted_per_tick > 1.0
    plain = ContinuousBatchingScheduler(eng, policy="adaptive", execute=False,
                                        calibration=cal).run(reqs)
    # fewer busy ticks than one-token-per-slot decode on the same stream
    assert a.time_s < plain.time_s


def test_policy_sees_verify_ticks():
    """The duty-cycle busy ledger splits out verify ticks so policies can
    observe the speculative busy composition."""
    eng = InferenceEngine(get_reduced_config("whisper-tiny"),
                          sc=ServeConfig(max_batch=2, max_len=64, spec_slack=2))
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=5e-4, verify_per_tok_s=2e-4)
    reqs = poisson_stream(6, rate_hz=50.0, seed=0, vocab_size=64,
                          prompt_lens=(8,), new_tokens=(2, 6))
    sched = ContinuousBatchingScheduler(eng, policy="adaptive", execute=False,
                                        calibration=cal, speculate_k=2)
    rep = sched.run(reqs)
    busy = sched.policy.busy_s
    assert busy["prefill"] > 0 and busy["verify"] > 0 and "decode" not in busy
    assert busy["verify"] == pytest.approx(rep.verify_ticks * cal.verify_s(2))


def test_repetitive_prompts_lift_acceptance():
    """prompt_period tiling produces periodic prompts, and the drafter's
    acceptance on them exceeds 1 token per tick pool-wide."""
    reqs = bursty_stream(12, fast_rate_hz=200.0, slow_rate_hz=2.0, seed=0,
                         vocab_size=64, prompt_lens=(8, 16), new_tokens=(2, 6),
                         prompt_period=4)
    for r in reqs:
        p = r.prompt
        assert (p[4:] == p[: len(p) - 4]).all()  # period-4 tiling
    eng = _engine_f32("whisper-tiny", max_batch=4, max_len=32, slack=4)
    reqs = bursty_stream(6, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=1,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 8),
                         new_tokens=(6, 12), prompt_period=4)
    rep = ContinuousBatchingScheduler(eng, policy="adaptive",
                                      speculate_k=4).run(reqs)
    assert rep.accepted_per_tick > 1.0
