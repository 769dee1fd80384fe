"""Paged serving acceptance: the paged KV-cache pool must be token-for-token
identical to the contiguous pool — per family, in f32 — across blocking,
chunked, speculative, and fault/quarantine paths, and shared-prefix reuse
must change the work done, never the tokens."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.faults import FaultProfile
from repro.serving.load import bursty_stream, shared_prefix_stream
from repro.serving.pages import PagedSlotPool
from repro.serving.scheduler import ContinuousBatchingScheduler, FixedCalibration

FAMILY_ARCHS = ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                "zamba2-7b", "whisper-tiny", "granite-4.0-h-small")


def _engines_f32(arch, *, max_batch=2, max_len=32, page_size=4, slack=0,
                 **paged_kw):
    """A contiguous and a paged engine over IDENTICAL f32 params — parity is
    exact modulo float reassociation, and in f32 an argmax tie within that
    noise is measure-zero (same argument as the speculative tests)."""
    from repro.models.model import init_model

    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(cfg, jax.random.PRNGKey(0)))
    contig = InferenceEngine(cfg, params=params, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, spec_slack=slack))
    paged = InferenceEngine(cfg, params=params, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, **paged_kw))
    return contig, paged


def _stream(eng, n=6, seed=3, new_tokens=(1, 6)):
    return bursty_stream(n, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=seed,
                         vocab_size=eng.cfg.vocab_size, prompt_lens=(4, 9),
                         new_tokens=new_tokens)


def _tokens(rep):
    return {r.rid: r.tokens for r in rep.records}


def _drained(sched):
    pool = sched.pool
    assert pool.active_count == 0 and not pool.admitting.any()
    if isinstance(pool, PagedSlotPool):
        pool.check_invariants()
        # no leak: everything not pinned by the registry is free again
        assert pool.pages.free_count == pool.num_pages - 1 - len(pool._prefix)


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_token_identical_every_family(arch):
    """ACCEPTANCE: gather-through-the-table decode must reproduce blocking
    contiguous serving exactly for every cache layout — GQA, MLA, pure-SSM
    (unpaged O(1) state), hybrid, and audio cross-attention."""
    contig, paged = _engines_f32(arch)
    reqs = _stream(contig)
    base = ContinuousBatchingScheduler(contig, policy="adaptive").run(reqs)
    sched = ContinuousBatchingScheduler(paged, policy="adaptive")
    rep = sched.run(reqs)
    assert _tokens(base) == _tokens(rep)
    _drained(sched)


@pytest.mark.parametrize("arch", ("granite-3-8b", "zamba2-7b", "granite-4.0-h-small"))
def test_paged_chunked_and_speculative_identical(arch):
    """Chunked admission activates out of a contiguous group cache into
    pages; speculative verify windows write tail blocks allocated on demand
    (NO spec_slack spare rows — the paged engine runs with spec_slack=0)."""
    contig, paged = _engines_f32(arch, max_batch=3, max_len=48, slack=4)
    reqs = _stream(contig, n=8)
    chunked = ContinuousBatchingScheduler(contig, policy="adaptive",
                                          prefill_chunk=3).run(reqs)
    sched = ContinuousBatchingScheduler(paged, policy="adaptive",
                                        prefill_chunk=3)
    rep = sched.run(reqs)
    assert rep.chunks > 0 and _tokens(chunked) == _tokens(rep)
    _drained(sched)

    spec = ContinuousBatchingScheduler(contig, policy="adaptive",
                                       speculate_k=3).run(reqs)
    sched = ContinuousBatchingScheduler(paged, policy="adaptive",
                                        speculate_k=3)
    rep = sched.run(reqs)
    assert rep.verify_ticks > 0 and _tokens(spec) == _tokens(rep)
    _drained(sched)


@pytest.mark.parametrize("speculate_k", (None, 3))
def test_paged_fault_quarantine_identical(speculate_k):
    """Under a seeded fault profile the paged pool must poison, quarantine,
    scrub, and retry to the SAME tokens as the contiguous pool — NaNs from a
    poisoned slot's pages (including scratch-redirected verify writes) must
    never leak into a healthy slot's gather."""
    contig, paged = _engines_f32("granite-3-8b", max_batch=3, max_len=48,
                                 slack=4)
    faults = FaultProfile(seed=7, nan_rate=0.08, stall_rate=0.1,
                          stall_factor=3.0, chunk_fault_rate=0.2)
    reqs = _stream(contig, n=8, new_tokens=(2, 6))
    # a FIXED calibration, not measured: the per-tick fault draws must land
    # on the SAME virtual-time tick sequence in both pools, or the
    # quarantine counts drift apart run to run with measured step times
    cal = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                           prefill_per_tok_s=0.001, verify_per_tok_s=0.0001)
    kw = dict(policy="adaptive", faults=faults, speculate_k=speculate_k,
              calibration=cal)
    base = ContinuousBatchingScheduler(contig, **kw).run(reqs)
    sched = ContinuousBatchingScheduler(paged, **kw)
    rep = sched.run(reqs)
    assert base.quarantined == rep.quarantined > 0
    assert base.failed == rep.failed == 0
    assert _tokens(base) == _tokens(rep)
    _drained(sched)


def test_shared_prefix_same_tokens_less_work():
    """Copy-on-write prefix sharing on a common-system-prompt stream: the
    warm requests map the resident prefix pages read-only and chunk-prefill
    only their tails — fewer chunk ticks, shared page hits, ZERO in-place
    writes to shared pages, and exactly the full-prefill tokens."""
    contig, paged = _engines_f32("granite-3-8b", max_batch=4, max_len=32,
                                 share_prefix=True)
    reqs = shared_prefix_stream(6, rate_hz=30.0, prefix_len=8, tail_len=4,
                                warm_s=1.0, seed=0,
                                vocab_size=contig.cfg.vocab_size,
                                new_tokens=(2, 5))
    base = ContinuousBatchingScheduler(contig, policy="adaptive",
                                       prefill_chunk=4).run(reqs)
    sched = ContinuousBatchingScheduler(paged, policy="adaptive",
                                        prefill_chunk=4)
    rep = sched.run(reqs)
    assert _tokens(base) == _tokens(rep)
    assert rep.shared_hit_pages > 0 and rep.chunks < base.chunks
    assert rep.cow_copies == 0  # decode writes never land in a prompt block
    _drained(sched)
    assert len(sched.pool._prefix) > 0  # the prefix stays resident


def test_paged_pool_packs_more_requests_than_contiguous_bytes():
    """The capacity claim at test scale: with the HBM budget of TWO
    contiguous slots re-spent on pages, the paged pool serves a burst with
    more than two requests in flight at once (short requests only occupy
    the blocks they touch)."""
    from repro.serving.kv_cache import cache_bytes, paged_cache_bytes

    contig, paged = _engines_f32("granite-3-8b", max_batch=2, max_len=32,
                                 page_size=4)
    cfg = contig.cfg
    budget = cache_bytes(cfg, batch=2, max_len=32)
    paged8 = InferenceEngine(cfg, params=paged.params, sc=ServeConfig(
        max_batch=8, max_len=32, paged=True, page_size=4, num_pages=15))
    pool = paged8.make_pool()
    assert paged_cache_bytes(cfg, batch=8, num_pages=15, page_size=4,
                             max_blocks=pool.max_blocks) <= budget
    reqs = bursty_stream(8, fast_rate_hz=5000.0, slow_rate_hz=50.0, seed=0,
                         vocab_size=cfg.vocab_size, prompt_lens=(4,),
                         new_tokens=(4, 4))
    base = ContinuousBatchingScheduler(contig, policy="adaptive").run(reqs)
    sched = ContinuousBatchingScheduler(paged8, policy="adaptive")
    rep = sched.run(reqs)
    assert _tokens(base) == _tokens(rep)
    assert rep.peak_active > base.peak_active == 2
    _drained(sched)


def test_paged_rejects_oversized_worst_case():
    """A request whose worst case cannot fit the page pool is rejected up
    front — blocked admissions may WAIT for pages but never deadlock."""
    _, paged = _engines_f32("granite-3-8b", max_batch=2, max_len=32,
                            page_size=4, num_pages=4)
    reqs = bursty_stream(2, fast_rate_hz=100.0, slow_rate_hz=10.0, seed=0,
                         vocab_size=paged.cfg.vocab_size, prompt_lens=(9,),
                         new_tokens=(8, 8))
    with pytest.raises(ValueError, match="pages"):
        ContinuousBatchingScheduler(paged, policy="adaptive").run(reqs)
