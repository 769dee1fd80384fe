"""Serving benchmark: static vs continuous vs chunked vs speculative.

One REPETITIVE bursty DECODE-HEAVY (Markov-modulated) arrival stream is
served four ways on the SAME engine with the SAME online adaptive
duty-cycle policy class and ONE shared accelerator cost model:

  static       wait for a full batch (or flush timeout), pad every request
               to the cohort's longest prompt and largest token budget,
               lockstep
  continuous   admit into free slots mid-decode with BLOCKING prefill — each
               admission stalls the whole pool for its prompt's duration
  chunked      the same scheduler with chunked admission: FIFO same-length
               groups advance ``--chunk`` prompt tokens per tick between
               masked decode steps (the head-of-line blocking fix; its p99
               win shows on prefill-heavy streams — here it is gated only
               not to regress, since short prompts leave little to chunk)
  speculative  continuous admission + self-speculative decode: an n-gram
               drafter proposes ``--speculate-k`` candidates per slot and
               ONE verify pass commits the greedy-matched prefix, so a tick
               can emit several tokens (output unchanged, token-for-token)

The virtual-time/energy ledger uses a FIXED target-accelerator cost model
(decode step 4 ms; prefill affine in tokens, 1 ms + 1 ms/token; a verify
tick is one step + 0.1 ms/candidate — extra window positions ride the
weight-bandwidth-bound step's weight reads, adding only attention and
activation work), so every derived ratio is DETERMINISTIC given the seed
and CI gates on them via ``scripts/check_bench.py``. Tokens still come
from real jitted execution — which is why the default arch is
whisper-tiny: its reduced decoder settles into run-structured repetitive
output, the templated-workload regime (transcripts, form letters, code)
self-speculation exists for, and the stream's periodic prompts plus long
continuations put the ledger in the decode-bound regime where the drafter's
accepted-token surplus turns into items/J. Archs with chaotic reduced
outputs accept ~0 drafts and degrade to the ≥1-token-per-tick floor.

A second scenario, ``serve_overload_robustness``, drives a flash-crowd
overload (one spike window arriving far beyond pool capacity, every request
carrying a latency deadline) through the same engine three ways: serve
everything, deadline-aware admission control (``shed=True``), and shedding
under a seeded fault profile (NaN slot poisoning + stall ticks) with
quarantine-and-retry. Gated: shedding must not lose on-time completions per
joule vs serving everything, and every non-shed request must complete under
the fault profile.

Two paged-KV scenarios (``serving/pages.py``) close out the file:

  serve_paged_capacity       the SAME HBM byte budget — set by a contiguous
                             pool's ``cache_bytes`` — is re-spent on a paged
                             pool (``paged_cache_bytes``), and a burst of
                             short requests measures peak concurrency.
                             Contiguous slots own max_len rows whether used
                             or not; pages are allocated per occupied block,
                             so the same bytes hold ≥ 2x the requests
                             (gated: ``paged_capacity_multiplier``).
  serve_shared_prefix        a common-system-prompt stream (one shared
                             prefix, random tails) served chunked two ways:
                             contiguous (every prompt prefilled in full) vs
                             paged with copy-on-write prefix reuse (resident
                             prefix pages mapped read-only, only the tail
                             chunk-prefilled). Gated: prefill energy saved
                             must show up as ``shared_prefix_items_per_j_gain``
                             >= 1 with zero COW copies on a read-only prefix.

A fifth scenario, ``serve_memory_pressure``, over-commits a paged pool
(physical pages sized well below the pool's worst-case demand) and drives a
mixed-SLO-tier bursty stream through it under a seeded page-pressure fault
profile, three ways: tiered preempt-and-restore (victims swapped out to a
host buffer or recomputed, whichever the cost model says is cheaper),
emergency-only relief (no watermark, no tier awareness — the shed-only
baseline), and crash-era admission headroom (a pool sized so exhaustion
cannot happen, i.e. the concurrency the old code had to give up). Gated:
preemption must not lose on-time completions per joule vs emergency-only
(``memory_pressure_goodput_per_j_gain`` >= 1) and must serve the latency
tier at least as fast (``latency_tier_p99_gain`` >= 1). No run may crash
on page exhaustion — typed ``PageExhausted`` handling is load-bearing.

A sixth scenario, ``serve_quantized``, serves the capacity burst on an
int8-quantized engine (int8 weight residency via ``models/quant.py`` AND
int8 KV pages via ``kv_quant="int8"``) against the f32 paged pool at the
SAME HBM byte budget, then measures per-family argmax agreement of the
fully quantized engine vs f32 on a shared stream. Gated: the int8 pool
must pack >= 2x the concurrent requests into equal bytes at items/J no
worse than f32, and the minimum per-family agreement must clear the floor
in ``scripts/check_bench.py`` (int8 serving is argmax-agreement close, NOT
token-identical — see docs/kernels.md for the tolerance semantics).

A seventh scenario, ``serve_power_cap``, drives the mixed-SLO-tier bursty
stream through a seeded :class:`PowerEnvelope` (one sustained cap window
plus thermal-throttle dips) composed with the ``therm=`` fault axis, three
ways: ignore the cap (violations counted, nothing enforced — the
measurement baseline), naive uniform hard-throttling (every busy tick
paced to the cap, both tiers slowed identically), and the hysteretic
brownout ladder (``serving/brownout.py``: shrink speculation, fall back
to blocking, duty-cycle idle, then preempt/shed batch-tier work so the
latency tier keeps its deadlines). Gated: the ladder must turn at least
as much energy into ON-TIME completions as uniform throttling
(``brownout_goodput_per_j_gain`` >= 1) at ZERO cap violations in any
compliance window (``cap_violation_free`` == 1) while serving the latency
tier at least as fast (``latency_tier_p99_gain`` >= 1); the ignore arm
must actually witness violations (``ignore_cap_violation_ticks`` >= 1) or
the envelope never bound and the comparison is vacuous.

Reported per mode: items/J, p50/p99 latency, reloads, accepted/tick;
headline ratios go into the BENCH_<timestamp>.json artifact (via
benchmarks/run.py, or standalone: ``python benchmarks/serve_bench.py
--quick``).
"""
import argparse
import json
from datetime import datetime, timezone
from pathlib import Path

import numpy as np

from repro.configs import get_reduced_config
from repro.kernels.runtime import enable_compile_cache
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.faults import make_profile
from repro.serving.kv_cache import cache_bytes, paged_cache_bytes
from repro.serving.load import (
    bursty_stream,
    flash_crowd_stream,
    poisson_stream,
    shared_prefix_stream,
)
from repro.serving.power import PowerEnvelope
from repro.serving.scheduler import (
    ContinuousBatchingScheduler,
    FixedCalibration,
    run_static_batches,
)

# the one shared target-accelerator cost model (seconds)
STEP_S = 0.004          # masked decode step over the pool
PREFILL_BASE_S = 0.001  # per-prefill-call overhead (program dispatch)
PREFILL_TOK_S = 0.001   # per prompt token (compute-bound prefill)
# per drafted candidate on top of one decode step: the masked step is
# WEIGHT-BANDWIDTH bound, so K extra in-flight window positions ride the
# same weight stream and only add attention/activation work (~2.5% of a
# step per candidate) — the memory-bound premise speculation exists for
VERIFY_TOK_S = 0.0001
PROMPT_LENS = (4, 8)    # short prompts: the stream is DECODE-dominated
NEW_TOKENS = (32, 80)   # long continuations — the regime where per-token
                        # decode latency (not prefill) bounds items/J
PROMPT_PERIOD = 4       # repetitive (templated) prompts — see load.py
# overload scenario: shorter budgets keep the three extra runs cheap while
# the spike still drives queueing delay far past the deadline
OVERLOAD_NEW_TOKENS = (8, 24)
# shared-prefix scenario: short decodes keep the run PREFILL-dominated —
# the phase copy-on-write prefix reuse actually accelerates
NEW_TOKENS_SHARED = (4, 16)


def run(arch: str = "whisper-tiny", n: int = 96, max_batch: int = 8,
        chunk: int = 16, speculate_k: int = 6, seed: int = 0,
        execute: bool = True) -> dict:
    cfg = get_reduced_config(arch)
    engine = InferenceEngine(cfg, sc=ServeConfig(max_batch=max_batch, max_len=96,
                                                 spec_slack=speculate_k))
    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    service = (PREFILL_BASE_S + PREFILL_TOK_S * float(np.mean(PROMPT_LENS))
               + float(np.mean(NEW_TOKENS)) * STEP_S)
    reqs = bursty_stream(n, fast_rate_hz=4.0 / service,
                         slow_rate_hz=0.1 / service, p_leave_burst=0.05,
                         seed=seed, vocab_size=cfg.vocab_size,
                         prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS,
                         prompt_period=PROMPT_PERIOD)

    kw = dict(policy="adaptive", execute=execute, calibration=cal)
    cont = ContinuousBatchingScheduler(engine, **kw).run(reqs)
    chkd = ContinuousBatchingScheduler(engine, prefill_chunk=chunk, **kw).run(reqs)
    spec = ContinuousBatchingScheduler(engine, speculate_k=speculate_k,
                                       **kw).run(reqs)
    stat = run_static_batches(engine, reqs, policy="adaptive", execute=execute,
                              calibration=cal, flush_s=16 * service)
    print(f"{arch}: {n} repetitive bursty decode-heavy requests, "
          f"{max_batch}-slot pool, chunk={chunk}, K={speculate_k}, "
          f"t_step={STEP_S * 1e3:.1f} ms (fixed cost model)")
    for rep in (stat, cont, chkd, spec):
        print("  " + rep.summary())
    gain_ipj = cont.items_per_joule / stat.items_per_joule
    gain_p50 = stat.p50_s / cont.p50_s
    gain_p99 = stat.p99_s / cont.p99_s
    chunk_p99 = cont.p99_s / chkd.p99_s
    spec_ipj = spec.items_per_joule / cont.items_per_joule
    print(f"  continuous vs static: {gain_ipj:.2f}x items/J, "
          f"{gain_p50:.2f}x lower p50, {gain_p99:.2f}x lower p99")
    print(f"  chunked vs blocking admission: {chunk_p99:.2f}x lower p99 "
          f"({chkd.chunks} chunks)")
    print(f"  speculative vs plain continuous: {spec_ipj:.2f}x items/J, "
          f"{spec.accepted_per_tick:.2f} accepted tokens/verify tick "
          f"({spec.verify_ticks} verify ticks)")
    return {
        "continuous_items_per_j": cont.items_per_joule,
        "static_items_per_j": stat.items_per_joule,
        "items_per_j_gain": gain_ipj,
        "continuous_p50_ms": cont.p50_s * 1e3,
        "static_p50_ms": stat.p50_s * 1e3,
        "p50_speedup": gain_p50,
        "continuous_p99_ms": cont.p99_s * 1e3,
        "static_p99_ms": stat.p99_s * 1e3,
        "p99_speedup": gain_p99,
        "chunked_items_per_j": chkd.items_per_joule,
        "chunked_p50_ms": chkd.p50_s * 1e3,
        "chunked_p99_ms": chkd.p99_s * 1e3,
        "chunked_p99_speedup": chunk_p99,
        "chunked_chunks": chkd.chunks,
        "speculative_items_per_j": spec.items_per_joule,
        "speculative_items_per_j_gain": spec_ipj,
        "speculative_p50_ms": spec.p50_s * 1e3,
        "speculative_p99_ms": spec.p99_s * 1e3,
        "spec_accepted_per_tick": spec.accepted_per_tick,
        "spec_verify_ticks": spec.verify_ticks,
        "continuous_reloads": cont.reloads,
        "static_reloads": stat.reloads,
        "chunked_reloads": chkd.reloads,
        "speculative_reloads": spec.reloads,
    }


def run_overload(arch: str = "whisper-tiny", n: int = 64, max_batch: int = 8,
                 seed: int = 0, execute: bool = True,
                 fault_spec: str = "light") -> dict:
    """Flash-crowd overload with deadlines: serve-everything vs deadline-aware
    shedding vs shedding under a seeded fault profile. The gated claims:
    shedding turns at least as much energy into ON-TIME completions as
    serving everything (``shed_goodput_per_j_gain`` >= 1), and under faults
    every request admission control keeps is still completed by
    quarantine-and-retry (``fault_completed_frac`` == 1, no failures)."""
    cfg = get_reduced_config(arch)
    engine = InferenceEngine(cfg, sc=ServeConfig(max_batch=max_batch,
                                                 max_len=96))
    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    service = (PREFILL_BASE_S + PREFILL_TOK_S * float(np.mean(PROMPT_LENS))
               + float(np.mean(OVERLOAD_NEW_TOKENS)) * STEP_S)
    # the spike arrives ~4x faster than the pool can drain; the deadline
    # admits a modest queue but not the spike's full backlog
    deadline = 4.0 * service
    reqs = flash_crowd_stream(n, base_rate_hz=0.5 / service,
                              spike_rate_hz=4.0 * max_batch / service,
                              spike_start_s=4.0 * service,
                              spike_len_s=8.0 * service, seed=seed,
                              vocab_size=cfg.vocab_size,
                              prompt_lens=PROMPT_LENS,
                              new_tokens=OVERLOAD_NEW_TOKENS,
                              deadline_s=deadline,
                              prompt_period=PROMPT_PERIOD)
    kw = dict(policy="adaptive", execute=execute, calibration=cal)
    noshed = ContinuousBatchingScheduler(engine, **kw).run(reqs)
    shedr = ContinuousBatchingScheduler(engine, shed=True, **kw).run(reqs)
    faults = make_profile(fault_spec, seed=seed)
    frep = ContinuousBatchingScheduler(engine, shed=True, faults=faults,
                                       **kw).run(reqs)
    print(f"\n{arch}: flash-crowd overload, {n} requests, "
          f"deadline={deadline * 1e3:.0f} ms, pool={max_batch}, "
          f"faults={fault_spec}")
    for label, rep in (("serve-all", noshed), ("shed", shedr),
                       ("shed+faults", frep)):
        print(f"  [{label:11s}] " + rep.summary())
    gain = shedr.goodput_per_joule / noshed.goodput_per_joule
    completed_frac = frep.items / max(n - frep.shed, 1)
    print(f"  shedding vs serve-everything: {gain:.2f}x on-time items/J "
          f"({shedr.shed} shed, {shedr.missed} vs {noshed.missed} missed)")
    print(f"  under faults: {completed_frac * 100:.0f}% of admitted requests "
          f"completed ({frep.quarantined} quarantined, {frep.retried} "
          f"retried, {frep.failed} failed)")
    return {
        "deadline_ms": deadline * 1e3,
        "noshed_goodput_per_j": noshed.goodput_per_joule,
        "noshed_missed": noshed.missed,
        "noshed_wasted_j": noshed.wasted_energy_j,
        "shed_goodput_per_j": shedr.goodput_per_joule,
        "shed_goodput_per_j_gain": gain,
        "shed_count": shedr.shed,
        "shed_missed": shedr.missed,
        "shed_items": shedr.items,
        "shed_wasted_j": shedr.wasted_energy_j,
        "fault_goodput_per_j": frep.goodput_per_joule,
        "fault_completed_frac": completed_frac,
        "fault_items": frep.items,
        "fault_shed": frep.shed,
        "fault_quarantined": frep.quarantined,
        "fault_retried": frep.retried,
        "fault_failed": frep.failed,
        "fault_stragglers": frep.stragglers,
        "fault_wasted_j": frep.wasted_energy_j,
    }


def run_paged_capacity(arch: str = "granite-3-8b", n: int = 32,
                       contig_batch: int = 4, paged_batch: int = 16,
                       page_size: int = 16, seed: int = 0) -> dict:
    """Concurrent capacity at a FIXED HBM byte budget. A contiguous pool of
    ``contig_batch`` slots sets the budget (every slot owns max_len rows up
    front); the paged pool re-spends those bytes as ``num_pages`` shared
    pages and admits by actual block demand, so a burst of short requests
    packs >= 2x as many concurrent decodes into the same memory. Gated:
    ``paged_capacity_multiplier`` (peak concurrently active slots, paged /
    contiguous). Always executes for real — the virtual pool used by
    ``--no-execute`` has no page accounting to measure."""
    cfg = get_reduced_config(arch)
    max_len = 96
    budget = cache_bytes(cfg, batch=contig_batch, max_len=max_len)
    # mirror PagedSlotPool sizing (slack=0): one page of headroom plus one
    # spare block keeps a full-length sequence inside the table
    max_blocks = -(-(max_len + page_size) // page_size) + 1
    # paged bytes are affine in num_pages: solve for the budget's capacity
    b1 = paged_cache_bytes(cfg, batch=paged_batch, num_pages=1,
                           page_size=page_size, max_blocks=max_blocks)
    b2 = paged_cache_bytes(cfg, batch=paged_batch, num_pages=2,
                           page_size=page_size, max_blocks=max_blocks)
    per_page = b2 - b1
    num_pages = int((budget - (b1 - per_page)) // per_page)
    paged_bytes = paged_cache_bytes(cfg, batch=paged_batch,
                                    num_pages=num_pages, page_size=page_size,
                                    max_blocks=max_blocks)
    assert paged_bytes <= budget and num_pages > paged_batch

    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    s0, toks = 8, 8  # short requests: ~1 block each of page_size=16 rows
    service = PREFILL_BASE_S + PREFILL_TOK_S * s0 + toks * STEP_S
    # the whole burst arrives well inside one request's service time, so
    # peak concurrency is limited by the pool, not the arrival process
    reqs = poisson_stream(n, rate_hz=8.0 * paged_batch / service, seed=seed,
                          vocab_size=cfg.vocab_size, prompt_lens=(s0,),
                          new_tokens=(toks, toks))
    kw = dict(policy="adaptive", execute=True, calibration=cal)
    contig = InferenceEngine(cfg, sc=ServeConfig(max_batch=contig_batch,
                                                 max_len=max_len))
    crep = ContinuousBatchingScheduler(contig, **kw).run(reqs)
    pagede = InferenceEngine(cfg, sc=ServeConfig(
        max_batch=paged_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=num_pages))
    prep = ContinuousBatchingScheduler(pagede, **kw).run(reqs)
    mult = prep.peak_active / max(crep.peak_active, 1)
    print(f"\n{arch}: paged capacity at fixed HBM budget "
          f"({budget / 1e6:.2f} MB = {contig_batch} contiguous slots), "
          f"{n} short requests")
    print(f"  [contiguous ] peak {crep.peak_active:2d} active "
          f"({cache_bytes(cfg, batch=contig_batch, max_len=max_len) / 1e6:.2f} MB) "
          + crep.summary())
    print(f"  [paged      ] peak {prep.peak_active:2d} active "
          f"({paged_bytes / 1e6:.2f} MB, {num_pages} pages of {page_size}) "
          + prep.summary())
    print(f"  same bytes hold {mult:.2f}x the concurrent requests")
    return {
        "hbm_budget_mb": budget / 1e6,
        "paged_bytes_mb": paged_bytes / 1e6,
        "num_pages": num_pages,
        "page_size": page_size,
        "contig_peak_active": crep.peak_active,
        "paged_peak_active": prep.peak_active,
        "paged_capacity_multiplier": mult,
        "contig_items_per_j": crep.items_per_joule,
        "paged_items_per_j": prep.items_per_joule,
        "contig_p99_ms": crep.p99_s * 1e3,
        "paged_p99_ms": prep.p99_s * 1e3,
    }


def run_shared_prefix(arch: str = "granite-3-8b", n: int = 12,
                      max_batch: int = 4, page_size: int = 8,
                      chunk: int = 8, seed: int = 0) -> dict:
    """Shared-prefix prefill efficiency on common-system-prompt traffic.
    Every prompt is one 48-token prefix plus an 8-token random tail; request
    0 warms the prefix registry, then paged admission maps the resident
    prefix pages read-only (copy-on-write guards them) and chunk-prefills
    only the tail — the contiguous baseline prefills every prompt in full.
    Gated: ``shared_prefix_items_per_j_gain`` >= 1 (the skipped prefill
    energy must reach the ledger). Always executes for real — prefix
    matching needs the actual page registry."""
    cfg = get_reduced_config(arch)
    max_len, prefix_len, tail_len = 96, 48, 8
    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    s0 = prefix_len + tail_len
    service = (PREFILL_BASE_S + PREFILL_TOK_S * s0
               + float(np.mean(NEW_TOKENS_SHARED)) * STEP_S)
    reqs = shared_prefix_stream(n, rate_hz=2.0 / service,
                                prefix_len=prefix_len, tail_len=tail_len,
                                warm_s=3.0 * service, seed=seed,
                                vocab_size=cfg.vocab_size,
                                new_tokens=NEW_TOKENS_SHARED)
    kw = dict(policy="adaptive", execute=True, calibration=cal,
              prefill_chunk=chunk)
    contig = InferenceEngine(cfg, sc=ServeConfig(max_batch=max_batch,
                                                 max_len=max_len))
    crep = ContinuousBatchingScheduler(contig, **kw).run(reqs)
    shared = InferenceEngine(cfg, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, share_prefix=True))
    srep = ContinuousBatchingScheduler(shared, **kw).run(reqs)
    gain = srep.items_per_joule / crep.items_per_joule
    print(f"\n{arch}: shared-prefix stream, {n} requests of "
          f"{prefix_len}+{tail_len} tokens, chunk={chunk}, page={page_size}")
    print(f"  [full prefill] {crep.chunks} chunks " + crep.summary())
    print(f"  [prefix reuse] {srep.chunks} chunks, "
          f"{srep.shared_hit_pages} shared page hits, "
          f"{srep.cow_copies} COW copies " + srep.summary())
    print(f"  prefix reuse: {gain:.2f}x items/J "
          f"({crep.chunks - srep.chunks} chunk ticks saved)")
    return {
        "prefix_len": prefix_len,
        "tail_len": tail_len,
        "contig_items_per_j": crep.items_per_joule,
        "shared_items_per_j": srep.items_per_joule,
        "shared_prefix_items_per_j_gain": gain,
        "contig_chunks": crep.chunks,
        "shared_chunks": srep.chunks,
        "shared_hit_pages": srep.shared_hit_pages,
        "cow_copies": srep.cow_copies,
        "contig_p99_ms": crep.p99_s * 1e3,
        "shared_p99_ms": srep.p99_s * 1e3,
    }


def run_memory_pressure(arch: str = "granite-3-8b", n: int = 48,
                        max_batch: int = 8, page_size: int = 16,
                        speculate_k: int = 4, tier_mix: float = 0.375,
                        seed: int = 0,
                        press_spec: str = "press=0.25,pressn=2") -> dict:
    """Over-committed paged pool under page-pressure faults, mixed SLO tiers.

    The pool's physical pages cover ~55% of worst-case demand (every slot
    at full budget plus its speculative verify tail), so mid-decode
    exhaustion is ROUTINE, not exceptional. Latency-tier requests carry a
    tight deadline, batch-tier a loose one. Three ways through the same
    stream: tiered preempt-and-restore, emergency-only relief (tierless —
    what the scheduler does with no preemption policy configured), and
    crash-era headroom (admission capped so exhaustion cannot happen — the
    concurrency cost of never over-committing). Gated:
    ``memory_pressure_goodput_per_j_gain`` and ``latency_tier_p99_gain``
    >= 1, preemption vs emergency-only."""
    cfg = get_reduced_config(arch)
    max_len, s0 = 96, 8
    budget_max = 24
    # worst-case per-slot pages: full budget plus the speculative verify
    # tail, in blocks of page_size rows
    worst_resv = -(-(s0 + budget_max) // page_size)           # reservation
    worst_full = -(-(s0 + budget_max + speculate_k) // page_size)  # + tail
    parity = 1 + max_batch * worst_full  # SCRATCH + every slot worst-case
    num_pages = 1 + int(max_batch * worst_full * 0.55)        # over-commit
    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    service = (PREFILL_BASE_S + PREFILL_TOK_S * s0
               + float(np.mean(OVERLOAD_NEW_TOKENS)) * STEP_S)
    reqs = bursty_stream(n, fast_rate_hz=3.0 * max_batch / service,
                         slow_rate_hz=0.1 / service, p_leave_burst=0.05,
                         seed=seed, vocab_size=cfg.vocab_size,
                         prompt_lens=(s0,), new_tokens=OVERLOAD_NEW_TOKENS,
                         prompt_period=PROMPT_PERIOD, tier_mix=tier_mix)
    # per-tier deadlines, assigned post-hoc so the stream itself (prompts,
    # budgets, arrivals, tiers) is shared by all three runs
    # the latency-tier deadline sits between the tiered and tierless p99s,
    # so protecting the tier converts directly into on-time completions
    for r in reqs:
        r.deadline_s = 4.0 * service if r.tier == "latency" else 40.0 * service
    tiers = {r.rid: r.tier for r in reqs}
    prof = make_profile(press_spec, seed=seed)

    def _tier_p99(rep, tier):
        lats = [r.latency_s for r in rep.records
                if tiers[r.rid] == tier and not r.shed and not r.failed]
        return float(np.percentile(lats, 99)) if lats else 1e6

    kw = dict(policy="adaptive", execute=True, calibration=cal,
              speculate_k=speculate_k, shed=True)
    engine = InferenceEngine(cfg, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=num_pages))
    pre = ContinuousBatchingScheduler(engine, preempt="tiered", swap=True,
                                      faults=prof, **kw).run(reqs)
    emg = ContinuousBatchingScheduler(engine, faults=prof, **kw).run(reqs)
    # crash-era answer: cap admission so worst-case demand always fits —
    # no pressure handling needed (or exercised), concurrency given up
    head_batch = max((num_pages - 1) // worst_full, 1)
    heade = InferenceEngine(cfg, params=engine.params, sc=ServeConfig(
        max_batch=head_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=num_pages))
    head = ContinuousBatchingScheduler(heade, **kw).run(reqs)

    gain = pre.goodput_per_joule / max(emg.goodput_per_joule, 1e-12)
    p99_gain = _tier_p99(emg, "latency") / max(_tier_p99(pre, "latency"), 1e-12)
    n_lat = sum(1 for t in tiers.values() if t == "latency")
    print(f"\n{arch}: memory pressure, {n} requests ({n_lat} latency-tier), "
          f"{num_pages} pages of {page_size} (worst-case {parity}), "
          f"pool={max_batch}, K={speculate_k}, faults={press_spec}")
    for label, rep in (("preempt", pre), ("emergency", emg),
                       (f"headroom-{head_batch}", head)):
        print(f"  [{label:11s}] " + rep.summary())
    print(f"  preempt vs emergency-only: {gain:.2f}x on-time items/J, "
          f"latency-tier p99 {_tier_p99(pre, 'latency') * 1e3:.1f} ms vs "
          f"{_tier_p99(emg, 'latency') * 1e3:.1f} ms ({p99_gain:.2f}x)")
    print(f"  crash-era headroom: {head_batch} slots "
          f"(vs {max_batch} over-committed), "
          f"goodput/J {head.goodput_per_joule:.5f} vs {pre.goodput_per_joule:.5f}")
    return {
        "num_pages": num_pages,
        "worst_case_pages": parity,
        "worst_resv_blocks": worst_resv,
        "preempt_goodput_per_j": pre.goodput_per_joule,
        "emergency_goodput_per_j": emg.goodput_per_joule,
        "memory_pressure_goodput_per_j_gain": gain,
        "preempt_latency_p99_ms": _tier_p99(pre, "latency") * 1e3,
        "emergency_latency_p99_ms": _tier_p99(emg, "latency") * 1e3,
        "latency_tier_p99_gain": p99_gain,
        "preempt_batch_p99_ms": _tier_p99(pre, "batch") * 1e3,
        "preempted": pre.preempted,
        "swapped": pre.swapped,
        "recomputed": pre.recomputed,
        "preempt_wasted_j": pre.preempt_wasted_j,
        "emergency_preempted": emg.preempted,
        "preempt_shed": pre.shed,
        "emergency_shed": emg.shed,
        "preempt_missed": pre.missed,
        "emergency_missed": emg.missed,
        "headroom_batch": head_batch,
        "headroom_goodput_per_j": head.goodput_per_joule,
        "headroom_peak_active": head.peak_active,
        "preempt_peak_active": pre.peak_active,
    }


def run_quantized(arch: str = "granite-3-8b", n: int = 48, cap_batch: int = 24,
                  page_size: int = 16, seed: int = 0,
                  agree_n: int = 6) -> dict:
    """End-to-end quantized serving (int8 weights + int8 KV pages) vs the
    f32 paged pool, two claims at once:

    CAPACITY: an f32-KV paged pool's HBM bytes are the budget; the int8-KV
    pool re-spends them (int8 payloads + per-(page,row,head) f32 scales cost
    ~1/4 of f32 rows at paper head dims; less at the reduced config's tiny
    head_dim, where the scale overhead looms larger), holds proportionally
    more pages, and a short-request burst packs >= 2x the concurrent decodes
    (``quant_capacity_multiplier``) at items/J no worse than f32
    (``quant_items_per_j_gain``) — more in-flight decodes amortize each
    fixed-cost tick over more requests. Both pools get the SAME ``cap_batch``
    slots, sized past what their pages can hold, so PAGES (the bytes), not
    slot count, bound concurrency.

    ACCURACY: int8 is NOT token-identical — rounding noise flips argmax on
    near-ties — so the acceptance metric is the per-family ARGMAX AGREEMENT
    rate: fraction of positions where the fully quantized engine (int8
    weights AND int8 KV) emits the same greedy token as the f32 engine on
    the same stream. Greedy chains diverge PERMANENTLY at the first flipped
    token (the context differs from there on), so this chain-agreement rate
    lower-bounds per-step agreement, and reduced configs at random init are
    the worst case — near-ties everywhere. Gated on the minimum and mean
    over all five families (``quant_min_argmax_agreement``,
    ``quant_mean_argmax_agreement``); the floors live in
    ``scripts/check_bench.py``, the semantics in docs/kernels.md.
    Always executes for real (quantization error needs real tokens)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models.model import init_model

    # f32 cache dtype for the byte comparison: the claim is int8 pages vs
    # F32 pages at equal HBM (the reduced configs default to bf16)
    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    max_len = 96
    max_blocks = -(-(max_len + page_size) // page_size) + 1

    def _solve_pages(kv_quant, budget):
        # paged bytes are affine in num_pages — same solve as
        # run_paged_capacity, with the quantized layout's per-page cost
        b1 = paged_cache_bytes(cfg, batch=cap_batch, num_pages=1,
                               page_size=page_size, max_blocks=max_blocks,
                               kv_quant=kv_quant)
        b2 = paged_cache_bytes(cfg, batch=cap_batch, num_pages=2,
                               page_size=page_size, max_blocks=max_blocks,
                               kv_quant=kv_quant)
        per = b2 - b1
        return int((budget - (b1 - per)) // per), per

    # the f32 paged pool sets the byte budget (anchored at two contiguous
    # slots' bytes, like serve_paged_capacity's four — smaller here so both
    # pools stay PAGE-limited under cap_batch slots)
    contig_budget = cache_bytes(cfg, batch=2, max_len=max_len)
    f32_pages, f32_per_page = _solve_pages(None, contig_budget)
    budget = paged_cache_bytes(cfg, batch=cap_batch, num_pages=f32_pages,
                               page_size=page_size, max_blocks=max_blocks)
    q8_pages, q8_per_page = _solve_pages("int8", budget)
    q8_bytes = paged_cache_bytes(cfg, batch=cap_batch, num_pages=q8_pages,
                                 page_size=page_size, max_blocks=max_blocks,
                                 kv_quant="int8")
    assert q8_bytes <= budget and q8_pages > f32_pages

    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    s0, toks = 8, 8
    service = PREFILL_BASE_S + PREFILL_TOK_S * s0 + toks * STEP_S
    reqs = poisson_stream(n, rate_hz=8.0 * cap_batch / service, seed=seed,
                          vocab_size=cfg.vocab_size, prompt_lens=(s0,),
                          new_tokens=(toks, toks))
    kw = dict(policy="adaptive", execute=True, calibration=cal)
    f32e = InferenceEngine(cfg, sc=ServeConfig(
        max_batch=cap_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=f32_pages))
    frep = ContinuousBatchingScheduler(f32e, **kw).run(reqs)
    qcfg = dataclasses.replace(cfg, quant="int8")
    q8e = InferenceEngine(qcfg, sc=ServeConfig(
        max_batch=cap_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=q8_pages, kv_quant="int8"))
    qrep = ContinuousBatchingScheduler(q8e, **kw).run(reqs)
    mult = qrep.peak_active / max(frep.peak_active, 1)
    ipj_gain = qrep.items_per_joule / frep.items_per_joule
    print(f"\n{arch}: quantized serving at fixed HBM budget "
          f"({budget / 1e6:.2f} MB), {n} short requests")
    print(f"  [f32  pages] peak {frep.peak_active:2d} active "
          f"({f32_pages} pages of {page_size}) " + frep.summary())
    print(f"  [int8 pages] peak {qrep.peak_active:2d} active "
          f"({q8_pages} pages of {page_size}, {q8_bytes / 1e6:.2f} MB) "
          + qrep.summary())
    print(f"  int8 KV: {f32_per_page / q8_per_page:.2f}x smaller pages, "
          f"{mult:.2f}x the concurrent requests, {ipj_gain:.2f}x items/J")

    # per-family argmax agreement: fully quantized engine vs f32, shared
    # params, identical stream — the documented acceptance metric
    agreement = {}
    for fam_arch in ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                     "zamba2-7b", "whisper-tiny"):
        fcfg = dataclasses.replace(get_reduced_config(fam_arch),
                                   dtype=jnp.float32)
        params = jax.tree.map(lambda t: t.astype(jnp.float32),
                              init_model(fcfg, jax.random.PRNGKey(seed)))
        akw = dict(max_batch=2, max_len=32, paged=True, page_size=4)
        base_e = InferenceEngine(fcfg, params=params, sc=ServeConfig(**akw))
        quant_e = InferenceEngine(dataclasses.replace(fcfg, quant="int8"),
                                  params=params,
                                  sc=ServeConfig(kv_quant="int8", **akw))
        areqs = bursty_stream(agree_n, fast_rate_hz=2000.0, slow_rate_hz=20.0,
                              seed=seed + 3, vocab_size=fcfg.vocab_size,
                              prompt_lens=(4, 9), new_tokens=(1, 6))
        base = ContinuousBatchingScheduler(base_e, **kw).run(areqs)
        qrun = ContinuousBatchingScheduler(quant_e, **kw).run(areqs)
        bt = {r.rid: r.tokens for r in base.records}
        qt = {r.rid: r.tokens for r in qrun.records}
        total = sum(len(v) for v in bt.values())
        same = sum(int(a == b) for rid in bt
                   for a, b in zip(bt[rid], qt[rid]))
        agreement[fam_arch] = same / total
        print(f"  [{fam_arch:18s}] argmax agreement "
              f"{agreement[fam_arch]:.3f} ({same}/{total} tokens)")
    min_agree = min(agreement.values())
    mean_agree = sum(agreement.values()) / len(agreement)
    print(f"  per-family argmax agreement: min {min_agree:.3f}, "
          f"mean {mean_agree:.3f}")
    return {
        "hbm_budget_mb": budget / 1e6,
        "q8_bytes_mb": q8_bytes / 1e6,
        "f32_pages": f32_pages,
        "q8_pages": q8_pages,
        "page_size": page_size,
        "page_bytes_ratio": f32_per_page / q8_per_page,
        "f32_peak_active": frep.peak_active,
        "q8_peak_active": qrep.peak_active,
        "quant_capacity_multiplier": mult,
        "f32_items_per_j": frep.items_per_joule,
        "q8_items_per_j": qrep.items_per_joule,
        "quant_items_per_j_gain": ipj_gain,
        "f32_p99_ms": frep.p99_s * 1e3,
        "q8_p99_ms": qrep.p99_s * 1e3,
        "quant_min_argmax_agreement": min_agree,
        "quant_mean_argmax_agreement": mean_agree,
        **{f"argmax_agreement_{k.replace('-', '_')}": v
           for k, v in agreement.items()},
    }


def run_power_cap(arch: str = "whisper-tiny", n: int = 48, max_batch: int = 8,
                  page_size: int = 16, speculate_k: int = 4,
                  tier_mix: float = 0.375, seed: int = 0, execute: bool = True,
                  therm_spec: str = "therm=0.1,thermf=0.5,thermt=24") -> dict:
    """Bursty mixed-tier stream under a seeded power envelope, three ways.

    The envelope (one sustained cap window over most of the stream plus
    seeded thermal dips, composed with the ``therm=`` fault axis's dynamic
    dips) is IDENTICAL across the arms:

      ignore    measure violations, enforce nothing — what the ledger says
                happens if the scheduler pretends the cap isn't there
      uniform   pace EVERY busy tick to the cap (both tiers slowed alike)
      ladder    the hysteretic brownout controller: degrade speculation and
                admission first, then pace, then preempt/shed BATCH-tier
                work so latency-tier deadlines survive the deficit

    Gated: ladder >= uniform on on-time goodput/J and latency-tier p99 at
    zero cap violations, and the ignore arm must witness violations (else
    the cap never bound). Brownout changes scheduling only — all three
    arms emit token-identical completions for every non-shed request."""
    cfg = get_reduced_config(arch)
    max_len, s0 = 96, 8
    budget_max = max(OVERLOAD_NEW_TOKENS)
    # parity pages: this scenario stresses WATTS, not memory — the pool
    # must never hit page exhaustion, only the power governor
    worst = -(-(s0 + budget_max + speculate_k) // page_size)
    num_pages = 1 + max_batch * worst
    cal = FixedCalibration(step_s=STEP_S, prefill_base_s=PREFILL_BASE_S,
                           prefill_per_tok_s=PREFILL_TOK_S,
                           verify_per_tok_s=VERIFY_TOK_S)
    service = (PREFILL_BASE_S + PREFILL_TOK_S * s0
               + float(np.mean(OVERLOAD_NEW_TOKENS)) * STEP_S)
    reqs = bursty_stream(n, fast_rate_hz=3.0 * max_batch / service,
                         slow_rate_hz=0.1 / service, p_leave_burst=0.05,
                         seed=seed, vocab_size=cfg.vocab_size,
                         prompt_lens=(s0,), new_tokens=OVERLOAD_NEW_TOKENS,
                         prompt_period=PROMPT_PERIOD, tier_mix=tier_mix)
    # per-tier deadlines, assigned post-hoc so all three arms share the
    # stream; the latency-tier deadline sits between the ladder's and the
    # uniform throttle's p99 under the cap, so tier protection converts
    # directly into on-time completions
    for r in reqs:
        r.deadline_s = 4.0 * service if r.tier == "latency" else 60.0 * service
    tiers = {r.rid: r.tier for r in reqs}
    # the envelope spans the arrivals plus drain time, so the sustained cap
    # window covers the burst the pool is still digesting
    horizon = max(r.arrival_s for r in reqs) + 30.0 * service
    env = PowerEnvelope.seeded(seed, horizon_s=horizon)
    prof = make_profile(therm_spec, seed=seed)

    def _tier_p99(rep, tier):
        # no survivor bias: a shed (or failed) request was never served, so
        # it is charged the run's makespan — uniform throttling that sheds
        # latency-tier arrivals cannot improve its p99 by refusing them
        lats = [(rep.time_s if r.shed or r.failed else r.latency_s)
                for r in rep.records if tiers[r.rid] == tier]
        return float(np.percentile(lats, 99)) if lats else 1e6

    kw = dict(policy="adaptive", execute=execute, calibration=cal,
              speculate_k=speculate_k, shed=True, faults=prof, power=env)
    engine = InferenceEngine(cfg, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=num_pages))
    ign = ContinuousBatchingScheduler(engine, **kw).run(reqs)
    uni = ContinuousBatchingScheduler(engine, brownout="uniform", **kw).run(reqs)
    lad = ContinuousBatchingScheduler(engine, brownout="ladder",
                                      preempt="tiered", **kw).run(reqs)

    gain = lad.goodput_per_joule / max(uni.goodput_per_joule, 1e-12)
    p99_gain = (_tier_p99(uni, "latency")
                / max(_tier_p99(lad, "latency"), 1e-12))
    cap_free = float(lad.cap_violation_ticks == 0
                     and uni.cap_violation_ticks == 0)
    n_lat = sum(1 for t in tiers.values() if t == "latency")
    print(f"\n{arch}: power cap, {n} requests ({n_lat} latency-tier), "
          f"cap {env.caps[0].cap_w:.0f} W over "
          f"[{env.caps[0].start_s:.2f}, {env.caps[0].end_s:.2f}] s, "
          f"{len(env.scripted)} thermal dips, faults={therm_spec}")
    for label, rep in (("ignore-cap", ign), ("uniform", uni),
                       ("ladder", lad)):
        print(f"  [{label:10s}] " + rep.summary())
    print(f"  ladder vs uniform: {gain:.2f}x on-time items/J, latency-tier "
          f"p99 {_tier_p99(lad, 'latency') * 1e3:.1f} ms vs "
          f"{_tier_p99(uni, 'latency') * 1e3:.1f} ms ({p99_gain:.2f}x)")
    print(f"  cap compliance: ignore {ign.cap_violation_ticks} violation "
          f"ticks (peak {ign.peak_window_w:.0f} W), governed "
          f"{uni.cap_violation_ticks}+{lad.cap_violation_ticks} "
          f"(ladder dwell {tuple(lad.level_dwell)})")
    return {
        "cap_w": env.caps[0].cap_w,
        "ignore_goodput_per_j": ign.goodput_per_joule,
        "ignore_cap_violation_ticks": ign.cap_violation_ticks,
        "ignore_peak_window_w": ign.peak_window_w,
        "ignore_missed": ign.missed,
        "uniform_goodput_per_j": uni.goodput_per_joule,
        "uniform_cap_violation_ticks": uni.cap_violation_ticks,
        "uniform_brownout_ticks": uni.brownout_ticks,
        "uniform_forgone_j": uni.brownout_forgone_j,
        "uniform_missed": uni.missed,
        "ladder_goodput_per_j": lad.goodput_per_joule,
        "ladder_cap_violation_ticks": lad.cap_violation_ticks,
        "ladder_brownout_ticks": lad.brownout_ticks,
        "ladder_transitions": lad.brownout_transitions,
        "ladder_forgone_j": lad.brownout_forgone_j,
        "ladder_preempted": lad.preempted,
        "ladder_shed": lad.shed,
        "ladder_missed": lad.missed,
        "brownout_goodput_per_j_gain": gain,
        "ladder_latency_p99_ms": _tier_p99(lad, "latency") * 1e3,
        "uniform_latency_p99_ms": _tier_p99(uni, "latency") * 1e3,
        "latency_tier_p99_gain": p99_gain,
        "cap_violation_free": cap_free,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="small stream (CI smoke)")
    ap.add_argument("--arch", default="whisper-tiny")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prompt tokens per chunked-prefill tick")
    ap.add_argument("--speculate-k", type=int, default=6,
                    help="drafted candidates per speculative verify tick")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-profile", default="light",
                    help="fault profile for the overload scenario "
                         "(none/light/heavy or a spec string)")
    ap.add_argument("--no-execute", action="store_true",
                    help="virtual pools only (ledger unchanged, no real tokens)")
    ap.add_argument("--out", default=".", help="directory for the BENCH_*.json artifact")
    args = ap.parse_args(argv)
    enable_compile_cache()

    n = args.n or (56 if args.quick else 96)
    batch = args.batch or 8
    derived = run(arch=args.arch, n=n, max_batch=batch, chunk=args.chunk,
                  speculate_k=args.speculate_k, seed=args.seed,
                  execute=not args.no_execute)
    n_over = 40 if args.quick else 64
    overload = run_overload(arch=args.arch, n=n_over, max_batch=batch,
                            seed=args.seed, execute=not args.no_execute,
                            fault_spec=args.fault_profile)
    n_cap = 24 if args.quick else 32
    capacity = run_paged_capacity(n=n_cap, seed=args.seed)
    n_shared = 8 if args.quick else 12
    shared = run_shared_prefix(n=n_shared, seed=args.seed)
    n_press = 32 if args.quick else 48
    pressure = run_memory_pressure(n=n_press, seed=args.seed)
    n_quant = 40 if args.quick else 48
    quant = run_quantized(n=n_quant, seed=args.seed)
    n_power = 32 if args.quick else 48
    power = run_power_cap(arch=args.arch, n=n_power, max_batch=batch,
                          seed=args.seed, execute=not args.no_execute)

    stamp = datetime.now(timezone.utc).strftime("%Y%m%d-%H%M%S")
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    artifact = out_dir / f"BENCH_{stamp}.json"
    artifact.write_text(json.dumps({
        "schema_version": 2,
        "timestamp_utc": stamp,
        "meta": {
            "driver": "serve_bench",
            "quick": bool(args.quick),
            "seed": args.seed,
            "execute": not args.no_execute,
        },
        "results": [{
            "name": "serve_continuous_batching",
            "arch": args.arch,
            "n_requests": n,
            "max_batch": batch,
            "prefill_chunk": args.chunk,
            "speculate_k": args.speculate_k,
            "derived": {k: float(v) for k, v in derived.items()},
        }, {
            "name": "serve_overload_robustness",
            "arch": args.arch,
            "n_requests": n_over,
            "max_batch": batch,
            "fault_profile": args.fault_profile,
            "derived": {k: float(v) for k, v in overload.items()},
        }, {
            "name": "serve_paged_capacity",
            "arch": "granite-3-8b",
            "n_requests": n_cap,
            "derived": {k: float(v) for k, v in capacity.items()},
        }, {
            "name": "serve_shared_prefix",
            "arch": "granite-3-8b",
            "n_requests": n_shared,
            "derived": {k: float(v) for k, v in shared.items()},
        }, {
            "name": "serve_memory_pressure",
            "arch": "granite-3-8b",
            "n_requests": n_press,
            "derived": {k: float(v) for k, v in pressure.items()},
        }, {
            "name": "serve_quantized",
            "arch": "granite-3-8b",
            "n_requests": n_quant,
            "derived": {k: float(v) for k, v in quant.items()},
        }, {
            "name": "serve_power_cap",
            "arch": args.arch,
            "n_requests": n_power,
            "max_batch": batch,
            "derived": {k: float(v) for k, v in power.items()},
        }],
    }, indent=1, sort_keys=True))
    print(f"\nwrote {artifact}")
    # gating lives in ONE place — scripts/check_bench.py reads the artifact
    # and applies the floors with the configured tolerance
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
