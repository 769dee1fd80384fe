"""Workload-aware serving engine (RQ2 on TPU).

Two layers:

  * ``InferenceEngine`` — the real execution path: jitted prefill + greedy
    decode against the family-appropriate cache (KV / compressed-MLA / SSM
    state), batched requests, optional mesh. This is what examples/ and the
    smoke tests run on CPU with reduced configs.

  * ``WorkloadAwareServer`` — the duty-cycle layer: between request batches
    it applies the paper's strategies (On-Off / Idle-Waiting / Slow-Down /
    adaptive with predefined or learned threshold, core/workload.py) with
    TPU constants — "configuration" is XLA program load + HBM weight refill
    (DESIGN.md §2). It measures real inference latency, models energy with
    the same AccelProfile machinery that reproduces C3/C4 on FPGA constants,
    and reports items/J per strategy so the Generator's choice is validated
    end-to-end.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.core.energy import DEFAULT_CHIP, TPUChip
from repro.core.workload import AccelProfile, break_even_tau, learn_tau, simulate
from repro.models.model import (
    commit_verify,
    decode_step,
    decode_verify,
    encoder_cross_cache,
    init_model,
    PagedRows,
    prefill,
    prefill_chunk,
    verify_block_span,
)
from repro.models.params import init_params
from repro.serving.faults import FaultProfile
from repro.serving.kv_cache import cache_defs, paged_keys, quantize_kv
from repro.serving.pages import PagedSlotPool
from repro.serving.slots import SlotPool, grow_cache


def tpu_reload_costs(cfg: ArchConfig, chip: TPUChip = DEFAULT_CHIP, *,
                     chips: int = 1, weight_bytes: float | None = None
                     ) -> tuple[float, float]:
    """(t_reload_s, e_reload_j) for the TPU "configuration" analogue:
    program load + HBM weight refill after a power-off (DESIGN.md §2)."""
    if weight_bytes is None:
        weight_bytes = 2.0 * cfg.param_count() / max(chips, 1)
    t_reload = chip.reload_time(weight_bytes)
    return t_reload, t_reload * chip.p_idle_w * chips


# ---------------------------------------------------------------------------
# Real execution engine
# ---------------------------------------------------------------------------
def _greedy(v):
    """Greedy tokens of one slot's logits (..., vocab), and whether every
    logit is finite (the finiteness guard)."""
    with jax.named_scope("logits"):
        return jnp.argmax(v, axis=-1).astype(jnp.int32), jnp.isfinite(v).all()


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256  # admission bound (prompt + generated)
    greedy: bool = True
    # spare cache rows past max_len for speculative verify windows: a verify
    # of K drafts writes K+1 positions starting anywhere up to max_len-2, so
    # speculative serving needs spec_slack >= K to keep the window's tail
    # writes off live positions (the rows only ever hold rejected drafts)
    spec_slack: int = 0
    # seeded fault-injection scenario (serving/faults.py): the scheduler
    # reads it from here unless given one explicitly, so an (engine, config)
    # pair pins a reproducible chaos run; None = no injected faults
    faults: FaultProfile | None = None
    # paged KV cache (serving/pages.py): slots map logical blocks of
    # page_size cache rows onto shared physical pages through a dense page
    # table instead of owning a contiguous max_len+slack rectangle. Verify
    # windows need no spec_slack here (the table always has spare blocks);
    # num_pages=None sizes the pool for contiguous parity (fit everything),
    # smaller values trade HBM for admission-control backpressure
    paged: bool = False
    page_size: int = 16
    num_pages: int | None = None
    # copy-on-write sharing of block-aligned prompt prefixes between
    # requests (paged only; common-system-prompt traffic prefills the
    # shared prefix once)
    share_prefix: bool = False
    # int8 KV page residency (paged only): payloads are stored int8 with
    # per-row f32 scales in parallel "{key}_scale" page leaves — ~4x less
    # HBM per page, quantize-on-write in every scatter path and
    # dequantize-in-gather in every virtual-cache gather. Token identity vs
    # the f32 path is NOT expected; the acceptance metric is argmax
    # agreement rate (see docs/kernels.md). "int8" or None.
    kv_quant: str | None = None
    # hard energy-budget enforcement (serving/power.py): when set, the
    # scheduler's rolling ledger is GUARANTEED never to exceed
    # energy_budget_j joules in any budget_window_s-second window — busy
    # ticks wait at p_idle_w until they fit, and a brownout governor (if
    # one is running) degrades batch-tier service first so latency-tier
    # deadlines survive the squeeze. None = unenforced. The budget must
    # exceed the idle floor p_idle_w * chips * budget_window_s or no
    # schedule is feasible (the scheduler raises at construction).
    energy_budget_j: float | None = None
    budget_window_s: float = 1.0


class InferenceEngine:
    """Batched prefill → decode loop for every architecture family."""

    def __init__(self, cfg: ArchConfig, params=None, sc: ServeConfig | None = None,
                 seed: int = 0):
        self.cfg = cfg
        self.sc = sc or ServeConfig()
        self.params = params if params is not None else init_model(
            cfg, jax.random.PRNGKey(seed)
        )
        if cfg.quant == "int8":
            # idempotent: pre-quantized leaves pass through, so callers may
            # hand in either f32 or already-quantized param trees
            from repro.models.quant import quantize_params

            self.params = quantize_params(self.params, cfg)
        # each program is a named function, so a profile shows it as
        # jit_<name>; the chunk step below is the one still a lambda
        self._prefill = jax.jit(self._prefill_impl)
        # the cache argument is donated: each decode step updates it in place
        # instead of doubling cache memory per step (no-op where the backend
        # lacks donation — the semantics are unchanged either way)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._masked_decode = jax.jit(self._masked_decode_impl, donate_argnums=(1,))
        # speculative verify: one donated jit, keyed on K by the drafts'
        # (max_batch, K) shape — a new K retraces, a fixed K reuses
        self._masked_verify = jax.jit(self._masked_verify_impl, donate_argnums=(1,))
        # chunked prefill: T prompt tokens appended to a full-capacity cache
        # at a traced offset — one compile per (batch, chunk-length) signature.
        # Still a lambda, so a profile shows it as jit__lambda: the engine's
        # last unnamed program
        self._chunk = jax.jit(
            lambda p, cache, toks, pos, fe: prefill_chunk(
                p, cache, toks, pos, cfg, frontend_embeds=fe
            ),
            donate_argnums=(1,),
        )
        self._cross_cache = jax.jit(self._cross_cache_impl)
        self._chunk_probe_fn = None  # non-donating twin of _chunk (calibration)
        # fault injection: overwrite one slot's cache rows with NaN (the
        # slot index is traced, so all slots share one compile)
        self._poison = jax.jit(self._poison_impl, donate_argnums=(0,))
        # paged twins of the masked decode/verify jits: same per-slot bodies,
        # but each slot's contiguous cache row is GATHERED through its page-
        # table row at jit entry and the written blocks are scattered back by
        # page id at exit — the dense int32 table is just another traced
        # argument, so the paged path also keeps one compile signature
        self._paged_decode = jax.jit(self._paged_decode_impl, donate_argnums=(1,))
        self._paged_verify = jax.jit(self._paged_verify_impl, donate_argnums=(1,))
        # physical cache rows per slot: the admission bound plus the
        # speculative verify slack (see ServeConfig.spec_slack)
        self.capacity = self.sc.max_len + self.sc.spec_slack

    def _prefill_impl(self, params, tokens, frontend):
        return prefill(params, tokens, self.cfg, frontend_embeds=frontend)

    def _decode_impl(self, params, cache, tok, pos):
        return decode_step(params, cache, tok, pos, self.cfg)

    def _cross_cache_impl(self, params, frontend):
        return encoder_cross_cache(params, self.cfg, frontend)

    def _frontend_stub(self, batch: int):
        cfg = self.cfg
        if cfg.frontend == "vision":
            return jnp.zeros((batch, cfg.frontend_seq, cfg.d_model), cfg.dtype)
        if cfg.frontend == "audio":
            return jnp.zeros((batch, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        return None

    def generate(self, prompts: np.ndarray, new_tokens: int) -> np.ndarray:
        """prompts: (B, S0) int32 → (B, new_tokens) greedy continuations.

        The family-appropriate cache layout comes from prefill itself; the
        fixed-capacity cache from cache_defs is used by decode-only flows.
        """
        b, s0 = prompts.shape
        assert b <= self.sc.max_batch and s0 + new_tokens <= self.sc.max_len
        fe = self._frontend_stub(b)
        logits, cache = self._prefill(self.params, jnp.asarray(prompts), fe)
        cache = grow_cache(self.cfg, cache, self.capacity)
        out = np.zeros((b, new_tokens), np.int32)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for i in range(new_tokens):
            out[:, i] = np.asarray(tok[:, 0])
            logits, cache = self._decode(self.params, cache, tok, jnp.int32(s0 + i))
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return out

    # -- continuous-batching execution path ---------------------------------
    def make_pool(self) -> SlotPool:
        if self.sc.paged:
            return PagedSlotPool(
                self.cfg, max_batch=self.sc.max_batch,
                max_len=self.sc.max_len, page_size=self.sc.page_size,
                slack=self.sc.spec_slack, num_pages=self.sc.num_pages,
                share_prefix=self.sc.share_prefix, kv_quant=self.sc.kv_quant)
        assert self.sc.kv_quant is None, "kv_quant requires paged=True"
        return SlotPool(self.cfg, max_batch=self.sc.max_batch,
                        max_len=self.sc.max_len, slack=self.sc.spec_slack)

    def prefill_into_slot(self, pool: SlotPool, slot: int, prompt: np.ndarray,
                          *, rid: int, budget: int) -> int:
        """Prefill one request (batch 1) and admit it into ``slot``.

        Returns the request's first emitted token (greedy argmax of the
        prefill logits). The jitted prefill retraces per distinct prompt
        length — arrival generators keep prompt lengths in a small bucket
        set for exactly that reason.
        """
        prompt = np.asarray(prompt, np.int32)
        (s0,) = prompt.shape
        if s0 + budget > self.sc.max_len:
            raise ValueError(f"prompt {s0} + budget {budget} exceeds "
                             f"max_len {self.sc.max_len}")
        with TraceAnnotation("engine.prefill.dispatch"):
            logits, cache = self._prefill(self.params, jnp.asarray(prompt)[None],
                                          self._frontend_stub(1))
            if not isinstance(pool, PagedSlotPool):
                cache = grow_cache(self.cfg, cache, self.capacity)
        with TraceAnnotation("engine.prefill.readback"):
            first = int(jnp.argmax(logits[0, : self.cfg.vocab_size]))
        with TraceAnnotation("engine.land", rows=1):
            pool.admit(slot, cache, rid=rid, pos=s0, budget=budget,
                       first_tok=first, prompt=prompt)
        return first

    def masked_decode_step(self, pool: SlotPool) -> tuple[np.ndarray, np.ndarray]:
        """One decode step over the whole pool. Returns

          next:   (max_batch,) int32 — next greedy token per slot; entries
                  for inactive slots are garbage
          finite: (max_batch,) bool — the per-tick FINITENESS GUARD: False
                  where the slot's logits contain NaN/Inf (poisoned cache, a
                  kernel overflow). The token for such a slot is garbage and
                  must NOT be committed — the scheduler quarantines the slot
                  and re-prefills the request from its committed tokens.

        The guard rides inside the decode jit (one ``isfinite`` reduction
        over the vocab row per slot — noise next to the matmuls), so robust
        serving costs no extra device round-trip. Slots whose chunked
        prefill is still in flight (``admitting``) are masked out along with
        free slots: their cache rows are dead until ``activate`` lands the
        prefilled state. Host-side slot bookkeeping (pos/emitted
        advancement, retirement) is the scheduler's job; this only advances
        the device state.

        Profiler spans: ``engine.decode.prepare`` (page-table writes and the
        host arrays), ``engine.decode.dispatch`` (uploads and the launch)
        and ``engine.decode.readback`` (waiting for the device, the copies
        and the guard's check).
        """
        paged = isinstance(pool, PagedSlotPool)
        with TraceAnnotation("engine.decode.prepare"):
            if paged:
                # every decoding slot writes exactly position pos this tick:
                # allocate/COW its block up-front so the write never lands
                # in a shared or unmapped page
                for s in pool.decoding_slots():
                    p = pool.slots[s].pos
                    pool.ensure_writable(s, p, p + 1)
            host = (pool.tok, pool.positions(), pool.decode_mask())
            if paged:
                host += (pool.table,)
        with TraceAnnotation("engine.decode.dispatch"):
            step = self._paged_decode if paged else self._masked_decode
            (nxt, fin), pool.cache = step(self.params, pool.cache,
                                          *map(jnp.asarray, host))
        with TraceAnnotation("engine.decode.readback"):
            nxt, fin = np.asarray(nxt), np.asarray(fin)
            if paged and not bool(fin[pool.decode_mask()].all()):
                # a non-finite slot may have scattered NaN into the scratch
                # page (which every unmapped block gathers) — scrub before
                # the next tick's gather
                pool.scrub_scratch()
        return nxt, fin

    def _masked_decode_impl(self, params, cache, tok, pos, active):
        """vmapped per-slot decode: every slot steps at its OWN position.

        Inactive slots are clamped to position 0 — their writes land in dead
        cache rows that the next admit overwrites wholesale. vmap over the
        batch axis (axis 1 on every cache leaf) reuses the per-family
        ``decode_step`` bodies unchanged, so all ten architecture families
        get the masked path for free.
        """
        cfg = self.cfg
        pos = jnp.where(active, pos, 0)

        def one(cache_b, tok_b, pos_b):
            c1 = jax.tree.map(lambda t: jnp.expand_dims(t, 1), cache_b)
            logits, c1 = decode_step(params, c1, tok_b[None, None], pos_b, cfg)
            nxt, fin = _greedy(logits[0, : cfg.vocab_size])
            return (nxt, fin), jax.tree.map(lambda t: jnp.squeeze(t, 1), c1)

        return jax.vmap(one, in_axes=(1, 0, 0), out_axes=((0, 0), 1))(
            cache, tok, pos)

    def _paged_rows(self, cache, table_row, n_blocks):
        """One slot's ``PagedRows`` view of each paged cache leaf (the model
        gathers them a layer at a time)."""
        pkeys = paged_keys(self.cfg)
        quant = self.sc.kv_quant
        views = {}
        for k in pkeys:
            pages = cache[k]
            views[k] = PagedRows(
                pages, cache[f"{k}_scale"] if quant else None,
                jnp.broadcast_to(table_row, (pages.shape[0], *table_row.shape)),
                n_blocks=n_blocks, page=self.sc.page_size)
        return views

    def _paged_decode_impl(self, params, cache, tok, pos, active, table):
        """Paged twin of ``_masked_decode_impl``: each slot's cache row is
        gathered through its table row, one layer at a time inside the
        model's layer loop (``PagedRows``), the identical per-slot decode
        body runs, and the written block is scattered back by page id.

        Rows gathered from unmapped blocks (scratch) are garbage, but every
        position > pos is masked to NEG_INF before the softmax, so they are
        exactly inert — the paged step is token-for-token the contiguous
        step in f32. Inactive slots' writes are redirected to page 0.

        Under ``kv_quant`` the gather also dequantizes (payload pages times
        their "{key}_scale" pages) and the written block is re-quantized
        before the scatter; re-quantizing the block's untouched rows is
        idempotent, so only the freshly written position changes."""
        cfg = self.cfg
        pkeys = paged_keys(cfg)
        quant = self.sc.kv_quant
        skeys = tuple(f"{k}_scale" for k in pkeys) if quant else ()
        paged = {k: cache[k] for k in (*pkeys, *skeys)}
        rest = {k: v for k, v in cache.items() if k not in paged}
        pos = jnp.where(active, pos, 0)

        def one(rest_b, tok_b, pos_b, tab_b, act_b):
            c1 = {**jax.tree.map(lambda t: jnp.expand_dims(t, 1), rest_b),
                  **self._paged_rows(paged, tab_b, 1)}
            logits, c1 = decode_step(params, c1, tok_b[None, None], pos_b, cfg)
            nxt, fin = _greedy(logits[0, : cfg.vocab_size])
            with jax.named_scope("kv_pages"):
                written = {}
                for k in pkeys:
                    w = c1[k][:, 0]  # (lead, page, *tail): the one written block
                    if quant:
                        written[k], written[f"{k}_scale"] = quantize_kv(w)
                    else:
                        written[k] = w
                pid = jnp.where(act_b, jnp.take(tab_b, pos_b // self.sc.page_size), 0)
            return (nxt, fin, written, pid), {
                k: jnp.squeeze(c1[k], 1) for k in rest}

        (nxt, fin, written, pids), rest1 = jax.vmap(
            one, in_axes=(1, 0, 0, 0, 0), out_axes=((0, 0, 0, 0), 1))(
            rest, tok, pos, table, active)
        with jax.named_scope("kv_pages"):
            for k in paged:
                paged[k] = paged[k].at[:, pids].set(jnp.moveaxis(written[k], 0, 1))
        return (nxt, fin), {**rest1, **paged}

    # -- fault injection ------------------------------------------------------
    def poison_slot(self, pool: SlotPool, slot: int) -> None:
        """Overwrite ``slot``'s cache rows with NaN (injected fault: HBM
        corruption / kernel overflow). The next masked decode or verify tick
        produces non-finite logits for the slot, which the in-jit finiteness
        guard reports — the recovery path (quarantine + re-prefill) is the
        scheduler's job."""
        assert pool.cache is not None, "cannot poison a virtual pool"
        if isinstance(pool, PagedSlotPool):
            # COW-aware: force-exclusive then corrupt, so shared prefix pages
            # and the registry keep clean bytes (see PagedSlotPool.poison)
            pool.poison(slot)
            return
        pool.cache = self._poison(pool.cache, jnp.int32(slot))

    @staticmethod
    def _poison_impl(cache, slot):
        def one(leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                return leaf
            row = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.full_like(row, jnp.nan), slot, axis=1)

        return jax.tree.map(one, cache)

    def resume_into_slot(self, pool: SlotPool, slot: int, context: np.ndarray, *,
                         rid: int, budget: int, emitted: int,
                         next_tok: int) -> None:
        """Re-admit a quarantined request: re-prefill its COMMITTED context
        (prompt + all-but-the-last emitted token) into a fresh cache and land
        it in ``slot``, wholesale overwriting the poisoned rows.

        ``next_tok`` is the request's last committed token — the slot's next
        decode input, exactly as it was before the fault — so the greedy
        continuation is token-for-token what the fault-free run emits (the
        re-prefilled cache differs from the incrementally-built one only by
        float reassociation, the same caveat as chunked prefill). Retraces
        the prefill jit per distinct context length, like any admission.
        """
        context = np.asarray(context, np.int32)
        (s,) = context.shape
        if s + (budget - emitted) + 1 > self.sc.max_len:
            raise ValueError(f"resume context {s} + remaining budget "
                             f"{budget - emitted} exceeds max_len {self.sc.max_len}")
        with TraceAnnotation("engine.prefill.dispatch"):
            _, cache = self._prefill(self.params, jnp.asarray(context)[None],
                                     self._frontend_stub(1))
            if not isinstance(pool, PagedSlotPool):
                cache = grow_cache(self.cfg, cache, self.capacity)
        # prompt=None: a resume context includes emitted tokens, which must
        # never enter the shared-prefix registry
        with TraceAnnotation("engine.land", rows=1):
            pool.admit(slot, cache, rid=rid, pos=s, budget=budget,
                       first_tok=next_tok, emitted=emitted)

    # -- speculative multi-token decode --------------------------------------
    def masked_speculative_step(
        self, pool: SlotPool, drafts: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One speculative verify tick over the whole pool.

        ``drafts``: (max_batch, K) int32 candidate tokens per slot (garbage
        for non-decoding slots). A single jitted pass scores every slot's
        K+1 window (its next decode input + the K drafts) at the slot's own
        position via ``decode_verify`` and commits each slot's cache to its
        greedily-accepted prefix in-device. Returns

          tokens:   (max_batch, K+1) int32 — the greedy token after each
                    window position; entries for non-decoding slots garbage
          accepted: (max_batch,) int32 — accepted drafts a ∈ [0, K]; the
                    tick's emission for a slot is tokens[:a+1] (a accepted
                    drafts + the bonus token), and tokens[a] is the slot's
                    next decode input
          finite:   (max_batch,) bool — per-tick finiteness guard over the
                    slot's whole verify window (see ``masked_decode_step``):
                    False means nothing from this tick may be committed for
                    the slot — quarantine and re-prefill it

        Host-side slot bookkeeping (``SlotPool.advance``, retirement, budget
        truncation) stays the scheduler's job, exactly like masked decode.
        Profiler spans as for decode, under ``engine.verify.*``.
        """
        drafts = np.asarray(drafts, np.int32)
        k = drafts.shape[1]
        assert drafts.shape == (pool.max_batch, k) and k >= 1
        paged = isinstance(pool, PagedSlotPool)
        with TraceAnnotation("engine.verify.prepare"):
            if paged:
                # no spec_slack spare rows needed: the verify window's tail
                # blocks are allocated on demand — just check the table can
                # hold the worst-case window (start as late as max_len-2)
                assert (pool.max_len - 2 + k) // pool.page + 1 <= pool.max_blocks, (
                    f"verify window of {k + 1} tokens exceeds the page table "
                    f"({pool.max_blocks} blocks of {pool.page}) — raise "
                    f"spec_slack or page_size")
                for s in pool.decoding_slots():
                    p = pool.slots[s].pos
                    pool.ensure_writable(s, p, p + k + 1)
            else:
                assert pool.slack >= k, (
                    f"speculative verify of {k} drafts needs spec_slack >= {k} "
                    f"spare cache rows (have {pool.slack}) — see "
                    f"ServeConfig.spec_slack")
            host = (pool.tok, drafts, pool.positions(), pool.decode_mask())
            if paged:
                host += (pool.table,)
        with TraceAnnotation("engine.verify.dispatch"):
            step = self._paged_verify if paged else self._masked_verify
            (toks, acc, fin), pool.cache = step(self.params, pool.cache,
                                                *map(jnp.asarray, host))
        with TraceAnnotation("engine.verify.readback"):
            toks, acc, fin = np.asarray(toks), np.asarray(acc), np.asarray(fin)
            if paged and not bool(fin[pool.decode_mask()].all()):
                pool.scrub_scratch()
        return toks, acc, fin

    def _masked_verify_impl(self, params, cache, tok, drafts, pos, active):
        """vmapped per-slot verify: every slot scores its own K+1 window.

        Greedy acceptance is exact prefix match against the verify argmaxes,
        so accepted output is token-for-token what plain masked decode would
        emit; the cache commit (``commit_verify``) happens inside the same
        jit, before the donated cache is returned."""
        cfg = self.cfg
        pos = jnp.where(active, pos, 0)
        tokens = jnp.concatenate([tok[:, None], drafts], axis=1)  # (B, K+1)

        def one(cache_b, toks_b, pos_b):
            c1 = jax.tree.map(lambda t: jnp.expand_dims(t, 1), cache_b)
            logits, c1 = decode_verify(params, c1, toks_b[None, :], pos_b, cfg)
            g, fin = _greedy(logits[0, :, : cfg.vocab_size])
            # accept the longest prefix of drafts matching the greedy chain
            ok = jnp.cumprod((toks_b[1:] == g[:-1]).astype(jnp.int32))
            a = jnp.sum(ok).astype(jnp.int32)
            c1 = commit_verify(c1, a, cfg)
            return (g, a, fin), jax.tree.map(lambda t: jnp.squeeze(t, 1), c1)

        return jax.vmap(one, in_axes=(1, 0, 0), out_axes=((0, 0, 0), 1))(
            cache, tokens, pos)

    def _paged_verify_impl(self, params, cache, tok, drafts, pos, active, table):
        """Paged twin of ``_masked_verify_impl``: gather (a layer at a time),
        verify, scatter.

        A K+1 window can straddle up to ``verify_block_span`` blocks; all of
        them are extracted, and blocks past the slot's last written block —
        plus everything from inactive slots — are redirected to scratch page
        0, so rejected-draft tails overwrite only pages the slot owns (the
        contiguous pool needs spec_slack spare rows for exactly this).

        ``kv_quant`` follows the decode twin: dequantize-in-gather,
        re-quantize the extracted window blocks (payload + scale) before the
        scatter."""
        cfg, page = self.cfg, self.sc.page_size
        pkeys = paged_keys(cfg)
        quant = self.sc.kv_quant
        skeys = tuple(f"{k}_scale" for k in pkeys) if quant else ()
        paged = {k: cache[k] for k in (*pkeys, *skeys)}
        rest = {k: v for k, v in cache.items() if k not in paged}
        pos = jnp.where(active, pos, 0)
        tokens = jnp.concatenate([tok[:, None], drafts], axis=1)  # (B, K+1)
        w = tokens.shape[1]
        nw = verify_block_span(w, page)
        mb = table.shape[1]

        def one(rest_b, toks_b, pos_b, tab_b, act_b):
            c1 = {**jax.tree.map(lambda t: jnp.expand_dims(t, 1), rest_b),
                  **self._paged_rows(paged, tab_b, nw)}
            logits, c1 = decode_verify(params, c1, toks_b[None, :], pos_b, cfg)
            g, fin = _greedy(logits[0, :, : cfg.vocab_size])
            ok = jnp.cumprod((toks_b[1:] == g[:-1]).astype(jnp.int32))
            a = jnp.sum(ok).astype(jnp.int32)
            c1 = commit_verify(c1, a, cfg)
            first_blk = pos_b // page
            last_blk = (pos_b + w - 1) // page
            written = {}
            for k in pkeys:
                wb = c1[k]  # (lead, nw, page, *tail): the window's blocks
                if quant:
                    written[k], written[f"{k}_scale"] = quantize_kv(wb)
                else:
                    written[k] = wb
            blks = first_blk + jnp.arange(nw)
            valid = act_b & (blks <= last_blk)
            pids = jnp.where(valid,
                             jnp.take(tab_b, jnp.minimum(blks, mb - 1)), 0)
            return (g, a, fin, written, pids), {
                k: jnp.squeeze(c1[k], 1) for k in rest}

        (g, a, fin, written, pids), rest1 = jax.vmap(
            one, in_axes=(1, 0, 0, 0, 0), out_axes=((0, 0, 0, 0, 0), 1))(
            rest, tokens, pos, table, active)
        flat = pids.reshape(-1)  # (B * nw,) — duplicates only ever hit scratch
        with jax.named_scope("kv_pages"):
            for k in paged:
                wr = jnp.moveaxis(written[k], 1, 0)  # (lead, B, nw, page, *tail)
                wr = wr.reshape(wr.shape[0], -1, page, *wr.shape[4:])
                paged[k] = paged[k].at[:, flat].set(wr)
        return (g, a, fin), {**rest1, **paged}

    # -- chunked prefill ------------------------------------------------------
    def begin_chunked_prefill(self, pool: SlotPool, slots: list[int],
                              prompts: np.ndarray, *, rids: list[int],
                              budgets: list[int]) -> "ChunkedPrefillState":
        """Reserve ``slots`` for a same-length admission group and build the
        group's fresh full-capacity cache (batch = group size).

        The group prefills OUTSIDE the pool — the pool's masked decode keeps
        serving the decoding slots between chunks — and ``finish_chunked_
        prefill`` lands each row into its reserved slot at the end."""
        prompts = np.asarray(prompts, np.int32)
        k, s0 = prompts.shape
        assert len(slots) == len(rids) == len(budgets) == k
        # validated before any reservation below; the scheduler additionally
        # validates every request up-front in run(), so its own pre-reserved
        # slots can never be stranded by this raise
        for rid, budget in zip(rids, budgets):
            if s0 + budget > self.sc.max_len:
                raise ValueError(f"request {rid}: prompt {s0} + budget {budget} "
                                 f"exceeds max_len {self.sc.max_len}")
        paged = isinstance(pool, PagedSlotPool)
        with TraceAnnotation("engine.begin", rows=k):
            # shared-prefix hit: every member maps the common block-aligned
            # prefix read-only and chunk-prefills only its delta. The group is
            # formed over requests with the SAME match length, so the min is a
            # no-op for scheduler-formed groups and a guard for direct callers.
            shared_len, pins = 0, None
            if paged and pool.share_prefix:
                shared_len = min(pool.match_prefix_len(p) for p in prompts)
                if shared_len:
                    pins = [pool.pin_prefix(p, shared_len) for p in prompts]
            for slot, rid, budget in zip(slots, rids, budgets):
                if not pool.admitting[slot]:  # the scheduler may have reserved already
                    pool.reserve(slot, rid=rid, s0=s0, budget=budget,
                                 shared_len=shared_len)
            group_len = pool.virtual_len if paged else self.capacity
            cache = init_params(
                cache_defs(self.cfg, batch=k, max_len=group_len),
                jax.random.PRNGKey(0),
            )
            if self.cfg.family == "audio":
                ck, cv = self._cross_cache(self.params, self._frontend_stub(k))
                cache = dict(cache, cross_k=ck.astype(cache["cross_k"].dtype),
                             cross_v=cv.astype(cache["cross_v"].dtype))
            if pins is not None:
                # land the resident prefix pages in the group rows; chunking
                # starts at shared_len (pos below) and computes only the delta
                cache = pool.fill_group_prefix(cache, pins)
            return ChunkedPrefillState(prompts=prompts, rids=list(rids),
                                       budgets=list(budgets), slots=list(slots),
                                       cache=cache,
                                       frontend=self._chunk_frontend(k, group_len),
                                       pos=shared_len, shared_len=shared_len,
                                       pins=pins)

    def _chunk_frontend(self, batch: int, seq_len: int | None = None):
        """VLM frontend stub padded to cache capacity on the seq axis, so
        every chunk can slice it at its offset (built once per group)."""
        if self.cfg.family != "vlm":
            return None
        return jnp.zeros((batch, seq_len or self.capacity, self.cfg.d_model),
                         self.cfg.dtype)

    def chunk_step_probe(self, batch: int, chunk_tokens: int):
        """Zero-arg callable running ONE representative chunked-prefill step
        (zeros chunk at pos 0 against a fresh full-capacity cache) for
        calibration timing. Uses a non-donating twin of the chunk jit so the
        probe cache can be reused across timing repeats; the step's cost is
        position-independent (attention always spans the whole cache
        capacity, dead rows are masked, not skipped). It returns only the
        logits, so no second group-sized cache is ever materialized."""
        if self._chunk_probe_fn is None:
            self._chunk_probe_fn = jax.jit(self._chunk_probe_impl)
        cache = init_params(
            cache_defs(self.cfg, batch=batch, max_len=self.capacity),
            jax.random.PRNGKey(0),
        )
        toks = jnp.zeros((batch, chunk_tokens), jnp.int32)
        fe = self._chunk_frontend(batch)
        return lambda: self._chunk_probe_fn(self.params, cache, toks,
                                            jnp.int32(0), fe)

    def _chunk_probe_impl(self, params, cache, tokens, pos, frontend):
        return prefill_chunk(params, cache, tokens, pos, self.cfg,
                             frontend_embeds=frontend)[0]

    def chunked_prefill_step(self, st: "ChunkedPrefillState",
                             chunk_tokens: int) -> int:
        """Advance the admitting group by one chunk of ≤ ``chunk_tokens``
        prompt tokens. Returns the number of tokens processed; after the
        final chunk ``st.first`` holds each request's first emitted token."""
        assert not st.done
        k = len(st.rids)
        t = min(chunk_tokens, st.s0 - st.pos)
        with TraceAnnotation("engine.chunk.dispatch", rows=k):
            toks = jnp.asarray(st.prompts[:, st.pos : st.pos + t])
            logits, st.cache = self._chunk(self.params, st.cache, toks,
                                           jnp.int32(st.pos), st.frontend)
        st.pos += t
        if st.done:
            with TraceAnnotation("engine.chunk.readback", rows=k):
                st.first = np.asarray(
                    jnp.argmax(logits[:, : self.cfg.vocab_size], axis=-1), np.int32
                )
        return t

    def finish_chunked_prefill(self, pool: SlotPool,
                               st: "ChunkedPrefillState") -> np.ndarray:
        """Land each prefilled row into its reserved slot (admitting →
        decoding) and return the group's first emitted tokens."""
        assert st.done and st.first is not None
        with TraceAnnotation("engine.land", rows=len(st.rids)):
            if isinstance(pool, PagedSlotPool):
                # atomic commit: check the group's TOTAL delta up front (typed
                # PageExhausted, evicting registry pages as needed) so exhaustion
                # never strands a half-activated group — the scheduler catches
                # the signal and cancels the whole group cleanly
                shared = len(st.pins[0]) if st.pins else 0
                pool.require_pages(
                    len(st.slots) * (pool._blocks_for(st.s0) - shared))
                for j, slot in enumerate(st.slots):
                    pool.activate_from_group(
                        slot, st.cache, j, rid=st.rids[j], pos=st.s0,
                        budget=st.budgets[j], first_tok=int(st.first[j]),
                        prompt=st.prompts[j],
                        pins=st.pins[j] if st.pins else ())
                st.pins = None  # refs transferred into the slots' tables
                return st.first
            for j, slot in enumerate(st.slots):
                row = jax.tree.map(lambda t: t[:, j : j + 1], st.cache)
                pool.activate(slot, row, rid=st.rids[j], pos=st.s0,
                              budget=st.budgets[j], first_tok=int(st.first[j]))
            return st.first

    def cancel_chunked_prefill(self, pool: SlotPool,
                               st: "ChunkedPrefillState") -> None:
        """Abort an in-flight admitting group (the scheduler's degrade path
        after repeated chunk faults): release the group's pinned prefix
        pages and retire its reserved slots so nothing leaks."""
        if st.pins:
            for pins in st.pins:
                pool.unpin_prefix(pins)
            st.pins = None
        for slot in st.slots:
            pool.retire(slot)


@dataclasses.dataclass
class ChunkedPrefillState:
    """One in-flight same-length admission group (chunked prefill)."""

    prompts: np.ndarray           # (k, s0) int32 — identical prompt lengths
    rids: list[int]
    budgets: list[int]
    slots: list[int]              # reserved pool slots, one per request
    cache: Any = None             # (L, k, max_len, ...) device cache; None = virtual
    frontend: Any = None          # capacity-padded VLM frontend stub (or None)
    pos: int = 0                  # prompt tokens prefilled so far
    first: np.ndarray | None = None  # first emitted token per request (when done)
    shared_len: int = 0           # resident shared-prefix tokens (paged + COW)
    pins: list | None = None      # pinned prefix page ids per row (until activate)

    @property
    def s0(self) -> int:
        return self.prompts.shape[1]

    @property
    def done(self) -> bool:
        return self.pos >= self.s0


# ---------------------------------------------------------------------------
# Workload-aware duty-cycle layer
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServerStats:
    items: int = 0
    energy_j: float = 0.0
    busy_s: float = 0.0
    idle_s: float = 0.0
    reloads: int = 0
    missed: int = 0

    @property
    def items_per_joule(self) -> float:
        return self.items / self.energy_j if self.energy_j else 0.0


class WorkloadAwareServer:
    """Applies RQ2 strategies to a real engine over a request trace.

    Energy is modeled through the same ``AccelProfile``/``simulate`` path
    that reproduces the paper's C3/C4 (FPGA constants) — here with TPU
    constants and the engine's *measured* per-batch latency.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        *,
        strategy: str = "adaptive",
        tau: float | None = None,
        chip: TPUChip = DEFAULT_CHIP,
        chips: int = 1,
        weight_bytes: float | None = None,
    ):
        self.engine = engine
        self.strategy = strategy
        self.chip = chip
        self.chips = chips
        self.t_reload, self.e_reload = tpu_reload_costs(
            engine.cfg, chip, chips=chips, weight_bytes=weight_bytes
        )
        self.tau = tau
        self._measured_t: float | None = None

    def profile(self, t_inf_s: float) -> AccelProfile:
        return AccelProfile(
            t_inf_s=t_inf_s,
            p_active_w=self.chip.p_peak_w * self.chips,
            p_idle_w=self.chip.p_idle_w * self.chips,
            e_cfg_j=self.e_reload,
            t_cfg_s=self.t_reload,
        )

    def measure_latency(self, batch: int = 4, prompt_len: int = 16,
                        new_tokens: int = 8) -> float:
        prompts = np.zeros((batch, prompt_len), np.int32)
        self.engine.generate(prompts, 2)  # warm the jit caches
        t0 = time.perf_counter()
        self.engine.generate(prompts, new_tokens)
        self._measured_t = time.perf_counter() - t0
        return self._measured_t

    def run_trace(
        self,
        gaps: np.ndarray,
        *,
        batch: int = 4,
        prompt_len: int = 16,
        new_tokens: int = 8,
        learn: bool = False,
        execute_every: int = 0,
        t_inf: float | None = None,
    ) -> ServerStats:
        """Serve one request batch per trace entry; ``gaps[i]`` is the idle
        time after batch i. ``execute_every=k`` really runs the engine every
        k-th batch (0 = once up front) — the rest reuse the measured latency
        (keeps CPU test time sane while the energy ledger stays faithful).
        ``t_inf`` overrides the measured batch latency (no engine run)."""
        if t_inf is None:
            t_inf = self._measured_t or self.measure_latency(batch, prompt_len, new_tokens)
        prof = self.profile(t_inf)
        tau = self.tau
        if self.strategy == "adaptive" and tau is None:
            tau = learn_tau(gaps, prof) if learn else break_even_tau(prof)

        g = np.asarray(gaps, float).ravel()
        if execute_every:
            prompts = np.zeros((batch, prompt_len), np.int32)
            for _ in range(-(-g.size // execute_every)):
                self.engine.generate(prompts, new_tokens)

        # the whole energy ledger in ONE vectorized simulate call: simulate
        # already charges the single initial configuration plus per-gap energy
        res = simulate(g, self.strategy, prof, tau=tau)
        if self.strategy == "on_off":
            reloads = g.size
        elif self.strategy == "adaptive":
            reloads = int(np.count_nonzero(g > (tau or 0.0)))
        else:
            reloads = 0
        return ServerStats(
            items=res.items,
            energy_j=res.energy_j,
            busy_s=res.items * t_inf,
            idle_s=float(g.sum()),
            reloads=reloads,
            missed=res.missed_deadlines,
        )

    def compare_strategies(self, gaps: np.ndarray, *, t_inf: float | None = None,
                           **kw) -> dict[str, ServerStats]:
        """Run every strategy over ``gaps`` at one shared measured latency.

        The latency is passed to each per-strategy server explicitly —
        no private-attribute side channel, and ``self`` is left untouched
        when ``t_inf`` is supplied."""
        if t_inf is None:
            t_inf = self._measured_t or self.measure_latency()
        out = {}
        for strat in ("on_off", "idle_waiting", "slow_down", "adaptive"):
            srv = WorkloadAwareServer(
                self.engine, strategy=strat, chip=self.chip, chips=self.chips
            )
            out[strat] = srv.run_trace(gaps, t_inf=t_inf, **kw)
        return out
