"""Paged KV-cache subsystem: physical pages + page table + copy-on-write
shared-prefix reuse (the serving-memory analogue of the paper's
application-specific provisioning — stop paying worst-case HBM per slot).

Logical-block ↔ physical-page mapping
-------------------------------------

The contiguous ``SlotPool`` gives every slot a full ``max_len + slack``
rectangle of cache rows. Here, each family's SEQUENCE-dim cache leaves
(``kv_cache.paged_keys``) are instead allocated as a shared array of
physical pages, ``(lead, num_pages, page_size, ...)``, and each slot's
sequence positions are split into logical blocks of ``page_size`` rows:

  position p  →  logical block p // page_size, in-page row p % page_size
  physical row of leaf = pages[:, table[slot, p // page_size], p % page_size]

``table`` is a dense int32 ``(max_batch, max_blocks)`` array passed INTO the
decode/verify jits, so the paged paths keep ONE compile signature. The
decode step's attention reads a slot's K/V pages in place through its
table row and the new rows are written into their pages afterwards; other
paged leaves and the verify window gather a virtual contiguous row through
the table, one layer at a time, and scatter the written blocks back by page
id (``models.paged.PagedRows``). Page index 0 is a reserved SCRATCH
page: unmapped table entries point at it, so reads of never-written
blocks see garbage that the engine's positional masks keep inert, and
writes from inactive slots or invalid verify-window blocks are redirected
into it. Unpaged per-slot state (SSM conv/state — O(1) in sequence — and
audio cross K/V) keeps the contiguous batch-row layout.

Allocation, refcounts, COW rules
--------------------------------

``PagePool`` is the allocator: a FIFO free list plus a per-page refcount.
Rules the property tests (``tests/test_pages.py``) pin down:

  * a page is FREE iff its refcount is 0; alloc sets it to 1, every extra
    mapping (prefix share, fork, registry pin) increfs, every unmapping
    decrefs; a page returns to the free list exactly when it hits 0.
  * a slot may only WRITE a block whose page it owns EXCLUSIVELY
    (refcount 1). ``ensure_writable`` runs before every decode/verify
    tick's write span: unmapped blocks get fresh pages; shared blocks
    (refcount > 1) are COPIED to a fresh page first (copy-on-write) and
    the slot's table entry is repointed — the shared original is never
    written in place.
  * the prefix REGISTRY holds one pinned ref per registered page, so a
    registered page always has refcount >= 2 while any slot maps it, and
    keeps its clean bytes at refcount 1 after the owner retires —
    registry-only pages are the eviction pool (LRU) when the free list
    runs dry.

Prefix sharing: admission hashes the prompt's block-aligned prefix (a
blake2b chain over full blocks, so a prefix digest commits to every token
before it) and registers each full prompt block's page. A later admission
whose prompt matches a registered chain maps those pages read-only
(incref), and its chunked prefill starts at the shared length — only the
delta is computed. At most ``s0 - 1`` tokens are ever shared: the first
emitted token comes from the prefill logits at the last prompt position,
so at least one prompt token is always chunk-prefilled by the consumer.
Sharing is causal-correct because a K/V row at position p depends only on
tokens <= p; it is disabled for every family whose cache holds recurrent
state (``kv_cache.has_recurrent_state``), which is not positional.

Speculative verify windows need no ``spec_slack`` spare rows here: the
table always has at least one spare block past ``max_len``, and tail
blocks are allocated on demand by ``ensure_writable`` — rejected-draft
writes land in pages the slot owns, never in a neighbour's rows.

Memory pressure: typed exhaustion, watermark, preemption
--------------------------------------------------------

``can_admit`` bounds the worst case of co-resident *reservations*, but
mid-tick on-demand allocation can still outrun the pool: speculative
verify windows extend past a slot's reserved budget (rejected-draft tail
blocks), force-exclusive COW (``poison``) is outside every estimate, the
LRU-evictable registry count can go stale between probe and allocation,
and the page-pressure fault (``pin_free_pages``) transiently shrinks the
free list. Exhaustion is therefore a SCHEDULING EVENT, not a crash:

  * allocation failure is TYPED — ``_alloc_page`` returns a
    :class:`PageExhausted` signal instead of raising ``RuntimeError``;
    every lifecycle caller either unwinds cleanly (``admit`` /
    ``swap_in`` release partial allocations and un-claim the slot) or
    flushes its committed device work first (``ensure_writable``), then
    raises the typed signal for the scheduler to catch.
  * the WATERMARK contract: before a decode/verify tick the scheduler
    sums ``blocks_needed(slot, pos, pos + span)`` over the decoding
    slots (span = 1 or the K+1 verify window — unmapped blocks plus
    shared blocks whose write needs a COW page) and compares against
    ``free + evictable - reserved_admitting()``. Demand past the mark is
    relieved by PREEMPTING victims *before* the tick runs, so
    ``ensure_writable`` almost never sees an empty pool; when it still
    does (stale estimate), the scheduler catches ``PageExhausted``,
    preempts, and retries the tick.
  * PREEMPTION restores a victim by one of two exact paths: ``swap_out``
    copies the victim's mapped pages (positions [0, pos)) plus its
    unpaged per-slot rows to host buffers and releases the slot;
    ``swap_in`` re-maps the bytes into fresh pages — bit-identical
    state, so the continuation is trivially token-for-token. The
    alternative (cheaper for short contexts) is recompute: retire the
    slot and re-prefill prompt + committed tokens through the engine's
    ``resume_into_slot``, the same path quarantine-retry uses. Verify
    tail blocks past ``pos`` are dropped by either path — they only ever
    held rejected drafts — so a preempt/restore cycle shrinks a slot's
    footprint back inside its reservation.
"""
from __future__ import annotations

import collections
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.params import init_params
from repro.serving.kv_cache import (cache_defs, dequantize_kv, has_recurrent_state,
                                    page_defs, paged_keys, quantize_kv)
from repro.serving.slots import SlotInfo, SlotPool

SCRATCH = 0  # reserved physical page: unmapped / redirected writes land here


class PageExhausted(Exception):
    """Typed allocation-failure signal: the page pool (free list plus
    LRU-evictable registry pages) cannot supply the requested pages.

    ``_alloc_page`` RETURNS an instance instead of raising, so lifecycle
    methods can unwind partial allocations first and then ``raise`` it for
    the scheduler, which treats exhaustion as a preemption event — never a
    crash."""

    def __init__(self, need: int = 1, free: int = 0):
        super().__init__(
            f"page pool exhausted: need {need} page(s), {free} free/evictable")
        self.need = need
        self.free = free


class PagePool:
    """Free list + per-page refcounts over ``num_pages`` physical pages.

    Page ``SCRATCH`` (index 0) is permanently pinned and never allocated.
    Pure host-side bookkeeping — device arrays live in ``PagedSlotPool``.
    """

    def __init__(self, num_pages: int):
        assert num_pages >= 2, "need at least one page beyond scratch"
        self.num_pages = num_pages
        self.refcount = np.zeros(num_pages, np.int64)
        self.refcount[SCRATCH] = 1  # pinned forever
        self._free = collections.deque(range(1, num_pages))

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self) -> int | None:
        """Pop a free page (refcount 0 → 1); None when the list is empty."""
        if not self._free:
            return None
        pid = self._free.popleft()
        assert self.refcount[pid] == 0, f"page {pid} on free list with refs"
        self.refcount[pid] = 1
        return pid

    def incref(self, pid: int) -> None:
        assert pid != SCRATCH and self.refcount[pid] >= 1, pid
        self.refcount[pid] += 1

    def decref(self, pid: int) -> bool:
        """Drop one reference; returns True when the page was freed."""
        assert pid != SCRATCH and self.refcount[pid] >= 1, pid
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            self._free.append(pid)
            return True
        return False


class PagedSlotPool(SlotPool):
    """Drop-in paged replacement for ``SlotPool`` (see module docstring).

    The device cache mixes paged leaves ``(lead, num_pages, page_size, ...)``
    with the unpaged per-slot leaves at their usual ``(lead, max_batch, ...)``
    layout; ``table`` maps logical blocks to page ids. The scheduler drives
    it through the same surface as the contiguous pool plus the
    memory-aware ``can_admit``.
    """

    def __init__(self, cfg: ArchConfig, *, max_batch: int, max_len: int,
                 page_size: int = 16, slack: int = 0,
                 num_pages: int | None = None, share_prefix: bool = False,
                 kv_quant: str | None = None):
        super().__init__(cfg, max_batch=max_batch, max_len=max_len,
                         virtual=True, slack=slack)
        self.page = int(page_size)
        assert self.page >= 1
        # verify-window headroom replaces spec_slack spare rows: at least one
        # spare block past max_len (more when slack asks), plus one block of
        # margin so a window starting at max_len-2 always fits the table
        headroom = max(slack, self.page)
        self.max_blocks = -(-(max_len + headroom) // self.page) + 1
        self.virtual_len = self.max_blocks * self.page
        self.capacity = self.virtual_len  # what the gathered jits attend over
        self._pkeys = paged_keys(cfg)
        # int8 page residency: payloads store int8, per-row f32 scales ride a
        # parallel "{key}_scale" paged leaf. Pure page-index operations (copy /
        # zero / swap / restore / scrub) treat payloads and scales uniformly
        # via _pleaves; only the quantize (admit/activate/engine scatter) and
        # dequantize (gather) sites know which is which.
        self.kv_quant = kv_quant if self._pkeys else None
        self._skeys = (tuple(f"{k}_scale" for k in self._pkeys)
                       if self.kv_quant else ())
        self._pleaves = self._pkeys + self._skeys
        # recurrent SSM state is not positional — prefix K/V reuse is
        # unsound; frontend families (vlm/audio) are excluded too, since the
        # registry digests prompt TOKENS only and early cache rows also
        # depend on per-request frontend embeddings
        self.share_prefix = (bool(share_prefix)
                             and not has_recurrent_state(cfg)
                             and cfg.frontend is None)
        if num_pages is None:
            # parity default: same worst case as the contiguous pool, plus
            # scratch — on-demand tail allocation can never fail at this size
            num_pages = max_batch * self.max_blocks + 1
        self.num_pages = int(num_pages)
        self.pages = PagePool(self.num_pages)
        self.table = np.zeros((max_batch, self.max_blocks), np.int32)
        defs = dict(page_defs(cfg, num_pages=self.num_pages,
                              page_size=self.page, kv_quant=self.kv_quant))
        for key, d in cache_defs(cfg, batch=max_batch, max_len=1).items():
            if key not in self._pkeys:
                defs[key] = d  # unpaged leaves are max_len-independent
        self.cache = init_params(defs, jax.random.PRNGKey(0))
        # prefix registry: block-digest chain -> page id (insertion order is
        # LRU order; hits move_to_end). Each entry holds one pinned ref.
        self._prefix: collections.OrderedDict[bytes, int] = collections.OrderedDict()
        # page-budget accounting: pages a slot still needs vs already owns
        self._resv = np.zeros(max_batch, np.int64)
        self._owned = np.zeros(max_batch, np.int64)
        # NaN hygiene: pages freed from a poisoned slot are scrubbed lazily
        # on reallocation; the slot's unpaged rows are zeroed at retire
        self._tainted: set[int] = set()
        self._slot_tainted: set[int] = set()
        self.cow_copies = 0
        self.shared_hit_pages = 0
        self.evictions = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.swapped_bytes = 0
        # page-pressure fault: transiently pinned-out free pages
        self._press_pins: list[int] = []
        self._admit_jit = jax.jit(self._admit_impl, donate_argnums=(0,))
        self._activate_jit = jax.jit(self._activate_impl, donate_argnums=(0,),
                                     static_argnames=("bs", "nb"))
        self._fill_prefix_jit = jax.jit(self._fill_prefix_impl,
                                        donate_argnums=(0,))
        self._copy_pages_jit = jax.jit(self._copy_pages_impl,
                                       donate_argnums=(0,))
        self._copy_row_jit = jax.jit(self._copy_row_impl, donate_argnums=(0,))
        self._zero_pages_jit = jax.jit(self._zero_pages_impl,
                                       donate_argnums=(0,))
        self._zero_row_jit = jax.jit(self._zero_row_impl, donate_argnums=(0,))
        self._nan_jit = jax.jit(self._nan_impl, donate_argnums=(0,))
        self._restore_jit = jax.jit(self._restore_impl, donate_argnums=(0,))

    # -- device-side primitives (pool-owned jits) ----------------------------
    def _admit_impl(self, cache, req_cache, slot, pids):
        """Land a batch-1 request cache: paged leaves are padded to whole
        blocks and scattered to ``pids`` (quantize-on-write under
        ``kv_quant``); unpaged leaves overwrite the slot row."""
        page, nb = self.page, pids.shape[0]
        out = {}
        for key, leaf in cache.items():
            if key in self._skeys:
                continue  # written alongside its payload below
            if key in self._pkeys:
                r = req_cache[key][:, 0]  # (lead, s, *tail)
                widths = [(0, 0), (0, nb * page - r.shape[1])]
                widths += [(0, 0)] * (r.ndim - 2)
                r = jnp.pad(r, widths)
                r = r.reshape(r.shape[0], nb, page, *r.shape[2:])
                if self.kv_quant:
                    q, s = quantize_kv(r)
                    out[key] = leaf.at[:, pids].set(q)
                    sk = f"{key}_scale"
                    out[sk] = cache[sk].at[:, pids].set(s)
                else:
                    out[key] = leaf.at[:, pids].set(r.astype(leaf.dtype))
            else:
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    leaf, req_cache[key].astype(leaf.dtype), slot, axis=1)
        return out

    def _activate_impl(self, cache, group_cache, slot, j, pids, *, bs, nb):
        """Land row ``j`` of a chunked group cache: delta blocks
        [``bs``, ``nb``) scatter to ``pids``; unpaged leaves overwrite the
        slot row. Shared prefix blocks are already resident — only their
        table mapping changes (host side)."""
        page = self.page
        out = {}
        for key, leaf in cache.items():
            if key in self._skeys:
                continue  # written alongside its payload below
            row = jax.lax.dynamic_slice_in_dim(group_cache[key], j, 1, axis=1)
            if key in self._pkeys:
                r = row[:, 0, bs * page : nb * page]
                r = r.reshape(r.shape[0], nb - bs, page, *r.shape[2:])
                if self.kv_quant:
                    q, s = quantize_kv(r)
                    out[key] = leaf.at[:, pids].set(q)
                    sk = f"{key}_scale"
                    out[sk] = cache[sk].at[:, pids].set(s)
                else:
                    out[key] = leaf.at[:, pids].set(r.astype(leaf.dtype))
            else:
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    leaf, row.astype(leaf.dtype), slot, axis=1)
        return out

    def _fill_prefix_impl(self, group_cache, cache, tables):
        """Gather shared prefix pages into the leading rows of a group's
        contiguous prefill cache (tables: (k, bs) page ids per row)."""
        out = dict(group_cache)
        for key in self._pkeys:
            g = jnp.take(cache[key], tables, axis=1)  # (lead, k, bs, page, *)
            if self.kv_quant:  # dequantize-in-gather
                s = jnp.take(cache[f"{key}_scale"], tables, axis=1)
                g = dequantize_kv(g, s)
            rows = g.reshape(g.shape[0], g.shape[1], g.shape[2] * g.shape[3],
                             *g.shape[4:])
            gc = group_cache[key]
            out[key] = gc.at[:, :, : rows.shape[2]].set(rows.astype(gc.dtype))
        return out

    def _copy_pages_impl(self, cache, srcs, dsts):
        out = dict(cache)
        for key in self._pleaves:
            leaf = cache[key]
            out[key] = leaf.at[:, dsts].set(jnp.take(leaf, srcs, axis=1))
        return out

    def _copy_row_impl(self, cache, src, dst):
        out = dict(cache)
        for key, leaf in cache.items():
            if key in self._pleaves:
                continue
            row = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)
            out[key] = jax.lax.dynamic_update_slice_in_dim(leaf, row, dst,
                                                           axis=1)
        return out

    def _zero_pages_impl(self, cache, pids):
        out = dict(cache)
        for key in self._pleaves:
            leaf = cache[key]
            z = jnp.zeros((leaf.shape[0], pids.shape[0]) + leaf.shape[2:],
                          leaf.dtype)
            out[key] = leaf.at[:, pids].set(z)
        return out

    def _zero_row_impl(self, cache, slot):
        out = dict(cache)
        for key, leaf in cache.items():
            if key in self._pleaves:
                continue
            row = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
            out[key] = jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.zeros_like(row), slot, axis=1)
        return out

    def _restore_impl(self, cache, pages, row, slot, pids):
        """Swap-in: scatter a host image's page blocks back to fresh pages
        and its unpaged per-slot rows back into the slot row — the exact
        bytes ``swap_out`` gathered, so the restore is bit-identical."""
        out = {}
        for key, leaf in cache.items():
            if key in self._pleaves:
                out[key] = leaf.at[:, pids].set(pages[key].astype(leaf.dtype))
            else:
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    leaf, row[key].astype(leaf.dtype), slot, axis=1)
        return out

    def _nan_impl(self, cache, pids, slot):
        # int8 payloads cannot carry a NaN — their f32 scale leaves do, and
        # dequantize-in-gather (q * NaN) re-poisons every value they cover,
        # so the engine's finiteness guard fires exactly as in f32 mode.
        out = dict(cache)
        for key, leaf in cache.items():
            if key in self._pleaves:
                if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                    continue
                v = jnp.full((leaf.shape[0], pids.shape[0]) + leaf.shape[2:],
                             jnp.nan, leaf.dtype)
                out[key] = leaf.at[:, pids].set(v)
            elif jnp.issubdtype(leaf.dtype, jnp.inexact):
                row = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    leaf, jnp.full_like(row, jnp.nan), slot, axis=1)
        return out

    # -- page accounting -----------------------------------------------------
    def _blocks_for(self, extent: int) -> int:
        """Blocks covering cache positions [0, extent)."""
        return max(1, -(-extent // self.page))

    def _evictable(self) -> int:
        return sum(1 for pid in self._prefix.values()
                   if self.pages.refcount[pid] == 1)

    def _outstanding(self) -> int:
        """Pages occupied slots have reserved but not yet allocated."""
        occ = self.active  # includes admitting slots (reserved groups)
        return int(np.maximum(self._resv - self._owned, 0)[occ].sum())

    def can_admit(self, s0: int, budget: int, *, shared_len: int = 0) -> bool:
        """A free slot AND enough pages (free + LRU-evictable registry pages,
        minus what already-admitted slots still have reserved) for the
        request's worst case, net of its shared prefix blocks."""
        if self.free_count == 0:
            return False
        need = self._blocks_for(s0 + budget - 1) - shared_len // self.page
        avail = self.pages.free_count + self._evictable() - self._outstanding()
        return need <= avail

    def _evict_one(self) -> bool:
        """Drop the least-recently-used registry-only page (refcount 1)."""
        for digest, pid in self._prefix.items():
            if self.pages.refcount[pid] == 1:
                del self._prefix[digest]
                freed = self.pages.decref(pid)
                assert freed
                self.evictions += 1
                return True
        return False

    def _alloc_page(self) -> int | PageExhausted:
        """One fresh page, evicting LRU registry pages if the free list is
        dry. Exhaustion is TYPED: returns a ``PageExhausted`` signal (never
        raises ``RuntimeError``) so callers can unwind before raising."""
        pid = self.pages.alloc()
        if pid is None and self._evict_one():
            pid = self.pages.alloc()
        if pid is None:
            return PageExhausted(need=1, free=self.pages.free_count)
        if pid in self._tainted:  # recycled from a poisoned slot: scrub
            self.cache = self._zero_pages_jit(
                self.cache, jnp.asarray([pid], jnp.int32))
            self._tainted.discard(pid)
        return pid

    def _alloc_pages(self, n: int) -> list[int] | PageExhausted:
        """``n`` fresh pages, all-or-nothing: on exhaustion every page
        already taken is released and the signal is returned."""
        pids: list[int] = []
        for _ in range(n):
            pid = self._alloc_page()
            if isinstance(pid, PageExhausted):
                for p in pids:
                    self.pages.decref(p)
                return PageExhausted(need=n, free=self.pages.free_count)
            pids.append(pid)
        return pids

    def require_pages(self, n: int) -> None:
        """Assert ``n`` pages are obtainable NOW (evicting registry pages as
        needed) or raise ``PageExhausted`` — used to make multi-slot commits
        (chunked-group activation) atomic: check before touching any slot."""
        while self.pages.free_count < n and self._evict_one():
            pass
        if self.pages.free_count < n:
            raise PageExhausted(need=n, free=self.pages.free_count)

    def reserved_admitting(self) -> int:
        """Worst-case pages still owed to in-flight admitting groups — the
        share of the pool a decode/verify tick must not consume."""
        occ = self.active & self.admitting
        return int(np.maximum(self._resv - self._owned, 0)[occ].sum())

    def blocks_needed(self, slot: int, start: int, end: int) -> int:
        """Fresh pages ``ensure_writable(slot, start, end)`` would allocate
        right now: unmapped blocks plus shared blocks needing a COW copy.
        The scheduler's pre-tick watermark sums this over decoding slots."""
        need = 0
        for blk in range(start // self.page, (end - 1) // self.page + 1):
            pid = int(self.table[slot, blk])
            if pid == SCRATCH or self.pages.refcount[pid] > 1:
                need += 1
        return need

    def pin_free_pages(self, n: int) -> list[int]:
        """Page-pressure fault: pin up to ``n`` FREE pages out of the pool
        (no registry eviction — the squeeze is transient). Release with
        ``unpin_pages`` at the end of the tick."""
        pids: list[int] = []
        for _ in range(n):
            pid = self.pages.alloc()
            if pid is None:
                break
            pids.append(pid)
        self._press_pins.extend(pids)
        return pids

    def unpin_pages(self, pids) -> None:
        for pid in pids:
            self._press_pins.remove(pid)
            self.pages.decref(pid)

    # -- prefix registry -----------------------------------------------------
    def _block_digests(self, prompt: np.ndarray) -> list[bytes]:
        """Chained digests over FULL blocks only — digest j commits to every
        token in blocks 0..j, so one lookup per block walks the prefix."""
        out = []
        h = hashlib.blake2b(b"kv-prefix", digest_size=16).digest()
        for j in range(len(prompt) // self.page):
            blk = np.ascontiguousarray(
                prompt[j * self.page : (j + 1) * self.page], dtype=np.int32)
            h = hashlib.blake2b(h + blk.tobytes(), digest_size=16).digest()
            out.append(h)
        return out

    def match_prefix_len(self, prompt) -> int:
        """Longest registered block-aligned prefix of ``prompt`` in tokens,
        capped at s0-1 (the consumer must chunk-prefill at least the last
        prompt position to produce its first logits)."""
        if not self.share_prefix:
            return 0
        prompt = np.asarray(prompt, np.int32)
        cap = (len(prompt) - 1) // self.page
        m = 0
        for d in self._block_digests(prompt)[:cap]:
            if d not in self._prefix:
                break
            self._prefix.move_to_end(d)
            m += 1
        return m * self.page

    def pin_prefix(self, prompt, shared_len: int) -> list[int]:
        """Incref the pages of ``prompt``'s matched prefix for one consumer;
        the refs transfer to its table at activate (or release via
        ``unpin_prefix`` on cancellation)."""
        digests = self._block_digests(
            np.asarray(prompt, np.int32))[: shared_len // self.page]
        pids = [self._prefix[d] for d in digests]
        for pid in pids:
            self.pages.incref(pid)
        self.shared_hit_pages += len(pids)
        return pids

    def unpin_prefix(self, pids) -> None:
        for pid in pids:
            self.pages.decref(pid)

    def _register_prompt(self, slot: int, prompt: np.ndarray) -> None:
        """Publish the slot's full prompt blocks. The registry takes one ref
        per page, so consumers can share them and they outlive the owner
        (until LRU eviction). Partial blocks are never registered."""
        for j, d in enumerate(self._block_digests(prompt)):
            if d in self._prefix:
                self._prefix.move_to_end(d)
                continue
            pid = int(self.table[slot, j])
            if pid == SCRATCH:
                break
            self.pages.incref(pid)
            self._prefix[d] = pid

    # -- write preparation (COW) ---------------------------------------------
    def ensure_writable(self, slot: int, start: int, end: int) -> None:
        """Make cache positions [start, end) of ``slot`` writable: allocate
        unmapped blocks; copy-on-write blocks whose page is shared. Must run
        (host-side) before every decode/verify tick's write span."""
        assert self.active[slot] and not self.admitting[slot]
        srcs, dsts = [], []
        try:
            for blk in range(start // self.page, (end - 1) // self.page + 1):
                pid = int(self.table[slot, blk])
                if pid == SCRATCH:
                    npid = self._alloc_page()
                    if isinstance(npid, PageExhausted):
                        raise npid  # table untouched for this block
                    self.table[slot, blk] = npid
                    self._owned[slot] += 1
                elif self.pages.refcount[pid] > 1:
                    npid = self._alloc_page()
                    if isinstance(npid, PageExhausted):
                        raise npid  # COW not started for this block
                    srcs.append(pid)
                    dsts.append(npid)
                    self.pages.decref(pid)  # shared: cannot hit 0 here
                    self.table[slot, blk] = npid
                    self.cow_copies += 1
        finally:
            # flush COW copies for the blocks already repointed, even on the
            # typed-exhaustion path — the table must never point at garbage
            if srcs:
                self.cache = self._copy_pages_jit(
                    self.cache, jnp.asarray(srcs, jnp.int32),
                    jnp.asarray(dsts, jnp.int32))

    # -- lifecycle overrides -------------------------------------------------
    def admit(self, slot: int, req_cache: dict, *, rid: int, pos: int,
              budget: int, first_tok: int, emitted: int = 1,
              prompt=None) -> None:
        assert pos >= 1
        assert pos + (budget - emitted) + 1 <= self.max_len, (pos, budget,
                                                              emitted,
                                                              self.max_len)
        assert 1 <= emitted <= budget
        self._claim(slot)
        nb = self._blocks_for(pos)
        pids = self._alloc_pages(nb)
        if isinstance(pids, PageExhausted):
            self.active[slot] = False  # unwind the claim cleanly
            self.slots[slot] = SlotInfo()
            self._free.appendleft(slot)
            raise pids
        self.table[slot, :] = SCRATCH
        self.table[slot, :nb] = pids
        self._owned[slot] = nb
        self._resv[slot] = self._blocks_for(pos + budget - emitted)
        self.cache = self._admit_jit(self.cache, req_cache, jnp.int32(slot),
                                     jnp.asarray(pids, jnp.int32))
        self.slots[slot] = SlotInfo(rid=rid, pos=pos, budget=budget,
                                    emitted=emitted)
        self.tok[slot] = first_tok
        if prompt is not None and self.share_prefix:
            self._register_prompt(slot, np.asarray(prompt, np.int32))

    def reserve(self, slot: int, *, rid: int, s0: int = 0, budget: int = 0,
                shared_len: int = 0) -> None:
        super().reserve(slot, rid=rid)
        if s0:
            # worst case net of the shared prefix (those pages come from the
            # registry, not the free list) — can_admit sees this immediately,
            # so forming a group reserves member by member
            self._resv[slot] = (self._blocks_for(s0 + budget - 1)
                                - shared_len // self.page)
            self._owned[slot] = 0

    def activate_from_group(self, slot: int, group_cache, j: int, *, rid: int,
                            pos: int, budget: int, first_tok: int,
                            prompt=None, pins=()) -> None:
        """Paged counterpart of ``activate``: map the shared prefix pages
        (ref transfer from the group's pins), allocate + scatter the delta
        blocks out of the group cache row, and register the prompt."""
        assert self.active[slot] and self.admitting[slot], f"slot {slot}"
        assert self.slots[slot].rid == rid, (self.slots[slot].rid, rid)
        assert pos + budget <= self.max_len and budget >= 1
        bs = len(pins)
        nb = self._blocks_for(pos)
        assert bs < nb, (bs, nb)  # the last prompt position is never shared
        delta = self._alloc_pages(nb - bs)
        if isinstance(delta, PageExhausted):
            raise delta  # slot stays admitting; the group cancels atomically
        self.table[slot, :] = SCRATCH
        self.table[slot, :bs] = pins
        self.table[slot, bs:nb] = delta
        self._owned[slot] = nb
        self._resv[slot] = self._blocks_for(pos + budget - 1)
        self.cache = self._activate_jit(
            self.cache, group_cache, jnp.int32(slot), jnp.int32(j),
            jnp.asarray(delta, jnp.int32), bs=bs, nb=nb)
        self.slots[slot] = SlotInfo(rid=rid, pos=pos, budget=budget, emitted=1)
        self.admitting[slot] = False
        self.tok[slot] = first_tok
        if prompt is not None and self.share_prefix:
            self._register_prompt(slot, np.asarray(prompt, np.int32))

    def fill_group_prefix(self, group_cache, pins: list[list[int]]):
        """Gather each group member's pinned prefix pages into the leading
        rows of the group's contiguous prefill cache."""
        tables = jnp.asarray(pins, jnp.int32)
        return self._fill_prefix_jit(group_cache, self.cache, tables)

    def fork_slot(self, src: int, dst: int, *, rid: int) -> None:
        """Parallel-sampling style fork: ``dst`` shares every page of
        ``src`` copy-on-write (table row copied, pages increfed); the O(1)
        unpaged per-slot rows are deep-copied. Either side's next write to a
        shared block triggers COW via ``ensure_writable``."""
        assert self.active[src] and not self.admitting[src]
        self._claim(dst)
        self.table[dst] = self.table[src]
        for pid in self.table[dst]:
            if pid != SCRATCH:
                self.pages.incref(int(pid))
        self._owned[dst] = self._owned[src]
        self._resv[dst] = self._resv[src]
        info = self.slots[src]
        self.slots[dst] = SlotInfo(rid=rid, pos=info.pos, budget=info.budget,
                                   emitted=info.emitted)
        self.tok[dst] = self.tok[src]
        self.cache = self._copy_row_jit(self.cache, jnp.int32(src),
                                        jnp.int32(dst))

    def poison(self, slot: int) -> None:
        """Fault injection: NaN the slot's cache. Shared pages (registry,
        forks) are force-exclusived FIRST — copy-on-write, then corrupt only
        the copies — so innocent sharers and the registry keep clean bytes.
        The slot is marked tainted: its pages are scrubbed on reallocation
        and its unpaged rows zeroed at retire, so recycled NaNs can never
        leak into another slot's value matmul (masked softmax weights are
        exactly 0.0, but 0.0 * NaN = NaN)."""
        assert self.active[slot] and not self.admitting[slot]
        srcs, dsts = [], []
        for blk in range(self.max_blocks):
            pid = int(self.table[slot, blk])
            if pid != SCRATCH and self.pages.refcount[pid] > 1:
                npid = self._alloc_page()
                if isinstance(npid, PageExhausted):
                    # exhaustion-tolerant: leave this block shared and clean.
                    # The slot's exclusive pages and unpaged rows still get
                    # NaN'd below, so the fault is detected and quarantined;
                    # innocent sharers keep their bytes either way.
                    continue
                srcs.append(pid)
                dsts.append(npid)
                self.pages.decref(pid)
                self.table[slot, blk] = npid
                self.cow_copies += 1
        if srcs:
            self.cache = self._copy_pages_jit(
                self.cache, jnp.asarray(srcs, jnp.int32),
                jnp.asarray(dsts, jnp.int32))
        # NaN only exclusively-owned pages: a block whose COW was skipped
        # under exhaustion is still shared and MUST keep its clean bytes
        pids = [int(p) for p in self.table[slot]
                if p != SCRATCH and self.pages.refcount[int(p)] == 1]
        self.cache = self._nan_jit(self.cache, jnp.asarray(pids, jnp.int32),
                                   jnp.int32(slot))
        self._slot_tainted.add(slot)

    def scrub_scratch(self) -> None:
        """Zero the scratch page. The engine calls this after any tick whose
        finiteness guard fired: a poisoned slot's redirected verify-window
        writes may have parked NaNs in scratch, which every slot's unmapped
        blocks gather."""
        if self._pleaves:
            self.cache = self._zero_pages_jit(
                self.cache, jnp.asarray([SCRATCH], jnp.int32))

    def retire(self, slot: int) -> None:
        tainted = slot in self._slot_tainted
        for pid in self.table[slot]:
            pid = int(pid)
            if pid == SCRATCH:
                continue
            freed = self.pages.decref(pid)
            if tainted and freed:
                self._tainted.add(pid)
        if tainted:
            self._slot_tainted.discard(slot)
            self.cache = self._zero_row_jit(self.cache, jnp.int32(slot))
        self.table[slot, :] = SCRATCH
        self._owned[slot] = 0
        self._resv[slot] = 0
        super().retire(slot)

    # -- preemption: swap-out / swap-in --------------------------------------
    def swap_image_bytes(self, slot: int) -> int:
        """Host-buffer size a ``swap_out`` of ``slot`` would produce — the
        deterministic input to the scheduler's swap-vs-recompute cost model,
        computable before building the image."""
        nb = self._blocks_for(self.slots[slot].pos)
        page_b = sum(self.cache[k].nbytes // self.num_pages
                     for k in self._pleaves)
        row_b = sum(v.nbytes // self.max_batch
                    for k, v in self.cache.items() if k not in self._pleaves)
        return nb * page_b + row_b

    def swap_out(self, slot: int) -> dict:
        """Preempt ``slot`` by copying its state to host buffers: the pages
        mapping positions [0, pos) (every one written, hence mapped) plus the
        unpaged per-slot rows (SSM conv/state, audio cross K/V — the FULL
        state for those families), with the slot bookkeeping needed to
        continue. Verify-tail blocks past ``pos`` held only rejected drafts
        and are dropped. The slot is then released; restore with
        ``swap_in`` is bit-identical."""
        assert self.active[slot] and not self.admitting[slot]
        assert slot not in self._slot_tainted, "cannot swap a poisoned slot"
        info = self.slots[slot]
        nb = self._blocks_for(info.pos)
        pids = [int(self.table[slot, b]) for b in range(nb)]
        assert SCRATCH not in pids, (slot, pids)
        idx = jnp.asarray(pids, jnp.int32)
        pages = {k: np.asarray(self.cache[k][:, idx]) for k in self._pleaves}
        row = {k: np.asarray(v[:, slot : slot + 1])
               for k, v in self.cache.items() if k not in self._pleaves}
        image = {
            "rid": info.rid, "pos": info.pos, "budget": info.budget,
            "emitted": info.emitted, "tier": info.tier,
            "tok": int(self.tok[slot]), "resv": int(self._resv[slot]),
            "pages": pages, "row": row,
            "bytes": sum(a.nbytes for a in (*pages.values(), *row.values())),
        }
        self.swap_outs += 1
        self.swapped_bytes += image["bytes"]
        self.retire(slot)
        return image

    def swap_in(self, slot: int, image: dict) -> None:
        """Restore a ``swap_out`` image into a free slot: map fresh pages and
        scatter the saved bytes back through the table. Raises
        ``PageExhausted`` (after a clean unwind) when the pool cannot supply
        the image's blocks — the scheduler retries once pages free up."""
        nb = self._blocks_for(image["pos"])
        self._claim(slot)
        pids = self._alloc_pages(nb)
        if isinstance(pids, PageExhausted):
            self.active[slot] = False
            self._free.appendleft(slot)
            raise pids
        self.table[slot, :] = SCRATCH
        self.table[slot, :nb] = pids
        self._owned[slot] = nb
        self._resv[slot] = image["resv"]
        self.cache = self._restore_jit(
            self.cache,
            {k: jnp.asarray(v) for k, v in image["pages"].items()},
            {k: jnp.asarray(v) for k, v in image["row"].items()},
            jnp.int32(slot), jnp.asarray(pids, jnp.int32))
        self.slots[slot] = SlotInfo(rid=image["rid"], pos=image["pos"],
                                    budget=image["budget"],
                                    emitted=image["emitted"],
                                    tier=image["tier"])
        self.tok[slot] = image["tok"]
        self.swap_ins += 1

    # -- invariants (exercised by tests/test_pages.py) -----------------------
    def check_invariants(self) -> None:
        """Refcount conservation: every page's refcount equals its table
        mappings plus its registry pin; free pages are exactly the
        refcount-0 pages, each listed once."""
        refs = np.zeros(self.num_pages, np.int64)
        refs[SCRATCH] = 1
        for pid in self.table.ravel():
            if pid != SCRATCH:
                refs[pid] += 1
        for pid in self._prefix.values():
            refs[pid] += 1
        pinned = getattr(self, "_extra_pins", ())
        for pid in pinned:
            refs[pid] += 1
        for pid in self._press_pins:
            refs[pid] += 1
        assert (refs == self.pages.refcount).all(), (
            refs.tolist(), self.pages.refcount.tolist())
        free = sorted(self.pages._free)
        assert len(free) == len(set(free)), "duplicate free-list entry"
        assert free == [int(p) for p in np.flatnonzero(refs == 0)], (
            free, np.flatnonzero(refs == 0).tolist())
