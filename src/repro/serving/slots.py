"""Slot-pool decode state for continuous batching.

The family-appropriate cache from ``kv_cache.cache_defs`` becomes a fixed
pool of ``max_batch`` slots sharing ONE device cache pytree (batch axis 1 on
every leaf, by construction). Requests of different prompt lengths and token
budgets are admitted into free slots mid-decode and retired independently,
so the engine runs a single jitted masked decode step over the whole pool
instead of lockstep fixed batches:

  * ``active`` / per-slot ``pos`` are host-side scheduler state; the device
    only ever sees the full (max_batch,) vectors, so the decode step has one
    compile signature for the lifetime of the pool.
  * ``admit`` writes a prefill-produced per-request cache (grown to pool
    capacity with ``grow_cache``) into the slot's batch row with a jitted
    donated ``dynamic_update_slice`` — the slot index is a traced scalar, so
    all slots share one compile.
  * ``retire`` only flips host-side bookkeeping: a freed slot's cache rows
    are dead data, fully overwritten by the next ``admit``. (The masked
    decode step clamps inactive slots to position 0, so their scribbles land
    in dead rows too.)
  * chunked admission reserves slots up-front (``reserve`` → ``admitting``
    state, excluded from the decode mask) and lands the prefilled cache with
    ``activate`` once the group's last chunk completed.
  * an explicit free-slot deque makes the scheduler's admission scan O(1)
    per tick (and gives FIFO slot reuse) instead of scanning all
    ``max_batch`` slots.

This pool is the CONTIGUOUS layout: every slot owns a full
``max_len + slack`` rectangle of cache rows, so a 12-token request costs
the same HBM as one at the admission bound. ``serving/pages.PagedSlotPool``
is the drop-in paged alternative — each slot's logical blocks of
``page_size`` sequence rows map through a dense int32 page table onto a
shared physical page array, with refcounted copy-on-write sharing of
block-aligned prompt prefixes (see that module's docstring for the
logical-block ↔ physical-page mapping and the COW rules). The scheduler
talks to both through the same surface; the capacity probes it needs
(``can_admit``) are trivially true here and memory-aware there.
"""
from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.params import init_params
from repro.serving.kv_cache import cache_defs, paged_keys


def grow_cache(cfg: ArchConfig, cache: dict, max_len: int) -> dict:
    """Pad prefill-produced seq-dim caches out to ``max_len`` capacity.

    Only the sequence-dim leaves grow (``kv_cache.paged_keys``, axis 2):
    recurrent state rows are O(1) in sequence and audio cross K/V is fixed
    at encoder_seq.
    """

    def grow(x, axis):
        pad = max_len - x.shape[axis]
        if pad <= 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    return dict(cache, **{k: grow(cache[k], 2) for k in paged_keys(cfg)})


@dataclasses.dataclass
class SlotInfo:
    """Host-side bookkeeping for one slot."""

    rid: int | None = None
    pos: int = 0      # next cache position to write (== tokens resident)
    budget: int = 0   # total new tokens this request will emit
    emitted: int = 0  # tokens emitted so far (prefill's argmax counts as #1)
    tier: str = "batch"  # SLO tier: "latency" may preempt "batch" slots


class SlotPool:
    """Fixed pool of decode slots over one shared device cache.

    ``slack`` adds dead cache rows past ``max_len``: a speculative verify
    window of K+1 tokens may start as late as position max_len-2, and
    without the spare rows its tail writes would clamp (dynamic_update_slice
    shifts the whole window) and corrupt live positions. The admission
    bound stays ``max_len``; slack rows only ever hold rejected candidates.
    """

    def __init__(self, cfg: ArchConfig, *, max_batch: int, max_len: int,
                 virtual: bool = False, slack: int = 0):
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.slack = slack
        self.capacity = max_len + slack
        # virtual pools carry only the host-side bookkeeping (scheduler
        # studies with FixedCalibration — no device cache, no engine)
        self.cache = None if virtual else init_params(
            cache_defs(cfg, batch=max_batch, max_len=self.capacity),
            jax.random.PRNGKey(0),
        )
        # accepted-token accounting: tokens committed through ``advance``
        # (every decode/verify tick), and how many were drafted — the
        # above-one-per-tick surplus speculation exists for (0 under plain
        # decode, whose ticks are the n=1 special case)
        self.committed = 0
        self.drafted = 0
        self.slots = [SlotInfo() for _ in range(max_batch)]
        self.active = np.zeros(max_batch, bool)       # slot occupied at all
        self.admitting = np.zeros(max_batch, bool)    # reserved, prefill in flight
        self.tok = np.zeros(max_batch, np.int32)  # next decode input per slot
        # explicit free-slot list: admission pops in O(1) instead of scanning
        # all max_batch slots every scheduler tick
        self._free = collections.deque(range(max_batch))
        self._write = jax.jit(self._write_impl, donate_argnums=(0,))

    @staticmethod
    def _write_impl(pool_cache, req_cache, slot):
        return jax.tree.map(
            lambda p, r: jax.lax.dynamic_update_slice_in_dim(
                p, r.astype(p.dtype), slot, axis=1
            ),
            pool_cache,
            req_cache,
        )

    # -- host-side views ----------------------------------------------------
    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    @property
    def free_count(self) -> int:
        return len(self._free)

    def next_free(self) -> int:
        """Peek the next free slot (FIFO over retirements) without claiming it."""
        return self._free[0]

    def free_slots(self) -> list[int]:
        return list(self._free)

    def active_slots(self) -> list[int]:
        return [i for i in range(self.max_batch) if self.active[i]]

    def decode_mask(self) -> np.ndarray:
        """Slots the masked decode step should advance: active and NOT still
        admitting (their prefill is in flight; their cache rows are dead)."""
        return self.active & ~self.admitting

    @property
    def decoding_count(self) -> int:
        return int(self.decode_mask().sum())

    def decoding_slots(self) -> list[int]:
        m = self.decode_mask()
        return [i for i in range(self.max_batch) if m[i]]

    def positions(self) -> np.ndarray:
        return np.asarray([s.pos for s in self.slots], np.int32)

    # -- lifecycle ----------------------------------------------------------
    def can_admit(self, s0: int, budget: int, *, shared_len: int = 0) -> bool:
        """Admission capacity probe: contiguous pools only need a free slot
        (every slot owns its full cache rectangle). The paged pool overrides
        this with page-budget accounting; the scheduler calls it before every
        admission so both layouts share one admission loop."""
        return self.free_count > 0

    def _claim(self, slot: int) -> None:
        assert not self.active[slot], f"slot {slot} already active"
        if self._free and self._free[0] == slot:
            self._free.popleft()  # O(1): callers claim the peeked FIFO head
        else:
            self._free.remove(slot)  # O(free) fallback for out-of-order claims
        self.active[slot] = True

    def admit(self, slot: int, req_cache: dict, *, rid: int, pos: int,
              budget: int, first_tok: int, emitted: int = 1,
              prompt=None) -> None:
        """Place a prefilled request (cache already grown to max_len) into a
        free slot. ``pos`` is the prefilled context length; ``first_tok`` the
        slot's next decode input (the argmax of the prefill logits for a
        fresh admission, or the last committed token for a quarantine-retry
        re-admission, where ``emitted`` carries the tokens already emitted
        before the fault). ``prompt`` is ignored here; the paged pool uses
        it to register the request's block-aligned prefix for sharing."""
        assert self.cache is not None, "cannot admit a real cache into a virtual pool"
        assert pos + (budget - emitted) + 1 <= self.max_len, (pos, budget, emitted,
                                                              self.max_len)
        assert 1 <= emitted <= budget
        self._claim(slot)
        self.cache = self._write(self.cache, req_cache, jnp.int32(slot))
        self.slots[slot] = SlotInfo(rid=rid, pos=pos, budget=budget, emitted=emitted)
        self.tok[slot] = first_tok

    def admit_virtual(self, slot: int, *, rid: int, pos: int, budget: int,
                      emitted: int = 1) -> None:
        """Claim a slot with bookkeeping only (virtual pools / engine-free
        scheduler runs): no device cache is written."""
        assert pos + (budget - emitted) + 1 <= self.max_len, (pos, budget, emitted,
                                                              self.max_len)
        assert 1 <= emitted <= budget
        self._claim(slot)
        self.slots[slot] = SlotInfo(rid=rid, pos=pos, budget=budget, emitted=emitted)

    def reserve(self, slot: int, *, rid: int, s0: int = 0, budget: int = 0,
                shared_len: int = 0) -> None:
        """Claim a free slot for a request whose chunked prefill is about to
        start. The slot is ``admitting``: occupied (no other admission may
        take it) but excluded from the masked decode step until
        ``activate`` lands the prefilled cache. ``s0``/``budget``/
        ``shared_len`` are ignored here; the paged pool uses them to reserve
        the request's worst-case page count at claim time."""
        self._claim(slot)
        self.admitting[slot] = True
        self.slots[slot] = SlotInfo(rid=rid)

    def activate(self, slot: int, req_cache: dict | None, *, rid: int, pos: int,
                 budget: int, first_tok: int) -> None:
        """Flip a reserved slot admitting → decoding once its chunked prefill
        completed. ``req_cache`` is the request's prefilled batch-1 cache
        (None for virtual pools)."""
        assert self.active[slot] and self.admitting[slot], f"slot {slot} not admitting"
        assert self.slots[slot].rid == rid, (self.slots[slot].rid, rid)
        assert pos + budget <= self.max_len, (pos, budget, self.max_len)
        assert budget >= 1
        if self.cache is not None:
            self.cache = self._write(self.cache, req_cache, jnp.int32(slot))
        self.slots[slot] = SlotInfo(rid=rid, pos=pos, budget=budget, emitted=1)
        self.admitting[slot] = False
        self.tok[slot] = first_tok

    def advance(self, slot: int, n: int, next_tok: int) -> None:
        """Commit ``n`` emitted tokens to a decoding slot in one move — the
        variable-advance a speculative verify tick needs; a plain decode
        tick is the n=1 special case. ``next_tok`` is the new next decode
        input (the verify bonus token, or the truncation point at budget
        end)."""
        assert n >= 1
        info = self.slots[slot]
        assert self.active[slot] and not self.admitting[slot]
        info.pos += n
        info.emitted += n
        self.tok[slot] = next_tok
        self.committed += n
        self.drafted += n - 1

    def retire(self, slot: int) -> None:
        assert self.active[slot], f"slot {slot} not active"
        self.active[slot] = False
        self.admitting[slot] = False
        self.slots[slot] = SlotInfo()
        self._free.append(slot)
