"""Spans and counters around the calls into the engine, recorded from the
benchmark's own files.

``Recorder.install`` wraps four methods of one ``InferenceEngine`` instance
(the scheduler calls them through the instance):

  begin_chunked_prefill    a prefill group is formed (its cache is zeroed)
  chunked_prefill_step     one chunk of every member's prompt
  finish_chunked_prefill   the group lands in the pool; first tokens
  masked_decode_step       one decode step over the pool; one token per slot

Each span holds its kind, its wall start and end in window seconds, and what
the call worked on (group size and positions, or the decoding slots and
their positions). A token's time is the end of the call that handed it to
the host: the ``np.asarray`` readbacks inside these calls wait for the
device, so the host holds the token by then. Before each call the wrapper
asks the clock whether the window is over (``WallClock.check``).

With tracing on, each call is also a ``jax.profiler.TraceAnnotation`` named
``bench.<kind>``, so the device trace can say what the host was doing in a
device idle gap.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses

import numpy as np


@dataclasses.dataclass
class Span:
    kind: str            # begin | chunk | finish | decode
    t0: float
    t1: float
    rows: int            # group members, or decoding slots
    positions: tuple     # cache positions each row starts the call at
    tokens: int = 0      # tokens per row this call processed


class Recorder:
    def __init__(self, clock, *, annotate: bool = False, fault=None):
        self.clock = clock
        self.spans: list[Span] = []
        self.tokens: dict[int, list[tuple[float, int]]] = collections.defaultdict(list)
        self.admitted: dict[int, float] = {}  # rid -> window s of its group start
        self.annotate = annotate
        self.fault = fault  # a planted fault's token hook (tests only)
        self.budget: dict[int, int] = {}  # rid -> tokens it asks for
        self.finished_tokens = 0  # tokens of requests served in full
        self.pool_size = 0        # decode slots of the pool
        self.vocab = 0            # the model's vocabulary size
        self.full_at: float | None = None  # end of the first full decode step

    def _scope(self, kind: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{kind}")

    def install(self, engine) -> None:
        clock, spans, tokens = self.clock, self.spans, self.tokens
        begin0 = engine.begin_chunked_prefill
        chunk0 = engine.chunked_prefill_step
        finish0 = engine.finish_chunked_prefill
        decode0 = engine.masked_decode_step

        def begin_chunked_prefill(pool, slots, prompts, *, rids, budgets):
            clock.check()
            t0 = clock.now()
            with self._scope("begin"):
                st = begin0(pool, slots, prompts, rids=rids, budgets=budgets)
            spans.append(Span("begin", t0, clock.now(), len(rids), (0,) * len(rids)))
            for rid in rids:
                self.admitted[rid] = t0
            return st

        def chunked_prefill_step(st, chunk_tokens):
            clock.check()
            t0, pos = clock.now(), st.pos
            with self._scope("chunk"):
                n = chunk0(st, chunk_tokens)
            spans.append(Span("chunk", t0, clock.now(), len(st.rids),
                              (pos,) * len(st.rids), n))
            return n

        def finish_chunked_prefill(pool, st):
            clock.check()
            t0 = clock.now()
            with self._scope("finish"):
                first = finish0(pool, st)
            t1 = clock.now()
            spans.append(Span("finish", t0, t1, len(st.rids), (st.s0,) * len(st.rids)))
            if self.fault is not None:
                first = self.fault.on_first(first, st.rids, self)
            for j, rid in enumerate(st.rids):
                self._commit(rid, t1, int(first[j]))
            return first

        def masked_decode_step(pool):
            clock.check()
            slots = pool.decoding_slots()
            rids = [pool.slots[s].rid for s in slots]
            pos = tuple(pool.slots[s].pos for s in slots)
            t0 = clock.now()
            with self._scope("decode"):
                nxt, fin = decode0(pool)
            t1 = clock.now()
            if self.fault is not None:
                nxt = self.fault.on_decode(nxt, slots, rids, self)
            spans.append(Span("decode", t0, t1, len(slots), pos, 1))
            if self.full_at is None and len(slots) == self.pool_size:
                self.full_at = t1
            for s, rid in zip(slots, rids):
                if fin[s]:
                    self._commit(rid, t1, int(nxt[s]))
            return nxt, fin

        engine.begin_chunked_prefill = begin_chunked_prefill
        engine.chunked_prefill_step = chunked_prefill_step
        engine.finish_chunked_prefill = finish_chunked_prefill
        engine.masked_decode_step = masked_decode_step

    def _commit(self, rid: int, t: float, tok: int) -> None:
        toks = self.tokens[rid]
        toks.append((t, tok))
        if len(toks) == self.budget.get(rid):
            self.finished_tokens += len(toks)

    # -- views the metric readers use -----------------------------------------
    def of(self, kind: str) -> list[Span]:
        return [s for s in self.spans if s.kind == kind]

    def iterations(self) -> int:
        """Scheduler loop iterations that called the engine: each decoding
        iteration calls the decode step once, and an iteration with no
        decoding slot advances its prefill group by one chunk alone."""
        kinds = [s.kind for s in self.spans if s.kind in ("chunk", "decode")]
        alone = sum(1 for a, b in zip(kinds, kinds[1:] + ["end"])
                    if a == "chunk" and b != "decode")
        return kinds.count("decode") + alone

    def engine_seconds(self, until: float) -> float:
        return sum(min(s.t1, until) - s.t0 for s in self.spans if s.t0 < until)

    def first_token_s(self) -> dict[int, float]:
        return {rid: toks[0][0] for rid, toks in self.tokens.items() if toks}

    def served(self, rid: int) -> np.ndarray:
        return np.asarray([t for _, t in self.tokens[rid]], np.int32)
