"""Paged decode attention: one new token per slot against K/V pages read in
place from the serving pool.

The pool keeps its serving layout, ``(lead, num_pages, page, KV, D)``: one
physical-page axis shared by every slot, ``lead`` stacking the layers. Each
slot's page-table row, its position and the layer index arrive as scalar
prefetch. For slot ``b`` the kernel walks pages ``0 .. pos[b] // page`` of
its table row, ``ppb`` pages a block, with the next block's page DMAs in
flight while the current block computes; pages past the slot's last are
never fetched.

A page ``(page, KV, D)`` is read as ``page * KV`` rows of ``D`` (row
``t * KV + j`` is token ``t``'s KV head ``j``), and all ``H`` query heads are
contracted with a block's rows in one matmul. Query head ``h`` reads KV head
``h // G`` (``G = H // KV``, the layout of ``models.layers._grouped_
attention``): an additive head mask sends every other KV head's score to
``NEG_INF`` before the softmax, so each head attends only to its own group's
rows. Scores accumulate in f32 from the bf16 K as stored, scaled by
``1/sqrt(D)``; the softmax is online in f32, and P meets V in V's dtype with
an f32 sum, as XLA's default precision contracts them on a TPU.

The new token's own k and v are an input: they enter as position ``pos``
before any page is read, so the pool need not hold them yet, and every pool
row at or past ``pos`` is masked out (its V rows zeroed too, so stale or NaN
rows past ``pos`` cannot reach the output).

``paged_decode_attention`` is written for one slot and batches itself: a
``jax.vmap`` over slots (``serving.engine`` vmaps one slot's decode body)
becomes one kernel call with a grid over the slots, never a loop of calls.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30
# rows (tokens x KV heads) per block: 8 pages of 16 tokens x 8 heads fill
# eight 128-row MXU passes and keep a block's DMA near 512 KB for K and V
BLOCK_ROWS = 1024


def pages_per_block(page: int, kv_heads: int, max_blocks: int) -> int:
    """Pages a kernel block reads: ``BLOCK_ROWS`` rows, at most a table row."""
    return max(1, min(max_blocks, BLOCK_ROWS // (page * kv_heads)))


def _kernel(table_ref, pos_ref, layer_ref,  # scalar prefetch (SMEM)
            q_ref, kn_ref, vn_ref, hmask_ref, k_hbm, v_hbm,  # inputs
            o_ref,  # output
            kbuf, vbuf, sems,  # scratch
            *, page: int, ppb: int, kv: int, max_blocks: int, scale: float):
    b = pl.program_id(0)
    pos = pos_ref[b]
    layer = layer_ref[0]
    n_pages = pos // page + 1
    n_blk = (n_pages + ppb - 1) // ppb
    rows = ppb * page * kv

    def copies(blk, slot, j):
        pid = table_ref[b * max_blocks + blk * ppb + j]
        return (pltpu.make_async_copy(k_hbm.at[layer, pid], kbuf.at[slot, j],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, pid], vbuf.at[slot, j],
                                      sems.at[1, slot]))

    def each_page(blk, slot, act):
        """``act`` on the K and V copies of every page of block ``blk``
        that lies at or before the slot's last page."""
        def one(j, carry):
            for c in copies(blk, slot, j):
                act(c)
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_pages - blk * ppb), one, 0)

    def start(blk, slot):
        each_page(blk, slot, lambda c: c.start())

    def wait(blk, slot):
        each_page(blk, slot, lambda c: c.wait())

    start(0, 0)
    q = q_ref[...]  # (H, D)
    qf = q.astype(jnp.float32)
    # the new token, at position pos, opens the online softmax: m is finite
    # from here on, so a block whose rows are all masked adds exactly zero
    m = jnp.sum(qf * kn_ref[...].astype(jnp.float32), axis=-1, keepdims=True) * scale
    l = jnp.ones_like(m)
    acc = vn_ref[...].astype(jnp.float32)
    hmask = hmask_ref[...]  # (H, rows): 0 on the head's own KV rows, else NEG_INF

    def body(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < n_blk)
        def _():
            start(i + 1, 1 - slot)

        wait(i, slot)
        k = kbuf[slot].reshape(rows, -1)  # (rows, D), row t * KV + j
        v = vbuf[slot].reshape(rows, -1)
        live = (pos - i * ppb * page) * kv  # rows before position pos
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < live, s + hmask, NEG_INF)
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(row < live, v, jnp.zeros_like(v))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True), acc * corr + pv

    m, l, acc = jax.lax.fori_loop(0, n_blk, body, (m, l, acc))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_call(q, k_new, v_new, k_pages, v_pages, table, pos, layer, *,
                interpret: bool):
    """Batched call. q: (B, H, D); k_new/v_new: (B, KV, D); pages
    (lead, num_pages, page, KV, D); table (B, max_blocks); pos (B,); layer ()."""
    bsz, h, d = q.shape
    _, _, page, kv, _ = k_pages.shape
    g = h // kv
    max_blocks = table.shape[1]
    ppb = pages_per_block(page, kv, max_blocks)
    rows = ppb * page * kv
    # row r of a block holds KV head r % KV; query head h reads KV head h // G
    hmask = jnp.where(jnp.arange(h)[:, None] // g == jnp.arange(rows)[None, :] % kv,
                      0.0, NEG_INF).astype(jnp.float32)
    # the new token's k and v, per query head (B, H, D): KBs, not a K/V row
    kn = jnp.repeat(k_new, g, axis=1)
    vn = jnp.repeat(v_new, g, axis=1)
    flat = lambda t: t.reshape(*t.shape[:2], page * kv, d)  # noqa: E731
    per_slot = pl.BlockSpec((None, h, d), lambda b, *_: (b, 0, 0))
    kernel = functools.partial(_kernel, page=page, ppb=ppb, kv=kv,
                               max_blocks=max_blocks, scale=1.0 / math.sqrt(d))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz,),
            in_specs=[per_slot, per_slot, per_slot,
                      pl.BlockSpec((h, rows), lambda b, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_slot,
            scratch_shapes=[pltpu.VMEM((2, ppb, page * kv, d), k_pages.dtype),
                            pltpu.VMEM((2, ppb, page * kv, d), v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, kn, vn, hmask, flat(k_pages), flat(v_pages))


@functools.lru_cache(maxsize=None)
def _per_slot(interpret: bool):
    """The one-slot function for ``interpret``, whose vmap over slots is one
    batched kernel call (``jax.custom_batching.custom_vmap``)."""

    @jax.custom_batching.custom_vmap
    def one(q, k_new, v_new, k_pages, v_pages, table, pos, layer):
        return _paged_call(q[None], k_new[None], v_new[None], k_pages, v_pages,
                           table[None], pos[None], layer, interpret=interpret)[0]

    @one.def_vmap
    def _batched(axis_size, in_batched, q, k_new, v_new, k_pages, v_pages,
                 table, pos, layer):
        if any(in_batched[3:5]) or in_batched[7]:
            raise NotImplementedError(
                "paged decode attention batches over slots: the pool and the "
                "layer index are shared by every slot")
        grow = lambda x, b: x if b else jnp.broadcast_to(x, (axis_size, *x.shape))  # noqa: E731
        q, k_new, v_new, table, pos = (
            grow(x, b) for x, b in zip((q, k_new, v_new, table, pos),
                                       (*in_batched[:3], *in_batched[5:7])))
        return _paged_call(q, k_new, v_new, k_pages, v_pages, table, pos, layer,
                           interpret=interpret), True

    return one


def paged_decode_attention(q, k_new, v_new, k_pages, v_pages, table, pos, layer,
                           *, interpret: bool | None = None):
    """One slot's decode attention over its pages, read in place.

    q: (H, D), the query at position ``pos``; k_new/v_new: (KV, D), the new
    token's own k and v; k_pages/v_pages: (lead, num_pages, page, KV, D), the
    whole pool leaf; table: (max_blocks,) the slot's page-table row; pos: ()
    int32; layer: () int32 index into ``lead``. Returns (H, D) in q's dtype.
    ``H % KV == 0``. Under ``jax.vmap`` over q, k_new, v_new, table and pos
    it is one kernel call over all slots.
    """
    return _per_slot(resolve_interpret(interpret))(
        q, k_new, v_new, k_pages, v_pages, table, jnp.asarray(pos, jnp.int32),
        jnp.asarray(layer, jnp.int32))
