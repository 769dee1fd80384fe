"""Paged-cache views for the decode and verify bodies (``serving/pages.py``).

The per-slot bodies in ``models.model`` are layout-agnostic: they see one
slot's cache and write positions [pos, pos+T). The paged serving path hands
them a ``PagedRows`` per paged leaf instead of an array, and a view is used
in one of two ways:

* **In place** (the decode step's GQA K/V leaves, T=1): the attention reads
  the slot's pages straight from the pool through ``kernels.paged_
  attention``, with the new token's k and v merged at ``pos``, and the layer
  returns only that new row. The engine writes the rows into their pages
  after the step. No per-slot row is built and nothing is cut out of one.
* **Gathered** (MLA's latent leaves, int8 pages, the speculative verify
  window): the layer's pages are gathered into a VIRTUAL contiguous row
  through the slot's table row, the unchanged body runs on it, and the
  written blocks are cut back out for a scatter by page id. Rows gathered
  from unmapped blocks (the scratch page) are garbage, but the positional
  masks select NEG_INF for every position > pos before the softmax.

Either way the view holds the whole pool leaf and a layer index: slicing a
view, or a layer scan over it, moves the index and never copies a layer's
page array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ref import paged_decode_attention_ref
from repro.kernels.runtime import default_interpret


@jax.tree_util.register_pytree_node_class
class PagedRows:
    """One slot's view of a paged cache leaf.

    pages: (lead, num_pages, page, *tail) physical pages, the whole leaf
    (int8 payloads under ``kv_quant``, with their f32 ``scales`` pages; else
    None); table: (max_blocks,) the slot's page-table row; layer: int32 index
    into ``lead`` — () for one layer, (n,) for a stack of n. ``in_place``
    views are read by the decode attention kernel; the others are gathered,
    and ``n_blocks`` whole blocks from the block holding ``pos`` are what the
    step writes back (1 for decode, the verify window's span for verify).
    """

    def __init__(self, pages, scales, table, layer, *, in_place: bool,
                 n_blocks: int, page: int):
        self.pages, self.scales, self.table, self.layer = pages, scales, table, layer
        self.in_place, self.n_blocks, self.page = in_place, n_blocks, page

    def tree_flatten(self):
        return ((self.pages, self.scales, self.table, self.layer),
                (self.in_place, self.n_blocks, self.page))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, in_place=aux[0], n_blocks=aux[1], page=aux[2])

    def at(self, layer):
        """The same view over other layers (``layer`` indexes ``lead``)."""
        return PagedRows(self.pages, self.scales, self.table, layer,
                         in_place=self.in_place, n_blocks=self.n_blocks, page=self.page)

    def __getitem__(self, idx):
        """Slice the layers the view covers (deepseek's dense/MoE split,
        hybrid's per-application shared attention, hybrid_moe's attention
        layers): only the index moves."""
        return self.at(self.layer[idx])

    def attend(self, q, k_new, v_new, v_rows: PagedRows, pos):
        """Decode attention of q (1, 1, H, D) at ``pos`` over this K view's
        pages and ``v_rows``' with the new token's k_new/v_new (1, 1, KV, D)
        merged at ``pos``: (1, 1, H, D). The kernel on a TPU; under the
        interpreter its jnp reference, which gathers the row."""
        fn = paged_decode_attention_ref if default_interpret() else paged_decode_attention
        with jax.named_scope("attention"):
            out = fn(q[0, 0], k_new[0, 0], v_new[0, 0], self.pages, v_rows.pages,
                     self.table, pos, self.layer)
        return out[None, None]

    def gather(self):
        """One layer's virtual contiguous row: (1, max_blocks * page, *tail)."""
        with jax.named_scope("kv_pages"):
            g = self.pages[self.layer, self.table]  # (max_blocks, page, *tail)
            if self.scales is not None:
                from repro.serving.kv_cache import dequantize_kv

                g = dequantize_kv(g, self.scales[self.layer, self.table])
            return g.reshape(1, -1, *g.shape[2:])

    def written(self, new, pos):
        """What the step writes back of one layer: the new row (1, *tail) of
        an in-place view, else the written blocks (n_blocks, page, *tail) of
        the updated virtual row."""
        if self.in_place:
            return new[:, 0]
        with jax.named_scope("kv_pages"):
            return paged_written_blocks(new, pos // self.page, self.n_blocks,
                                        self.page)[:, 0]


def cache_view(c):
    """What a layer body reads of a cache leaf: arrays and in-place views as
    they are, other views gathered into their virtual row."""
    return c.gather() if isinstance(c, PagedRows) and not c.in_place else c


def cache_written(new, c, pos):
    return c.written(new, pos) if isinstance(c, PagedRows) else new


def paged_written_blocks(row, first_blk, n_blocks, page_size):
    """Extract ``n_blocks`` whole blocks of a virtual cache row starting at
    traced block index ``first_blk``.

    row: (lead, S, *tail) → (n_blocks, lead, page_size, *tail). The row is
    padded by the slice width first so ``dynamic_slice`` never clamps the
    start (a clamp would silently misalign block boundaries)."""
    span = n_blocks * page_size
    widths = [(0, 0), (0, span)] + [(0, 0)] * (row.ndim - 2)
    padded = jnp.pad(row, widths)
    w = jax.lax.dynamic_slice_in_dim(padded, first_blk * page_size, span, axis=1)
    w = w.reshape(w.shape[0], n_blocks, page_size, *w.shape[2:])
    return jnp.moveaxis(w, 1, 0)


def verify_block_span(window: int, page_size: int) -> int:
    """Worst-case whole blocks a verify window of ``window`` tokens can touch
    (window starting at the last row of a block spills ceil((window-1)/page)
    more blocks)."""
    return 1 + (window + page_size - 2) // page_size
