"""Paged decode attention reads each slot's K/V pages in place.

``kernels.paged_attention.paged_decode_attention`` runs here in the Pallas
interpreter, vmapped over slots as the decode step calls it, against its
plain jnp reference (``kernels.ref.paged_decode_attention_ref``) and against
``models.layers._grouped_attention`` over the slot's gathered row. The pool
holds two layers; each slot's pages are scattered through it; positions sit
at 0, at the last and first rows of a page and at the table's last row; one
slot is inactive on the scratch page; and every pool row at or past a
slot's position, the scratch page included, holds NaN.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ref import paged_decode_attention_ref
from repro.models.layers import _grouped_attention

MAX_BLOCKS = 10  # at page 16 and 8 KV heads, two kernel blocks of 8 pages
LAYER = 1


def _case(heads, kv_heads, page, dim, seed=0):
    """(args of one vmapped call, live-row mask of the pool)."""
    pos = np.array([0, 2 * page - 1, 2 * page, MAX_BLOCKS * page - 1, 0], np.int32)
    slots = pos.size
    n_pages = slots * MAX_BLOCKS + 1
    rng = np.random.default_rng(seed)
    table = (1 + rng.permutation(n_pages - 1)).reshape(slots, MAX_BLOCKS).astype(np.int32)
    table[-1] = 0  # inactive: every block on scratch page 0
    live = (np.arange(MAX_BLOCKS * page)[None] < pos[:, None]).reshape(slots, MAX_BLOCKS, page)
    rows = np.zeros((2, n_pages, page), bool)
    rows[:, table[:-1]] = live[None, :-1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    dead = jnp.asarray(~rows)[..., None, None]
    pool = (2, n_pages, page, kv_heads, dim)
    k_pages = jnp.where(dead, jnp.nan, normal(ks[0], pool))
    v_pages = jnp.where(dead, jnp.nan, normal(ks[1], pool))
    q = normal(ks[2], (slots, heads, dim))
    k_new = normal(ks[3], (slots, kv_heads, dim))
    v_new = normal(ks[4], (slots, kv_heads, dim))
    return (q, k_new, v_new, k_pages, v_pages, jnp.asarray(table), jnp.asarray(pos)), rows


def _per_slot(fn, args):
    q, k_new, v_new, k_pages, v_pages, table, pos = args
    return jax.jit(jax.vmap(lambda q, kn, vn, t, p: fn(q, kn, vn, k_pages, v_pages, t, p, LAYER)))(
        q, k_new, v_new, table, pos)


def _grouped(args, rows):
    """``_grouped_attention`` over each slot's gathered row, the new token
    written at ``pos`` and the dead rows zeroed (its mask keeps them out;
    zeros keep NaN out of P x V)."""
    q, k_new, v_new, k_pages, v_pages, table, pos = args
    live = jnp.asarray(rows)[LAYER][table].reshape(table.shape[0], -1)[..., None, None]
    at = jnp.arange(live.shape[1])[None, :, None, None] == pos[:, None, None, None]

    def row(pages, new):
        r = pages[LAYER][table].reshape(table.shape[0], -1, *pages.shape[3:])
        return jnp.where(at, new[:, None], jnp.where(live, r, 0))

    k, v = row(k_pages, k_new), row(v_pages, v_new)
    return jax.vmap(lambda q, k, v, p: _grouped_attention(
        q[None, None], k[None], v[None], p[None])[0, 0])(q, k, v, pos)


@pytest.mark.parametrize("dim", [64, 128])
@pytest.mark.parametrize("page", [4, 16])
@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (8, 8), (4, 1)],
                         ids=["G4", "G1-MHA", "KV1-MQA"])
def test_kernel_reads_pages_in_place(heads, kv_heads, page, dim):
    """Finite wherever the pool holds NaN past a slot's position, and equal
    to both references within two bf16 spacings of the largest output: the
    kernel's P meets V in bf16 with an f32 sum, the references keep P in
    f32."""
    args, rows = _case(heads, kv_heads, page, dim)
    got = np.asarray(_per_slot(lambda *a: paged_decode_attention(*a, interpret=True), args),
                     np.float32)
    want = np.asarray(_per_slot(paged_decode_attention_ref, args), np.float32)
    grouped = np.asarray(_grouped(args, rows), np.float32)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    tol = 2.0 ** -6 * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    np.testing.assert_allclose(got, grouped, rtol=0, atol=tol)
    np.testing.assert_allclose(want, grouped, rtol=0, atol=2.0 ** -7 * np.abs(want).max())


def _kernel_grids(jaxpr):
    """The grid of every ``pallas_call`` in a jaxpr, nested ones included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                grids += _kernel_grids(inner)
    return grids


def test_vmap_over_slots_is_one_kernel_call():
    """A vmap over slots batches the one-slot function into a single kernel
    call with a grid over the slots, not a loop of calls."""
    args, _ = _case(8, 2, 4, 64)
    jaxpr = jax.make_jaxpr(lambda *a: _per_slot(
        lambda *b: paged_decode_attention(*b, interpret=True), a))(*args)
    assert _kernel_grids(jaxpr.jaxpr) == [(args[0].shape[0],)]


def test_paged_decode_serves_the_same_tokens_through_the_kernel(monkeypatch):
    """The engine's paged decode step with the kernel (interpreted) in place
    of its jnp reference serves the same greedy tokens, for a dense family
    (layer scan) and for hybrid_moe (attention among Mamba-2 layers, its
    view sliced to one layer)."""
    from repro.configs import get_reduced_config
    from repro.models import paged
    from repro.models.model import init_model
    from repro.serving.engine import InferenceEngine, ServeConfig
    from repro.serving.load import bursty_stream
    from repro.serving.scheduler import ContinuousBatchingScheduler

    def served(arch, kernel: bool):
        monkeypatch.setattr(paged, "default_interpret", lambda: not kernel)
        cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
        params = jax.tree.map(lambda t: t.astype(jnp.float32),
                              init_model(cfg, jax.random.PRNGKey(0)))
        eng = InferenceEngine(cfg, params=params, sc=ServeConfig(
            max_batch=2, max_len=32, paged=True, page_size=4))
        reqs = bursty_stream(4, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=3,
                             vocab_size=cfg.vocab_size, prompt_lens=(4, 9),
                             new_tokens=(2, 8))
        rep = ContinuousBatchingScheduler(eng, policy="adaptive").run(reqs)
        return {r.rid: r.tokens for r in rep.records}

    for arch in ("granite-3-8b", "granite-4.0-h-small"):
        assert served(arch, kernel=True) == served(arch, kernel=False), arch
