"""Core transformer layers: norms, RoPE, GQA & MLA attention, MLP.

Pure functional: each module exposes ``*_defs(cfg) -> ParamDef tree`` and
``*_apply(params, ...) -> array``. Attention provides three execution paths
(a generator design-point axis, DESIGN.md §2):

  naive   — full (S×S) score matrix; fine for short sequences
  chunked — lax.scan over KV blocks with online softmax ("flash" dataflow in
            pure jnp) — bounded memory for 32k prefill; lowers on any backend
  decode  — single-query attention against a KV cache

The Pallas flash kernel (repro.kernels.flash_attention) implements the same
online-softmax dataflow with explicit VMEM BlockSpecs for the TPU target.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.paged import PagedRows
from repro.models.params import ParamDef
from repro.models.quant import qeinsum
from repro.sharding.rules import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_defs(dim: int) -> dict:
    return {"scale": ParamDef((dim,), (None,), init="ones", dtype=jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(x.dtype)


def layernorm_defs(dim: int) -> dict:
    return {
        "scale": ParamDef((dim,), (None,), init="ones", dtype=jnp.float32),
        "bias": ParamDef((dim,), (None,), init="zeros", dtype=jnp.float32),
    }


def layernorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotate-half RoPE; positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core: naive / chunked online-softmax / decode
# ---------------------------------------------------------------------------
def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, KV, D) → (B, S, KV·groups, D) for GQA score einsums."""
    if groups == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, d)).reshape(
        b, s, kv * groups, d
    )


def attention_naive(q, k, v, *, causal: bool, q_offset: int = 0) -> jax.Array:
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,D). Full score matrix."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(d)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def attention_chunked(q, k, v, *, causal: bool, chunk: int = 1024) -> jax.Array:
    """Online-softmax over KV chunks — flash-attention dataflow in jnp.

    Memory: O(Sq·chunk) scores instead of O(Sq·Sk). Lowers to a lax.scan, so
    XLA schedules it as a loop (and on TPU the Pallas kernel replaces it).
    """
    b, sq, h, d = q.shape
    dv = v.shape[-1]  # may differ from d (MLA: qk 192, v 128)
    sk, kvh = k.shape[1], k.shape[2]
    if sk % chunk != 0:
        return attention_naive(q, k, v, causal=causal)
    g = h // kvh
    nchunks = sk // chunk
    kc = k.reshape(b, nchunks, chunk, kvh, d)
    vc = v.reshape(b, nchunks, chunk, kvh, dv)
    qf = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(d)
    qpos = jnp.arange(sq)

    def step(carry, inp):
        m, l, acc = carry
        idx, kb, vb = inp  # kb/vb: (b, chunk, kvh, d)
        kb = _repeat_kv(kb, g)  # (b, chunk, h, d)
        vb = _repeat_kv(vb, g)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32)) * scale
        if causal:
            kpos = idx * chunk + jnp.arange(chunk)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(nchunks), kc.swapaxes(0, 1), vc.swapaxes(0, 1))
    )
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.swapaxes(1, 2).astype(q.dtype)  # (B,Sq,H,D)


def _grouped_attention(q, k_cache, v_cache, qpos) -> jax.Array:
    """q: (B,T,H,D) queries at positions ``qpos`` (T,); caches (B,Smax,KV,D).

    Query head ``j*G + i`` reads KV head ``j`` (G = H // KV, from the
    shapes), the layout ``_repeat_kv`` gives, but q is viewed as
    (B,T,KV,G,D) and each KV head is contracted with its group in place:
    K and V are read once in their own dtype, with no copy repeated to H
    heads and no f32 copy. Scores accumulate in f32 (bf16 products are
    exact there), the softmax is f32, and P stays f32 against V. The
    sequence-sharding pins are ``attention_decode``'s.
    """
    b, t, h, d = q.shape
    kvh = k_cache.shape[2]
    qg = q.reshape(b, t, kvh, h // kvh, d)
    k = constrain(k_cache, ("batch", "kv_seq", None, None))
    v = constrain(v_cache, ("batch", "kv_seq", None, None))
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    s = constrain(s / jnp.sqrt(d), ("batch", None, None, None, "kv_seq"))
    valid = jnp.arange(k_cache.shape[1])[None, :] <= qpos[:, None]  # (T, Smax)
    s = jnp.where(valid, s, NEG_INF)
    p = constrain(jax.nn.softmax(s, axis=-1), ("batch", None, None, None, "kv_seq"))
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    out = constrain(out.reshape(b, t, h, v_cache.shape[-1]), ("batch", None, None, None))
    return out.astype(q.dtype)


def attention_decode(q, k_cache, v_cache, pos) -> jax.Array:
    """q: (B,1,H,D); caches: (B,Smax,KV,D); pos: scalar index of the new token.

    Attends over cache[0..pos] inclusive (cache already updated at pos),
    each KV head against its group of H // KV query heads in place
    (``_grouped_attention``): the cache is never repeated to H heads nor
    copied to f32.

    Flash-decoding dataflow: the cache's SEQUENCE axis is the sharded one
    ("kv_seq" → "model"), so K/V stay pinned to ("batch", "kv_seq", ·, ·)
    and the (B,KV,G,1,Smax) scores and probabilities to ("batch", ·, ·, ·,
    "kv_seq") — without the pins, GSPMD propagates the output projection's
    heads-sharding backwards and re-shards (= fully all-gathers) the K/V
    cache, which dominates the decode step (measured: 2×67 MB × layers per
    step on granite-3-8b × 32k). The only collectives left are the softmax
    partials and the (B,1,H,D) output all-reduce.
    """
    with jax.named_scope("attention"):
        return _grouped_attention(q, k_cache, v_cache, jnp.reshape(pos, (1,)))


def run_attention(cfg: ArchConfig, q, k, v, *, causal: bool) -> jax.Array:
    impl = cfg.attention_impl
    sq = q.shape[1]
    if impl == "auto":
        impl = "chunked" if sq > 2 * cfg.attn_chunk else "naive"
    with jax.named_scope("attention"):
        if impl == "chunked":
            return attention_chunked(q, k, v, causal=causal, chunk=cfg.attn_chunk)
        return attention_naive(q, k, v, causal=causal)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------
def gqa_defs(cfg: ArchConfig, *, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", None)),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", None)),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", None)),
        "wo": ParamDef((h, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((kv, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef((kv, hd), ("kv_heads", None), init="zeros")
    return defs


def gqa_project_qkv(params, x, cfg: ArchConfig, positions, *, rope: bool = True):
    with jax.named_scope("proj"):
        q = qeinsum("bsd,dhe->bshe", x, params["wq"])
        k = qeinsum("bsd,dhe->bshe", x, params["wk"])
        v = qeinsum("bsd,dhe->bshe", x, params["wv"])
        if cfg.qkv_bias:
            q = q + params["bq"]
            k = k + params["bk"]
            v = v + params["bv"]
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q = constrain(q, ("batch", None, "heads", None))
        k = constrain(k, ("batch", None, "kv_heads", None))
        v = constrain(v, ("batch", None, "kv_heads", None))
        return q, k, v


def gqa_apply(params, x, cfg: ArchConfig, *, causal: bool = True, rope: bool = True):
    """Full-sequence GQA attention (train / prefill path)."""
    positions = jnp.arange(x.shape[1])[None, :]
    q, k, v = gqa_project_qkv(params, x, cfg, positions, rope=rope)
    out = run_attention(cfg, q, k, v, causal=causal)
    out = constrain(out, ("batch", None, "heads", None))
    return qeinsum("bshe,hed->bsd", out, params["wo"])


def gqa_cross_apply(params, x, kv_pair, cfg: ArchConfig):
    """Cross-attention (whisper decoder): kv_pair = (k, v) precomputed."""
    positions = jnp.arange(x.shape[1])[None, :]
    q = qeinsum("bsd,dhe->bshe", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    q = constrain(q, ("batch", None, "heads", None))
    k, v = kv_pair
    out = run_attention(cfg, q, k, v, causal=False)
    return qeinsum("bshe,hed->bsd", out, params["wo"])


def write_cache(cache, new, pos, cfg: ArchConfig, axis: int = 1):
    """Write a length-1 slice at ``pos`` along ``axis``.

    "dus"    — dynamic_update_slice. With the cache's sequence axis sharded
               over "model", GSPMD cannot place a dynamic-index update and
               falls back to involuntary full rematerialization (replicate →
               repartition): one full cache copy over the ICI per layer.
    "onehot" — masked select against an iota: every op is elementwise in the
               sharded layout, so each device rewrites only its own shard —
               no collective at all. Costs one extra cache read+write of
               HBM; wins whenever the cache shard ≪ ICI copy (hillclimb H1
               of the decode cell, EXPERIMENTS.md §Perf).
    """
    with jax.named_scope("kv_pages"):
        new = new.astype(cache.dtype)
        if cfg.cache_update == "onehot":
            mask = jax.lax.broadcasted_iota(jnp.int32, cache.shape, axis) == pos
            return jnp.where(mask, jnp.broadcast_to(new, cache.shape), cache)
        return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=axis)


def write_cache_span(cache, new, pos, axis: int = 1):
    """Write a length-T slice starting at ``pos`` along ``axis``.

    The chunked-prefill path always uses dynamic_update_slice: chunk writes
    are a host-driven serving flow over a pool-resident cache, not the
    TP-sharded decode step that needs the onehot variant."""
    with jax.named_scope("kv_pages"):
        return jax.lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), pos, axis=axis
        )


def attention_chunk(q, k_cache, v_cache, pos) -> jax.Array:
    """Chunk attention: q: (B,T,H,D) queries at positions pos..pos+T-1
    against caches (B,Smax,KV,D) already updated through pos+T-1.

    Each query attends causally over cache[0..pos+i]; rows past the written
    prefix are dead data and masked out. This is ``attention_decode``
    generalized from one query to a chunk of T, through the same grouped
    contraction and sharding pins (``_grouped_attention``): scores are
    (B,KV,G,T,Smax) in f32, and K/V are read in place.

    The strict positional mask is also what makes speculative verify
    windows rollback-free for attention caches: rows written for REJECTED
    candidates sit past the committed prefix, so the next window's queries
    never see them and its writes overwrite them — acceptance only moves
    the slot's position, no cache surgery (``models.model.decode_verify``).
    """
    with jax.named_scope("attention"):
        return _grouped_attention(q, k_cache, v_cache, pos + jnp.arange(q.shape[1]))


def gqa_chunk_apply(params, x, cache_k, cache_v, pos, cfg: ArchConfig, *, rope: bool = True,
                    q_scale: float = 1.0):
    """Chunked-prefill attention: T prompt tokens appended at ``pos``.

    x: (B,T,D). Returns (out, k_cache, v_cache) with the chunk's K/V written
    into the cache span [pos, pos+T); given in-place ``PagedRows`` (one
    decode token, ``models.paged``) it reads the pages and returns the new
    k and v rows instead, as ``gqa_decode_apply`` does. ``q_scale``
    multiplies the queries (in f32, before they are rounded back), so the
    scores are scaled by ``q_scale / sqrt(head_dim)``."""
    positions = (pos + jnp.arange(x.shape[1]))[None, :]
    q, k_new, v_new = gqa_project_qkv(params, x, cfg, positions, rope=rope)
    if q_scale != 1.0:
        with jax.named_scope("proj"):
            q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    if isinstance(cache_k, PagedRows):  # one decode token over pages in place
        out = cache_k.attend(q, k_new, v_new, cache_v, pos)
        k_cache, v_cache = k_new, v_new
    else:
        k_cache = write_cache_span(cache_k, k_new, pos)
        v_cache = write_cache_span(cache_v, v_new, pos)
        out = attention_chunk(q, k_cache, v_cache, pos)
    with jax.named_scope("proj"):
        out = qeinsum("bshe,hed->bsd", out, params["wo"])
    return out, k_cache, v_cache


def gqa_decode_apply(params, x, cache_k, cache_v, pos, cfg: ArchConfig, *, rope: bool = True):
    """One-token decode. x: (B,1,D). Returns (out, k_cache, v_cache), the
    caches updated at ``pos``; for in-place ``PagedRows`` caches
    (``models.paged``) the pages are read where they lie and the new k and v
    rows, (B,1,KV,D), come back in the caches' place.

    Flash-decoding sharding: the KV cache is SEQUENCE-sharded over "model"
    while q comes out of the projection heads-sharded over the same axis —
    left alone, GSPMD reconciles the conflict by all-gathering the whole
    K/V cache (67 MB × 2 × layers per step, the dominant decode collective).
    Constraining the per-step q/k_new/v_new to be replicated (they are a
    single token — KBs) keeps the score/PV contractions sequence-sharded:
    each device attends over its own cache shard and only the (B,1,H,hd)
    partial output is all-reduced. See EXPERIMENTS.md §Perf (decode cell).
    """
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q, k_new, v_new = gqa_project_qkv(params, x, cfg, positions, rope=rope)
    q = constrain(q, ("batch", None, None, None))
    k_new = constrain(k_new, ("batch", None, None, None))
    v_new = constrain(v_new, ("batch", None, None, None))
    if isinstance(cache_k, PagedRows):
        out = cache_k.attend(q, k_new, v_new, cache_v, pos)
        k_cache, v_cache = k_new, v_new
    else:
        k_cache = write_cache(cache_k, k_new, pos, cfg)
        v_cache = write_cache(cache_v, v_new, pos, cfg)
        out = attention_decode(q, k_cache, v_cache, pos)
    with jax.named_scope("proj"):
        out = qeinsum("bshe,hed->bsd", out, params["wo"])
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V3) — compressed-KV attention variant
# ---------------------------------------------------------------------------
def mla_defs(cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": ParamDef((d, m.q_lora_rank), ("embed", None)),
        "q_norm": rmsnorm_defs(m.q_lora_rank),
        "wq_b": ParamDef((m.q_lora_rank, h, qd), (None, "heads", None)),
        "wkv_a": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None)),
        "kv_norm": rmsnorm_defs(m.kv_lora_rank),
        "wk_b": ParamDef((m.kv_lora_rank, h, m.qk_nope_head_dim), (None, "heads", None)),
        "wv_b": ParamDef((m.kv_lora_rank, h, m.v_head_dim), (None, "heads", None)),
        "wo": ParamDef((h, m.v_head_dim, d), ("heads", None, "embed")),
    }


def _mla_q(params, x, cfg, positions):
    m = cfg.mla
    cq = qeinsum("bsd,dr->bsr", x, params["wq_a"])
    cq = rmsnorm(params["q_norm"], cq, cfg.norm_eps)
    q = qeinsum("bsr,rhe->bshe", cq, params["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim :], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(params, x, cfg, positions):
    m = cfg.mla
    ckv = qeinsum("bsd,dr->bsr", x, params["wkv_a"])
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank :]
    c = rmsnorm(params["kv_norm"], c, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c, k_rope  # (B,S,r), (B,S,rope_d)


def mla_apply(params, x, cfg: ArchConfig, *, causal: bool = True):
    """Train/prefill MLA: decompress K/V per head, then standard attention."""
    m = cfg.mla
    positions = jnp.arange(x.shape[1])[None, :]
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    c, k_rope = _mla_ckv(params, x, cfg, positions)
    k_nope = qeinsum("bsr,rhe->bshe", c, params["wk_b"])
    v = qeinsum("bsr,rhe->bshe", c, params["wv_b"])
    h = cfg.num_heads
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (*k_rope.shape[:2], h, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "heads", None))
    v = constrain(v, ("batch", None, "heads", None))
    # kv heads == q heads here (decompressed)
    out = run_attention(cfg, q, k, v, causal=causal)
    out = constrain(out, ("batch", None, "heads", None))
    return qeinsum("bshe,hed->bsd", out, params["wo"])


def mla_decode_apply(params, x, cache_c, cache_krope, pos, cfg: ArchConfig):
    """Absorbed-MLA decode: attend directly over the compressed cache.

    q_nope is absorbed through wk_b (scores) and the output through wv_b, so
    the per-step cost is O(S·r) instead of O(S·h·d) — the memory-optimized
    attention variant in the generator's design space.
    """
    m = cfg.mla
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(params, x, cfg, positions)  # (B,1,H,*)
    c_new, krope_new = _mla_ckv(params, x, cfg, positions)  # (B,1,r), (B,1,rd)
    # pin the flash-decoding dataflow (see attention_decode docstring): the
    # compressed cache stays sequence-sharded; per-step tensors replicate
    q_nope = constrain(q_nope, ("batch", None, None, None))
    q_rope = constrain(q_rope, ("batch", None, None, None))
    c_new = constrain(c_new, ("batch", None, None))
    krope_new = constrain(krope_new, ("batch", None, None))
    cache_c = write_cache(cache_c, c_new, pos, cfg)
    cache_krope = write_cache(cache_krope, krope_new, pos, cfg)
    # absorb: q_abs (B,1,H,r) = q_nope @ wk_b^T
    q_abs = qeinsum("bqhe,rhe->bqhr", q_nope, params["wk_b"])
    s = jnp.einsum("bqhr,bkr->bhqk", q_abs.astype(jnp.float32), cache_c.astype(jnp.float32))
    s = s + jnp.einsum(
        "bqhe,bke->bhqk", q_rope.astype(jnp.float32), cache_krope.astype(jnp.float32)
    )
    s = constrain(s / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim),
                  ("batch", None, None, "kv_seq"))
    valid = jnp.arange(cache_c.shape[1]) <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = constrain(jax.nn.softmax(s, axis=-1), ("batch", None, None, "kv_seq"))
    o_c = jnp.einsum("bhqk,bkr->bqhr", p, cache_c.astype(jnp.float32)).astype(x.dtype)
    o_c = constrain(o_c, ("batch", None, None, None))
    out = qeinsum("bqhr,rhe->bqhe", o_c, params["wv_b"])
    out = qeinsum("bshe,hed->bsd", out, params["wo"])
    return out, cache_c, cache_krope


def mla_chunk_apply(params, x, cache_c, cache_krope, pos, cfg: ArchConfig):
    """Absorbed-MLA chunk: ``mla_decode_apply`` generalized to T queries.

    The chunk's compressed (c, k_rope) rows are written at [pos, pos+T) and
    every query attends causally over the compressed cache — same absorbed
    dataflow the decode step uses, so chunked prefill and decode share one
    numerical path."""
    m = cfg.mla
    b, t, _ = x.shape
    positions = (pos + jnp.arange(t))[None, :]
    q_nope, q_rope = _mla_q(params, x, cfg, positions)  # (B,T,H,*)
    c_new, krope_new = _mla_ckv(params, x, cfg, positions)  # (B,T,r), (B,T,rd)
    cache_c = write_cache_span(cache_c, c_new, pos)
    cache_krope = write_cache_span(cache_krope, krope_new, pos)
    q_abs = qeinsum("bqhe,rhe->bqhr", q_nope, params["wk_b"])
    s = jnp.einsum("bqhr,bkr->bhqk", q_abs.astype(jnp.float32), cache_c.astype(jnp.float32))
    s = s + jnp.einsum(
        "bqhe,bke->bhqk", q_rope.astype(jnp.float32), cache_krope.astype(jnp.float32)
    )
    s = s / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    qpos = pos + jnp.arange(t)
    valid = jnp.arange(cache_c.shape[1])[None, :] <= qpos[:, None]
    s = jnp.where(valid[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bhqk,bkr->bqhr", p, cache_c.astype(jnp.float32)).astype(x.dtype)
    out = qeinsum("bqhr,rhe->bqhe", o_c, params["wv_b"])
    out = qeinsum("bshe,hed->bsd", out, params["wo"])
    return out, cache_c, cache_krope


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU) with activation-variant axis
# ---------------------------------------------------------------------------
def mlp_defs(cfg: ArchConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.activation == "gelu":  # classic 2-matrix MLP (whisper)
        return {
            "wi": ParamDef((d, f), ("embed", "mlp")),
            "bi": ParamDef((f,), ("mlp",), init="zeros"),
            "wo": ParamDef((f, d), ("mlp", "embed")),
            "bo": ParamDef((d,), (None,), init="zeros"),
        }
    return {  # SwiGLU
        "wg": ParamDef((d, f), ("embed", "mlp")),
        "wu": ParamDef((d, f), ("embed", "mlp")),
        "wd": ParamDef((f, d), ("mlp", "embed")),
    }


def mlp_apply(params, x, cfg: ArchConfig):
    with jax.named_scope("mlp"):
        from repro.models.activations import get_activation

        act = get_activation(cfg.activation, cfg.activation_impl)
        if "wi" in params:
            h = qeinsum("bsd,df->bsf", x, params["wi"]) + params["bi"].astype(x.dtype)
            h = constrain(act(h), ("batch", None, "mlp"))
            return qeinsum("bsf,fd->bsd", h, params["wo"]) + params["bo"].astype(x.dtype)
        g = qeinsum("bsd,df->bsf", x, params["wg"])
        u = qeinsum("bsd,df->bsf", x, params["wu"])
        h = constrain(act(g) * u, ("batch", None, "mlp"))
        return qeinsum("bsf,fd->bsd", h, params["wd"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_defs(cfg: ArchConfig) -> dict:
    v = cfg.padded_vocab
    defs = {"tokens": ParamDef((v, cfg.d_model), ("vocab", "embed"), init="normal")}
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.d_model, v), ("embed", "vocab"))
    return defs


def embed_apply(params, tokens, cfg: ArchConfig):
    x = jnp.take(params["tokens"], tokens, axis=0)
    return constrain(x, ("batch", None, None))


def unembed_apply(params, x, cfg: ArchConfig):
    w = params.get("unembed")
    if w is None:
        w = params["tokens"].T
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return constrain(logits, ("batch", None, "vocab"))
