"""Decode-cache definitions per architecture family.

Layouts (ParamDef trees, so the same machinery that shards weights shards
caches — logical axes drive the mesh mapping):

  GQA families    k/v: (L, B, S, KV, hd)          bf16
  MLA (deepseek)  c: (L, B, S, r), krope: (L, B, S, rope_d)  — compressed
  SSM (mamba2)    conv: (L, B, W-1, d_inner+2N) bf16, state: (L, B, H, P, N) f32
  hybrid (zamba2) SSM caches + shared-attn k/v: (n_apps, B, S, KV, hd)
  hybrid_moe      SSM caches over its Mamba-2 layers + k/v over its
                  attention layers: (n_attn, B, S, KV, hd)
  audio (whisper) decoder self k/v + static cross k/v over encoder frames

Sharding: batch → DP axes (when divisible), the KV *sequence* axis → "model"
(flash-decoding style: each TP device holds a sequence slice and GSPMD turns
the softmax into partial-reduction collectives). Sharding S instead of
kv_heads is what keeps GQA archs with few KV heads (granite-34b has kv=1)
memory-feasible at 32k–500k contexts — kv_heads can't split 16 ways, the
sequence always can.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamDef


def _kv(num_layers: int, b: int, s: int, kv: int, hd: int, dtype) -> ParamDef:
    return ParamDef(
        (num_layers, b, s, kv, hd),
        ("layers", "batch", "kv_seq", "kv_heads", None),
        init="zeros",
        dtype=dtype,
    )


def cache_defs(cfg: ArchConfig, *, batch: int, max_len: int) -> dict:
    f = cfg.family
    l, b, s = cfg.num_layers, batch, max_len
    hd = cfg.resolved_head_dim
    dt = cfg.kv_dtype or cfg.dtype  # fp8 KV: halves decode cache reads
    if f in ("dense", "vlm") or (f == "moe" and cfg.mla is None):
        return {"k": _kv(l, b, s, cfg.num_kv_heads, hd, dt), "v": _kv(l, b, s, cfg.num_kv_heads, hd, dt)}
    if f == "moe":  # deepseek MLA — compressed cache
        m = cfg.mla
        return {
            "c": ParamDef((l, b, s, m.kv_lora_rank), ("layers", "batch", "kv_seq", None), init="zeros", dtype=dt),
            "krope": ParamDef((l, b, s, m.qk_rope_head_dim), ("layers", "batch", "kv_seq", None), init="zeros", dtype=dt),
        }
    if f in ("ssm", "hybrid"):
        out = _recurrent(cfg, l, b, dt)
        if f == "hybrid":
            n_apps = math.ceil(cfg.num_layers / cfg.attn_every)
            out["shared_k"] = _kv(n_apps, b, s, cfg.num_kv_heads, hd, dt)
            out["shared_v"] = _kv(n_apps, b, s, cfg.num_kv_heads, hd, dt)
        return out
    if f == "hybrid_moe":
        n_attn = len(cfg.attention_layers)
        return {**_recurrent(cfg, l - n_attn, b, dt),
                "k": _kv(n_attn, b, s, cfg.num_kv_heads, hd, dt),
                "v": _kv(n_attn, b, s, cfg.num_kv_heads, hd, dt)}
    if f == "audio":
        return {
            "k": _kv(l, b, s, cfg.num_kv_heads, hd, dt),
            "v": _kv(l, b, s, cfg.num_kv_heads, hd, dt),
            "cross_k": _kv(l, b, cfg.encoder_seq, cfg.num_kv_heads, hd, dt),
            "cross_v": _kv(l, b, cfg.encoder_seq, cfg.num_kv_heads, hd, dt),
        }
    raise ValueError(f)


def _recurrent(cfg: ArchConfig, layers: int, b: int, dt) -> dict:
    """Mamba-2 state rows: conv tails (layers, B, W-1, d_inner+2N) and f32
    states (layers, B, H, P, N)."""
    sm = cfg.ssm
    d_in = sm.d_inner(cfg.d_model)
    nh = sm.num_heads(cfg.d_model)
    return {
        "conv": ParamDef(
            (layers, b, sm.conv_width - 1, d_in + 2 * sm.state_size),
            ("layers", "batch", None, None), init="zeros", dtype=dt,
        ),
        "state": ParamDef(
            (layers, b, nh, sm.head_dim, sm.state_size),
            ("layers", "batch", "ssm_heads", None, None), init="zeros", dtype=jnp.float32,
        ),
    }


def has_recurrent_state(cfg: ArchConfig) -> bool:
    """Whether the cache holds recurrent state rows (Mamba-2 conv tails and
    states). Such state is per slot and not positional: it is never paged,
    a prompt prefix cannot be shared through it, and a speculative verify
    must roll it back to the last accepted token."""
    return "state" in cache_defs(cfg, batch=1, max_len=1)


def paged_keys(cfg: ArchConfig) -> tuple[str, ...]:
    """Cache keys whose SEQUENCE axis (axis 2) is paged by ``serving/pages``.

    Everything per-slot and O(1)-in-sequence stays unpaged: SSM conv/state
    (recurrent, not positional) and audio cross K/V (fixed at encoder_seq).
    """
    f = cfg.family
    if f in ("dense", "vlm", "audio", "hybrid_moe") or (f == "moe" and cfg.mla is None):
        return ("k", "v")
    if f == "moe":
        return ("c", "krope")
    if f == "hybrid":
        return ("shared_k", "shared_v")
    if f == "ssm":
        return ()
    raise ValueError(f)


def page_defs(cfg: ArchConfig, *, num_pages: int, page_size: int,
              kv_quant: str | None = None) -> dict:
    """Paged layout for the sequence-dim cache leaves: ``(lead, num_pages,
    page_size, ...)`` — one shared physical-page axis in place of the
    per-slot (batch, seq) rectangle. Page index 0 is reserved as a scratch
    page by the pool (unmapped table entries point at it).

    ``kv_quant="int8"`` stores each paged payload as int8 with a companion
    f32 ``{key}_scale`` leaf of the payload shape minus its feature (last)
    axis — one symmetric scale per (page, row, head). Scales ride the same
    page axis as their payload, so every pure page-index operation (copy /
    zero / swap / restore) treats them as just more paged leaves.
    """
    if kv_quant not in (None, "int8"):
        raise ValueError(f"unsupported kv_quant {kv_quant!r}")
    defs = cache_defs(cfg, batch=num_pages, max_len=page_size)
    out = {}
    for key in paged_keys(cfg):
        d = defs[key]
        # the page axis is deliberately unsharded (pages migrate between
        # requests); the in-page seq axis keeps the flash-decoding mapping
        logical = (d.logical[0], None) + d.logical[2:]
        if kv_quant == "int8":
            out[key] = ParamDef(d.shape, logical, init="zeros", dtype=jnp.int8)
            out[f"{key}_scale"] = ParamDef(d.shape[:-1], logical[:-1],
                                           init="zeros", dtype=jnp.float32)
        else:
            out[key] = ParamDef(d.shape, logical, init="zeros", dtype=d.dtype)
    return out


def quantize_kv(x):
    """Symmetric per-row int8 quantization over the FEATURE (last) axis.

    Same convention as ``kernels.ref.quantize_rowwise`` (regression-pinned in
    tests): ``scale = max(|x|, 1e-8) / 127``, computed with ``jnp.maximum``
    so a NaN payload poisons its scale — int8 cannot carry the NaN itself,
    and the pool's fault hygiene watches the f32 scale leaves instead.
    Re-quantizing already-quantized rows is exactly idempotent (the max
    element maps back to ±127), so block-granular re-scatter per decode tick
    does not drift. Returns ``(q int8, scale f32 of x.shape[:-1])``.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of ``quantize_kv``: ``q * scale`` broadcast over the feature axis."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _defs_bytes(defs: dict) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return sum(math.prod(d.shape) * jnp.dtype(d.dtype).itemsize for d in leaves)


def cache_bytes(cfg: ArchConfig, *, batch: int, max_len: int) -> int:
    """HBM bytes of the contiguous layout: every slot owns max_len rows."""
    return _defs_bytes(cache_defs(cfg, batch=batch, max_len=max_len))


def paged_cache_bytes(cfg: ArchConfig, *, batch: int, num_pages: int,
                      page_size: int, max_blocks: int,
                      kv_quant: str | None = None) -> int:
    """HBM bytes of the paged layout: the shared page arrays (int8 payloads
    + f32 scales under ``kv_quant``), plus the per-slot UNPAGED leaves (SSM
    conv/state, audio cross K/V — none of which depend on max_len), plus the
    dense int32 page table."""
    unpaged = {k: d for k, d in cache_defs(cfg, batch=batch, max_len=1).items()
               if k not in paged_keys(cfg)}
    return (_defs_bytes(page_defs(cfg, num_pages=num_pages, page_size=page_size,
                                  kv_quant=kv_quant))
            + _defs_bytes(unpaged)
            + batch * max_blocks * jnp.dtype(jnp.int32).itemsize)
