"""Per-family transformer blocks (train/prefill/decode bodies).

Families and their blocks:

  dense / vlm       pre-norm GQA attention + (SwiGLU) MLP
  moe               GQA attention + top-k MoE FFN (+ shared experts)
  deepseek (moe)    MLA attention + dense MLP (first_k layers) or MoE
  ssm               Mamba2 (SSD) block
  hybrid (zamba2)   Mamba2 stack + ONE weight-shared attention block applied
                    every ``attn_every`` layers (input = concat(x, x0) → proj)
  audio (whisper)   enc-dec: bidirectional encoder blocks + causal decoder
                    blocks with cross-attention; LayerNorm + GELU

Every train/prefill body returns ``(x, aux)`` (aux = MoE load-balance loss,
0 elsewhere) so a single scan driver in ``model.py`` covers all families.
Prefill bodies additionally return the cache slices they produce; decode
bodies consume/update them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_rope,
    attention_decode,
    gqa_chunk_apply,
    gqa_cross_apply,
    gqa_decode_apply,
    gqa_defs,
    gqa_project_qkv,
    mla_chunk_apply,
    layernorm,
    layernorm_defs,
    mla_apply,
    mla_decode_apply,
    mla_defs,
    mlp_apply,
    mlp_defs,
    rmsnorm,
    rmsnorm_defs,
    run_attention,
    _mla_q,
    _mla_ckv,
)
from repro.models.moe import moe_apply, moe_defs
from repro.models.params import ParamDef
from repro.models.quant import qeinsum
from repro.sharding.rules import constrain

ZERO = jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Norm dispatch (whisper uses LayerNorm, everything else RMSNorm)
# ---------------------------------------------------------------------------
def norm_defs(cfg: ArchConfig, dim: int | None = None) -> dict:
    dim = dim or cfg.d_model
    return layernorm_defs(dim) if cfg.family == "audio" else rmsnorm_defs(dim)


def apply_norm(cfg: ArchConfig, p, x):
    if cfg.family == "audio":
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Dense (also VLM backbone)
# ---------------------------------------------------------------------------
def dense_block_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": norm_defs(cfg),
        "attn": gqa_defs(cfg),
        "ln2": norm_defs(cfg),
        "mlp": mlp_defs(cfg),
    }


def dense_block_apply(p, x, cfg: ArchConfig):
    x = x + gqa_full(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True, rope=True)[0]
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, ZERO


def gqa_full(p, x, cfg: ArchConfig, *, causal: bool, rope: bool):
    """GQA over the full sequence; returns (out, (k, v)) for cache fill."""
    positions = jnp.arange(x.shape[1])[None, :]
    q, k, v = gqa_project_qkv(p, x, cfg, positions, rope=rope)
    out = run_attention(cfg, q, k, v, causal=causal)
    out = constrain(out, ("batch", None, "heads", None))
    return qeinsum("bshe,hed->bsd", out, p["wo"]), (k, v)


def dense_block_prefill(p, x, cfg: ArchConfig):
    a, (k, v) = gqa_full(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True, rope=True)
    x = x + a
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, (k, v)


def _proj_norm(cfg: ArchConfig, p, x):
    """A block's pre-attention or pre-MLP norm, in the ``proj`` scope."""
    with jax.named_scope("proj"):
        return apply_norm(cfg, p, x)


def dense_block_chunk(p, x, cache, pos, cfg: ArchConfig):
    """Chunked-prefill body: T prompt tokens appended at ``pos``."""
    k_cache, v_cache = cache
    a, k_cache, v_cache = gqa_chunk_apply(
        p["attn"], _proj_norm(cfg, p["ln1"], x), k_cache, v_cache, pos, cfg
    )
    x = x + a
    x = x + mlp_apply(p["mlp"], _proj_norm(cfg, p["ln2"], x), cfg)
    return x, (k_cache, v_cache)


def dense_block_decode(p, x, cache, pos, cfg: ArchConfig):
    k_cache, v_cache = cache
    a, k_cache, v_cache = gqa_decode_apply(
        p["attn"], _proj_norm(cfg, p["ln1"], x), k_cache, v_cache, pos, cfg
    )
    x = x + a
    x = x + mlp_apply(p["mlp"], _proj_norm(cfg, p["ln2"], x), cfg)
    return x, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# MoE (granite-moe)
# ---------------------------------------------------------------------------
def moe_block_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": norm_defs(cfg),
        "attn": gqa_defs(cfg),
        "ln2": norm_defs(cfg),
        "moe": moe_defs(cfg),
    }


def moe_block_apply(p, x, cfg: ArchConfig):
    x = x + gqa_full(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True, rope=True)[0]
    y, aux = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, aux


def moe_block_prefill(p, x, cfg: ArchConfig):
    a, (k, v) = gqa_full(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True, rope=True)
    x = x + a
    y, _ = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, (k, v)


def moe_block_chunk(p, x, cache, pos, cfg: ArchConfig):
    k_cache, v_cache = cache
    a, k_cache, v_cache = gqa_chunk_apply(
        p["attn"], apply_norm(cfg, p["ln1"], x), k_cache, v_cache, pos, cfg
    )
    x = x + a
    y, _ = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, (k_cache, v_cache)


def moe_block_decode(p, x, cache, pos, cfg: ArchConfig):
    k_cache, v_cache = cache
    a, k_cache, v_cache = gqa_decode_apply(
        p["attn"], apply_norm(cfg, p["ln1"], x), k_cache, v_cache, pos, cfg
    )
    x = x + a
    y, _ = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# DeepSeek (MLA + MoE / leading dense layers)
# ---------------------------------------------------------------------------
def mla_dense_block_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": norm_defs(cfg),
        "attn": mla_defs(cfg),
        "ln2": norm_defs(cfg),
        "mlp": mlp_defs(cfg),
    }


def mla_moe_block_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": norm_defs(cfg),
        "attn": mla_defs(cfg),
        "ln2": norm_defs(cfg),
        "moe": moe_defs(cfg),
    }


def mla_dense_block_apply(p, x, cfg: ArchConfig):
    x = x + mla_apply(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True)
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, ZERO


def mla_moe_block_apply(p, x, cfg: ArchConfig):
    x = x + mla_apply(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True)
    y, aux = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, aux


def _mla_prefill_attn(p, x, cfg: ArchConfig):
    """MLA full-seq attention that also emits the compressed (c, k_rope) cache."""
    m = cfg.mla
    positions = jnp.arange(x.shape[1])[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c, k_rope = _mla_ckv(p, x, cfg, positions)
    k_nope = qeinsum("bsr,rhe->bshe", c, p["wk_b"])
    v = qeinsum("bsr,rhe->bshe", c, p["wv_b"])
    h = cfg.num_heads
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (*k_rope.shape[:2], h, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    out = run_attention(cfg, q, k, v, causal=True)
    return qeinsum("bshe,hed->bsd", out, p["wo"]), (c, k_rope)


def mla_dense_block_prefill(p, x, cfg: ArchConfig):
    a, cache = _mla_prefill_attn(p["attn"], apply_norm(cfg, p["ln1"], x), cfg)
    x = x + a
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, cache


def mla_moe_block_prefill(p, x, cfg: ArchConfig):
    a, cache = _mla_prefill_attn(p["attn"], apply_norm(cfg, p["ln1"], x), cfg)
    x = x + a
    y, _ = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, cache


def mla_dense_block_chunk(p, x, cache, pos, cfg: ArchConfig):
    c, krope = cache
    a, c, krope = mla_chunk_apply(p["attn"], apply_norm(cfg, p["ln1"], x), c, krope, pos, cfg)
    x = x + a
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, (c, krope)


def mla_moe_block_chunk(p, x, cache, pos, cfg: ArchConfig):
    c, krope = cache
    a, c, krope = mla_chunk_apply(p["attn"], apply_norm(cfg, p["ln1"], x), c, krope, pos, cfg)
    x = x + a
    y, _ = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, (c, krope)


def mla_dense_block_decode(p, x, cache, pos, cfg: ArchConfig):
    c, krope = cache
    a, c, krope = mla_decode_apply(p["attn"], apply_norm(cfg, p["ln1"], x), c, krope, pos, cfg)
    x = x + a
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, (c, krope)


def mla_moe_block_decode(p, x, cache, pos, cfg: ArchConfig):
    c, krope = cache
    a, c, krope = mla_decode_apply(p["attn"], apply_norm(cfg, p["ln1"], x), c, krope, pos, cfg)
    x = x + a
    y, _ = moe_apply(p["moe"], apply_norm(cfg, p["ln2"], x), cfg)
    return x + y, (c, krope)


# ---------------------------------------------------------------------------
# SSM (mamba2) and hybrid (zamba2)
# ---------------------------------------------------------------------------
def ssm_block_defs(cfg: ArchConfig) -> dict:
    return {"ln": norm_defs(cfg), "mamba": ssm_mod.mamba_defs(cfg)}


def ssm_block_apply(p, x, cfg: ArchConfig):
    return x + ssm_mod.mamba_apply(p["mamba"], apply_norm(cfg, p["ln"], x), cfg), ZERO


def ssm_block_chunk(p, x, cache, pos, cfg: ArchConfig):
    """Chunk body (``pos`` unused — the SSM carries state, not positions)."""
    conv, state = cache
    y, conv, state = ssm_mod.mamba_chunk_apply(
        p["mamba"], apply_norm(cfg, p["ln"], x), conv, state, cfg
    )
    return x + y, (conv, state)


def ssm_block_verify(p, x, cache, pos, cfg: ArchConfig):
    """Speculative-verify body: like ``ssm_block_chunk`` but the returned
    cache slices carry a per-position axis (T on axis 1 after batch) so the
    engine can roll the recurrent state back to the last accepted token."""
    conv, state = cache
    y, conv_all, state_all = ssm_mod.mamba_verify_apply(
        p["mamba"], apply_norm(cfg, p["ln"], x), conv, state, cfg
    )
    return x + y, (conv_all, state_all)


def ssm_block_decode(p, x, cache, pos, cfg: ArchConfig):
    conv, state = cache
    y, conv, state = ssm_mod.mamba_decode_apply(
        p["mamba"], apply_norm(cfg, p["ln"], x), conv, state, cfg
    )
    return x + y, (conv, state)


def shared_attn_defs(cfg: ArchConfig) -> dict:
    """Zamba2's weight-shared global attention block (one weight set)."""
    d = cfg.d_model
    return {
        "w_in": ParamDef((2 * d, d), (None, "embed")),  # concat(x, x0) → d
        "ln1": norm_defs(cfg),
        "attn": gqa_defs(cfg),
        "ln2": norm_defs(cfg),
        "mlp": mlp_defs(cfg),
        "w_out": ParamDef((d, d), ("embed", None)),
    }


def shared_attn_apply(p, x, x0, cfg: ArchConfig):
    inp = qeinsum("bsd,de->bse", jnp.concatenate([x, x0], axis=-1), p["w_in"])
    y = inp + gqa_full(p["attn"], apply_norm(cfg, p["ln1"], inp), cfg, causal=True, rope=True)[0]
    y = y + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], y), cfg)
    return x + qeinsum("bse,ed->bsd", y, p["w_out"])


def shared_attn_chunk(p, x, x0, k_cache, v_cache, pos, cfg: ArchConfig):
    inp = qeinsum("bsd,de->bse", jnp.concatenate([x, x0], axis=-1), p["w_in"])
    a, k_cache, v_cache = gqa_chunk_apply(
        p["attn"], apply_norm(cfg, p["ln1"], inp), k_cache, v_cache, pos, cfg
    )
    y = inp + a
    y = y + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], y), cfg)
    return x + qeinsum("bse,ed->bsd", y, p["w_out"]), k_cache, v_cache


def shared_attn_decode(p, x, x0, k_cache, v_cache, pos, cfg: ArchConfig):
    inp = qeinsum("bsd,de->bse", jnp.concatenate([x, x0], axis=-1), p["w_in"])
    a, k_cache, v_cache = gqa_decode_apply(
        p["attn"], apply_norm(cfg, p["ln1"], inp), k_cache, v_cache, pos, cfg
    )
    y = inp + a
    y = y + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], y), cfg)
    return x + qeinsum("bse,ed->bsd", y, p["w_out"]), k_cache, v_cache


# ---------------------------------------------------------------------------
# Whisper encoder / decoder blocks
# ---------------------------------------------------------------------------
def enc_block_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": norm_defs(cfg),
        "attn": gqa_defs(cfg),
        "ln2": norm_defs(cfg),
        "mlp": mlp_defs(cfg),
    }


def enc_block_apply(p, x, cfg: ArchConfig):
    x = x + gqa_full(p["attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=False, rope=False)[0]
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, ZERO


def dec_block_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": norm_defs(cfg),
        "self_attn": gqa_defs(cfg),
        "ln_x": norm_defs(cfg),
        "cross_attn": gqa_defs(cfg, cross=True),
        "ln2": norm_defs(cfg),
        "mlp": mlp_defs(cfg),
    }


def _cross_kv(p, enc, cfg: ArchConfig):
    k = qeinsum("bsd,dhe->bshe", enc, p["wk"])
    v = qeinsum("bsd,dhe->bshe", enc, p["wv"])
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    return k, v


def dec_block_apply(p, x, enc, cfg: ArchConfig):
    x = x + gqa_full(p["self_attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True, rope=False)[0]
    kv = _cross_kv(p["cross_attn"], enc, cfg)
    x = x + gqa_cross_apply(p["cross_attn"], apply_norm(cfg, p["ln_x"], x), kv, cfg)
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, ZERO


def dec_block_prefill(p, x, enc, cfg: ArchConfig):
    a, (k, v) = gqa_full(p["self_attn"], apply_norm(cfg, p["ln1"], x), cfg, causal=True, rope=False)
    x = x + a
    ck, cv = _cross_kv(p["cross_attn"], enc, cfg)
    x = x + gqa_cross_apply(p["cross_attn"], apply_norm(cfg, p["ln_x"], x), (ck, cv), cfg)
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, (k, v, ck, cv)


def dec_block_chunk(p, x, cache, pos, cfg: ArchConfig):
    """Decoder chunk: causal self-attn over the cache + cross-attn against
    the (static, precomputed) encoder K/V."""
    k_cache, v_cache, ck, cv = cache
    a, k_cache, v_cache = gqa_chunk_apply(
        p["self_attn"], apply_norm(cfg, p["ln1"], x), k_cache, v_cache, pos, cfg, rope=False
    )
    x = x + a
    x = x + gqa_cross_apply(p["cross_attn"], apply_norm(cfg, p["ln_x"], x), (ck, cv), cfg)
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, (k_cache, v_cache, ck, cv)


def dec_block_decode(p, x, cache, pos, cfg: ArchConfig):
    k_cache, v_cache, ck, cv = cache
    a, k_cache, v_cache = gqa_decode_apply(
        p["self_attn"], apply_norm(cfg, p["ln1"], x), k_cache, v_cache, pos, cfg, rope=False
    )
    x = x + a
    # cross attention: single query against the (static) encoder K/V
    q = qeinsum("bsd,dhe->bshe", apply_norm(cfg, p["ln_x"], x), p["cross_attn"]["wq"])
    if cfg.qkv_bias:
        q = q + p["cross_attn"]["bq"]
    out = run_attention(cfg, q, ck, cv, causal=False)
    x = x + qeinsum("bshe,hed->bsd", out, p["cross_attn"]["wo"])
    x = x + mlp_apply(p["mlp"], apply_norm(cfg, p["ln2"], x), cfg)
    return x, (k_cache, v_cache, ck, cv)


# ---------------------------------------------------------------------------
# Sinusoidal positions (whisper enc/dec — length-agnostic, no params)
# ---------------------------------------------------------------------------
def sinusoid_positions(seq: int, dim: int, offset=0) -> jax.Array:
    pos = (jnp.arange(seq) + offset)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / dim))
    pe = jnp.zeros((seq, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe
