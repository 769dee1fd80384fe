"""Model API: param_defs / forward / train_loss / prefill / decode_step.

One driver for all 10 architecture families. The repeated block of each
family is scanned (``lax.scan`` over stacked params, remat per ``cfg.remat``)
or unrolled (``cfg.scan_layers=False`` — a generator design axis: scan is
compile-fast/remat-friendly, unroll lets XLA overlap across layers).

Family wiring:
  dense / vlm      single dense stack (vlm: frontend patch embeds overwrite
                   the first ``frontend_seq`` token positions; labels there
                   are masked by the data pipeline)
  moe              single MoE stack
  deepseek         ``first_k_dense`` MLA+dense blocks, then MLA+MoE blocks,
                   optional MTP head (depth-1 multi-token prediction loss)
  ssm              single Mamba2 stack
  hybrid (zamba2)  segments of ``attn_every`` Mamba2 layers, each preceded by
                   the ONE weight-shared attention block (14 applications for
                   81 layers / every 6)
  audio (whisper)  encoder stack over stubbed frames + causal decoder with
                   cross-attention; sinusoidal positions; tied unembedding

Cross-entropy is vocab-sharded by default (logits constrained to
("batch", None, "vocab") so GSPMD keeps the (B,S,V) tensor TP-sharded and
inserts the log-sum-exp all-reduce). ``cfg.logits_chunk > 0`` switches to a
sequence-chunked CE that never materializes the full logits tensor.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.models import ssm as ssm_mod
from repro.models.layers import embed_defs, embed_apply, unembed_apply
from repro.models.params import ParamDef, init_params, stacked
from repro.models.quant import qeinsum
from repro.sharding.rules import constrain

ZERO = jnp.zeros((), jnp.float32)
MOE_AUX_COEF = 0.01
MTP_WEIGHT = 0.1


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------
def param_defs(cfg: ArchConfig) -> dict:
    f = cfg.family
    defs: dict[str, Any] = {"embed": embed_defs(cfg), "final_norm": T.norm_defs(cfg)}
    if f in ("dense", "vlm"):
        defs["blocks"] = stacked(cfg.num_layers, T.dense_block_defs(cfg))
    elif f == "moe" and cfg.mla is None:
        defs["blocks"] = stacked(cfg.num_layers, T.moe_block_defs(cfg))
    elif f == "moe":  # deepseek
        k = cfg.first_k_dense
        defs["dense_blocks"] = stacked(k, T.mla_dense_block_defs(cfg))
        defs["blocks"] = stacked(cfg.num_layers - k, T.mla_moe_block_defs(cfg))
        if cfg.mtp:
            defs["mtp"] = {
                "norm_h": T.norm_defs(cfg),
                "norm_e": T.norm_defs(cfg),
                "proj": ParamDef((2 * cfg.d_model, cfg.d_model), (None, "embed")),
                "block": T.mla_dense_block_defs(cfg),
            }
    elif f == "ssm":
        defs["blocks"] = stacked(cfg.num_layers, T.ssm_block_defs(cfg))
    elif f == "hybrid":
        defs["blocks"] = stacked(cfg.num_layers, T.ssm_block_defs(cfg))
        defs["shared"] = T.shared_attn_defs(cfg)
    elif f == "audio":
        defs["enc_blocks"] = stacked(cfg.encoder_layers, T.enc_block_defs(cfg))
        defs["enc_norm"] = T.norm_defs(cfg)
        defs["blocks"] = stacked(cfg.num_layers, T.dec_block_defs(cfg))
    else:
        raise ValueError(f"unknown family {f!r}")
    return defs


def init_model(cfg: ArchConfig, key: jax.Array):
    return init_params(param_defs(cfg), key)


# ---------------------------------------------------------------------------
# Stack drivers (scan or unroll, remat)
# ---------------------------------------------------------------------------
def _remat(f, cfg: ArchConfig):
    if cfg.remat == "none":
        return f
    if cfg.remat == "dots":
        return jax.checkpoint(f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)


def _stack_len(stack) -> int:
    leaf = jax.tree.leaves(stack)[0]
    return leaf.shape[0]


def _layer(stack, i):
    return jax.tree.map(lambda t: t[i], stack)


def run_stack(stack, x, body, cfg: ArchConfig):
    """body(p, x) -> (x, aux). Returns (x, aux_sum)."""

    def f(carry, p):
        x, aux = carry
        x, a = body(p, x)
        return (x, aux + a), None

    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(_remat(f, cfg), (x, ZERO), stack)
        return x, aux
    aux = ZERO
    g = _remat(f, cfg)
    for i in range(_stack_len(stack)):
        (x, aux), _ = g((x, aux), _layer(stack, i))
    return x, aux


def run_stack_prefill(stack, x, body, cfg: ArchConfig):
    """body(p, x) -> (x, cache_slices). Returns (x, stacked cache)."""

    def f(x, p):
        x, cache = body(p, x)
        return x, cache

    if cfg.scan_layers:
        return jax.lax.scan(_remat(f, cfg), x, stack)
    outs = []
    for i in range(_stack_len(stack)):
        x, c = body(_layer(stack, i), x)
        outs.append(c)
    return x, jax.tree.map(lambda *ts: jnp.stack(ts), *outs)


def run_stack_decode(stack, caches, x, body, pos, cfg: ArchConfig):
    """body(p, x, cache, pos) -> (x, cache). caches: stacked tuple whose
    elements may be ``PagedRows``: those are gathered one layer at a time
    and come back as the layer's written blocks."""

    def f(x, inp):
        p, cache = inp
        x, new = body(p, x, tuple(map(_cache_view, cache)), pos)
        return x, tuple(_cache_written(n, c, pos) for n, c in zip(new, cache))

    if cfg.scan_layers:
        return jax.lax.scan(f, x, (stack, caches))
    outs = []
    for i in range(_stack_len(stack)):
        x, c = f(x, (_layer(stack, i), _layer(caches, i)))
        outs.append(c)
    return x, jax.tree.map(lambda *ts: jnp.stack(ts), *outs)


# ---------------------------------------------------------------------------
# Embedding front
# ---------------------------------------------------------------------------
def _embed_tokens(params, tokens, cfg: ArchConfig, frontend_embeds=None):
    x = embed_apply(params["embed"], tokens, cfg)
    if cfg.family == "vlm" and frontend_embeds is not None:
        fs = cfg.frontend_seq
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x[:, fs:]], axis=1)
    if cfg.family == "audio":
        pe = T.sinusoid_positions(x.shape[1], cfg.d_model).astype(x.dtype)
        x = x + pe[None]
    return constrain(x, ("batch", None, None))


def _hybrid_segments(cfg: ArchConfig) -> list[tuple[int, int]]:
    """[(start, length)] mamba-layer segments, each preceded by shared attn."""
    k = cfg.attn_every
    return [(s, min(k, cfg.num_layers - s)) for s in range(0, cfg.num_layers, k)]


def _stack_slice(stack, start, length):
    return jax.tree.map(lambda t: jax.lax.slice_in_dim(t, start, start + length, axis=0), stack)


# ---------------------------------------------------------------------------
# Forward (train path) → final hidden states
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg: ArchConfig, frontend_embeds=None):
    f = cfg.family
    if f == "audio":
        enc = _encode_audio(params, cfg, frontend_embeds)
        x = _embed_tokens(params, tokens, cfg)
        x, aux = run_stack(
            params["blocks"], x, lambda p, x: T.dec_block_apply(p, x, enc, cfg), cfg
        )
        return T.apply_norm(cfg, params["final_norm"], x), aux

    x = _embed_tokens(params, tokens, cfg, frontend_embeds)
    if f in ("dense", "vlm"):
        x, aux = run_stack(params["blocks"], x, partial(T.dense_block_apply, cfg=cfg), cfg)
    elif f == "moe" and cfg.mla is None:
        x, aux = run_stack(params["blocks"], x, partial(T.moe_block_apply, cfg=cfg), cfg)
    elif f == "moe":  # deepseek
        x, aux1 = run_stack(
            params["dense_blocks"], x, partial(T.mla_dense_block_apply, cfg=cfg), cfg
        )
        x, aux2 = run_stack(params["blocks"], x, partial(T.mla_moe_block_apply, cfg=cfg), cfg)
        aux = aux1 + aux2
    elif f == "ssm":
        x, aux = run_stack(params["blocks"], x, partial(T.ssm_block_apply, cfg=cfg), cfg)
    elif f == "hybrid":
        x0 = x
        aux = ZERO
        shared_fn = _remat(
            lambda p, x: (T.shared_attn_apply(p, x, x0, cfg), None), cfg
        )
        for start, length in _hybrid_segments(cfg):
            x, _ = shared_fn(params["shared"], x)
            seg = _stack_slice(params["blocks"], start, length)
            x, _ = run_stack(seg, x, partial(T.ssm_block_apply, cfg=cfg), cfg)
    else:
        raise ValueError(f)
    return T.apply_norm(cfg, params["final_norm"], x), aux


# ---------------------------------------------------------------------------
# Cross-entropy (vocab-sharded or sequence-chunked)
# ---------------------------------------------------------------------------
def _ce_block(params, hidden, labels, mask, cfg: ArchConfig):
    """CE over one block. hidden: (B,T,D), labels/mask: (B,T). Returns (nll_sum, n)."""
    logits = unembed_apply(params["embed"], hidden, cfg).astype(jnp.float32)
    v = logits.shape[-1]
    if v > cfg.vocab_size:  # mask the vocab-padding columns out of the lse
        logits = jnp.where(jnp.arange(v)[None, None, :] < cfg.vocab_size, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = (jnp.arange(v)[None, None, :] == labels[..., None]).astype(jnp.float32)
    correct = jnp.sum(logits * onehot, axis=-1)
    nll = (lse - correct) * mask
    return jnp.sum(nll), jnp.sum(mask)


def lm_loss(params, hidden, labels, cfg: ArchConfig):
    """Masked mean CE. labels < 0 are masked out."""
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    c = cfg.logits_chunk
    s = hidden.shape[1]
    if c and s % c == 0 and s > c:
        nc = s // c
        hc = hidden.reshape(hidden.shape[0], nc, c, -1).swapaxes(0, 1)
        lc = labels.reshape(labels.shape[0], nc, c).swapaxes(0, 1)
        mc = mask.reshape(mask.shape[0], nc, c).swapaxes(0, 1)

        def step(carry, inp):
            tot, n = carry
            h, l, m = inp
            t, k = _ce_block(params, h, l, m, cfg)
            return (tot + t, n + k), None

        (tot, n), _ = jax.lax.scan(step, (ZERO, ZERO), (hc, lc, mc))
    else:
        tot, n = _ce_block(params, hidden, labels, mask, cfg)
    return tot / jnp.maximum(n, 1.0)


def train_loss(params, batch, cfg: ArchConfig):
    """Scalar loss + metrics for one (global) batch."""
    hidden, aux = forward(
        params, batch["tokens"], cfg, frontend_embeds=batch.get("frontend_embeds")
    )
    ce = lm_loss(params, hidden, batch["labels"], cfg)
    loss = ce + MOE_AUX_COEF * aux
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp and "mtp" in params:
        mtp = params["mtp"]
        emb_next = embed_apply(params["embed"], batch["tokens"][:, 1:], cfg)
        h = T.apply_norm(cfg, mtp["norm_h"], hidden[:, :-1])
        e = T.apply_norm(cfg, mtp["norm_e"], emb_next)
        inp = jnp.einsum("bsd,de->bse", jnp.concatenate([h, e], axis=-1), mtp["proj"])
        h_mtp, _ = T.mla_dense_block_apply(mtp["block"], inp, cfg)
        mtp_ce = lm_loss(params, h_mtp, batch["labels"][:, 1:], cfg)
        loss = loss + MTP_WEIGHT * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Prefill → (last-token logits, cache)
# ---------------------------------------------------------------------------
def _encode_audio(params, cfg: ArchConfig, frontend_embeds):
    """The audio encoder pass shared by prefill and the chunked-prefill
    cross-cache builder (one definition keeps both token-identical)."""
    enc = frontend_embeds.astype(cfg.dtype)
    enc = enc + T.sinusoid_positions(enc.shape[1], cfg.d_model).astype(enc.dtype)[None]
    enc, _ = run_stack(params["enc_blocks"], enc, partial(T.enc_block_apply, cfg=cfg), cfg)
    return T.apply_norm(cfg, params["enc_norm"], enc)


def prefill(params, tokens, cfg: ArchConfig, frontend_embeds=None):
    f = cfg.family
    cache: dict[str, Any] = {}
    if f == "audio":
        enc = _encode_audio(params, cfg, frontend_embeds)
        x = _embed_tokens(params, tokens, cfg)
        x, (k, v, ck, cv) = run_stack_prefill(
            params["blocks"], x, lambda p, x: T.dec_block_prefill(p, x, enc, cfg), cfg
        )
        cache = {"k": k, "v": v, "cross_k": ck, "cross_v": cv}
    elif f in ("dense", "vlm"):
        x = _embed_tokens(params, tokens, cfg, frontend_embeds)
        x, (k, v) = run_stack_prefill(
            params["blocks"], x, partial(T.dense_block_prefill, cfg=cfg), cfg
        )
        cache = {"k": k, "v": v}
    elif f == "moe" and cfg.mla is None:
        x = _embed_tokens(params, tokens, cfg)
        x, (k, v) = run_stack_prefill(
            params["blocks"], x, partial(T.moe_block_prefill, cfg=cfg), cfg
        )
        cache = {"k": k, "v": v}
    elif f == "moe":  # deepseek — compressed MLA cache
        x = _embed_tokens(params, tokens, cfg)
        x, (c1, r1) = run_stack_prefill(
            params["dense_blocks"], x, partial(T.mla_dense_block_prefill, cfg=cfg), cfg
        )
        x, (c2, r2) = run_stack_prefill(
            params["blocks"], x, partial(T.mla_moe_block_prefill, cfg=cfg), cfg
        )
        cache = {
            "c": jnp.concatenate([c1, c2], axis=0),
            "krope": jnp.concatenate([r1, r2], axis=0),
        }
    elif f == "ssm":
        x = _embed_tokens(params, tokens, cfg)

        def body(p, x):
            y, tail, h = ssm_mod.mamba_prefill_apply(
                p["mamba"], T.apply_norm(cfg, p["ln"], x), cfg
            )
            return x + y, (tail, h.astype(jnp.float32))

        x, (conv, state) = run_stack_prefill(params["blocks"], x, body, cfg)
        cache = {"conv": conv, "state": state}
    elif f == "hybrid":
        x = _embed_tokens(params, tokens, cfg)
        x0 = x
        convs, states, sks, svs = [], [], [], []

        def body(p, x):
            y, tail, h = ssm_mod.mamba_prefill_apply(
                p["mamba"], T.apply_norm(cfg, p["ln"], x), cfg
            )
            return x + y, (tail, h.astype(jnp.float32))

        for start, length in _hybrid_segments(cfg):
            inp = qeinsum(
                "bsd,de->bse", jnp.concatenate([x, x0], axis=-1), params["shared"]["w_in"]
            )
            a, (sk, sv) = T.gqa_full(
                params["shared"]["attn"],
                T.apply_norm(cfg, params["shared"]["ln1"], inp),
                cfg, causal=True, rope=True,
            )
            y = inp + a
            from repro.models.layers import mlp_apply

            y = y + mlp_apply(params["shared"]["mlp"], T.apply_norm(cfg, params["shared"]["ln2"], y), cfg)
            x = x + qeinsum("bse,ed->bsd", y, params["shared"]["w_out"])
            sks.append(sk)
            svs.append(sv)
            seg = _stack_slice(params["blocks"], start, length)
            x, (conv, state) = run_stack_prefill(seg, x, body, cfg)
            convs.append(conv)
            states.append(state)
        cache = {
            "conv": jnp.concatenate(convs, axis=0),
            "state": jnp.concatenate(states, axis=0),
            "shared_k": jnp.stack(sks),
            "shared_v": jnp.stack(svs),
        }
    else:
        raise ValueError(f)
    with jax.named_scope("logits"):
        hidden = T.apply_norm(cfg, params["final_norm"], x)
        logits = unembed_apply(params["embed"], hidden[:, -1:], cfg)[:, 0]
        logits = _mask_pad_logits(logits, cfg).astype(jnp.float32)
    return logits, cache


# ---------------------------------------------------------------------------
# Decode step → (logits, cache)
# ---------------------------------------------------------------------------
def decode_step(params, cache, token, pos, cfg: ArchConfig):
    """token: (B, 1) int32; pos: scalar int32 (position being written)."""
    f = cfg.family
    x = embed_apply(params["embed"], token, cfg)
    if f == "audio":
        pe = T.sinusoid_positions(1, cfg.d_model, offset=pos).astype(x.dtype)
        x = x + pe[None]
        x, (k, v, ck, cv) = run_stack_decode(
            params["blocks"],
            (cache["k"], cache["v"], cache["cross_k"], cache["cross_v"]),
            x, partial(T.dec_block_decode, cfg=cfg), pos, cfg,
        )
        cache = {"k": k, "v": v, "cross_k": ck, "cross_v": cv}
    elif f in ("dense", "vlm"):
        x, (k, v) = run_stack_decode(
            params["blocks"], (cache["k"], cache["v"]), x,
            partial(T.dense_block_decode, cfg=cfg), pos, cfg,
        )
        cache = {"k": k, "v": v}
    elif f == "moe" and cfg.mla is None:
        x, (k, v) = run_stack_decode(
            params["blocks"], (cache["k"], cache["v"]), x,
            partial(T.moe_block_decode, cfg=cfg), pos, cfg,
        )
        cache = {"k": k, "v": v}
    elif f == "moe":  # deepseek
        kd = cfg.first_k_dense
        c, krope = cache["c"], cache["krope"]
        x, (c1, r1) = run_stack_decode(
            params["dense_blocks"], (c[:kd], krope[:kd]), x,
            partial(T.mla_dense_block_decode, cfg=cfg), pos, cfg,
        )
        x, (c2, r2) = run_stack_decode(
            params["blocks"], (c[kd:], krope[kd:]), x,
            partial(T.mla_moe_block_decode, cfg=cfg), pos, cfg,
        )
        cache = {
            "c": jnp.concatenate([c1, c2], axis=0),
            "krope": jnp.concatenate([r1, r2], axis=0),
        }
    elif f == "ssm":
        x, (conv, state) = run_stack_decode(
            params["blocks"], (cache["conv"], cache["state"]), x,
            partial(T.ssm_block_decode, cfg=cfg), pos, cfg,
        )
        cache = {"conv": conv, "state": state}
    elif f == "hybrid":
        x0 = x
        convs, states, sks, svs = [], [], [], []
        for i, (start, length) in enumerate(_hybrid_segments(cfg)):
            ck, cv = cache["shared_k"][i], cache["shared_v"][i]
            x, sk, sv = T.shared_attn_decode(
                params["shared"], x, x0, _cache_view(ck), _cache_view(cv),
                pos, cfg,
            )
            sks.append(_cache_written(sk, ck, pos))
            svs.append(_cache_written(sv, cv, pos))
            seg = _stack_slice(params["blocks"], start, length)
            segc = (
                jax.lax.slice_in_dim(cache["conv"], start, start + length, axis=0),
                jax.lax.slice_in_dim(cache["state"], start, start + length, axis=0),
            )
            x, (conv, state) = run_stack_decode(
                seg, segc, x, partial(T.ssm_block_decode, cfg=cfg), pos, cfg
            )
            convs.append(conv)
            states.append(state)
        cache = {
            "conv": jnp.concatenate(convs, axis=0),
            "state": jnp.concatenate(states, axis=0),
            "shared_k": jnp.stack(sks),
            "shared_v": jnp.stack(svs),
        }
    else:
        raise ValueError(f)
    with jax.named_scope("logits"):
        hidden = T.apply_norm(cfg, params["final_norm"], x)
        logits = unembed_apply(params["embed"], hidden, cfg)[:, 0]
        logits = _mask_pad_logits(logits, cfg).astype(jnp.float32)
    return logits, cache


# ---------------------------------------------------------------------------
# Chunked prefill → (last-chunk-token logits, cache)
# ---------------------------------------------------------------------------
def encoder_cross_cache(params, cfg: ArchConfig, frontend_embeds):
    """Run the audio encoder once and emit per-layer cross K/V stacks.

    Returns (cross_k, cross_v): (L, B, encoder_seq, KV, hd) — the static
    decoder-side cross caches that chunked prefill and decode consume."""
    enc = _encode_audio(params, cfg, frontend_embeds)
    return jax.vmap(lambda p: T._cross_kv(p["cross_attn"], enc, cfg))(params["blocks"])


def _chunk_forward(params, cache, tokens, pos, cfg: ArchConfig, frontend_embeds=None,
                   ssm_block=None):
    """Per-family chunk body shared by ``prefill_chunk`` and ``decode_verify``:
    T tokens against a full-capacity decode cache at positions [pos, pos+T).
    Returns (final hidden states before norm: (B, T, D), cache).

    ``ssm_block`` swaps the ssm/hybrid per-layer body (default
    ``ssm_block_chunk``); ``decode_verify`` passes ``ssm_block_verify``,
    whose cache slices carry a per-position snapshot axis for acceptance
    rollback — everything else about the two paths is identical."""
    f = cfg.family
    if ssm_block is None:
        ssm_block = T.ssm_block_chunk
    x = embed_apply(params["embed"], tokens, cfg)
    if f == "vlm" and frontend_embeds is not None:
        fs = cfg.frontend_seq
        t = tokens.shape[1]
        fe = jax.lax.dynamic_slice_in_dim(frontend_embeds, pos, t, axis=1)
        sel = (pos + jnp.arange(t))[None, :, None] < fs
        x = jnp.where(sel, fe.astype(x.dtype), x)
    x = constrain(x, ("batch", None, None))
    if f == "audio":
        pe = T.sinusoid_positions(tokens.shape[1], cfg.d_model, offset=pos).astype(x.dtype)
        x = x + pe[None]
        x, (k, v, ck, cv) = run_stack_decode(
            params["blocks"],
            (cache["k"], cache["v"], cache["cross_k"], cache["cross_v"]),
            x, partial(T.dec_block_chunk, cfg=cfg), pos, cfg,
        )
        cache = {"k": k, "v": v, "cross_k": ck, "cross_v": cv}
    elif f in ("dense", "vlm"):
        x, (k, v) = run_stack_decode(
            params["blocks"], (cache["k"], cache["v"]), x,
            partial(T.dense_block_chunk, cfg=cfg), pos, cfg,
        )
        cache = {"k": k, "v": v}
    elif f == "moe" and cfg.mla is None:
        x, (k, v) = run_stack_decode(
            params["blocks"], (cache["k"], cache["v"]), x,
            partial(T.moe_block_chunk, cfg=cfg), pos, cfg,
        )
        cache = {"k": k, "v": v}
    elif f == "moe":  # deepseek — absorbed attention over the compressed cache
        kd = cfg.first_k_dense
        c, krope = cache["c"], cache["krope"]
        x, (c1, r1) = run_stack_decode(
            params["dense_blocks"], (c[:kd], krope[:kd]), x,
            partial(T.mla_dense_block_chunk, cfg=cfg), pos, cfg,
        )
        x, (c2, r2) = run_stack_decode(
            params["blocks"], (c[kd:], krope[kd:]), x,
            partial(T.mla_moe_block_chunk, cfg=cfg), pos, cfg,
        )
        cache = {
            "c": jnp.concatenate([c1, c2], axis=0),
            "krope": jnp.concatenate([r1, r2], axis=0),
        }
    elif f == "ssm":
        x, (conv, state) = run_stack_decode(
            params["blocks"], (cache["conv"], cache["state"]), x,
            partial(ssm_block, cfg=cfg), pos, cfg,
        )
        cache = {"conv": conv, "state": state}
    elif f == "hybrid":
        x0 = x
        convs, states, sks, svs = [], [], [], []
        for i, (start, length) in enumerate(_hybrid_segments(cfg)):
            ck, cv = cache["shared_k"][i], cache["shared_v"][i]
            x, sk, sv = T.shared_attn_chunk(
                params["shared"], x, x0, _cache_view(ck), _cache_view(cv),
                pos, cfg,
            )
            sks.append(_cache_written(sk, ck, pos))
            svs.append(_cache_written(sv, cv, pos))
            seg = _stack_slice(params["blocks"], start, length)
            segc = (
                jax.lax.slice_in_dim(cache["conv"], start, start + length, axis=0),
                jax.lax.slice_in_dim(cache["state"], start, start + length, axis=0),
            )
            x, (conv, state) = run_stack_decode(
                seg, segc, x, partial(ssm_block, cfg=cfg), pos, cfg
            )
            convs.append(conv)
            states.append(state)
        cache = {
            "conv": jnp.concatenate(convs, axis=0),
            "state": jnp.concatenate(states, axis=0),
            "shared_k": jnp.stack(sks),
            "shared_v": jnp.stack(svs),
        }
    else:
        raise ValueError(f)
    return x, cache


def prefill_chunk(params, cache, tokens, pos, cfg: ArchConfig, frontend_embeds=None):
    """Process one chunk of T prompt tokens against a full-capacity decode
    cache at positions [pos, pos+T).

    tokens: (B, T) int32; pos: scalar int32 — the first cache position the
    chunk writes. ``cache`` uses the decode layout (``cache_defs`` capacity,
    zero-initialized; audio additionally needs ``encoder_cross_cache`` rows
    filled up-front). Successive chunks compose to the blocking ``prefill``
    recurrence: attention families mask dead cache rows past the written
    prefix, SSM families carry conv tail + state between chunks. For VLM,
    ``frontend_embeds`` must be padded to cache capacity on the seq axis so
    every chunk can slice it at ``pos``. Returns (last-position logits,
    cache) — after the final chunk the logits match ``prefill``'s up to
    chunk-boundary float reassociation."""
    x, cache = _chunk_forward(params, cache, tokens, pos, cfg, frontend_embeds)
    with jax.named_scope("logits"):
        hidden = T.apply_norm(cfg, params["final_norm"], x)
        logits = unembed_apply(params["embed"], hidden[:, -1:], cfg)[:, 0]
        logits = _mask_pad_logits(logits, cfg).astype(jnp.float32)
    return logits, cache


# ---------------------------------------------------------------------------
# Speculative multi-token verify → (all-position logits, cache)
# ---------------------------------------------------------------------------
def decode_verify(params, cache, tokens, pos, cfg: ArchConfig, frontend_embeds=None):
    """Score T candidate decode tokens in ONE pass at positions [pos, pos+T).

    tokens: (B, T) int32 — the last committed next-input token followed by
    T-1 drafted candidates. Unlike ``prefill_chunk`` this returns logits for
    ALL T positions ((B, T, V) float32): logits[:, j] is the model's
    next-token distribution after consuming tokens[:, :j+1], which is what
    greedy acceptance compares the drafts against.

    Cache semantics per family:
      * attention families (dense/vlm/moe/deepseek/audio) reuse the
        ``prefill_chunk`` machinery unchanged — K/V rows for rejected
        candidates are dead data past the committed prefix, masked out by
        position and overwritten by the next verify window. No rollback.
      * ssm/hybrid recurrent leaves (``conv``/``state``) come back with a
        per-position axis ((L, B, T, ...) snapshots after every candidate);
        ``commit_verify`` selects the snapshot at the last accepted token.
    """
    x, cache = _chunk_forward(params, cache, tokens, pos, cfg, frontend_embeds,
                              ssm_block=T.ssm_block_verify)
    with jax.named_scope("logits"):
        hidden = T.apply_norm(cfg, params["final_norm"], x)
        logits = unembed_apply(params["embed"], hidden, cfg)
        logits = _mask_pad_logits(logits, cfg).astype(jnp.float32)
    return logits, cache


def commit_verify(cache, accepted, cfg: ArchConfig):
    """Resolve a ``decode_verify`` cache to the accepted prefix.

    ``accepted``: traced scalar — number of accepted draft tokens a ∈ [0, K],
    i.e. a+1 tokens of the verify window were really consumed. Attention
    caches need nothing (rollback is positional); ssm/hybrid recurrent
    leaves select the per-position snapshot at index a, restoring the
    ``cache_defs`` layout the next decode/verify step expects."""
    if cfg.family in ("ssm", "hybrid"):
        def take(t):  # (L, B, T, ...) → (L, B, ...) at position ``accepted``
            return jax.lax.dynamic_index_in_dim(t, accepted, axis=2, keepdims=False)

        cache = dict(cache, conv=take(cache["conv"]), state=take(cache["state"]))
    return cache


def _mask_pad_logits(logits, cfg: ArchConfig):
    v = logits.shape[-1]
    if v > cfg.vocab_size:
        return jnp.where(jnp.arange(v) < cfg.vocab_size, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# Paged-cache bridges (serving/pages.py)
# ---------------------------------------------------------------------------
# The decode/verify bodies above are layout-agnostic: they see a per-slot
# contiguous cache row and write positions [pos, pos+T) through the strict
# positional masks in models/layers.py. The paged serving path reuses them
# unchanged by (a) gathering a slot's pages into a VIRTUAL contiguous row
# through its page-table row, and (b) extracting the written blocks back out
# for a scatter by page id. Rows gathered from unmapped blocks (the scratch
# page) are garbage, but the positional masks select NEG_INF for every
# position > pos before the softmax, so they are exactly inert in f32.
#
# The gather happens inside the layer loop, one layer at a time: a whole-
# stack virtual row per slot would hold max_batch x layers x max_len rows at
# once (8 GiB of temporaries for granite-3-8b at 16 layers, 16 slots and
# 4k rows, which does not fit a 16 GiB chip next to the weights and pages).


@jax.tree_util.register_pytree_node_class
class PagedRows:
    """One slot's view of a paged cache leaf, for the decode/verify bodies.

    pages: (lead, num_pages, page, *tail) physical pages (int8 payloads
    under ``kv_quant``, with their f32 ``scales`` pages; else None);
    table: (lead, max_blocks) — the slot's page-table row repeated along
    ``lead``, so a layer scan slices it together with the pages.
    ``n_blocks`` whole blocks from the block holding ``pos`` are what the
    step writes back (1 for decode, the verify window's span for verify).
    """

    def __init__(self, pages, scales, table, *, n_blocks: int, page: int):
        self.pages, self.scales, self.table = pages, scales, table
        self.n_blocks, self.page = n_blocks, page

    def tree_flatten(self):
        return (self.pages, self.scales, self.table), (self.n_blocks, self.page)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_blocks=aux[0], page=aux[1])

    def __getitem__(self, idx):
        """Slice the ``lead`` axis (deepseek's dense/MoE split, hybrid's
        per-application shared attention)."""
        return jax.tree.map(lambda t: t[idx], self)

    def gather(self):
        """One layer's virtual contiguous row: (1, max_blocks * page, *tail)."""
        with jax.named_scope("kv_pages"):
            g = jnp.take(self.pages, self.table, axis=0)  # (max_blocks, page, *tail)
            if self.scales is not None:
                from repro.serving.kv_cache import dequantize_kv

                g = dequantize_kv(g, jnp.take(self.scales, self.table, axis=0))
            return g.reshape(1, -1, *g.shape[2:])

    def written(self, row, pos):
        """The step's written blocks of an updated row: (n_blocks, page, *tail)."""
        with jax.named_scope("kv_pages"):
            return paged_written_blocks(row, pos // self.page, self.n_blocks,
                                        self.page)[:, 0]


def _cache_view(c):
    return c.gather() if isinstance(c, PagedRows) else c


def _cache_written(new, c, pos):
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
