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
  hybrid_moe       Mamba2 layers and NoPE attention layers (by index,
  (granite-4.0-h)  ``cfg.attn_layers``), each followed by routed + shared
                   experts; one body over T tokens against the cache
                   (``_hybrid_moe_stack``) serves all four entry points
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
from repro.models.paged import PagedRows, cache_view, cache_written
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
    elif f == "hybrid_moe":
        n_attn = len(cfg.attention_layers)
        defs["blocks"] = {
            "mamba": stacked(cfg.num_layers - n_attn, T.ssm_block_defs(cfg)),
            "attn": stacked(n_attn, T.attn_mixer_defs(cfg)),
            "ffn": stacked(cfg.num_layers, T.moe_ffn_defs(cfg)),
        }
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
    elements may be ``PagedRows`` (``models.paged``): the scan carries such a
    view's layer index, never its pages, and each layer's view comes back as
    what the layer writes (its new row, or its written blocks)."""
    per_layer = tuple(c.layer if isinstance(c, PagedRows) else c for c in caches)

    def f(x, inp):
        p, sliced = inp
        cache = tuple(c.at(s) if isinstance(c, PagedRows) else s
                      for c, s in zip(caches, sliced))
        x, new = body(p, x, tuple(map(cache_view, cache)), pos)
        return x, tuple(cache_written(n, c, pos) for n, c in zip(new, cache))

    if cfg.scan_layers:
        return jax.lax.scan(f, x, (stack, per_layer))
    outs = []
    for i in range(_stack_len(stack)):
        x, c = f(x, (_layer(stack, i), _layer(per_layer, i)))
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


def _hybrid_moe_stack(params, cache, tokens, pos, cfg: ArchConfig, *, verify=False):
    """The hybrid_moe family's one body: T tokens at positions [pos, pos+T)
    against a cache of Mamba-2 rows (``conv``, ``state``) and attention rows
    (``k``, ``v``; arrays, or ``PagedRows``).
    ``forward`` and ``prefill`` run it over a zero cache from position 0,
    ``decode_step`` at T=1, ``prefill_chunk`` and ``decode_verify`` as they
    come. Returns (hidden states before the final norm, cache)."""
    blocks = params["blocks"]
    x = embed_apply(params["embed"], tokens, cfg)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    x = constrain(x, ("batch", None, None))
    new = {"conv": [], "state": [], "k": [], "v": []}
    i_attn = i_mamba = 0
    for i in range(cfg.num_layers):
        ffn = _layer(blocks["ffn"], i)
        if i in cfg.attn_layers:
            kc, vc = cache["k"][i_attn], cache["v"][i_attn]
            x, (k, v) = T.hybrid_moe_layer(
                _layer(blocks["attn"], i_attn), ffn, x, (cache_view(kc), cache_view(vc)),
                pos, cfg, attn=True)
            new["k"].append(cache_written(k, kc, pos))
            new["v"].append(cache_written(v, vc, pos))
            i_attn += 1
        else:
            x, (conv, state) = T.hybrid_moe_layer(
                _layer(blocks["mamba"], i_mamba), ffn, x,
                (cache["conv"][i_mamba], cache["state"][i_mamba]), pos, cfg,
                attn=False, verify=verify)
            new["conv"].append(conv)
            new["state"].append(state)
            i_mamba += 1
    return x, {k: jnp.stack(v) for k, v in new.items()}


def _zero_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    from repro.serving.kv_cache import cache_defs

    return init_params(cache_defs(cfg, batch=batch, max_len=max_len), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Forward (train path) → final hidden states
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg: ArchConfig, frontend_embeds=None):
    f = cfg.family
    if f == "hybrid_moe":
        b, s = tokens.shape
        x, _ = _hybrid_moe_stack(params, _zero_cache(cfg, b, s), tokens, 0, cfg)
        return T.apply_norm(cfg, params["final_norm"], x), ZERO
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
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
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
    if f == "hybrid_moe":
        b, s = tokens.shape
        x, cache = _hybrid_moe_stack(params, _zero_cache(cfg, b, s), tokens, 0, cfg)
    elif f == "audio":
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
    x = None if f == "hybrid_moe" else embed_apply(params["embed"], token, cfg)
    if f == "hybrid_moe":
        x, cache = _hybrid_moe_stack(params, cache, token, pos, cfg)
    elif f == "audio":
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
                params["shared"], x, x0, cache_view(ck), cache_view(cv),
                pos, cfg,
            )
            sks.append(cache_written(sk, ck, pos))
            svs.append(cache_written(sv, cv, pos))
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
                   verify: bool = False):
    """Per-family chunk body shared by ``prefill_chunk`` and ``decode_verify``:
    T tokens against a full-capacity decode cache at positions [pos, pos+T).
    Returns (final hidden states before norm: (B, T, D), cache).

    ``verify`` (``decode_verify``) swaps the recurrent per-layer body for
    one whose cache slices carry a per-position snapshot axis for
    acceptance rollback (``ssm_block_verify``) — everything else about the
    two paths is identical."""
    f = cfg.family
    if f == "hybrid_moe":
        return _hybrid_moe_stack(params, cache, tokens, pos, cfg, verify=verify)
    ssm_block = T.ssm_block_verify if verify else T.ssm_block_chunk
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
                params["shared"], x, x0, cache_view(ck), cache_view(cv),
                pos, cfg,
            )
            sks.append(cache_written(sk, ck, pos))
            svs.append(cache_written(sv, cv, pos))
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
      * recurrent leaves (``conv``/``state``: ssm, hybrid, hybrid_moe) come
        back with a per-position axis ((L, B, T, ...) snapshots after every
        candidate); ``commit_verify`` selects the snapshot at the last
        accepted token.
    """
    x, cache = _chunk_forward(params, cache, tokens, pos, cfg, frontend_embeds,
                              verify=True)
    with jax.named_scope("logits"):
        hidden = T.apply_norm(cfg, params["final_norm"], x)
        logits = unembed_apply(params["embed"], hidden, cfg)
        logits = _mask_pad_logits(logits, cfg).astype(jnp.float32)
    return logits, cache


def commit_verify(cache, accepted, cfg: ArchConfig):
    """Resolve a ``decode_verify`` cache to the accepted prefix.

    ``accepted``: traced scalar — number of accepted draft tokens a ∈ [0, K],
    i.e. a+1 tokens of the verify window were really consumed. Attention
    caches need nothing (rollback is positional); recurrent leaves
    (``kv_cache.has_recurrent_state``) select the per-position snapshot at
    index a, restoring the ``cache_defs`` layout the next decode/verify step
    expects."""
    from repro.serving.kv_cache import has_recurrent_state

    if has_recurrent_state(cfg):
        def take(t):  # (L, B, T, ...) → (L, B, ...) at position ``accepted``
            return jax.lax.dynamic_index_in_dim(t, accepted, axis=2, keepdims=False)

        cache = dict(cache, conv=take(cache["conv"]), state=take(cache["state"]))
    return cache


def _mask_pad_logits(logits, cfg: ArchConfig):
    """Mask the vocabulary's padding columns, and divide by
    ``cfg.logits_scaling`` (in float32) where it is not 1."""
    v = logits.shape[-1]
    if v > cfg.vocab_size:
        logits = jnp.where(jnp.arange(v) < cfg.vocab_size, logits, -1e30)
    if cfg.logits_scaling != 1.0:
        logits = logits.astype(jnp.float32) / cfg.logits_scaling
    return logits
