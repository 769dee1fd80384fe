"""Decoder-only transformer as the program serves it: token embedding,
pre-RMSNorm blocks of grouped-query attention with rotary positions
(rotate-half, base ``rope_theta``, scores scaled by 1/sqrt(head_dim)) and a
SwiGLU MLP, a final RMSNorm and an output projection.

Departures from the published Granite 3.0 equations, shared with the program
under test: no embedding, attention, residual or logit multipliers.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (T, H, D); rotate the first and second halves of D as pairs."""
    t, _, dim = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(p, x, *, eps, theta):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    h = _rms(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = _rope(jnp.einsum("td,dhe->the", h, a["wq"]), theta)
    k = _rope(jnp.einsum("td,dhe->the", h, a["wk"]), theta)
    v = jnp.einsum("td,dhe->the", h, a["wv"])
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhe,khe->hqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    t = x.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("qhe,hed->qd", o, a["wo"])
    h = _rms(x, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    return x + (jax.nn.silu(h @ mlp["wg"]) * (h @ mlp["wu"])) @ mlp["wd"]


@partial(jax.jit, static_argnames=("eps", "vocab"))
def _head(params, x, positions, *, eps, vocab):
    h = _rms(x[positions], params["final_norm"]["scale"].astype(F32), eps)
    e = params["embed"]
    w = e["unembed"] if "unembed" in e else e["tokens"].T
    return (h @ w.astype(F32))[:, :vocab]


def logits(params, tokens, m, positions) -> np.ndarray:
    eps, theta = m["norm_eps"], m["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(tokens)].astype(F32)
        for i in range(m["num_layers"]):
            p = jax.tree.map(lambda a: a[i], params["blocks"])
            x = _layer(p, x, eps=eps, theta=theta)
        out = _head(params, x, jnp.asarray(positions), eps=eps, vocab=m["vocab_size"])
    return np.asarray(out)
