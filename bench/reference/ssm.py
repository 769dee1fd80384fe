"""Mamba-2 (arXiv:2405.21060) as the program serves it, in the quadratic
form of the state-space dual: per head,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t

with B and C shared by all heads (one group), x, B and C each through a
causal depthwise conv of width ``conv_width`` and SiLU, dt = softplus(dt_raw
+ dt_bias), A = -exp(A_log), and the output gated as RMSNorm(y * silu(z))
before the output projection. Pre-RMSNorm residual blocks, a final RMSNorm
and tied embeddings. The segment sums are summed directly (a masked
cumulative sum), never as a difference of two long prefix sums.

Departure from the published block, shared with the program: the input
projection is five separate matrices (z, x, B, C, dt), which is the same map.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _conv(x, w, b):
    """Causal depthwise conv over time. x: (T, C), w: (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    out = sum(xp[i : i + x.shape[0]] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


@partial(jax.jit, static_argnames=("eps", "head_dim"))
def _layer(p, x, *, eps, head_dim):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    mb = p["mamba"]
    h = _rms(x, p["ln"]["scale"], eps)
    z = h @ mb["wz"]
    xs = _conv(h @ mb["wx"], mb["conv_x"], mb["conv_x_b"])
    bm = _conv(h @ mb["wB"], mb["conv_B"], mb["conv_B_b"])
    cm = _conv(h @ mb["wC"], mb["conv_C"], mb["conv_C_b"])
    dt = jax.nn.softplus(h @ mb["wdt"] + mb["dt_bias"])      # (T, H)
    a = -jnp.exp(mb["A_log"])                                 # (H,)
    t, nh = dt.shape
    xh = xs.reshape(t, nh, head_dim)
    # segsum[t, s, h] = sum_{r = s+1 .. t} dt_r a_h, for s <= t
    later = jnp.arange(t)[:, None] > jnp.arange(t)[None, :]   # r > s
    seg = jnp.cumsum(jnp.where(later[:, :, None], (dt * a)[:, None, :], 0.0), axis=0)
    causal = jnp.tril(jnp.ones((t, t), bool))[:, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = (cm @ bm.T)[:, :, None] * decay * dt[None, :, :]     # (T, S, H)
    y = jnp.einsum("tsh,shp->thp", w, xh) + mb["D"][None, :, None] * xh
    y = y.reshape(t, nh * head_dim)
    y = _rms(y * jax.nn.silu(z), mb["norm"]["scale"], eps)
    return x + y @ mb["wo"]


@partial(jax.jit, static_argnames=("eps", "vocab"))
def _head(params, x, positions, *, eps, vocab):
    h = _rms(x[positions], params["final_norm"]["scale"].astype(F32), eps)
    return (h @ params["embed"]["tokens"].astype(F32).T)[:, :vocab]


def logits(params, tokens, m, positions) -> np.ndarray:
    eps, hd = m["norm_eps"], m["ssm"]["head_dim"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(tokens)].astype(F32)
        for i in range(m["num_layers"]):
            p = jax.tree.map(lambda a: a[i], params["blocks"])
            x = _layer(p, x, eps=eps, head_dim=hd)
        out = _head(params, x, jnp.asarray(positions), eps=eps, vocab=m["vocab_size"])
    return np.asarray(out)
