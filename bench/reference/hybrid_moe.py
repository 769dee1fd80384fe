"""Granite 4.0-H (Mamba-2 and NoPE attention layers, each followed by routed
and shared experts) as published, in float32 over one sequence, with the
expert share of the configuration.

    x = embedding_multiplier * E[tokens]
    per layer:  x = x + m_r * mixer(RMSNorm(x))
                x = x + m_r * (routed(h) + shared(h)),  h = RMSNorm(x)
    logits = RMSNorm(x) E^T / logits_scaling                  (tied)

The mixer is Mamba-2 (as ``ssm.py`` writes it: causal depthwise convs of
x, B and C with SiLU, dt = softplus(dt_raw + dt_bias), A = -exp(A_log), one
group, y gated as RMSNorm(y * silu(z))) or grouped-query attention with no
position embedding and scores scaled by ``attention_multiplier``. The
router scores every one of the ``num_experts`` experts, takes its top_k and
a softmax over the chosen logits; only the held experts [first_expert,
first_expert + experts_held) add their SwiGLU outputs, weighted by their
gates; the shared expert is a SwiGLU of its own width.

Computed a layer at a time so it fits beside the served weights, each
kind of layer compiled once for a sequence length and handed its layer's
index as a traced argument, so every request of a check reuses the
compiles: the state-space scan in blocks of ``chunk_size`` positions (within a block the
quadratic dual form with segment sums summed directly, never as a
difference of two prefix sums; the state carried between blocks), and
attention in blocks of queries. Departure from the published block,
shared with the program: the Mamba-2 input projection is five matrices
(z, x, B, C, dt), which is the same map.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _conv(x, w, b):
    """Causal depthwise conv over time. x: (T, C), w: (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    out = sum(xp[i : i + x.shape[0]] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def _ssd_block(h0, blk, a):
    """One block of the scan. h0: (H, P, N) state entering it; blk: x (L, H,
    P), dt (L, H), b and c (L, N). Returns (state leaving it, y (L, H, P))."""
    x, dt, b, c = blk
    da = dt * a                                                  # (L, H)
    n = da.shape[0]
    later = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]      # r > s
    # seg[t, s] = sum_{r = s+1 .. t} da_r for s <= t
    seg = jnp.cumsum(jnp.where(later[:, :, None], da[:, None, :], 0.0), axis=0)
    causal = jnp.tril(jnp.ones((n, n), bool))[:, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = (c @ b.T)[:, :, None] * decay * dt[None, :, :]           # (L, S, H)
    y = jnp.einsum("tsh,shp->thp", w, x)
    into = jnp.cumsum(da, axis=0)                                # sum_{r <= t} da_r
    y = y + jnp.einsum("tn,hpn->thp", c, h0) * jnp.exp(into)[:, :, None]
    # sum_{r = s+1 .. L-1} da_r, summed from the block's end
    after = jnp.concatenate([jnp.cumsum(da[::-1], axis=0)[::-1][1:],
                             jnp.zeros_like(da[:1])])
    h = h0 * jnp.exp(into[-1])[:, None, None] + jnp.einsum(
        "sh,shp,sn->hpn", jnp.exp(after) * dt, x, b)
    return h, y


def _layer(stack, i):
    """Layer ``i`` of a stack, in float32 (``i`` traced: one compile a kind)."""
    return jax.tree.map(lambda a: a[i].astype(F32), stack)


@partial(jax.jit, static_argnames=("eps", "head_dim", "block", "m_r"))
def _mamba(stack, i, x, *, eps, head_dim, block, m_r):
    p = _layer(stack, i)
    mb = p["mamba"]
    h = _rms(x, p["ln"]["scale"], eps)
    z = h @ mb["wz"]
    xs = _conv(h @ mb["wx"], mb["conv_x"], mb["conv_x_b"])
    bm = _conv(h @ mb["wB"], mb["conv_B"], mb["conv_B_b"])
    cm = _conv(h @ mb["wC"], mb["conv_C"], mb["conv_C_b"])
    dt = jax.nn.softplus(h @ mb["wdt"] + mb["dt_bias"])        # (T, H)
    a = -jnp.exp(mb["A_log"])                                   # (H,)
    t, nh = dt.shape
    xh = xs.reshape(t, nh, head_dim)
    nb = t // block
    blocks = (xh.reshape(nb, block, nh, head_dim), dt.reshape(nb, block, nh),
              bm.reshape(nb, block, -1), cm.reshape(nb, block, -1))
    h0 = jnp.zeros((nh, head_dim, bm.shape[-1]), F32)
    _, y = jax.lax.scan(partial(_ssd_block, a=a), h0, blocks)
    y = y.reshape(t, nh, head_dim) + mb["D"][None, :, None] * xh
    y = _rms(y.reshape(t, nh * head_dim) * jax.nn.silu(z), mb["norm"]["scale"], eps)
    return x + m_r * (y @ mb["wo"])


@partial(jax.jit, static_argnames=("eps", "scale", "m_r"))
def _attention(stack, i, x, *, eps, scale, m_r):
    p = _layer(stack, i)
    at = p["attn"]
    h = _rms(x, p["ln"]["scale"], eps)
    q = jnp.einsum("td,dhe->the", h, at["wq"])
    k = jnp.einsum("td,dhe->the", h, at["wk"])
    v = jnp.einsum("td,dhe->the", h, at["wv"])
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # head j*g+i reads j
    t = x.shape[0]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK)
        s = jnp.einsum("qhe,khe->hqk", qb, k) * scale
        seen = (start + jnp.arange(Q_BLOCK))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(rows, jnp.arange(0, t, Q_BLOCK)).reshape(t, *q.shape[1:])
    return x + m_r * jnp.einsum("qhe,hed->qd", o, at["wo"])


@partial(jax.jit, static_argnames=("eps", "top_k", "first", "m_r"))
def _ffn(stack, i, x, *, eps, top_k, first, m_r):
    p = _layer(stack, i)
    mo = p["moe"]
    h = _rms(x, p["ln"]["scale"], eps)
    top, ids = jax.lax.top_k(h @ mo["router"], top_k)           # over every expert
    gates = jax.nn.softmax(top, axis=-1)                        # over the chosen
    swiglu = lambda wg, wu, wd: (jax.nn.silu(h @ wg) * (h @ wu)) @ wd  # noqa: E731
    y = swiglu(mo["shared"]["wg"], mo["shared"]["wu"], mo["shared"]["wd"])
    for e in range(mo["wg"].shape[0]):                          # the held experts
        gate = jnp.sum(jnp.where(ids == first + e, gates, 0.0), axis=-1)
        y = y + gate[:, None] * swiglu(mo["wg"][e], mo["wu"][e], mo["wd"][e])
    return x + m_r * y


@partial(jax.jit, static_argnames=("eps", "vocab", "scaling"))
def _head(params, x, positions, *, eps, vocab, scaling):
    h = _rms(x[positions], params["final_norm"]["scale"].astype(F32), eps)
    return (h @ params["embed"]["tokens"].astype(F32).T)[:, :vocab] / scaling


def logits(params, tokens, m, positions) -> np.ndarray:
    eps, m_r = m["norm_eps"], m["residual_multiplier"]
    s, e = m["ssm"], m["moe"]
    blocks = params["blocks"]
    tokens = np.asarray(tokens)
    # the scan's blocks and the query blocks must tile the sequence
    t = len(tokens)
    pad = -t % np.lcm(s["chunk_size"], Q_BLOCK)
    tokens = np.concatenate([tokens, np.zeros(pad, tokens.dtype)])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(tokens)].astype(F32) * m["embedding_multiplier"]
        i_attn = i_mamba = 0
        for i in range(m["num_layers"]):
            if i in m["attn_layers"]:
                x = _attention(blocks["attn"], i_attn, x, eps=eps,
                               scale=m["attention_multiplier"], m_r=m_r)
                i_attn += 1
            else:
                x = _mamba(blocks["mamba"], i_mamba, x, eps=eps,
                           head_dim=s["head_dim"], block=s["chunk_size"], m_r=m_r)
                i_mamba += 1
            x = _ffn(blocks["ffn"], i, x, eps=eps, top_k=e["top_k"],
                     first=e["first_expert"], m_r=m_r)
        out = _head(params, x, jnp.asarray(positions), eps=eps, vocab=m["vocab_size"],
                    scaling=m["logits_scaling"])
    return np.asarray(out)
