"""Plain float32 reference of the ``hybrid_moe`` family (Granite 4.0-H) for
the tests: one sequence through the whole model in straightforward
``jax.numpy`` at the highest matmul precision, with no cache, kernel,
batching or shared code of the program's layers.

    x = embedding_multiplier * E[tokens]
    per layer:  x = x + m_r * mixer(RMSNorm(x))
                x = x + m_r * (routed(h) + shared(h)),  h = RMSNorm(x)
    logits = RMSNorm(x) W_out / logits_scaling

Mixers: Mamba-2 as a literal per-step recurrence (h_t = exp(dt_t A) h_{t-1}
+ dt_t x_t B_t^T, y_t = C_t h_t + D x_t; causal depthwise convs of x, B
and C with SiLU; dt = softplus(dt_raw + dt_bias); y gated as RMSNorm(y *
silu(z))), or grouped-query attention over the causal prefix with no
position embedding and scores scaled by ``attention_multiplier``. Routed experts: the router scores every expert,
keeps its top_k and takes a softmax over the chosen logits; the held
experts [first_expert, first_expert + experts_held) add their SwiGLU
outputs weighted by their gates, and the absent ones add nothing. No
departure from the published description beyond the program's layout of
the Mamba-2 input projection as five matrices (the same map).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


def _conv(x, w, b):
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[i : i + x.shape[0]] * w[i] for i in range(width)) + b)


def _mamba(p, x, cfg):
    s = cfg.ssm
    z = x @ p["wz"]
    xs = _conv(x @ p["wx"], p["conv_x"], p["conv_x_b"])
    bm = _conv(x @ p["wB"], p["conv_B"], p["conv_B_b"])
    cm = _conv(x @ p["wC"], p["conv_C"], p["conv_C_b"])
    dt = jax.nn.softplus(x @ p["wdt"] + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    t, nh = dt.shape
    xh = xs.reshape(t, nh, s.head_dim)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = h * jnp.exp(dtt * a)[:, None, None] + dtt[:, None, None] * xt[:, :, None] * bt
        return h, h @ ct

    h0 = jnp.zeros((nh, s.head_dim, s.state_size), F32)
    _, y = jax.lax.scan(step, h0, (xh, dt, bm, cm))
    y = (y + p["D"][:, None] * xh).reshape(t, -1)
    return _rms(y * jax.nn.silu(z), p["norm"], cfg.norm_eps) @ p["wo"]


def _attention(p, x, cfg):
    q = jnp.einsum("td,dhe->the", x, p["wq"])
    k = jnp.einsum("td,dhe->the", x, p["wk"])
    v = jnp.einsum("td,dhe->the", x, p["wv"])
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scale = cfg.attention_multiplier or 1.0 / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.einsum("qhe,khe->hqk", q, k) * scale
    t = x.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("qhe,hed->qd", o, p["wo"])


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _experts(p, x, cfg):
    m = cfg.moe
    logits = x @ p["router"]
    logits = jnp.where(jnp.arange(logits.shape[-1]) < m.num_experts, logits, -jnp.inf)
    top, ids = jax.lax.top_k(logits, m.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    sh = p.get("shared")
    y = _swiglu(x, sh["wg"], sh["wu"], sh["wd"]) if sh else jnp.zeros_like(x)
    for e in range(p["wg"].shape[0]):
        gate = jnp.sum(jnp.where(ids == m.first_expert + e, gates, 0.0), axis=-1)
        y = y + gate[:, None] * _swiglu(x, p["wg"][e], p["wu"][e], p["wd"][e])
    return y


def hybrid_moe_logits(params, tokens, cfg):
    """(T, vocab_size) float32 logits after each token of ``tokens`` (T,)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
    blocks = p["blocks"]
    layer = lambda stack, i: jax.tree.map(lambda a: a[i], stack)  # noqa: E731
    m_r, eps = cfg.residual_multiplier, cfg.norm_eps
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["tokens"][jnp.asarray(tokens)] * cfg.embedding_multiplier
        i_attn = i_mamba = 0
        for i in range(cfg.num_layers):
            if i in cfg.attn_layers:
                mix = layer(blocks["attn"], i_attn)
                x = x + m_r * _attention(mix["attn"], _rms(x, mix["ln"], eps), cfg)
                i_attn += 1
            else:
                mix = layer(blocks["mamba"], i_mamba)
                x = x + m_r * _mamba(mix["mamba"], _rms(x, mix["ln"], eps), cfg)
                i_mamba += 1
            ffn = layer(blocks["ffn"], i)
            x = x + m_r * _experts(ffn["moe"], _rms(x, ffn["ln"], eps), cfg)
        h = _rms(x, p["final_norm"], eps)
        w = p["embed"]["unembed"] if "unembed" in p["embed"] else p["embed"]["tokens"].T
        return (h @ w)[:, : cfg.vocab_size] / cfg.logits_scaling
