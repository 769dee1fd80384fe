"""Pure-jnp oracles for every Pallas kernel (the GHDL-simulation analogue:
mathematical ground truth the kernels must match, DESIGN.md §2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import activations as act_mod

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
def activation_ref(x, *, fn: str, impl: str):
    if fn == "sigmoid":
        return act_mod.get_sigmoid(impl)(x)
    if fn == "tanh":
        return act_mod.get_tanh(impl)(x)
    if fn in ("silu", "gelu"):
        return act_mod.get_activation(fn, impl)(x)
    raise ValueError(fn)


# ---------------------------------------------------------------------------
# Flash attention (GQA, optional causal)
# ---------------------------------------------------------------------------
def flash_attention_ref(q, k, v, *, causal: bool):
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D). Returns (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    kv = k.shape[1]
    g = h // kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(d)
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention (one new token per slot, GQA, pages in place)
# ---------------------------------------------------------------------------
def paged_decode_attention_ref(q, k_new, v_new, k_pages, v_pages, table, pos, layer):
    """One slot: q (H, D) at position ``pos``; k_new/v_new (KV, D), the new
    token's own k and v; pages (lead, num_pages, page, KV, D); table
    (max_blocks,); layer (). Returns (H, D) in q's dtype.

    Gathers the slot's row of layer ``layer`` through its table, puts the new
    token at ``pos`` and attends over positions ``0..pos``: query head ``h``
    reads KV head ``h // G``, scores in f32 from K as stored over
    ``sqrt(D)``, softmax in f32, P in f32 against V. Rows past ``pos`` are
    masked from the scores and zeroed in V, so whatever they hold (stale,
    scratch, NaN) adds nothing."""
    h, d = q.shape
    kv = k_pages.shape[3]
    k = k_pages[layer, table].reshape(-1, kv, d)  # (max_blocks * page, KV, D)
    v = v_pages[layer, table].reshape(-1, kv, d)
    at = jnp.arange(k.shape[0])[:, None, None]
    k = jnp.where(at == pos, k_new.astype(k.dtype), k)
    v = jnp.where(at <= pos, jnp.where(at == pos, v_new.astype(v.dtype), v), 0)
    qg = q.reshape(kv, h // kv, d)
    s = jnp.einsum("kgd,skd->kgs", qg, k, preferred_element_type=jnp.float32)
    s = jnp.where(at[:, 0, 0] <= pos, s / jnp.sqrt(d), NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgs,skd->kgd", p, v).reshape(h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused LSTM cell
# ---------------------------------------------------------------------------
def lstm_cell_ref(x, h, c, w, u, b, *, impl: str = "exact"):
    """x: (B, D); h/c: (B, H); w: (D, 4H); u: (H, 4H); b: (4H,)."""
    sig = act_mod.get_sigmoid(impl)
    tnh = act_mod.get_tanh(impl)
    z = x @ w + h @ u + b.astype(x.dtype)
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    i, f, o = sig(zi), sig(zf), sig(zo)
    g = tnh(zg)
    c_new = f * c + i * g
    h_new = o * tnh(c_new)
    return h_new, c_new


# ---------------------------------------------------------------------------
# Quantized sequence-resident LSTM (PACKED [i, f, o, g] gate layout)
# ---------------------------------------------------------------------------
def lstm_seq_q8_ref(x, w_q, u_q, b, w_scale, u_scale, *, impl: str = "exact"):
    """Recurrence oracle for the int8-resident kernels: weights arrive
    PACKED [i, f, o, g] and quantized per gate column (the
    ``lstm_quant.QuantizedLSTMWeights`` layout), dequantized AFTER each
    matmul exactly like the kernel's in-register epilogue.

    x: (B, S, D) f32 → hs (B, S, H), final (h, c).
    """
    sig = act_mod.get_sigmoid(impl)
    tnh = act_mod.get_tanh(impl)
    bsz, seq, _ = x.shape
    hidden = u_q.shape[0]
    wf = w_q.astype(jnp.float32)
    uf = u_q.astype(jnp.float32)
    h = jnp.zeros((bsz, hidden), jnp.float32)
    c = jnp.zeros((bsz, hidden), jnp.float32)
    hs = []
    for t in range(seq):
        z = (
            (x[:, t].astype(jnp.float32) @ wf) * w_scale[None, :]
            + (h @ uf) * u_scale[None, :]
            + b[None, :]
        )
        i = sig(z[:, :hidden])
        f = sig(z[:, hidden : 2 * hidden])
        o = sig(z[:, 2 * hidden : 3 * hidden])
        g = tnh(z[:, 3 * hidden :])
        c = f * c + i * g
        h = o * tnh(c)
        hs.append(h)
    return jnp.stack(hs, axis=1).astype(x.dtype), h, c


# ---------------------------------------------------------------------------
# Int8 matmul with per-channel scales
# ---------------------------------------------------------------------------
def int8_matmul_ref(x_q, w_q, x_scale, w_scale):
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (M, 1); w_scale: (N,)."""
    acc = jnp.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    return acc.astype(jnp.float32) * x_scale * w_scale[None, :]


def quantize_rowwise(x):
    """Symmetric per-row int8 quantization. Returns (x_q, scale (M,1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return xq, scale


def quantize_colwise(w):
    """Symmetric per-output-channel int8 quantization. Returns (w_q, scale (N,))."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    wq = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return wq, scale
