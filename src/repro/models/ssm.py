"""Mamba2 (SSD — state-space duality) blocks: chunked train path + O(1) decode.

The SSD algorithm (Dao & Gu, 2024) computes the scalar-decay SSM

    h_t = exp(dt_t * A) · h_{t-1} + dt_t · x_t ⊗ B_t          (per head)
    y_t = C_t · h_t + D · x_t

as a *chunked* dual form: a quadratic attention-like matmul inside each
length-L chunk plus a tiny inter-chunk state recurrence. This turns the
sequential scan into MXU-friendly batched GEMMs — the TPU adaptation of
Mamba2's GPU kernel (we re-block for the MXU instead of warp tiles).

Implementation notes:
  * ``in_proj`` is declared as five separate matrices (z/x/B/C/dt) instead of
    one fused projection — mathematically identical, but each output then has
    a clean logical axis for TP sharding ("inner" / "ssm_heads").
  * n_groups = 1 (B/C shared across heads), matching mamba2-780m / zamba2.
  * All SSM arithmetic in float32; cast back to activation dtype at the end.
  * ``ssm_reference`` is the sequential oracle used by tests to validate the
    chunked path.
  * Device scopes: the projections in and out run under ``proj``, the
    causal convs, the scan and the state update under ``ssm``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamDef
from repro.models.quant import qeinsum
from repro.models.layers import rmsnorm_defs, rmsnorm
from repro.sharding.rules import constrain


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------
def mamba_defs(cfg: ArchConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    h = s.num_heads(d)
    n = s.state_size
    w = s.conv_width
    return {
        "wz": ParamDef((d, di), ("embed", "inner")),
        "wx": ParamDef((d, di), ("embed", "inner")),
        "wB": ParamDef((d, n), ("embed", None)),
        "wC": ParamDef((d, n), ("embed", None)),
        "wdt": ParamDef((d, h), ("embed", "ssm_heads")),
        # depthwise causal convs over the x/B/C streams (width w)
        "conv_x": ParamDef((w, di), (None, "inner"), init="normal"),
        "conv_x_b": ParamDef((di,), ("inner",), init="zeros"),
        "conv_B": ParamDef((w, n), (None, None), init="normal"),
        "conv_B_b": ParamDef((n,), (None,), init="zeros"),
        "conv_C": ParamDef((w, n), (None, None), init="normal"),
        "conv_C_b": ParamDef((n,), (None,), init="zeros"),
        "A_log": ParamDef((h,), ("ssm_heads",), init="scalar_log", dtype=jnp.float32),
        "dt_bias": ParamDef((h,), ("ssm_heads",), init="zeros", dtype=jnp.float32),
        "D": ParamDef((h,), ("ssm_heads",), init="ones", dtype=jnp.float32),
        "norm": rmsnorm_defs(di),
        "wo": ParamDef((di, d), ("inner", "embed")),
    }


# ---------------------------------------------------------------------------
# Causal depthwise conv (full-sequence + incremental forms)
# ---------------------------------------------------------------------------
def _causal_conv(x, w, b):
    """x: (B, S, C), w: (W, C) depthwise, left-padded causal + silu."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):  # width is 4 — unrolled taps, no conv primitive
        out = out + xp[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype)


def _conv_chunk(tail, raw, w, b):
    """Causal depthwise conv over a T-token chunk with a carried raw tail.

    tail: (B, W-1, C) — the raw inputs immediately preceding the chunk (zeros
    for the first chunk, matching ``_causal_conv``'s left zero-padding).
    raw: (B, T, C). Returns (silu(conv), new_tail)."""
    width = w.shape[0]
    xp = jnp.concatenate([tail.astype(raw.dtype), raw], axis=1)  # (B, W-1+T, C)
    out = jnp.zeros_like(raw, dtype=jnp.float32)
    for i in range(width):
        out = out + xp[:, i : i + raw.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    return jax.nn.silu(out).astype(raw.dtype), xp[:, -(width - 1) :, :]


def _conv_step(conv_state, x_new, w, b):
    """Incremental conv. conv_state: (B, W-1, C); x_new: (B, 1, C)."""
    window = jnp.concatenate([conv_state.astype(x_new.dtype), x_new], axis=1)  # (B, W, C)
    out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w.astype(jnp.float32)) + b.astype(
        jnp.float32
    )
    out = jax.nn.silu(out)[:, None, :].astype(x_new.dtype)
    return out, window[:, 1:, :]


# ---------------------------------------------------------------------------
# SSD chunked scan (train / prefill)
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None):
    """Chunked SSD. All inputs float32.

    x:  (B, S, H, P)   per-head inputs
    dt: (B, S, H)      post-softplus timestep
    A:  (H,)           negative per-head decay rate
    Bm: (B, S, N)      input projection (shared across heads, n_groups=1)
    Cm: (B, S, N)      output projection
    Returns (y: (B, S, H, P), h_final: (B, H, P, N)).
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    if s % chunk != 0:
        chunk = s  # degenerate single-chunk fallback (smoke tests)
    nc = s // chunk

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = Bm.reshape(b, nc, chunk, n)
    Cc = Cm.reshape(b, nc, chunk, n)

    dA = dtc * A  # (B, nc, L, H), ≤ 0
    cum = jnp.cumsum(dA, axis=2)  # (B, nc, L, H)

    # -- intra-chunk (quadratic dual form) --------------------------------
    # seg[b,c,h,i,j] = exp(cum_i - cum_j) for i ≥ j else 0
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,L,L,H) i,j
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (B,nc,L,L)
    m = cb[:, :, :, :, None] * seg * dtc[:, :, None, :, :]  # [b,c,i,j,h]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", m, xc)

    # -- chunk-final states ------------------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,L,H)
    hc = jnp.einsum("bclh,bclhp,bcln->bchpn", decay_to_end * dtc, xc, Bc)

    # -- inter-chunk recurrence (tiny scan over nc) ------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)

    def step(h_prev, inp):
        cd, hck = inp  # (B,H), (B,H,P,N)
        h_in = h_prev  # state *entering* this chunk
        h_out = h_prev * cd[:, :, None, None] + hck
        return h_out, h_in

    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    h_final, h_in_stack = jax.lax.scan(
        step, h0, (chunk_decay.swapaxes(0, 1), hc.swapaxes(0, 1))
    )
    h_in = h_in_stack.swapaxes(0, 1)  # (B,nc,H,P,N) state entering each chunk

    # -- inter-chunk output contribution -----------------------------------
    y_inter = jnp.einsum("bcin,bchpn->bcihp", Cc, h_in) * jnp.exp(cum)[:, :, :, :, None]

    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y, h_final


def ssd_states(x, dt, A, Bm, Cm, h0):
    """Single-chunk SSD that also returns the state AFTER every position.

    Same dual form as ``ssd_chunked`` restricted to one chunk (speculative
    verify windows are K+1 ≤ ~8 tokens, so the quadratic seg matrix is tiny),
    but instead of only the chunk-final state it materializes

        h_i = exp(cum_i)·h0 + Σ_{j≤i} exp(cum_i - cum_j)·dt_j·(x_j ⊗ B_j)

    for every i — the per-position snapshots speculative decode needs to
    roll the recurrent state back to the last ACCEPTED token (a positional
    KV cache rolls back for free; an SSM state does not).

    x: (B,T,H,P), dt: (B,T,H), A: (H,), Bm/Cm: (B,T,N), h0: (B,H,P,N).
    Returns (y: (B,T,H,P), h_all: (B,T,H,P,N)) with h_all[:, i] the state
    after consuming i+1 tokens; y_i = C_i · h_i (matches ``ssm_reference``).
    """
    t = x.shape[1]
    dA = dt * A  # (B,T,H), ≤ 0
    cum = jnp.cumsum(dA, axis=1)
    diff = cum[:, :, None, :] - cum[:, None, :, :]  # (B, T_i, T_j, H)
    tri = jnp.tril(jnp.ones((t, t), bool))
    seg = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
    contrib = jnp.einsum("bth,bthp,btn->bthpn", dt, x, Bm)  # dt_j · x_j ⊗ B_j
    h_all = jnp.einsum("bijh,bjhpn->bihpn", seg, contrib)
    h_all = h_all + jnp.exp(cum)[..., None, None] * h0[:, None]
    y = jnp.einsum("bthpn,btn->bthp", h_all, Cm)
    return y, h_all


def ssm_reference(x, dt, A, Bm, Cm, h0=None):
    """Sequential oracle: literal per-step recurrence (tests only)."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    def step(hprev, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,N), (B,N)
        da = jnp.exp(dtt * A)  # (B,H)
        hnew = hprev * da[:, :, None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dtt, xt, bt
        )
        yt = jnp.einsum("bhpn,bn->bhp", hnew, ct)
        return hnew, yt

    hf, ys = jax.lax.scan(
        step, h0, (x.swapaxes(0, 1), dt.swapaxes(0, 1), Bm.swapaxes(0, 1), Cm.swapaxes(0, 1))
    )
    return ys.swapaxes(0, 1), hf  # (B,S,H,P), (B,H,P,N)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _project(params, x, cfg: ArchConfig):
    with jax.named_scope("proj"):
        z = qeinsum("bsd,di->bsi", x, params["wz"])
        xs = qeinsum("bsd,di->bsi", x, params["wx"])
        Bm = jnp.einsum("bsd,dn->bsn", x, params["wB"])
        Cm = jnp.einsum("bsd,dn->bsn", x, params["wC"])
        dt = jnp.einsum("bsd,dh->bsh", x, params["wdt"])
        return z, xs, Bm, Cm, dt


def _out(params, y, z, cfg: ArchConfig):
    """The gated RMSNorm and the output projection."""
    with jax.named_scope("proj"):
        y = rmsnorm(params["norm"], y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                    cfg.norm_eps)
        return qeinsum("bsi,id->bsd", y, params["wo"])


def mamba_apply(params, x, cfg: ArchConfig):
    """Full-sequence Mamba2 block. x: (B, S, D) → (B, S, D)."""
    s = cfg.ssm
    hd, st = s.head_dim, s.state_size
    nh = s.num_heads(cfg.d_model)
    z, xs, Bm, Cm, dt = _project(params, x, cfg)
    with jax.named_scope("ssm"):
        xs = _causal_conv(xs, params["conv_x"], params["conv_x_b"])
        Bm = _causal_conv(Bm, params["conv_B"], params["conv_B_b"])
        Cm = _causal_conv(Cm, params["conv_C"], params["conv_C_b"])
        xs = constrain(xs, ("batch", None, "inner"))

        b, sl, _ = x.shape
        xh = xs.reshape(b, sl, nh, hd).astype(jnp.float32)
        dtf = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        y, _ = ssd_chunked(xh, dtf, A, Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                           s.chunk_size)
        y = y + params["D"][None, None, :, None] * xh
        y = y.reshape(b, sl, nh * hd).astype(x.dtype)
        y = constrain(y, ("batch", None, "inner"))
    return _out(params, y, z, cfg)


def mamba_prefill_apply(params, x, cfg: ArchConfig):
    """Full-sequence pass that also returns the decode cache.

    Returns (out, conv_tail, h_final):
      conv_tail: (B, W-1, d_inner + 2N) — last W-1 *raw* projected x/B/C
                 values (the incremental conv consumes raw inputs).
      h_final:   (B, H, P, N) final SSM state.
    """
    s = cfg.ssm
    hd = s.head_dim
    nh = s.num_heads(cfg.d_model)
    w = s.conv_width
    z, xs_raw, B_raw, C_raw, dt = _project(params, x, cfg)
    with jax.named_scope("ssm"):
        tail = jnp.concatenate([xs_raw[:, -(w - 1) :], B_raw[:, -(w - 1) :],
                                C_raw[:, -(w - 1) :]], axis=-1)
        xs = _causal_conv(xs_raw, params["conv_x"], params["conv_x_b"])
        Bm = _causal_conv(B_raw, params["conv_B"], params["conv_B_b"])
        Cm = _causal_conv(C_raw, params["conv_C"], params["conv_C_b"])

        b, sl, _ = x.shape
        xh = xs.reshape(b, sl, nh, hd).astype(jnp.float32)
        dtf = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        y, h_final = ssd_chunked(
            xh, dtf, A, Bm.astype(jnp.float32), Cm.astype(jnp.float32), s.chunk_size
        )
        y = y + params["D"][None, None, :, None] * xh
        y = y.reshape(b, sl, nh * hd).astype(x.dtype)
    return _out(params, y, z, cfg), tail, h_final


def mamba_chunk_apply(params, x, conv_state, ssm_state, cfg: ArchConfig):
    """Chunked prefill: T tokens with carried conv tail + SSM state.

    x: (B, T, D). The conv consumes the previous W-1 *raw* projected values
    (``conv_state``, same layout the decode step maintains) and the SSD scan
    starts from ``ssm_state`` — so successive chunks compose to the same
    recurrence the full-sequence ``mamba_prefill_apply`` computes.
    Returns (out, new_conv_state, new_ssm_state)."""
    s = cfg.ssm
    hd, st = s.head_dim, s.state_size
    nh = s.num_heads(cfg.d_model)
    di = s.d_inner(cfg.d_model)
    z, xs_raw, B_raw, C_raw, dt = _project(params, x, cfg)
    with jax.named_scope("ssm"):
        cs_x = conv_state[:, :, :di]
        cs_B = conv_state[:, :, di : di + st]
        cs_C = conv_state[:, :, di + st :]
        xs, cs_x = _conv_chunk(cs_x, xs_raw, params["conv_x"], params["conv_x_b"])
        Bm, cs_B = _conv_chunk(cs_B, B_raw, params["conv_B"], params["conv_B_b"])
        Cm, cs_C = _conv_chunk(cs_C, C_raw, params["conv_C"], params["conv_C_b"])
        new_conv = jnp.concatenate(
            [cs_x.astype(conv_state.dtype), cs_B.astype(conv_state.dtype),
             cs_C.astype(conv_state.dtype)],
            axis=-1,
        )

        b, sl, _ = x.shape
        xh = xs.reshape(b, sl, nh, hd).astype(jnp.float32)
        dtf = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        y, h_final = ssd_chunked(
            xh, dtf, A, Bm.astype(jnp.float32), Cm.astype(jnp.float32), s.chunk_size,
            h0=ssm_state.astype(jnp.float32),
        )
        y = y + params["D"][None, None, :, None] * xh
        y = y.reshape(b, sl, nh * hd).astype(x.dtype)
    return _out(params, y, z, cfg), new_conv, h_final.astype(ssm_state.dtype)


def mamba_verify_apply(params, x, conv_state, ssm_state, cfg: ArchConfig):
    """Speculative-verify pass: T candidate tokens in ONE chunk pass, with
    per-position state snapshots for acceptance rollback.

    Identical math to ``mamba_chunk_apply`` (carried raw conv tail + SSD
    with h0), but every position's conv tail and SSM state are returned so
    the caller can commit the snapshot at the last accepted token:

    Returns (out, conv_all, h_all):
      conv_all: (B, T, W-1, d_inner+2N) raw tail after each position
      h_all:    (B, T, H, P, N) SSM state after each position
    """
    s = cfg.ssm
    hd, st = s.head_dim, s.state_size
    nh = s.num_heads(cfg.d_model)
    di = s.d_inner(cfg.d_model)
    w = s.conv_width
    z, xs_raw, B_raw, C_raw, dt = _project(params, x, cfg)
    with jax.named_scope("ssm"):
        cs_x = conv_state[:, :, :di]
        cs_B = conv_state[:, :, di : di + st]
        cs_C = conv_state[:, :, di + st :]
        xs, _ = _conv_chunk(cs_x, xs_raw, params["conv_x"], params["conv_x_b"])
        Bm, _ = _conv_chunk(cs_B, B_raw, params["conv_B"], params["conv_B_b"])
        Cm, _ = _conv_chunk(cs_C, C_raw, params["conv_C"], params["conv_C_b"])
        # per-position raw tails: after consuming t+1 tokens the window is rows
        # [t+1, t+W) of concat(old tail, raw chunk) — position T-1 reproduces
        # exactly the tail mamba_chunk_apply would carry forward
        raw = jnp.concatenate([xs_raw, B_raw, C_raw], axis=-1)
        full = jnp.concatenate([conv_state.astype(raw.dtype), raw], axis=1)
        sl = x.shape[1]
        conv_all = jnp.stack(
            [full[:, t + 1 : t + w, :] for t in range(sl)], axis=1
        ).astype(conv_state.dtype)

        b = x.shape[0]
        xh = xs.reshape(b, sl, nh, hd).astype(jnp.float32)
        dtf = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        y, h_all = ssd_states(
            xh, dtf, A, Bm.astype(jnp.float32), Cm.astype(jnp.float32),
            ssm_state.astype(jnp.float32),
        )
        y = y + params["D"][None, None, :, None] * xh
        y = y.reshape(b, sl, nh * hd).astype(x.dtype)
    return _out(params, y, z, cfg), conv_all, h_all.astype(ssm_state.dtype)


def mamba_decode_apply(params, x, conv_state, ssm_state, cfg: ArchConfig):
    """One-token decode. x: (B, 1, D).

    conv_state: (B, W-1, d_inner + 2N) stacked x/B/C conv windows.
    ssm_state:  (B, H, P, N)
    Returns (out, new_conv_state, new_ssm_state) — O(1) in context length.
    """
    s = cfg.ssm
    hd, st = s.head_dim, s.state_size
    nh = s.num_heads(cfg.d_model)
    di = s.d_inner(cfg.d_model)
    z, xs, Bm, Cm, dt = _project(params, x, cfg)
    with jax.named_scope("ssm"):
        cs_x = conv_state[:, :, :di]
        cs_B = conv_state[:, :, di : di + st]
        cs_C = conv_state[:, :, di + st :]
        xs, cs_x = _conv_step(cs_x, xs, params["conv_x"], params["conv_x_b"])
        Bm, cs_B = _conv_step(cs_B, Bm, params["conv_B"], params["conv_B_b"])
        Cm, cs_C = _conv_step(cs_C, Cm, params["conv_C"], params["conv_C_b"])
        new_conv = jnp.concatenate(
            [cs_x.astype(conv_state.dtype), cs_B.astype(conv_state.dtype),
             cs_C.astype(conv_state.dtype)],
            axis=-1,
        )

        b = x.shape[0]
        xh = xs.reshape(b, nh, hd).astype(jnp.float32)
        dtf = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B,H)
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        da = jnp.exp(dtf * A)  # (B,H)
        h_new = ssm_state.astype(jnp.float32) * da[:, :, None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dtf, xh, Bm[:, 0].astype(jnp.float32)
        )
        y = jnp.einsum("bhpn,bn->bhp", h_new, Cm[:, 0].astype(jnp.float32))
        y = y + params["D"][None, :, None] * xh
        y = y.reshape(b, 1, di).astype(x.dtype)
    return _out(params, y, z, cfg), new_conv, h_new.astype(ssm_state.dtype)


def conv_channels(cfg: ArchConfig) -> int:
    s = cfg.ssm
    return s.d_inner(cfg.d_model) + 2 * s.state_size
