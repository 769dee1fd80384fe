"""Sequence-resident fused LSTM — the whole recurrence in ONE ``pallas_call``.

The paper's C1/C2 headline (−47% latency, 2.33× GOPS/W) comes from keeping
the LSTM weights on-chip (BRAM) and pipelining all four gates through one
MAC array, so each timestep pays only for compute — never for re-streaming
weights.  ``lstm_cell.lstm_cell_fused`` ports the *cell* but re-launches a
fresh ``pallas_call`` per timestep under ``jax.lax.scan``, which re-streams
``w``/``u`` from HBM every step and bounces ``h``/``c`` through HBM between
steps.  This kernel ports the *residency*:

  * the grid walks batch blocks only; the time loop runs INSIDE the kernel
    body (``jax.lax.fori_loop``), so there is no per-timestep launch or
    block-dispatch machinery at all;
  * ``w`` (D, 4H), ``u`` (H, 4H), bias, and the activation LUT have
    constant index_maps: Pallas keeps them resident in VMEM for the entire
    grid — the paper's BRAM residency, mapped onto VMEM;
  * the batch tile's whole input sequence (S, bb, D) and output sequence
    (S, bb, H) are VMEM tiles too — for the embedded shapes the paper
    targets (S·(D+H) of a few KB per batch row) the entire working set is
    on-chip, exactly the paper's operating point.  ``h``/``c`` are the
    fori_loop carry: registers/VMEM, never HBM;
  * per-sequence weight traffic drops from S·(D+H)·4H·4 bytes (per-step
    path) to (D+H)·4H·4 per batch block — an S× reduction on the dominant
    term (S = 28 for the paper workload).

Two follow-on axes compose with the residency (this module provides both):

**int8 residency** (``lstm_seq_fused_q8`` / ``lstm_seq_fused_quantized``):
``w``/``u`` live in VMEM as int8 with per-gate-column f32 scales
(``kernels.lstm_quant``, same conventions as ``int8_matmul``), dequantized
at the MXU boundary — the casts sit inside the matmuls so the compiler
streams int8 tiles and converts in registers (``(x @ w_q) * sw``, a VPU
scale epilogue), never forcing a persistent f32 weight copy across the
recurrence.  Footprint arithmetic: one layer's resident
weights cost (D+H)·4H·4 B at f32 but (D+H)·4H·1 + 8H·4 (scales) + 4H·4
(bias) at int8 — 4× less on the payload, 3.9× overall at D=H=256
(2.10 MB → 0.54 MB).  The autotuner's dtype-aware footprint model converts
the freed VMEM into a wider ``block_b`` batch tile (fewer grid steps, less
padding, fewer weight re-streams), which is where the measured us/call win
comes from.

**layer-fused stacks** (``lstm_stack_fused``): L layers chained through one
``pallas_call``.  The inter-layer h sequence lives in a (S, bb, H) VMEM
scratch tile — written by layer l's recurrence, consumed whole by layer
l+1's batched input projection — and never bounces through HBM, unlike L
sequential ``lstm_seq`` calls which pay a (B, S, H) HBM write+read plus a
batch-major⇄time-major transpose at every layer boundary.  The packed-gate
layout and the shared activation LUT are preserved per layer, and the stack
takes the quantized weights too (``quantized=True``).

Layout: time-major (S, B, D) inside the kernel so the per-step slice is a
clean (bb, D) tile; the public wrappers take/return batch-major (B, S, D)
like ``models.lstm.lstm_apply``.

Gate activations honour the RQ1 axis (``impl ∈ {exact, pwl, lut, hard}``)
via the shared half-range sigmoid table, also VMEM-resident.

``block_b="auto"`` routes through ``repro.kernels.autotune``, whose VMEM
feasibility check is what bounds S·bb·(D+H) to the on-chip budget —
long-sequence workloads trade batch-tile width for residency automatically,
and int8 weights buy the width back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.activations import _apply_variant, _sigmoid_table
from repro.kernels.runtime import resolve_interpret


def _input_projection(x_all, w, sw, b, *, seq: int, bb: int, hidden: int):
    """Whole-sequence input projection in ONE MXU pass — only possible
    because the entire (S, bb, D) tile is resident: the per-step cell
    kernel can never batch this matmul.  ``w`` may be int8: it is cast at
    the MXU boundary and the per-gate-column scale ``sw`` is applied as a
    VPU epilogue (column scales commute with the matmul)."""
    zx = jax.lax.dot_general(
        x_all, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if sw is not None:
        zx = zx * sw[None, :]
    return (zx + b[None, :]).reshape(seq, bb, 4 * hidden)


def _layer_recurrence(zx_ref, u, su, table, write, *, impl: str, hidden: int,
                      seq: int, bb: int):
    """Run one layer's time loop over precomputed input projections, staged
    in the (S, bb, 4H) VMEM scratch ``zx_ref`` (Mosaic indexes a ref by the
    loop counter; it cannot dynamically slice a value).

    Gate columns arrive PACKED as [i, f, o, g] (the wrappers permute the
    weights): the three sigmoid gates are one contiguous (bb, 3H) VPU pass
    instead of three, and tanh(g) one more — 2 activation sweeps per step
    instead of 4.  ``u`` may be int8 (dequantized at the MXU boundary via
    the per-gate-column scale ``su``).  ``write(t, h_new)`` stores the
    step's output (output ref or inter-layer VMEM scratch).

    The int8→f32 cast sits INSIDE the step, at the matmul boundary, so the
    compiler is free to stream int8 weight tiles and convert in registers
    as the MXU consumes them — the kernel never forces a persistent f32
    copy of ``u`` to live across the recurrence."""

    def step(t, carry):
        h, c = carry
        zu = jax.lax.dot_general(
            h, u.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if su is not None:
            zu = zu * su[None, :]
        z = zx_ref[t] + zu
        gates = _apply_variant(z[:, : 3 * hidden], impl, "sigmoid", table)
        i = gates[:, :hidden]
        f = gates[:, hidden : 2 * hidden]
        o = gates[:, 2 * hidden :]
        g = _apply_variant(z[:, 3 * hidden :], impl, "tanh", table)
        c_new = f * c + i * g
        h_new = o * _apply_variant(c_new, impl, "tanh", table)
        write(t, h_new)
        return h_new, c_new

    h0 = jnp.zeros((bb, hidden), jnp.float32)
    c0 = jnp.zeros((bb, hidden), jnp.float32)
    return jax.lax.fori_loop(0, seq, step, (h0, c0))


def _kernel(x_ref, w_ref, u_ref, b_ref, *rest, impl: str, hidden: int,
            seq: int, quantized: bool):
    """Single-layer sequence-resident kernel (f32 or int8 weights)."""
    if quantized:
        sw_ref, su_ref, table_ref, hs_ref, hn_ref, cn_ref, zx_ref = rest
        sw, su = sw_ref[...], su_ref[...]
    else:
        table_ref, hs_ref, hn_ref, cn_ref, zx_ref = rest
        sw = su = None
    bb = x_ref.shape[1]
    table = table_ref[...]
    b = b_ref[...].astype(jnp.float32)

    x_all = x_ref[...].astype(jnp.float32).reshape(seq * bb, -1)
    zx_ref[...] = _input_projection(x_all, w_ref[...], sw, b, seq=seq, bb=bb,
                                    hidden=hidden)

    def write(t, h_new):
        hs_ref[t] = h_new.astype(hs_ref.dtype)

    h, c = _layer_recurrence(zx_ref, u_ref[...], su, table, write,
                             impl=impl, hidden=hidden, seq=seq, bb=bb)
    hn_ref[...] = h.astype(hn_ref.dtype)
    cn_ref[...] = c.astype(cn_ref.dtype)


def _stack_kernel(x_ref, w0_ref, wr_ref, u_ref, b_ref, *rest, impl: str,
                  hidden: int, seq: int, layers: int, quantized: bool):
    """Layer-fused stack: L recurrences chained entirely inside VMEM.

    ``seq_scr`` (S, bb, H) holds the inter-layer h sequence: layer l writes
    it step by step, layer l+1 consumes it whole for its batched input
    projection — safe to overwrite in place during l+1's own recurrence
    because the projection already read every step.  The final layer writes
    the output ref instead.  Per-layer weights keep the packed-gate layout
    and share one activation LUT."""
    if quantized:
        sw_ref, su_ref, table_ref, hs_ref, hn_ref, cn_ref, seq_scr, zx_ref = rest
    else:
        table_ref, hs_ref, hn_ref, cn_ref, seq_scr, zx_ref = rest
    bb = x_ref.shape[1]
    table = table_ref[...]

    for l in range(layers):
        inp = x_ref[...] if l == 0 else seq_scr[...]
        x_all = inp.astype(jnp.float32).reshape(seq * bb, -1)
        w = w0_ref[...] if l == 0 else wr_ref[l - 1]
        sw = sw_ref[l] if quantized else None
        su = su_ref[l] if quantized else None
        b = b_ref[l].astype(jnp.float32)
        zx_ref[...] = _input_projection(x_all, w, sw, b, seq=seq, bb=bb,
                                        hidden=hidden)

        if l == layers - 1:
            def write(t, h_new):
                hs_ref[t] = h_new.astype(hs_ref.dtype)
        else:
            def write(t, h_new):
                seq_scr[t] = h_new

        h, c = _layer_recurrence(zx_ref, u_ref[l], su, table, write,
                                 impl=impl, hidden=hidden, seq=seq, bb=bb)
        hn_ref[l] = h.astype(hn_ref.dtype)
        cn_ref[l] = c.astype(cn_ref.dtype)


def _pack_ifog(w, u, b, hidden: int):
    """Permute gate columns i,f,g,o → i,f,o,g so the sigmoid gates are
    contiguous (one VPU sweep) and tanh(g) is the tail block."""
    def perm(m):
        return jnp.concatenate(
            [m[..., :hidden], m[..., hidden : 2 * hidden],
             m[..., 3 * hidden :], m[..., 2 * hidden : 3 * hidden]], axis=-1
        )
    return perm(w), perm(u), perm(b)


@functools.partial(
    jax.jit,
    static_argnames=("impl", "block_b", "interpret", "return_state", "pre_packed"),
)
def _lstm_seq_call(x, w, u, b, sw, su, *, impl: str, block_b: int,
                   interpret: bool, return_state: bool, pre_packed: bool = False):
    """Shared single-layer launcher. ``sw``/``su`` None → f32 weights;
    int8 weights arrive pre-packed from ``lstm_quant``."""
    bsz, seq, d = x.shape
    hidden = u.shape[0]
    quantized = sw is not None
    if not pre_packed:
        w, u, b = _pack_ifog(w, u, b, hidden)
    bb = min(block_b, bsz)
    pad = (-bsz) % bb
    xt = x.swapaxes(0, 1)  # time-major (S, B, D)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad), (0, 0)))
    pb = xt.shape[1]
    from repro.kernels.activations import LUT_SIZE

    kernel = functools.partial(_kernel, impl=impl, hidden=hidden, seq=seq,
                               quantized=quantized)
    in_specs = [
        pl.BlockSpec((seq, bb, d), lambda i: (0, i, 0)),
        pl.BlockSpec((d, 4 * hidden), lambda i: (0, 0)),
        pl.BlockSpec((hidden, 4 * hidden), lambda i: (0, 0)),
        pl.BlockSpec((4 * hidden,), lambda i: (0,)),
    ]
    operands = [xt, w, u, b]
    if quantized:
        in_specs += [
            pl.BlockSpec((4 * hidden,), lambda i: (0,)),
            pl.BlockSpec((4 * hidden,), lambda i: (0,)),
        ]
        operands += [sw, su]
    in_specs.append(pl.BlockSpec((LUT_SIZE,), lambda i: (0,)))
    operands.append(_sigmoid_table())

    hs, hn, cn = pl.pallas_call(
        kernel,
        grid=(pb // bb,),  # batch blocks only; time loops inside the kernel
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((seq, bb, hidden), lambda i: (0, i, 0)),
            pl.BlockSpec((bb, hidden), lambda i: (i, 0)),
            pl.BlockSpec((bb, hidden), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq, pb, hidden), x.dtype),
            jax.ShapeDtypeStruct((pb, hidden), x.dtype),
            jax.ShapeDtypeStruct((pb, hidden), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((seq, bb, 4 * hidden), jnp.float32)],
        interpret=interpret,
    )(*operands)
    hs = hs.swapaxes(0, 1)[:bsz]
    if return_state:
        return hs, (hn[:bsz], cn[:bsz])
    return hs


def _autotune_block(kernel: str, x, hidden: int, dtype: str, layers: int | None = None):
    from repro.kernels.autotune import autotune

    bsz, seq, d = x.shape
    problem = {"batch": bsz, "seq": seq, "d_in": d, "hidden": hidden}
    if layers is not None:
        problem["layers"] = layers
    return autotune(kernel, problem, dtype=dtype)["block_b"]


def lstm_seq_fused(x, w, u, b, *, impl: str = "exact",
                   block_b: int | str = "auto", interpret: bool | None = None,
                   return_state: bool = False):
    """Whole-sequence fused LSTM. x: (B, S, D); w: (D, 4H); u: (H, 4H).

    Returns hs (B, S, H), plus the final (h, c) when ``return_state``.
    ``block_b`` is the batch tile ("auto" → autotuned); any B and S work
    (B is zero-padded to a block multiple, S is walked in-kernel).
    """
    interpret = resolve_interpret(interpret)
    if block_b == "auto":
        block_b = _autotune_block("lstm_seq", x, u.shape[0], str(x.dtype))
    return _lstm_seq_call(x, w, u, b, None, None, impl=impl, block_b=int(block_b),
                          interpret=interpret, return_state=return_state)


def lstm_seq_fused_quantized(x, qw, *, impl: str = "exact",
                             block_b: int | str = "auto",
                             interpret: bool | None = None,
                             return_state: bool = False):
    """int8-resident sequence LSTM over pre-quantized weights.

    ``qw`` is a ``lstm_quant.QuantizedLSTMWeights`` (packed gate layout,
    per-gate-column scales).  The resident w/u footprint is 4× smaller than
    f32, which the autotuner converts into a wider ``block_b`` (the tuner
    key uses dtype="int8", so f32 and int8 winners never mix).
    """
    interpret = resolve_interpret(interpret)
    if block_b == "auto":
        block_b = _autotune_block("lstm_seq", x, qw.hidden, "int8")
    return _lstm_seq_call(x, qw.w_q, qw.u_q, qw.b, qw.w_scale, qw.u_scale,
                          impl=impl, block_b=int(block_b), interpret=interpret,
                          return_state=return_state, pre_packed=True)


def lstm_seq_fused_q8(x, w, u, b, *, impl: str = "exact",
                      block_b: int | str = "auto",
                      interpret: bool | None = None,
                      return_state: bool = False):
    """Convenience wrapper: quantize f32 weights on the fly, then run the
    int8-resident kernel (deployments should pre-quantize once with
    ``lstm_quant.quantize_lstm_weights`` and call the ``_quantized``
    variant)."""
    from repro.kernels.lstm_quant import quantize_lstm_weights

    return lstm_seq_fused_quantized(
        x, quantize_lstm_weights(w, u, b, u.shape[0]), impl=impl,
        block_b=block_b, interpret=interpret, return_state=return_state,
    )


@functools.partial(
    jax.jit, static_argnames=("impl", "block_b", "interpret", "return_state")
)
def _lstm_stack_call(x, w0, wr, us, bs, sws, sus, *, impl: str, block_b: int,
                     interpret: bool, return_state: bool):
    """Layer-fused stack launcher.  All tensors pre-packed; layers ≥ 2.

    w0: (D, 4H); wr: (L-1, H, 4H); us: (L, H, 4H); bs: (L, 4H);
    sws/sus: (L, 4H) scales or None (f32 path).
    """
    bsz, seq, d = x.shape
    layers, hidden = us.shape[0], us.shape[1]
    quantized = sws is not None
    bb = min(block_b, bsz)
    pad = (-bsz) % bb
    xt = x.swapaxes(0, 1)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad), (0, 0)))
    pb = xt.shape[1]
    from repro.kernels.activations import LUT_SIZE

    kernel = functools.partial(_stack_kernel, impl=impl, hidden=hidden,
                               seq=seq, layers=layers, quantized=quantized)
    in_specs = [
        pl.BlockSpec((seq, bb, d), lambda i: (0, i, 0)),
        pl.BlockSpec((d, 4 * hidden), lambda i: (0, 0)),
        pl.BlockSpec((layers - 1, hidden, 4 * hidden), lambda i: (0, 0, 0)),
        pl.BlockSpec((layers, hidden, 4 * hidden), lambda i: (0, 0, 0)),
        pl.BlockSpec((layers, 4 * hidden), lambda i: (0, 0)),
    ]
    operands = [xt, w0, wr, us, bs]
    if quantized:
        in_specs += [
            pl.BlockSpec((layers, 4 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((layers, 4 * hidden), lambda i: (0, 0)),
        ]
        operands += [sws, sus]
    in_specs.append(pl.BlockSpec((LUT_SIZE,), lambda i: (0,)))
    operands.append(_sigmoid_table())

    hs, hn, cn = pl.pallas_call(
        kernel,
        grid=(pb // bb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((seq, bb, hidden), lambda i: (0, i, 0)),
            pl.BlockSpec((layers, bb, hidden), lambda i: (0, i, 0)),
            pl.BlockSpec((layers, bb, hidden), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq, pb, hidden), x.dtype),
            jax.ShapeDtypeStruct((layers, pb, hidden), x.dtype),
            jax.ShapeDtypeStruct((layers, pb, hidden), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((seq, bb, hidden), jnp.float32),
                        pltpu.VMEM((seq, bb, 4 * hidden), jnp.float32)],
        interpret=interpret,
    )(*operands)
    hs = hs.swapaxes(0, 1)[:bsz]
    if return_state:
        return hs, (hn[:, :bsz], cn[:, :bsz])
    return hs


def lstm_stack_fused(x, layers, *, impl: str = "exact",
                     block_b: int | str = "auto", quantized: bool = False,
                     interpret: bool | None = None,
                     return_state: bool = False):
    """L-layer layer-fused LSTM stack: ONE ``pallas_call`` for all layers.

    x: (B, S, D); ``layers`` is a list of (w, u, b) triples (or param
    dicts): layer 0 takes w (D, 4H); layers 1..L-1 take w (H, 4H); every
    layer's u is (H, 4H).  The inter-layer h sequence stays in a VMEM
    scratch tile — it never round-trips through HBM the way L sequential
    ``lstm_seq_fused`` calls do.  ``quantized=True`` holds every layer's
    w/u as int8 with per-gate-column scales (``kernels.lstm_quant``).

    Returns hs (B, S, H) of the LAST layer, plus per-layer final states
    (h, c) of shape (L, B, H) when ``return_state``.
    """
    triples = [
        (l["w"], l["u"], l["b"]) if isinstance(l, dict) else l for l in layers
    ]
    if not triples:
        raise ValueError("lstm_stack_fused needs at least one layer")
    hidden = triples[0][1].shape[0]
    for w, u, b in triples[1:]:
        if w.shape != (hidden, 4 * hidden) or u.shape != (hidden, 4 * hidden):
            raise ValueError(
                f"stack layers beyond the first must be ({hidden}, {4 * hidden})"
                f"-shaped, got w {w.shape} / u {u.shape}"
            )
    interpret = resolve_interpret(interpret)
    dtype = "int8" if quantized else str(x.dtype)
    if block_b == "auto":
        block_b = _autotune_block("lstm_stack", x, hidden, dtype,
                                  layers=len(triples))

    if len(triples) == 1:  # degenerate stack: the single-layer kernel IS it
        w, u, b = triples[0]
        fn = lstm_seq_fused_q8 if quantized else lstm_seq_fused
        out = fn(x, w, u, b, impl=impl, block_b=int(block_b),
                 interpret=interpret, return_state=return_state)
        if return_state:
            hs, (hn, cn) = out
            return hs, (hn[None], cn[None])
        return out

    if quantized:
        from repro.kernels.lstm_quant import quantize_lstm_stack

        qs = quantize_lstm_stack(triples)
        w0 = qs[0].w_q
        wr = jnp.stack([q.w_q for q in qs[1:]])
        us = jnp.stack([q.u_q for q in qs])
        bs = jnp.stack([q.b for q in qs])
        sws = jnp.stack([q.w_scale for q in qs])
        sus = jnp.stack([q.u_scale for q in qs])
    else:
        packed = [_pack_ifog(w, u, b, hidden) for w, u, b in triples]
        w0 = packed[0][0]
        wr = jnp.stack([p[0] for p in packed[1:]])
        us = jnp.stack([p[1] for p in packed])
        bs = jnp.stack([p[2] for p in packed])
        sws = sus = None
    return _lstm_stack_call(x, w0, wr, us, bs, sws, sus, impl=impl,
                            block_b=int(block_b), interpret=interpret,
                            return_state=return_state)
