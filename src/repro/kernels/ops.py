"""Public wrappers over the Pallas kernels.

Execution mode is resolved per call by ``repro.kernels.runtime``: Mosaic on
a real TPU backend, the interpreter elsewhere, overridable via
``REPRO_PALLAS_INTERPRET``. Setting the
module attribute ``INTERPRET`` to a bool still force-overrides everything
(back-compat escape hatch); leave it ``None`` for auto.

Block sizes default to ``"auto"`` here: shapes route through the
``repro.kernels.autotune`` roofline tuner (cached per shape/dtype/backend).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.activations import activation as _activation
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import int8_matmul as _int8_matmul
from repro.kernels.lstm_cell import lstm_cell_fused as _lstm_cell
from repro.kernels.lstm_seq import (
    lstm_seq_fused as _lstm_seq,
    lstm_seq_fused_q8 as _lstm_seq_q8,
    lstm_seq_fused_quantized as _lstm_seq_quantized,
    lstm_stack_fused as _lstm_stack,
)
from repro.kernels.ref import quantize_colwise, quantize_rowwise

# None → per-call auto-resolution (runtime.default_interpret); bool → forced.
INTERPRET: bool | None = None


def activation(x, *, fn: str = "sigmoid", impl: str = "exact", block_rows: int = 256):
    return _activation(x, fn=fn, impl=impl, block_rows=block_rows, interpret=INTERPRET)


def flash_attention(q, k, v, *, causal: bool = True, block_q="auto", block_k="auto"):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=INTERPRET)


def lstm_cell(x, h, c, w, u, b, *, impl: str = "exact", block_b="auto"):
    return _lstm_cell(x, h, c, w, u, b, impl=impl, block_b=block_b,
                      interpret=INTERPRET)


def lstm_seq(x, w, u, b, *, impl: str = "exact", block_b="auto",
             return_state: bool = False):
    """Sequence-resident fused LSTM: x (B, S, D) → hs (B, S, H)."""
    return _lstm_seq(x, w, u, b, impl=impl, block_b=block_b,
                     interpret=INTERPRET, return_state=return_state)


def lstm_seq_q8(x, w, u, b, *, impl: str = "exact", block_b="auto",
                return_state: bool = False):
    """int8-resident sequence LSTM (quantize-on-the-fly f32 weights)."""
    return _lstm_seq_q8(x, w, u, b, impl=impl, block_b=block_b,
                        interpret=INTERPRET, return_state=return_state)


def lstm_seq_quantized(x, qw, *, impl: str = "exact", block_b="auto",
                       return_state: bool = False):
    """int8-resident sequence LSTM over pre-quantized weights
    (``lstm_quant.QuantizedLSTMWeights``)."""
    return _lstm_seq_quantized(x, qw, impl=impl, block_b=block_b,
                               interpret=INTERPRET, return_state=return_state)


def lstm_stack(x, layers, *, impl: str = "exact", block_b="auto",
               quantized: bool = False, return_state: bool = False):
    """Layer-fused L-layer LSTM stack in one pallas_call: x (B, S, D) →
    last layer's hs (B, S, H); inter-layer h stays in VMEM."""
    return _lstm_stack(x, layers, impl=impl, block_b=block_b,
                       quantized=quantized, interpret=INTERPRET,
                       return_state=return_state)


def int8_matmul(x_q, w_q, x_scale, w_scale, **kw):
    for k in ("block_m", "block_n", "block_k"):
        kw.setdefault(k, "auto")
    return _int8_matmul(x_q, w_q, x_scale, w_scale, interpret=INTERPRET, **kw)


def quantized_matmul(x, w, **kw):
    """Quantize-on-the-fly f32/bf16 matmul through the int8 kernel."""
    xq, sx = quantize_rowwise(x)
    wq, sw = quantize_colwise(w)
    return int8_matmul(xq, wq, sx, sw, **kw).astype(x.dtype)
