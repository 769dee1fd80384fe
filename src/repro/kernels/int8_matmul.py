"""Per-channel-scaled int8 matmul — the precision axis of the design space.

The paper's precision story (Rybalkin et al.: reduced precision → better
memory/energy/throughput) maps on TPU to int8 MXU matmuls: the systolic
array runs int8 at 2× bf16 throughput (394 TOPS vs 197 TFLOPS on v5e) and
halves HBM traffic for the weights. Quantization is symmetric: per-row
scales for activations, per-output-channel scales for weights, dequantized
in the f32 epilogue.

Grid (M/bm, N/bn, K/bk) with the K loop innermost (sequential on TPU); an
int32 VMEM scratch accumulates partial products; the scale epilogue runs on
the last K step.

``interpret=None`` resolves via ``runtime.default_interpret()``;
``block_* = "auto"`` routes through the ``repro.kernels.autotune`` roofline
tuner (candidates must divide M/N/K exactly — this kernel does not pad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, num_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, bk) int8
    w = w_ref[...]  # (bk, bn) int8
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(ki == num_k - 1)
    def _finalize():
        sx = sx_ref[...]  # (bm, 1) f32
        sw = sw_ref[...]  # (1, bn) f32
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * sx * sw).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def _int8_matmul_call(x_q, w_q, x_scale, w_scale, *, block_m: int,
                      block_n: int, block_k: int, interpret: bool):
    m, k = x_q.shape
    n = w_q.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    num_k = k // bk

    kernel = functools.partial(_kernel, num_k=num_k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, num_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            # 2-D (1, bn) so Mosaic's tiling of the scale row matches XLA's
            # for any bn (a 1-D block must be the whole row or 1024-aligned)
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale.reshape(1, n))


def int8_matmul(x_q, w_q, x_scale, w_scale, *, block_m: int | str = 256,
                block_n: int | str = 256, block_k: int | str = 256,
                interpret: bool | None = None):
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (M, 1) f32; w_scale: (N,) f32."""
    interpret = resolve_interpret(interpret)
    if "auto" in (block_m, block_n, block_k):
        from repro.kernels.autotune import autotune

        m, k = x_q.shape
        n = w_q.shape[1]
        cfg = autotune("int8_matmul", {"m": m, "k": k, "n": n}, dtype="int8")
        block_m = cfg["block_m"] if block_m == "auto" else block_m
        block_n = cfg["block_n"] if block_n == "auto" else block_n
        block_k = cfg["block_k"] if block_k == "auto" else block_k
    return _int8_matmul_call(
        x_q, w_q, x_scale, w_scale, block_m=int(block_m), block_n=int(block_n),
        block_k=int(block_k), interpret=interpret,
    )
