"""Mamba-2 blocks (the program's ``ssm`` family): RMSNorm, five input
projections (z, x, B, C, dt), causal depthwise convs of x, B and C, the
state-space scan with one group, a gated RMSNorm and the output projection.

Initialisation, as in Mamba-2's published one: A = -exp(A_log) with
exp(A_log) uniform in [1, 16), and dt_bias the inverse softplus of a
log-uniform step in [1e-3, 1e-1]; the causal conv taps are drawn with std
1/sqrt(width).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32
PROGRAM_FAMILY = "ssm"


def _a_log(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)).astype(dtype)


def _dt_bias(key, shape, dtype):
    dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1


INITS = {"a_log": _a_log, "dt_bias": _dt_bias}


def blocks(m: dict) -> dict:
    L, d, s = m["num_layers"], m["d_model"], m["ssm"]
    di, n, w = s["expand"] * d, s["state_size"], s["conv_width"]
    nh = di // s["head_dim"]
    ones = lambda n: {"scale": ((L, n), F32, "ones", 0)}  # noqa: E731
    mat = lambda a, b: ((L, a, b), BF16, "normal", 1 / math.sqrt(a))  # noqa: E731
    conv = lambda c: ((L, w, c), BF16, "normal", 1 / math.sqrt(w))  # noqa: E731
    zeros = lambda c: ((L, c), BF16, "zeros", 0)  # noqa: E731
    return {
        "ln": ones(d),
        "mamba": {
            "wz": mat(d, di), "wx": mat(d, di), "wB": mat(d, n),
            "wC": mat(d, n), "wdt": mat(d, nh),
            "conv_x": conv(di), "conv_x_b": zeros(di),
            "conv_B": conv(n), "conv_B_b": zeros(n),
            "conv_C": conv(n), "conv_C_b": zeros(n),
            "A_log": ((L, nh), F32, "a_log", 0),
            "dt_bias": ((L, nh), F32, "dt_bias", 0),
            "D": ((L, nh), F32, "ones", 0),
            "norm": ones(di),
            "wo": mat(di, d),
        },
    }


def layer_params(m) -> int:
    d, s = m["d_model"], m["ssm"]
    di, n = s["expand"] * d, s["state_size"]
    return d * (2 * di + 2 * n + di // s["head_dim"]) + di * d


def _dims(m):
    s = m["ssm"]
    di = s["expand"] * m["d_model"]
    return di, di // s["head_dim"], s["head_dim"], s["state_size"], s["conv_width"]


def decode(m, positions) -> tuple[float, float]:
    L, rows = m["num_layers"], len(positions)
    di, nh, hp, n, w = _dims(m)
    flops = rows * L * (nh * hp * n * 5.0 + 2.0 * w * (di + 2 * n))
    # the f32 state is read and written; the bf16 conv window too
    byts = rows * L * (2 * 4.0 * nh * hp * n + 2 * 2.0 * (w - 1) * (di + 2 * n))
    return flops, byts


def chunk(m, rows: int, pos: int, tokens: int) -> tuple[float, float]:
    L = m["num_layers"]
    di, nh, hp, n, w = _dims(m)
    t = tokens
    ssd = 2.0 * t * t * n + 2.0 * t * t * nh * hp + 4.0 * t * nh * hp * n
    flops = rows * L * (ssd + 2.0 * t * w * (di + 2 * n))
    byts = rows * L * 2 * 4.0 * nh * hp * n
    return flops, byts


def program_fields(m) -> dict:
    from repro.configs.base import SSMConfig

    s = m["ssm"]
    return {"ssm": SSMConfig(state_size=s["state_size"], head_dim=s["head_dim"],
                             expand=s["expand"], conv_width=s["conv_width"],
                             chunk_size=s["chunk_size"])}
