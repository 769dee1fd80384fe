"""Decoder-only transformer: grouped-query attention with rotary positions
and a SwiGLU MLP in pre-RMSNorm blocks (the program's ``dense`` family)."""
from __future__ import annotations

import math

import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32
PROGRAM_FAMILY = "dense"
INITS = {}


def blocks(m: dict) -> dict:
    L, d = m["num_layers"], m["d_model"]
    h, kv, f = m["num_heads"], m["num_kv_heads"], m["d_ff"]
    hd = m["head_dim"]
    ones = lambda n: {"scale": ((L, n), F32, "ones", 0)}  # noqa: E731
    return {
        "ln1": ones(d), "ln2": ones(d),
        "attn": {
            "wq": ((L, d, h, hd), BF16, "normal", 1 / math.sqrt(d)),
            "wk": ((L, d, kv, hd), BF16, "normal", 1 / math.sqrt(d)),
            "wv": ((L, d, kv, hd), BF16, "normal", 1 / math.sqrt(d)),
            "wo": ((L, h, hd, d), BF16, "normal", 1 / math.sqrt(h * hd)),
        },
        "mlp": {
            "wg": ((L, d, f), BF16, "normal", 1 / math.sqrt(d)),
            "wu": ((L, d, f), BF16, "normal", 1 / math.sqrt(d)),
            "wd": ((L, f, d), BF16, "normal", 1 / math.sqrt(f)),
        },
    }


def layer_params(m) -> int:
    d = m["d_model"]
    h, kv, hd, f = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
    return d * h * hd * 2 + 2 * d * kv * hd + 3 * d * f


def decode(m, positions) -> tuple[float, float]:
    L = m["num_layers"]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    ctx = sum(p + 1 for p in positions)
    flops = L * 4.0 * h * hd * ctx        # q.k and p.v over the context
    byts = L * 2.0 * kv * hd * 2 * ctx    # K and V rows read, bf16
    return flops, byts


def chunk(m, rows: int, pos: int, tokens: int) -> tuple[float, float]:
    L = m["num_layers"]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    seen = tokens * pos + tokens * (tokens + 1) // 2  # causal keys, summed
    flops = rows * L * 4.0 * h * hd * seen
    byts = rows * L * 2.0 * kv * hd * 2 * (pos + tokens)
    return flops, byts


def program_fields(m) -> dict:
    return {"head_dim": m["head_dim"], "rope_theta": m["rope_theta"]}
