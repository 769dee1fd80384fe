"""Operations and bytes that each engine call needs, from its shapes.

These are the work any implementation must do, not what today's code does:
attention counts only the positions each query may see (not the whole cache
capacity the program masks), and a decode step's bytes are the weights read
once plus each decoding slot's own K/V (or recurrent state) at its actual
position. The peaks come from ``peaks.json``, keyed by ``device_kind``.

``m`` is a configuration's ``model`` dict (see ``bench/configs``).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS['devices'])}")
    return PEAKS["devices"][device_kind]


def _vocab(m) -> int:
    return -(-m["vocab_size"] // 256) * 256


def layer_params(m) -> int:
    """Matrix parameters of one layer (each is one multiply-add per token)."""
    d = m["d_model"]
    if m["family"] == "dense":
        h, kv, hd, f = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
        return d * h * hd * 2 + 2 * d * kv * hd + 3 * d * f
    s = m["ssm"]
    di, n = s["expand"] * d, s["state_size"]
    return d * (2 * di + 2 * n + di // s["head_dim"]) + di * d


def weight_bytes(m) -> int:
    """Bytes a step must read: every layer's matrices and the output
    projection (the input embedding is only gathered row by row), bf16."""
    return 2 * (m["num_layers"] * layer_params(m) + m["d_model"] * _vocab(m))


def _ssm_dims(m):
    s = m["ssm"]
    di = s["expand"] * m["d_model"]
    return di, di // s["head_dim"], s["head_dim"], s["state_size"], s["conv_width"]


def decode(m, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots at ``positions`` (the
    position each slot writes; it attends over positions 0..p)."""
    L, d = m["num_layers"], m["d_model"]
    rows = len(positions)
    flops = rows * 2.0 * (L * layer_params(m) + d * _vocab(m))
    byts = float(weight_bytes(m))
    if m["family"] == "dense":
        h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
        ctx = sum(p + 1 for p in positions)
        flops += L * 4.0 * h * hd * ctx        # q.k and p.v over the context
        byts += L * 2.0 * kv * hd * 2 * ctx    # K and V rows read, bf16
    else:
        di, nh, hp, n, w = _ssm_dims(m)
        flops += rows * L * (nh * hp * n * 5.0 + 2.0 * w * (di + 2 * n))
        # the f32 state is read and written; the bf16 conv window too
        byts += rows * L * (2 * 4.0 * nh * hp * n + 2 * 2.0 * (w - 1) * (di + 2 * n))
    return flops, byts


def chunk(m, rows: int, pos: int, tokens: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk: ``rows`` prompts advanced by
    ``tokens`` from position ``pos``; logits of the chunk's last token."""
    L, d = m["num_layers"], m["d_model"]
    flops = rows * (2.0 * tokens * L * layer_params(m) + 2.0 * d * _vocab(m))
    byts = float(weight_bytes(m))
    if m["family"] == "dense":
        h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
        seen = tokens * pos + tokens * (tokens + 1) // 2  # causal keys, summed
        flops += rows * L * 4.0 * h * hd * seen
        byts += rows * L * 2.0 * kv * hd * 2 * (pos + tokens)
    else:
        di, nh, hp, n, w = _ssm_dims(m)
        t = tokens
        ssd = 2.0 * t * t * n + 2.0 * t * t * nh * hp + 4.0 * t * nh * hp * n
        flops += rows * L * (ssd + 2.0 * t * w * (di + 2 * n))
        byts += rows * L * 2 * 4.0 * nh * hp * n
    return flops, byts


def least_seconds(flops: float, byts: float, pk: dict) -> float:
    """Roofline time: the larger of the compute and the memory bound."""
    return max(flops / pk["bf16_flops_per_s"], byts / pk["hbm_bytes_per_s"])
