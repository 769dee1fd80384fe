"""Operations and bytes that each engine call needs, from its shapes.

These are the work any implementation must do, not what today's code does:
attention counts only the positions each query may see (not the whole cache
capacity the program masks), and a decode step's bytes are the weights read
once plus each decoding slot's own K/V (or recurrent state) at its actual
position. The peaks come from ``peaks.json``, keyed by ``device_kind``.

``m`` is a configuration's ``model`` dict (see ``bench/configs``). What a
layer needs beyond its matrices comes from the model's family module
``fam`` (``bench/families``).
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


def layer_params(m, fam) -> int:
    """Matrix parameters of one layer (each is one multiply-add per token)."""
    return fam.layer_params(m)


def weight_bytes(m, fam) -> int:
    """Bytes a step must read: every layer's matrices and the output
    projection (the input embedding is only gathered row by row), bf16."""
    return 2 * (m["num_layers"] * layer_params(m, fam) + m["d_model"] * _vocab(m))


def decode(m, positions, fam) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots at ``positions`` (the
    position each slot writes; it attends over positions 0..p)."""
    L, d = m["num_layers"], m["d_model"]
    rows = len(positions)
    flops = rows * 2.0 * (L * fam.layer_params(m) + d * _vocab(m))
    byts = float(weight_bytes(m, fam))
    more_flops, more_bytes = fam.decode(m, positions)
    return flops + more_flops, byts + more_bytes


def chunk(m, rows: int, pos: int, tokens: int, fam) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk: ``rows`` prompts advanced by
    ``tokens`` from position ``pos``; logits of the chunk's last token."""
    L, d = m["num_layers"], m["d_model"]
    flops = rows * (2.0 * tokens * L * fam.layer_params(m) + 2.0 * d * _vocab(m))
    byts = float(weight_bytes(m, fam))
    more_flops, more_bytes = fam.chunk(m, rows, pos, tokens)
    return flops + more_flops, byts + more_bytes


def least_seconds(flops: float, byts: float, pk: dict) -> float:
    """Roofline time: the larger of the compute and the memory bound."""
    return max(flops / pk["bf16_flops_per_s"], byts / pk["hbm_bytes_per_s"])
