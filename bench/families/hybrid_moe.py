"""Mamba-2 and grouped-query attention layers in one stack, each followed
by routed experts and a shared expert (the program's ``hybrid_moe``
family, Granite 4.0-H).

A layer's mixer is attention where ``m["attn_layers"]`` lists its index
(no position embedding, scores scaled by ``attention_multiplier``) and
Mamba-2 elsewhere (the ``ssm`` family's layer, drawn the same way). Every
layer then adds ``residual_multiplier`` times the sum of a top-k router's
experts and a shared SwiGLU expert. The chip holds ``experts_held`` of the
router's ``num_experts`` experts, from ``first_expert`` on.

The weight tree's ``blocks`` keeps one stack per kind: ``mamba`` over the
Mamba-2 layers, ``attn`` over the attention layers, ``ffn`` over all.

Initialisation of its own. Each residual branch's output projection (the
Mamba-2 and attention ``wo``, the experts' ``wd``) is drawn with std
1/(sqrt(width) * residual_multiplier), so a branch adds unit scale once
the published multiplier has scaled it: with plain 1/sqrt(width) the
branches add 0.22 each, the embedding (x12) dominates the last hidden
state, and the tied unembedding makes every token predict itself. The
router is drawn with std ``ROUTER_STD``/sqrt(d), so its logits have std
3 and the softmax over the chosen ones is decisive, as a trained router's
is; at std 1 the chosen gates are nearly flat and the output hangs on
which expert rounding puts at the top-k boundary.

Counts. ``layer_params`` is what one token needs of one layer, averaged
over the stack: the mixers' matrices in their ratio, the shared expert,
the router, and top_k * held / num_experts of the held experts (the ones a
token is routed to here, on average). A step reads every held expert,
so ``decode`` and ``chunk`` add the bytes of the other held experts beside
the attention rows and the recurrent state at the actual positions.
"""
from __future__ import annotations

import math
from pathlib import Path

from bench import found

PROGRAM_FAMILY = "hybrid_moe"
_HERE = Path(__file__).resolve().parent
_SSM = found.module(_HERE, "ssm", "model family")
_DENSE = found.module(_HERE, "dense", "model family")
BF16, F32 = _SSM.BF16, _SSM.F32
INITS = _SSM.INITS
ROUTER_STD = 3.0


def _kinds(m) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    n_attn = len(m["attn_layers"])
    return m["num_layers"] - n_attn, n_attn


def blocks(m: dict) -> dict:
    L, d = m["num_layers"], m["d_model"]
    n_mamba, n_attn = _kinds(m)
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    e = m["moe"]
    f, fs = e["expert_d_ff"], e["shared_d_ff"]
    held, n_exp = e["experts_held"], e["num_experts"]
    ones = lambda n, c: {"scale": ((n, c), F32, "ones", 0)}  # noqa: E731
    m_r = m["residual_multiplier"]
    mat = lambda *s: (s, BF16, "normal", 1 / math.sqrt(s[-2]))  # noqa: E731
    # a residual branch's output projection: unit scale after the multiplier
    out = lambda *s: (s, BF16, "normal", 1 / math.sqrt(s[-2]) / m_r)  # noqa: E731
    mamba = _SSM.blocks({**m, "num_layers": n_mamba})
    mamba["mamba"]["wo"] = out(*mamba["mamba"]["wo"][0])
    return {
        "mamba": mamba,
        "attn": {
            "ln": ones(n_attn, d),
            "attn": {
                "wq": ((n_attn, d, h, hd), BF16, "normal", 1 / math.sqrt(d)),
                "wk": ((n_attn, d, kv, hd), BF16, "normal", 1 / math.sqrt(d)),
                "wv": ((n_attn, d, kv, hd), BF16, "normal", 1 / math.sqrt(d)),
                "wo": ((n_attn, h, hd, d), BF16, "normal", 1 / math.sqrt(h * hd) / m_r),
            },
        },
        "ffn": {
            "ln": ones(L, d),
            "moe": {
                "router": ((L, d, n_exp), F32, "normal", ROUTER_STD / math.sqrt(d)),
                "wg": mat(L, held, d, f), "wu": mat(L, held, d, f),
                "wd": out(L, held, f, d),
                "shared": {"wg": mat(L, d, fs), "wu": mat(L, d, fs), "wd": out(L, fs, d)},
            },
        },
    }


def _attn_params(m) -> int:
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return 2 * d * h * hd + 2 * d * kv * hd


def _expert_params(m) -> int:
    return 3 * m["d_model"] * m["moe"]["expert_d_ff"]


def _routed_here(m) -> float:
    """Held experts a token is routed to, on average."""
    e = m["moe"]
    return e["top_k"] * e["experts_held"] / e["num_experts"]


def layer_params(m):
    n_mamba, n_attn = _kinds(m)
    d, e = m["d_model"], m["moe"]
    mixers = (n_mamba * _SSM.layer_params(m) + n_attn * _attn_params(m)) / m["num_layers"]
    ffn = 3 * d * e["shared_d_ff"] + d * e["num_experts"] + _routed_here(m) * _expert_params(m)
    v = mixers + ffn
    return int(v) if v == int(v) else v


def _idle_experts_bytes(m) -> float:
    """bf16 bytes of the held experts beyond those ``layer_params`` counts."""
    e = m["moe"]
    return m["num_layers"] * (e["experts_held"] - _routed_here(m)) * _expert_params(m) * 2.0


def _per_kind(m, n_mamba, n_attn):
    return {**m, "num_layers": n_mamba}, {**m, "num_layers": n_attn}


def decode(m, positions) -> tuple[float, float]:
    ms, ma = _per_kind(m, *_kinds(m))
    sf, sb = _SSM.decode(ms, positions)
    af, ab = _DENSE.decode(ma, positions)
    return sf + af, sb + ab + _idle_experts_bytes(m)


def chunk(m, rows: int, pos: int, tokens: int) -> tuple[float, float]:
    ms, ma = _per_kind(m, *_kinds(m))
    sf, sb = _SSM.chunk(ms, rows, pos, tokens)
    af, ab = _DENSE.chunk(ma, rows, pos, tokens)
    return sf + af, sb + ab + _idle_experts_bytes(m)


def program_fields(m) -> dict:
    from repro.configs.base import MoEConfig

    e = m["moe"]
    return {**_SSM.program_fields(m),
            "head_dim": m["head_dim"],
            "moe": MoEConfig(num_experts=e["num_experts"], top_k=e["top_k"],
                             expert_d_ff=e["expert_d_ff"], num_shared=1,
                             shared_d_ff=e["shared_d_ff"],
                             experts_held=e["experts_held"],
                             first_expert=e["first_expert"]),
            "attn_layers": tuple(m["attn_layers"]), "rope": False,
            **{k: m[k] for k in ("embedding_multiplier", "attention_multiplier",
                                 "residual_multiplier", "logits_scaling")}}
