"""Seeded random weights, made by the benchmark in the program's layout.

The benchmark, not the program, makes the weights: one jitted call draws
every leaf on the device from ``--seed``, in the type it is served in. The
reference reads the same tree. The layout (leaf names and shapes) is the
program's parameter interface; ``harness`` checks it against the program's
own abstract parameter tree before serving, so a layout change fails loudly.

Every matrix is drawn with std 1/sqrt(contraction width), so activations
keep unit scale through each projection; the token embedding has std 0.02
(each block normalises its input, and with tied embeddings a larger table
would make every model predict its own input token). (The program's ``init_model`` draws
the attention projections with a per-head fan-in, which makes a random
network chaotic: bf16 rounding then moves logits by tens of percent and no
comparison with an f32 reference can be tight.) Norm scales are 1. For the
state-space layers: A = -exp(A_log) with exp(A_log) uniform in [1, 16), and
dt_bias the inverse softplus of a log-uniform step in [1e-3, 1e-1], as in
Mamba-2's published initialisation; the causal conv taps are drawn with
std 1/sqrt(width).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def spec(m: dict) -> dict:
    """Leaf -> (shape, dtype, init, std) for the model sizes ``m``."""
    L, d, vp = m["num_layers"], m["d_model"], padded_vocab(m["vocab_size"])
    embed = {"tokens": ((vp, d), BF16, "normal", 0.02)}
    if not m["tie_embeddings"]:
        embed["unembed"] = ((d, vp), BF16, "normal", 1 / math.sqrt(d))
    out = {"embed": embed, "final_norm": {"scale": ((d,), F32, "ones", 0)}}
    ones = lambda n: {"scale": ((L, n), F32, "ones", 0)}  # noqa: E731
    if m["family"] == "dense":
        h, kv, f = m["num_heads"], m["num_kv_heads"], m["d_ff"]
        hd = m["head_dim"]
        out["blocks"] = {
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
    elif m["family"] == "ssm":
        s = m["ssm"]
        di, n, w = s["expand"] * d, s["state_size"], s["conv_width"]
        nh = di // s["head_dim"]
        mat = lambda a, b: ((L, a, b), BF16, "normal", 1 / math.sqrt(a))  # noqa: E731
        conv = lambda c: ((L, w, c), BF16, "normal", 1 / math.sqrt(w))  # noqa: E731
        zeros = lambda c: ((L, c), BF16, "zeros", 0)  # noqa: E731
        out["blocks"] = {
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
    else:
        raise ValueError(f"no weight layout for family {m['family']!r}")
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[0], tuple)


def _draw(key, leaf):
    shape, dtype, init, std = leaf
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)).astype(dtype)
    if init == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def seed_key(seed: int):
    """A key for any non-negative seed (PRNGKey alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def make(m: dict, seed: int, device=None):
    """The whole tree, drawn on ``device`` by one jitted call."""
    tree = spec(m)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [_draw(k, l) for k, l in zip(keys, leaves)])

    key = jax.device_put(seed_key(seed), device)
    return jax.jit(build)(key)


def abstract(m: dict):
    """ShapeDtypeStructs of ``make``'s tree (no allocation)."""
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l[0], l[1]), spec(m),
                        is_leaf=_is_leaf)
