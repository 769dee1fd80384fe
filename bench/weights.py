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
comparison with an f32 reference can be tight.) Norm scales are 1. The
layers' leaves, and any initialisation of their own, come from the model's
family module (``bench/families``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def spec(m: dict, fam) -> dict:
    """Leaf -> (shape, dtype, init, std) for the model sizes ``m``; the
    layers' leaves come from the family module ``fam``."""
    d, vp = m["d_model"], padded_vocab(m["vocab_size"])
    embed = {"tokens": ((vp, d), BF16, "normal", 0.02)}
    if not m["tie_embeddings"]:
        embed["unembed"] = ((d, vp), BF16, "normal", 1 / math.sqrt(d))
    return {"embed": embed, "final_norm": {"scale": ((d,), F32, "ones", 0)},
            "blocks": fam.blocks(m)}


def is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[0], tuple)


def _draw(key, leaf, fam):
    shape, dtype, init, std = leaf
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "normal":
        return (jax.random.normal(key, shape, F32) * std).astype(dtype)
    return fam.INITS[init](key, shape, dtype)


def seed_key(seed: int):
    """A key for any non-negative seed (PRNGKey alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def make(m: dict, seed: int, device, fam):
    """The whole tree, drawn on ``device`` by one jitted call."""
    leaves, treedef = jax.tree.flatten(spec(m, fam), is_leaf=is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [_draw(k, l, fam) for k, l in zip(keys, leaves)])

    key = jax.device_put(seed_key(seed), device)
    return jax.jit(build)(key)


def abstract(m: dict, fam):
    """ShapeDtypeStructs of ``make``'s tree (no allocation)."""
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l[0], l[1]), spec(m, fam),
                        is_leaf=is_leaf)
