"""The weight tree each configuration draws, pinned against
``data/layouts.json``: every leaf's path, shape, type, initialisation and
std in ``jax.tree.flatten`` order, at full size (from the layout alone, no
allocation) and at the configuration's ``cpu_test`` size, and a digest of
the ``cpu_test`` weights that seed 7 gives. ``weights.make`` splits one key
per leaf in flatten order, so a leaf added, renamed or moved gives every
seed other weights; these tests catch it before a chip run does."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, harness, weights

DATA = json.loads((Path(__file__).resolve().parent / "data" / "layouts.json").read_text())
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SIZES = ("full", "cpu_test")


def model(name: str, size: str) -> dict:
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    return harness.at_size(harness.Cell(name, 1, conf, {}, [], []), size)[0]


def layout(m: dict) -> list:
    tree = weights.spec(m, families.module(m["family"]))
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=weights.is_leaf)
    return [{"path": jax.tree_util.keystr(p), "shape": list(shape),
             "dtype": jnp.dtype(dtype).name, "init": init, "std": std}
            for p, (shape, dtype, init, std) in leaves]


def digest(m: dict, seed: int) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(weights.make(m, seed, None, families.module(m["family"]))):
        a = np.asarray(leaf)
        h.update(f"{a.dtype.name}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("name", sorted(DATA["layouts"]))
def test_weight_layout_is_pinned(name, size):
    assert layout(model(name, size)) == DATA["layouts"][name][size]


@pytest.mark.parametrize("name", sorted(DATA["digests"]))
def test_cpu_test_weights_are_pinned(name):
    assert digest(model(name, "cpu_test"), DATA["seed"]) == DATA["digests"][name]
