"""The expert share (``MoEConfig.experts_held``/``first_expert``): a chip of
an expert-parallel deployment holds a contiguous slice of the experts,
routes over all of them and computes only its own experts' part. The parts
of every rank, with the shared expert (which every rank computes alike)
counted once, add up to the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.moe import _shared_ffn, moe_apply, moe_defs
from repro.models.params import init_params
from repro.models.reference import _experts

E = 8


def _cfg(shared: int, **share) -> ArchConfig:
    return ArchConfig(
        name="share", family="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=2, d_ff=16, vocab_size=64, dtype=jnp.float32,
        moe=MoEConfig(num_experts=E, top_k=3, expert_d_ff=16, num_shared=shared,
                      shared_d_ff=24, **share))


def _params(cfg):
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(0))
    return jax.tree.map(lambda t: t.astype(jnp.float32), p)


def _share(params, cfg, rank, ranks):
    held = E // ranks
    part = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=held, first_expert=rank * held))
    cut = {k: params[k][rank * held:(rank + 1) * held] for k in ("wg", "wu", "wd")}
    return {**params, **cut}, part


@pytest.mark.parametrize("shared", [0, 1], ids=["routed", "with_shared"])
@pytest.mark.parametrize("ranks", [2, 4, 8])
def test_shares_add_up_to_the_uncut_layer(ranks, shared):
    cfg = _cfg(shared)
    params = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_apply(params, x, cfg)
        common = _shared_ffn(params["shared"], x, cfg) if shared else 0.0
        total = common
        for r in range(ranks):
            p_r, cfg_r = _share(params, cfg, r, ranks)
            y, _ = moe_apply(p_r, x, cfg_r)
            total = total + (y - common)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-5, rtol=2e-5)


def test_a_share_holds_only_its_experts():
    """The layer's weights are the held experts' and its router keeps every
    expert's output."""
    cfg = _cfg(1, experts_held=2, first_expert=4)
    defs = moe_defs(cfg)
    assert defs["wg"].shape == (2, 32, 16) and defs["wd"].shape == (2, 16, 32)
    assert defs["router"].shape == (32, E)


@pytest.mark.parametrize("rank", [0, 3])
def test_a_share_matches_the_reference(rank):
    """The program's share against the plain reference's: softmax over the
    chosen top-k logits, the held experts' SwiGLUs weighted by their gates,
    and the shared expert."""
    cfg = _cfg(1)
    params, part = _share(_params(cfg), cfg, rank, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 7, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, _ = moe_apply(params, x, part)
        ref = _experts(params, x[0], part)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(ref), atol=2e-5, rtol=2e-5)
