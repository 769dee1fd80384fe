"""Serving attention contracts each KV head with its query group in place.

``attention_decode`` and ``attention_chunk`` view q as (B,T,KV,G,D) and
contract it against the cache as stored. The plain reference below is the
formulation they replaced: K/V copied to f32 and repeated to all H heads,
then contracted head by head. Both must agree to f32 summation order, for
every group size the served models use, wherever ``pos`` lies, and with
the dead rows past ``pos`` holding large garbage that the mask must keep
out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import NEG_INF, attention_chunk, attention_decode

B, S, D, T = 2, 64, 16, 4
GARBAGE = 1e30


def _repeat_kv(k, groups):
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, d)).reshape(
        b, s, kv * groups, d)


def reference(q, k_cache, v_cache, pos):
    """Queries at pos..pos+T-1 over K/V repeated to every head, in f32."""
    t, h, d = q.shape[1:]
    g = h // k_cache.shape[2]
    k = _repeat_kv(k_cache, g).astype(jnp.float32)
    v = _repeat_kv(v_cache, g).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) / jnp.sqrt(d)
    valid = jnp.arange(k_cache.shape[1])[None, :] <= (pos + jnp.arange(t))[:, None]
    s = jnp.where(valid[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _case(heads, kv_heads, t, pos, dtype, seed=0):
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (B, t, heads, D), jnp.float32).astype(dtype)
    shape = (B, S, kv_heads, D)
    dead = (jnp.arange(S) > pos + t - 1)[None, :, None, None]
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, shape), GARBAGE, -GARBAGE)
    k = jnp.where(dead, sign, jax.random.normal(kk, shape))
    v = jnp.where(dead, -sign, jax.random.normal(kv, shape))
    return q, k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("path", ["decode", "chunk"])
@pytest.mark.parametrize("where", ["first", "mid", "last"])
@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (8, 8), (8, 1), (48, 4)],
                         ids=["G4", "G1-MHA", "KV1-MQA", "G12"])
def test_grouped_attention_equals_repeated_heads(heads, kv_heads, where, path, dtype):
    t = 1 if path == "decode" else T
    pos = {"first": 0, "mid": S // 2 - 3, "last": S - t}[where]
    q, k, v = _case(heads, kv_heads, t, pos, dtype)
    fn = attention_decode if path == "decode" else attention_chunk
    got = jax.jit(fn)(q, k, v, jnp.int32(pos))
    with jax.default_matmul_precision("highest"):
        want = reference(q, k, v, pos)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        # both sums are f32; the output is rounded once to bf16, so the two
        # may land a bf16 step apart (8 significant bits)
        np.testing.assert_allclose(got, want, rtol=2.0**-7, atol=1e-6)
