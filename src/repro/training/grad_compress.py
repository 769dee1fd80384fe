"""Int8-compressed gradient all-reduce (beyond-paper distributed trick).

The DP gradient sum is the dominant training collective once TP epilogues
are overlapped. Compressing the wire format from f32/bf16 to int8 (symmetric
per-tensor scales) cuts the collective roofline term ~4× at a quantization
error the optimizer tolerates (momentum filters zero-mean noise; see
tests/test_grad_compress.py for the error bound).

Scheme (inside ``shard_map`` over the DP axes):

  q_i   = round(g_i / s_i),  s_i = amax(g_i)/127        (per device)
  wire  = all_gather(q_i) + all_gather(s_i)             (int8 + one f32)
  out   = Σ_i q_i·s_i / n                               (local dequant-sum)

Per-device wire bytes ≈ n·(E/n)·1B vs ring-AR's 2·E·4B — a ~4–8× cut
depending on baseline dtype. Exposed two ways: ``compressed_pmean_tree``
(for use inside an existing shard_map) and ``dp_value_and_grad`` (a drop-in
data-parallel value_and_grad whose gradient sync is compressed; weights must
be DP-replicated — the pure-DP/FSDP-off regime where gradient compression
matters).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding.rules import batch_axes


def _int8_pmean(g: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Per-device int8 quantize → all_gather → dequant-mean. Zero-safe."""
    gf = g.astype(jnp.float32)
    amax = jnp.max(jnp.abs(gf))
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    for ax in axes:
        q = jax.lax.all_gather(q, ax)          # (n_ax, ...) int8 on the wire
        scale = jax.lax.all_gather(scale, ax)  # (n_ax,) f32
    # flatten the gathered leading axes into one device axis
    qf = q.reshape((-1,) + gf.shape).astype(jnp.float32)
    sf = scale.reshape(-1)
    out = jnp.einsum("n...,n->...", qf, sf) / qf.shape[0]
    return out.astype(g.dtype)


def compressed_pmean_tree(grads, axes: tuple[str, ...]):
    """Compressed mean-all-reduce of a gradient pytree (inside shard_map)."""
    return jax.tree.map(lambda g: _int8_pmean(g, axes), grads)


def dp_value_and_grad(
    loss_fn: Callable,
    mesh: Mesh,
    *,
    compressed: bool = True,
    has_aux: bool = False,
):
    """Data-parallel value_and_grad with (optionally) compressed grad sync.

    ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)``). Batch leading
    dim shards over the DP axes; params replicate. Returns a function with
    the same signature computing the *synchronized* (loss, grads).
    """
    dp = batch_axes(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]

    def body(params, batch):
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            aux = None
        loss = jax.lax.pmean(loss, dp)
        if compressed:
            grads = compressed_pmean_tree(grads, dp)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp), grads)
        if has_aux:
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, dp), aux)
            return loss, aux, grads
        return loss, grads

    out_specs = (P(), P(), P()) if has_aux else (P(), P())
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(dp_spec)),
        out_specs=out_specs,
        check_vma=False,
    )
    return fn
