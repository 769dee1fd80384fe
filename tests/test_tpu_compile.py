"""Compile the chip's hot path for a TPU v5e without a chip.

Interpret mode enforces none of Mosaic's tiling or VMEM limits, so these
tests lower every Pallas kernel with ``interpret=False``, and the engine's
paged decode step at granite-3-8b widths, against a described ``v5e:2x2``
topology, at the shapes ``chip_smoke.py`` runs on the chip. A compile that
passes here is not a chip run: nothing executes.

The topology is described inside a module fixture (never at import), so
only the test worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.lstm_quant import quantize_lstm_weights
from repro.kernels.lstm_seq import (lstm_seq_fused, lstm_seq_fused_quantized,
                                    lstm_stack_fused)
from repro.models.model import init_model
from repro.models.params import init_params
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.kv_cache import cache_defs, page_defs, paged_cache_bytes
from repro.serving.pages import PagedSlotPool

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parent.parent / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

HBM_BYTES = 16 * 2**30
# what the v5e compiler lets one program use of its 16 GiB
USABLE_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec_of(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _compile(fn, args, sharding):
    specs = jax.tree.map(lambda x: _spec_of(x, sharding), args)
    return jax.jit(fn).lower(*specs).compile()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB over 16 GiB"
    return total


def _lstm_case(variant, shape):
    bsz, seq, d_in, hidden = shape
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((bsz, seq, d_in), f32)
    l1 = (jax.ShapeDtypeStruct((d_in, 4 * hidden), f32),
          jax.ShapeDtypeStruct((hidden, 4 * hidden), f32),
          jax.ShapeDtypeStruct((4 * hidden,), f32))
    l2 = (jax.ShapeDtypeStruct((hidden, 4 * hidden), f32), *l1[1:])
    if variant == "seq":
        return (lambda x, l1: lstm_seq_fused(x, *l1, interpret=False)), (x, l1)
    if variant == "seq_q8":
        return (lambda x, l1: lstm_seq_fused_quantized(
            x, quantize_lstm_weights(*l1), interpret=False)), (x, l1)
    quant = variant == "stack_q8"
    return (lambda x, l1, l2: lstm_stack_fused(
        x, [l1, l2], quantized=quant, interpret=False)), (x, l1, l2)


@pytest.mark.parametrize("shape", [smoke.PAPER_LSTM, smoke.WIDE_LSTM],
                         ids=["paper", "wide"])
@pytest.mark.parametrize("variant", ["seq", "seq_q8", "stack_f32", "stack_q8"])
def test_lstm_kernels_compile(one_chip, variant, shape):
    fn, args = _lstm_case(variant, shape)
    compiled = _compile(fn, args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_int8_matmul_compiles(one_chip):
    m, k, n = smoke.GRANITE_PROJ
    args = (jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32))
    compiled = _compile(lambda *a: int8_matmul(
        *a, block_m="auto", block_n="auto", block_k="auto", interpret=False),
        args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_flash_attention_compiles(one_chip):
    b, h, kvh, s, d = smoke.GRANITE_ATTN
    args = (jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16))
    compiled = _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q="auto", block_k="auto",
        interpret=False), args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("layers", [smoke.NUMERICS_LAYERS, smoke.SERVE_LAYERS])
def test_paged_decode_compiles_at_granite_widths(one_chip, layers):
    """The serving decode step at published widths with the smoke's pool:
    16 slots, 4096 positions, parity-sized page pool. At 16 layers it fits
    only because the model gathers each slot's pages one layer at a time
    (``models.model.PagedRows``); a whole-stack gather needs 8 GiB more."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=layers)
    sc = ServeConfig(max_batch=16, max_len=4096, paged=True)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_model(cfg, key))
    engine = InferenceEngine(cfg, params=params, sc=sc)
    pool = jax.eval_shape(lambda: PagedSlotPool(
        cfg, max_batch=sc.max_batch, max_len=sc.max_len,
        page_size=sc.page_size).cache)
    mb = -(-(sc.max_len + sc.page_size) // sc.page_size) + 1
    assert pool["k"].shape == page_defs(
        cfg, num_pages=sc.max_batch * mb + 1, page_size=sc.page_size)["k"].shape
    vec = lambda dt: jax.ShapeDtypeStruct((sc.max_batch,), dt)  # noqa: E731
    args = (params, pool, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
            jax.ShapeDtypeStruct((sc.max_batch, mb), jnp.int32))
    specs = jax.tree.map(lambda x: _spec_of(x, one_chip), args)
    _fits(engine._paged_decode.lower(*specs).compile())


def test_chunk_step_fits_beside_the_page_pool(one_chip):
    """A chunked-prefill step of a 4-request group (the smoke's traffic
    keeps groups smaller) at 16 layers, 256-token chunks, with the serving
    page pool resident beside it."""
    cfg = dataclasses.replace(get_config("granite-3-8b"),
                              num_layers=smoke.SERVE_LAYERS)
    sc = ServeConfig(max_batch=16, max_len=4096, paged=True)
    key = jax.random.PRNGKey(0)
    engine = InferenceEngine(
        cfg, params=jax.eval_shape(lambda: init_model(cfg, key)), sc=sc)
    mb = -(-(sc.max_len + sc.page_size) // sc.page_size) + 1
    group = jax.eval_shape(lambda: init_params(
        cache_defs(cfg, batch=4, max_len=mb * sc.page_size), key))
    args = (engine.params, group, jax.ShapeDtypeStruct((4, 256), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32), None)
    specs = jax.tree.map(lambda x: _spec_of(x, one_chip), args)
    compiled = engine._chunk.lower(*specs).compile()
    pool_bytes = paged_cache_bytes(cfg, batch=sc.max_batch,
                                   num_pages=sc.max_batch * mb + 1,
                                   page_size=sc.page_size, max_blocks=mb)
    assert _fits(compiled) + pool_bytes <= USABLE_HBM_BYTES
