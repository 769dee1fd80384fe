"""Compile the chip's hot path for a TPU v5e without a chip.

Interpret mode enforces none of Mosaic's tiling or VMEM limits, so these
tests lower every Pallas kernel with ``interpret=False``, and the engine's
paged decode step at granite-3-8b and granite-4.0-h-small widths (its
attention kernel through Mosaic too), against a described ``v5e:2x2``
topology, at the shapes ``chip_smoke.py`` and the benchmark run on the chip.
A compile that passes here is not a chip run: nothing executes.

The topology is described inside a module fixture (never at import), so
only the test worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.lstm_quant import quantize_lstm_weights
from repro.kernels.lstm_seq import (lstm_seq_fused, lstm_seq_fused_quantized,
                                    lstm_stack_fused)
from repro.models.layers import gqa_decode_apply, gqa_defs
from repro.models.model import init_model
from repro.models.params import init_params
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.kv_cache import cache_defs, page_defs, paged_cache_bytes
from repro.serving.pages import PagedSlotPool
from repro.sharding.rules import (DATA, MODEL, activate_mesh, sharding_for,
                                  tensor_parallel_rules)

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


def test_paged_attention_compiles(one_chip):
    """The paged decode attention kernel alone, vmapped over the smoke's 16
    slots as the decode step calls it, over one layer of a two-layer pool."""
    slots, h, kvh, d, page, max_len = smoke.GRANITE_PAGED
    mb = max_len // page
    pool = jax.ShapeDtypeStruct((2, slots * mb + 1, page, kvh, d), jnp.bfloat16)
    args = (jax.ShapeDtypeStruct((slots, h, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((slots, kvh, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((slots, kvh, d), jnp.bfloat16),
            pool, pool, jax.ShapeDtypeStruct((slots, mb), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32))
    step = jax.vmap(lambda q, kn, vn, kp, vp, t, p, layer: paged_decode_attention(
        q, kn, vn, kp, vp, t, p, layer, interpret=False),
        in_axes=(0, 0, 0, None, None, 0, 0, None))
    compiled = _compile(step, args, one_chip)
    assert compiled.as_text().count("tpu_custom_call") == 1
    _fits(compiled)


def _granite4h_10l():
    """granite-4.0-h-small cut as the benchmark serves it: one 10-layer
    period (attention at layer 5), experts 0-8 of 72 held."""
    base = get_config("granite-4.0-h-small")
    return dataclasses.replace(base, num_layers=10, moe=dataclasses.replace(
        base.moe, experts_held=9, first_expert=0))


def _paged_decode_step(one_chip, monkeypatch, cfg, *, max_batch=16, max_len=4096):
    """The engine's paged decode step compiled for a v5e at ``cfg``'s widths,
    with the paged attention kernel lowered through Mosaic (on this CPU the
    engine would otherwise take the kernel's jnp reference). Returns
    (virtual row length, pool page count, pool, compiled)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    sc = ServeConfig(max_batch=max_batch, max_len=max_len, paged=True)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_model(cfg, key))
    engine = InferenceEngine(cfg, params=params, sc=sc)
    pool = jax.eval_shape(lambda: PagedSlotPool(
        cfg, max_batch=sc.max_batch, max_len=sc.max_len,
        page_size=sc.page_size).cache)
    mb = -(-(sc.max_len + sc.page_size) // sc.page_size) + 1
    n_pages = sc.max_batch * mb + 1
    assert pool["k"].shape == page_defs(
        cfg, num_pages=n_pages, page_size=sc.page_size)["k"].shape
    vec = lambda dt: jax.ShapeDtypeStruct((sc.max_batch,), dt)  # noqa: E731
    args = (params, pool, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
            jax.ShapeDtypeStruct((sc.max_batch, mb), jnp.int32))
    specs = jax.tree.map(lambda x: _spec_of(x, one_chip), args)
    return mb * sc.page_size, n_pages, pool, engine._paged_decode.lower(*specs).compile()


DECODE_CELLS = {
    "granite": lambda: (dataclasses.replace(get_config("granite-3-8b"),
                                            num_layers=smoke.SERVE_LAYERS), 16, 4096),
    "granite4h": lambda: (_granite4h_10l(), 48, 9216),
}


def _decode_cell(one_chip, monkeypatch, cell):
    cfg, max_batch, max_len = DECODE_CELLS[cell]()
    return (cfg, *_paged_decode_step(one_chip, monkeypatch, cfg,
                                     max_batch=max_batch, max_len=max_len))


def _chunk_step(one_chip):
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
    pool_bytes = paged_cache_bytes(cfg, batch=sc.max_batch,
                                   num_pages=sc.max_batch * mb + 1,
                                   page_size=sc.page_size, max_blocks=mb)
    return cfg, mb * sc.page_size, engine._chunk.lower(*specs).compile(), pool_bytes


@pytest.mark.parametrize("layers", [smoke.NUMERICS_LAYERS, smoke.SERVE_LAYERS])
def test_paged_decode_compiles_at_granite_widths(one_chip, monkeypatch, layers):
    """The serving decode step at published widths with the smoke's pool:
    16 slots, 4096 positions, parity-sized page pool, the attention kernel
    reading each slot's pages in place."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=layers)
    compiled = _paged_decode_step(one_chip, monkeypatch, cfg)[3]
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_paged_decode_compiles_at_granite4h_widths(one_chip, monkeypatch):
    """granite-4.0-h-small's decode step as ``granite4h-longdoc`` serves it:
    48 slots of 9216 positions, Mamba-2 state rows beside one attention
    layer's pages, within what one v5e program may use."""
    compiled = _decode_cell(one_chip, monkeypatch, "granite4h")[4]
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled) <= USABLE_HBM_BYTES


def _arrays(hlo: str):
    """(dtype, dims) of every array type named in compiled HLO text."""
    return {(dt, tuple(int(n) for n in dims.split(",") if n))
            for dt, dims in re.findall(r"\b(bf16|f16|f32|s8)\[([0-9,]*)\]", hlo)}


def _ops(hlo: str, kinds: str):
    """(op, dims) of every ``kinds`` instruction's result in compiled HLO text."""
    return [(op, tuple(int(n) for n in dims.split(",") if n)) for dims, op in re.findall(
        rf"= \w+\[([0-9,]*)\]\S* ({kinds})\(", hlo)]


@pytest.mark.parametrize("step", ["decode", "chunk", "decode-granite4h"])
def test_attention_reads_the_cache_in_place(one_chip, monkeypatch, step):
    """Decode (both cells' widths): attention reads each slot's pages where
    they lie, so no array of the compiled step carries a slot's whole
    virtual row (4128 or 9248 rows) with the head dim, no dynamic-slice,
    pad or copy has a layer's whole page array as its shape, and the pool
    is updated in place (its bytes alias the step's output).

    Chunk: attention contracts each KV head with its query group in place:
    no array carries the row together with the head dim and all query heads
    (a K/V repeated to 32 heads, as ``[..., rows, 32, 128]`` or ``[..., rows,
    8, 4, 128]``), and no K/V row is copied to f32. Scores carry rows and
    heads but no head dim, so they pass."""
    if step.startswith("decode"):
        cell = step.partition("-")[2] or "granite"
        cfg, rows, n_pages, pool, compiled = _decode_cell(one_chip, monkeypatch, cell)
        d = cfg.resolved_head_dim
        text = compiled.as_text()
        assert "tpu_custom_call" in text, "no attention kernel in the decode step"
        row_arrays = [(dt, dims) for dt, dims in _arrays(text) if rows in dims and d in dims]
        assert not row_arrays, f"a whole virtual K/V row: {row_arrays}"
        moved = [(op, dims) for op, dims in _ops(text, "dynamic-slice|pad|copy|copy-start")
                 if n_pages in dims]
        assert not moved, f"a layer's page array sliced, padded or copied: {moved}"
        page_bytes = sum(pool[k].size * pool[k].dtype.itemsize for k in ("k", "v"))
        assert compiled.memory_analysis().alias_size_in_bytes >= page_bytes
        return
    cfg, rows, compiled, _ = _chunk_step(one_chip)
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    d = cfg.resolved_head_dim
    arrays = _arrays(compiled.as_text())
    row_arrays = [(dt, dims) for dt, dims in arrays if rows in dims and d in dims]
    assert row_arrays, "the step reads no K/V row at all"
    for dt, dims in row_arrays:
        grouped = any(dims[i:i + 2] == (kvh, h // kvh) for i in range(len(dims)))
        assert h not in dims and not grouped, f"K/V repeated to all heads: {dt}{list(dims)}"
        assert dt != "f32", f"f32 copy of a K/V row: {dt}{list(dims)}"


def test_chunk_step_fits_beside_the_page_pool(one_chip):
    """A chunked-prefill step of a 4-request group (the smoke's traffic
    keeps groups smaller) at 16 layers, 256-token chunks, with the serving
    page pool resident beside it."""
    _, _, compiled, pool_bytes = _chunk_step(one_chip)
    assert _fits(compiled) + pool_bytes <= USABLE_HBM_BYTES


def test_decode_attention_keeps_the_cache_sequence_sharded(topo):
    """Flash-decoding under a 4-chip mesh: the K/V cache is sharded along
    its sequence axis, and one granite decode attention block adds no
    collective that carries that axis (an all-gather of the cache would);
    only the single token's q/k/v, the softmax partials and the output
    cross chips."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), (DATA, MODEL))
    rules = tensor_parallel_rules()
    cfg = get_config("granite-3-8b")
    batch, seq = 4, 2048
    params = {k: jax.ShapeDtypeStruct(d.shape, jnp.bfloat16,
                                      sharding=sharding_for(d, mesh, rules))
              for k, d in gqa_defs(cfg).items()}
    cache = jax.ShapeDtypeStruct(
        (batch, seq, cfg.num_kv_heads, cfg.resolved_head_dim), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, MODEL, None, None)))
    replicated = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((batch, 1, cfg.d_model), jnp.bfloat16,
                             sharding=replicated)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    with activate_mesh(mesh, rules):
        hlo = jax.jit(lambda p, x, k, v, pos: gqa_decode_apply(
            p, x, k, v, pos, cfg)).lower(params, x, cache, cache, pos).compile().as_text()
    ops = "all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter"
    moved = re.findall(rf"= \w+\[([0-9,]*)\]\S* (?:{ops})(?:-start)?\(", hlo)
    assert moved, "no collective at all: the mesh did not take"
    for dims in moved:
        sizes = [int(n) for n in dims.split(",")]
        assert seq not in sizes and seq // 4 not in sizes, f"cache moved: [{dims}]"
