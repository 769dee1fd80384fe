"""Quantized serving end-to-end: int8 weight residency + int8 KV pages.

ACCEPTANCE is argmax AGREEMENT, not token identity — int8 rounding flips
greedy picks on near-ties (the documented tolerance lives with the
``serve_quantized`` BENCH gate; see docs/kernels.md). What IS exact, and
pinned here:

  * the serving weight quantizer and the LSTM quantizer share ONE scale
    convention — both are ``kernels.ref.quantize_colwise`` to the byte;
  * ``qeinsum`` over a ``QuantTensor`` is bit-identical to the
    ``int8_matmul_ref`` contraction it routes to, and its non-matmul
    fallback computes with exactly the dequantized weights;
  * int8 KV pages round-trip preemption swap-out/swap-in BIT-identically
    (payload and per-(page,row,head) scales), so a preempted quantized run
    emits token-for-token what the undisturbed quantized run emits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels.ref import int8_matmul_ref, quantize_colwise, quantize_rowwise
from repro.models.model import init_model
from repro.models.quant import QuantTensor, dequantize, qeinsum, quantize_params
from repro.serving.engine import InferenceEngine, ServeConfig
from repro.serving.faults import FaultProfile
from repro.serving.kv_cache import dequantize_kv, quantize_kv
from repro.serving.load import bursty_stream, poisson_stream
from repro.serving.scheduler import ContinuousBatchingScheduler, FixedCalibration

FAMILY_ARCHS = ("granite-3-8b", "deepseek-v3-671b", "mamba2-780m",
                "zamba2-7b", "whisper-tiny", "granite-4.0-h-small")

CAL = FixedCalibration(step_s=0.004, prefill_base_s=0.001,
                       prefill_per_tok_s=0.001, verify_per_tok_s=0.0001)


# ---------------------------------------------------------------------------
# one scale convention (regression pin)
# ---------------------------------------------------------------------------
def test_weight_quantizer_is_quantize_colwise_to_the_byte():
    """``quantize_params`` must produce EXACTLY ``ref.quantize_colwise``
    bytes for a plain 2D projection — the same call ``lstm_quant`` makes, so
    the two quantized paths can never drift apart in convention."""
    cfg = get_reduced_config("granite-3-8b")
    params = init_model(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, cfg)
    w = np.asarray(params["blocks"]["mlp"]["wg"])  # stacked (L, d, f)
    qt = qp["blocks"]["mlp"]["wg"]
    assert isinstance(qt, QuantTensor)
    for layer in range(w.shape[0]):
        q_ref, s_ref = quantize_colwise(jnp.asarray(w[layer]))
        np.testing.assert_array_equal(np.asarray(qt.q[layer]), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(qt.scale[layer]),
                                      np.asarray(s_ref))


def test_lstm_quantizer_shares_the_convention():
    """The pin from the other side: ``quantize_lstm_weights`` on the same
    matrix yields the same bytes as ``quantize_colwise`` — so by transitivity
    LSTM and serving weights are quantized identically."""
    from repro.kernels.lstm_quant import quantize_lstm_weights
    from repro.kernels.lstm_seq import _pack_ifog

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    b = jnp.zeros((32,), jnp.float32)
    qw = quantize_lstm_weights(w, u, b)
    # the LSTM path packs its gate columns i,f,g,o -> i,f,o,g first; the
    # quantizer applied to the packed matrix must be quantize_colwise exactly
    w_packed, _, _ = _pack_ifog(w, u, b, u.shape[0])
    q_ref, s_ref = quantize_colwise(w_packed)
    np.testing.assert_array_equal(np.asarray(qw.w_q), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(qw.w_scale), np.asarray(s_ref))


def test_kv_quantizer_matches_rowwise_convention():
    """``quantize_kv`` is ``ref.quantize_rowwise`` over the feature axis
    (scale shape aside): same scales, same int8 payload."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 32)).astype(np.float32))
    q, s = quantize_kv(x)
    q_ref, s_ref = quantize_rowwise(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref)[:, 0])
    np.testing.assert_allclose(np.asarray(dequantize_kv(q, s)),
                               np.asarray(x), atol=float(jnp.max(s)) / 2)


# ---------------------------------------------------------------------------
# qeinsum semantics
# ---------------------------------------------------------------------------
def test_qeinsum_passthrough_and_int8_path():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 5, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 4, 8)).astype(np.float32))
    # plain array: exact jnp.einsum
    np.testing.assert_array_equal(
        np.asarray(qeinsum("bsd,dhe->bshe", x, w)),
        np.asarray(jnp.einsum("bsd,dhe->bshe", x, w)))
    # QuantTensor: exactly the reference int8 contraction
    q, s = quantize_colwise(w.reshape(16, 32))
    qt = QuantTensor(q=q.reshape(16, 4, 8), scale=s.reshape(4, 8))
    got = qeinsum("bsd,dhe->bshe", x, qt)
    xq, xs = quantize_rowwise(x.reshape(10, 16))
    want = int8_matmul_ref(xq, q, xs, s).reshape(2, 5, 4, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qeinsum_fallback_uses_dequantized_weights():
    """MLA's absorbed-decode specs cannot collapse to a col-scaled matmul;
    the fallback must compute with exactly ``dequantize(w)``."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 1, 3, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(6, 3, 8)).astype(np.float32))
    q, s = quantize_colwise(w.reshape(6, 24))
    qt = QuantTensor(q=q.reshape(6, 3, 8), scale=s.reshape(3, 8))
    got = qeinsum("bqhe,rhe->bqhr", x, qt)
    want = jnp.einsum("bqhe,rhe->bqhr", x, dequantize(qt)).astype(x.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_quantize_params_idempotent_and_typed(arch):
    cfg = get_reduced_config(arch)
    params = init_model(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, cfg)
    n_quant = sum(isinstance(l, QuantTensor)
                  for l in jax.tree.leaves(
                      qp, is_leaf=lambda l: isinstance(l, QuantTensor)))
    assert n_quant > 0
    qp2 = quantize_params(qp, cfg)
    for a, b in zip(jax.tree.leaves(qp), jax.tree.leaves(qp2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# int8 KV pages
# ---------------------------------------------------------------------------
def _quant_engines(arch, *, max_batch=3, max_len=32, page_size=4,
                   num_pages=None, **sc_kw):
    """Two fully quantized engines (int8 weights + int8 KV) over identical
    params: parity-sized reference and an over-committed tight pool."""
    cfg = dataclasses.replace(get_reduced_config(arch),
                              dtype=jnp.float32, quant="int8")
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(dataclasses.replace(cfg, quant=None),
                                     jax.random.PRNGKey(0)))
    ref = InferenceEngine(cfg, params=params, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, kv_quant="int8", **sc_kw))
    tight = InferenceEngine(cfg, params=params, sc=ServeConfig(
        max_batch=max_batch, max_len=max_len, paged=True,
        page_size=page_size, num_pages=num_pages, kv_quant="int8", **sc_kw))
    return ref, tight


def test_int8_page_swap_roundtrip_bit_identical():
    """swap_out → swap_in of an int8-KV slot restores payload AND scale
    pages byte-for-byte (both are just paged leaves to the swap path)."""
    cfg = dataclasses.replace(get_reduced_config("granite-3-8b"),
                              dtype=jnp.float32)
    eng = InferenceEngine(cfg, sc=ServeConfig(
        max_batch=2, max_len=32, paged=True, page_size=4, kv_quant="int8"))
    pool = eng.make_pool()
    assert pool.kv_quant == "int8"
    skeys = tuple(f"{k}_scale" for k in pool._pkeys)
    assert pool._pleaves == pool._pkeys + skeys
    reqs = poisson_stream(1, rate_hz=100.0, seed=0,
                          vocab_size=cfg.vocab_size, prompt_lens=(9,),
                          new_tokens=(4, 4))
    # admit by hand (prefill quantizes-on-write into the slot's pages), then
    # round-trip the slot through the swap path
    slot = 0
    eng.prefill_into_slot(pool, slot, np.asarray(reqs[0].prompt, np.int32),
                          rid=reqs[0].rid, budget=4)
    assert pool.active[slot]
    before = {k: np.asarray(pool.cache[k]).copy() for k in pool._pleaves}
    pids_before = [int(p) for p in pool.table[slot] if p != 0]
    image = pool.swap_out(slot)
    for k in pool._pleaves:
        assert k in image["pages"], k
    pool.swap_in(slot, image)
    pids_after = [int(p) for p in pool.table[slot] if p != 0]
    for k in pool._pleaves:
        a = before[k][:, pids_before]
        b = np.asarray(pool.cache[k])[:, pids_after]
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # the payload really is int8 and the scales really are f32
    assert all(np.asarray(pool.cache[k]).dtype == np.int8
               for k in pool._pkeys)
    assert all(np.asarray(pool.cache[k]).dtype == np.float32 for k in skeys)


@pytest.mark.parametrize("arch", ("granite-3-8b", "zamba2-7b"))
def test_quantized_preemption_token_identical_to_undisturbed(arch):
    """The end-to-end form of the round-trip pin: the SAME quantized engine
    emits the SAME tokens whether or not it was preempted-and-restored under
    page pressure — int8 pages lose nothing across swap."""
    ref, tight = _quant_engines(arch, num_pages=6)
    reqs = poisson_stream(6, rate_hz=40.0, seed=1,
                          vocab_size=ref.cfg.vocab_size, prompt_lens=(4, 6),
                          new_tokens=(2, 8))
    press = FaultProfile(seed=3, press_rate=0.5, press_pages=2)
    base = ContinuousBatchingScheduler(ref, policy="idle_waiting",
                                       calibration=CAL).run(reqs)
    sched = ContinuousBatchingScheduler(tight, policy="idle_waiting",
                                        calibration=CAL, preempt="tiered",
                                        swap=True, faults=press)
    rep = sched.run(reqs)
    assert rep.preempted > 0 and rep.swapped > 0
    assert ({r.rid: r.tokens for r in base.records}
            == {r.rid: r.tokens for r in rep.records})


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_quantized_serving_runs_every_family(arch):
    """Full quantization (int8 weights AND int8 KV pages) serves a bursty
    stream on every family, drains cleanly, and stays argmax-close to the
    f32 engine (the loose in-test floor; the calibrated floor is the
    ``serve_quantized`` BENCH gate)."""
    cfg = dataclasses.replace(get_reduced_config(arch), dtype=jnp.float32)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          init_model(cfg, jax.random.PRNGKey(0)))
    kw = dict(max_batch=2, max_len=32, paged=True, page_size=4)
    f32 = InferenceEngine(cfg, params=params, sc=ServeConfig(**kw))
    q8 = InferenceEngine(dataclasses.replace(cfg, quant="int8"),
                         params=params,
                         sc=ServeConfig(kv_quant="int8", **kw))
    reqs = bursty_stream(6, fast_rate_hz=2000.0, slow_rate_hz=20.0, seed=3,
                         vocab_size=cfg.vocab_size, prompt_lens=(4, 9),
                         new_tokens=(1, 6))
    base = ContinuousBatchingScheduler(f32, policy="adaptive",
                                       calibration=CAL).run(reqs)
    sched = ContinuousBatchingScheduler(q8, policy="adaptive",
                                        calibration=CAL)
    rep = sched.run(reqs)
    pool = sched.pool
    assert pool.active_count == 0
    bt = {r.rid: r.tokens for r in base.records}
    qt = {r.rid: r.tokens for r in rep.records}
    total = sum(len(v) for v in bt.values())
    same = sum(int(a == b) for rid in bt for a, b in zip(bt[rid], qt[rid]))
    # loose floor: greedy chains diverge permanently at the first flipped
    # near-tie, and reduced random-init logits are near-ties everywhere —
    # the calibrated per-family floors live with the serve_quantized gate
    assert same / total >= 0.3, (arch, same, total)
