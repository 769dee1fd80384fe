"""Autotuner validation: feasibility pruning, determinism, cache behaviour,
and the block_*="auto" routing through the real kernels."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.energy import DEFAULT_CHIP
from repro.kernels import autotune as at
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)
PROBLEM = {"m": 256, "k": 256, "n": 256}


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Each test gets a fresh in-process and on-disk cache."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    at.clear_cache()
    yield
    at.clear_cache()


# ---------------------------------------------------------------------------
# Feasibility pruning
# ---------------------------------------------------------------------------
def test_feasible_candidates_fit_vmem():
    tiny = dataclasses.replace(DEFAULT_CHIP, vmem_bytes=64 * 1024)
    cands = at.feasible_candidates("int8_matmul", PROBLEM, tiny)
    assert cands
    for c in cands:
        assert at.vmem_footprint_bytes("int8_matmul", PROBLEM, c) <= tiny.vmem_bytes


def test_tuned_choice_respects_vmem_budget():
    """Distinct chips get distinct cache keys — a winner tuned for the big
    budget must never be served for the small one."""
    tiny = dataclasses.replace(DEFAULT_CHIP, vmem_bytes=64 * 1024)
    big = at.autotune("int8_matmul", PROBLEM, dtype="int8")  # caches first
    best = at.autotune("int8_matmul", PROBLEM, dtype="int8", chip=tiny)
    assert at.vmem_footprint_bytes("int8_matmul", PROBLEM, best) <= tiny.vmem_bytes
    # the default budget admits coarser (faster-predicted) blocks
    t_big = at.predict_time_s("int8_matmul", PROBLEM, big, dtype="int8")
    t_tiny = at.predict_time_s("int8_matmul", PROBLEM, best, dtype="int8")
    assert t_big <= t_tiny
    assert at.cache_key("int8_matmul", PROBLEM, "int8") != at.cache_key(
        "int8_matmul", PROBLEM, "int8", chip=tiny
    )


def test_poisoned_disk_entry_rejected(tmp_path):
    """Disk cache is untrusted: malformed entries are re-tuned, not served."""
    key = at.cache_key("int8_matmul", PROBLEM, "int8")
    with open(at._cache_path(), "w") as f:
        json.dump({key: {"block_m": "rm -rf", "block_n": -1}}, f)
    best = at.autotune("int8_matmul", PROBLEM, dtype="int8")
    assert all(isinstance(v, int) and v > 0 for v in best.values())


def test_divisibility_for_matmul_blocks():
    for prob in ({"m": 96, "k": 160, "n": 224}, {"m": 33, "k": 7, "n": 65}):
        best = at.autotune("int8_matmul", prob, dtype="int8")
        assert prob["m"] % best["block_m"] == 0
        assert prob["n"] % best["block_n"] == 0
        assert prob["k"] % best["block_k"] == 0


def test_lstm_seq_long_sequence_narrows_batch_tile():
    """VMEM feasibility must shrink block_b once S·bb·(D+H) outgrows VMEM."""
    prob = {"batch": 512, "seq": 512, "d_in": 32, "hidden": 32}
    best = at.autotune("lstm_seq", prob, dtype="float32")
    assert at.vmem_footprint_bytes("lstm_seq", prob, best) <= DEFAULT_CHIP.vmem_bytes
    assert best["block_b"] < 512
    # a short sequence at the same budget affords a wider batch tile
    short = at.autotune("lstm_seq", {**prob, "seq": 16}, dtype="float32")
    assert short["block_b"] > best["block_b"]


# ---------------------------------------------------------------------------
# dtype-aware footprints (int8 residency) + the lstm_stack traffic model
# ---------------------------------------------------------------------------
def test_int8_weights_shrink_footprint_and_widen_tile():
    """int8-resident weights cost 4× less VMEM than f32, so at a shape
    where the f32 weight block crowds the budget the int8 tuner must admit
    a WIDER batch tile."""
    prob = {"batch": 128, "seq": 16, "d_in": 256, "hidden": 256}
    cand = {"block_b": 64}
    fp = at.vmem_footprint_bytes("lstm_seq", prob, cand, dtype="float32")
    q8 = at.vmem_footprint_bytes("lstm_seq", prob, cand, dtype="int8")
    # difference is exactly the weight payload shrink (minus scale vectors)
    assert q8 < fp
    best_fp = at.autotune("lstm_seq", prob, dtype="float32")
    best_q8 = at.autotune("lstm_seq", prob, dtype="int8")
    assert best_q8["block_b"] > best_fp["block_b"], (best_fp, best_q8)


def test_dtype_cache_keys_distinct():
    """float32 and int8 must never share autotune winners: distinct cache
    keys, independently cached entries."""
    prob = {"batch": 128, "seq": 16, "d_in": 256, "hidden": 256}
    k_fp = at.cache_key("lstm_seq", prob, "float32")
    k_q8 = at.cache_key("lstm_seq", prob, "int8")
    assert k_fp != k_q8
    best_fp = at.autotune("lstm_seq", prob, dtype="float32")
    best_q8 = at.autotune("lstm_seq", prob, dtype="int8")
    assert at._CACHE[k_fp] == best_fp
    assert at._CACHE[k_q8] == best_q8
    assert best_fp != best_q8  # at this shape the winners genuinely differ


def test_lstm_stack_model_beats_sequential_traffic():
    """The fused stack's HBM traffic must undercut L sequential lstm_seq
    calls (which bounce the inter-layer h sequence through HBM)."""
    prob = {"batch": 32, "seq": 28, "d_in": 128, "hidden": 128, "layers": 3}
    best = at.autotune("lstm_stack", prob, dtype="float32")
    assert at.vmem_footprint_bytes("lstm_stack", prob, best,
                                   dtype="float32") <= DEFAULT_CHIP.vmem_bytes
    seq_prob = {k: v for k, v in prob.items() if k != "layers"}
    stack = at._lstm_stack_analyze(prob, best, "float32")
    per_layer = at._lstm_seq_analyze(seq_prob, best, "float32")
    assert stack.hbm_bytes < prob["layers"] * per_layer.hbm_bytes
    # int8 stack fits the same tile in less VMEM
    assert at.vmem_footprint_bytes("lstm_stack", prob, best, dtype="int8") < \
        at.vmem_footprint_bytes("lstm_stack", prob, best, dtype="float32")


def test_measured_refinement_via_bench_driver(monkeypatch):
    """The benchmarks/run.py hook (REPRO_AUTOTUNE_MEASURE=1) re-ranks the
    analytic top-k with REAL kernel timings in interpret mode and caches
    the measured winners."""
    import sys
    from pathlib import Path

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_MEASURE", "1")
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        from benchmarks import run as bench_run

        assert bench_run.autotune_measure_enabled()
        refined = bench_run.refine_lstm_autotune(quick=True, top_k=2)
    finally:
        sys.path.pop(0)
    assert refined  # every bench shape got a measured winner...
    for entry in refined:
        key = at.cache_key(entry["kernel"], entry["problem"], entry["dtype"])
        assert at._CACHE[key] == entry["best"]  # ...and it landed in the cache
    kernels = {e["kernel"] for e in refined}
    dtypes = {e["dtype"] for e in refined}
    assert kernels == {"lstm_seq", "lstm_stack"}  # the fp32/int8/stack trio
    assert dtypes == {"float32", "int8"}


# ---------------------------------------------------------------------------
# Determinism + cache
# ---------------------------------------------------------------------------
def test_choice_deterministic_and_cached(tmp_path, monkeypatch):
    c1 = at.autotune("int8_matmul", PROBLEM, dtype="int8")
    c2 = at.autotune("int8_matmul", PROBLEM, dtype="int8")
    assert c1 == c2
    key = at.cache_key("int8_matmul", PROBLEM, "int8")
    assert at._CACHE[key] == c1
    disk = json.load(open(at._cache_path()))
    assert disk[key] == c1
    # a fresh process (cleared in-process cache) reloads the disk entry
    # without re-scoring: poison the candidate generator to prove it
    at.clear_cache()
    monkeypatch.setitem(
        at._KERNELS, "int8_matmul",
        (lambda p: (_ for _ in ()).throw(AssertionError("re-scored")),
         at._KERNELS["int8_matmul"][1]),
    )
    assert at.autotune("int8_matmul", PROBLEM, dtype="int8") == c1


def test_distinct_keys_tune_independently():
    a = at.autotune("int8_matmul", {"m": 64, "k": 64, "n": 64}, dtype="int8")
    b = at.autotune("int8_matmul", {"m": 512, "k": 512, "n": 512}, dtype="int8")
    assert a["block_m"] <= 64 and b["block_m"] >= 64
    k1 = at.cache_key("int8_matmul", {"m": 64, "k": 64, "n": 64}, "int8")
    k2 = at.cache_key("int8_matmul", {"m": 512, "k": 512, "n": 512}, "int8")
    assert k1 != k2 and k1 in at._CACHE and k2 in at._CACHE


def test_measure_fn_refines_top_k():
    calls = []

    def fake_time(cand):
        calls.append(dict(cand))
        return float(cand["block_b"])  # pretend smaller tiles are faster

    best = at.autotune(
        "lstm_seq", {"batch": 256, "seq": 16, "d_in": 8, "hidden": 16},
        dtype="float32", backend="measured", measure_fn=fake_time, top_k=3,
    )
    assert 1 < len(calls) <= 3
    assert best["block_b"] == min(c["block_b"] for c in calls)


def test_unknown_kernel_rejected():
    with pytest.raises(ValueError):
        at.autotune("nope", {"m": 1})


# ---------------------------------------------------------------------------
# "auto" routing through the real kernels
# ---------------------------------------------------------------------------
def test_int8_matmul_auto_blocks_match_ref():
    from repro.kernels.int8_matmul import int8_matmul

    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (64, 128), jnp.float32)
    w = jax.random.normal(k2, (128, 96), jnp.float32)
    xq, sx = ref.quantize_rowwise(x)
    wq, sw = ref.quantize_colwise(w)
    got = int8_matmul(xq, wq, sx, sw, block_m="auto", block_n="auto",
                      block_k="auto", interpret=True)
    want = ref.int8_matmul_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_flash_attention_auto_blocks_match_ref():
    from repro.kernels.flash_attention import flash_attention

    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (1, 4, 64, 32), jnp.float32)
    k = jax.random.normal(k2, (1, 4, 64, 32), jnp.float32)
    v = jax.random.normal(k3, (1, 4, 64, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q="auto", block_k="auto",
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_lstm_cell_auto_blocks_match_ref():
    from repro.kernels.lstm_cell import lstm_cell_fused

    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (24, 6), jnp.float32)
    h = jax.random.normal(ks[1], (24, 20), jnp.float32)
    c = jax.random.normal(ks[2], (24, 20), jnp.float32)
    w = jax.random.normal(ks[3], (6, 80), jnp.float32) * 0.3
    u = jax.random.normal(ks[4], (20, 80), jnp.float32) * 0.3
    b = jax.random.normal(ks[5], (80,), jnp.float32) * 0.1
    got_h, got_c = lstm_cell_fused(x, h, c, w, u, b, block_b="auto", interpret=True)
    want_h, want_c = ref.lstm_cell_ref(x, h, c, w, u, b)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Runtime interpret-mode resolution (satellite: no hard-coded interpret=True)
# ---------------------------------------------------------------------------
def test_default_interpret_env_override(monkeypatch):
    from repro.kernels import runtime

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert runtime.default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert runtime.default_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    # the retired REPRO_INTERPRET alias no longer steers anything
    monkeypatch.setenv("REPRO_INTERPRET", "false")
    assert runtime.default_interpret() is True
    monkeypatch.delenv("REPRO_INTERPRET")
    # no env: a CPU backend has no TPU → interpret
    assert runtime.default_interpret() is True
    assert runtime.resolve_interpret(None) is True
    assert runtime.resolve_interpret(False) is False
