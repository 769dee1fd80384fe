"""``chip_smoke.py`` off the chip: its phases at reduced sizes in interpret
mode, and its refusal to report success without a TPU."""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_reduced_config

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parent.parent / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


def test_main_fails_without_a_tpu(capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(RuntimeError, match="not a TPU"):
            smoke.main()
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_agrees_with_oracles():
    errs = smoke.kernel_phase(lstm_shapes=((16, 6, 6, 20), (8, 4, 128, 128)),
                              matmul_shape=(128, 256, 384),
                              attention_shape=(1, 4, 2, 256, 128),
                              interpret=True)
    assert len(errs) == 10


def test_serve_phase_serves_every_request():
    out = smoke.serve_phase(get_reduced_config("granite-3-8b"), batch=4,
                            max_len=96, prefill_chunk=8, n_requests=8,
                            rate_hz=50.0, prompt_lens=(16, 32, 48),
                            new_tokens=(4, 12))
    assert out["completed"] == out["requests"] == 8
    assert out["committed"] == out["budget_committed"]
    assert out["chunks"] > 0


def test_numerics_phase_matches_the_f32_forward():
    cfg = dataclasses.replace(get_reduced_config("granite-3-8b"), num_layers=2)
    assert smoke.numerics_phase(cfg, prompt_len=24) <= smoke.LOGIT_RTOL


def test_numerics_phase_catches_a_stale_cache(monkeypatch):
    """A decode step that never writes its K/V row (a stale cache) must fail
    the logit comparison, not slip through as bf16 rounding."""
    from repro.models import layers

    monkeypatch.setattr(layers, "write_cache", lambda cache, new, pos, cfg,
                        axis=1: cache)
    cfg = dataclasses.replace(get_reduced_config("granite-3-8b"), num_layers=2)
    with pytest.raises(RuntimeError, match="logit error"):
        smoke.numerics_phase(cfg, prompt_len=24)
