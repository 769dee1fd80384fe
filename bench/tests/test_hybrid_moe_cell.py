"""The ``hybrid_moe`` family (granite-4.0-h-small-10L) as the benchmark knows
it: the hand counts of ``bench/families/hybrid_moe.py`` against the
configuration's reckoning at published widths, its weight tree pinned in
``data/layouts_hybrid_moe.json`` and equal to the program's parameter
layout, and the correctness check of ``granite4h-longdoc`` refusing the
int8 control and planted faults at the configuration's CPU test size."""
import json
import time
from pathlib import Path

import pytest

from bench import costs, families, harness, weights
from bench.tests.test_bench_faults import half, state, token
from bench.tests.test_bench_layout import digest, layout, model

NAME = "granite-4.0-h-small-10L"
CELL = "granite4h-longdoc"
DATA = json.loads((Path(__file__).resolve().parent / "data"
                   / "layouts_hybrid_moe.json").read_text())
FAM = families.module("hybrid_moe")

D, F, FS, E, HELD, TOP = 4096, 768, 1536, 72, 9, 10
MAMBA = D * (2 * 8192 + 2 * 128 + 128) + 8192 * D    # wz wx wB wC wdt, wo
ATTN = 2 * D * 32 * 128 + 2 * D * 8 * 128            # wq wo, wk wv
EXPERT = 3 * D * F


def test_layer_params_are_the_mean_active_layer():
    m = model(NAME, "full")
    assert MAMBA == 102_236_160 and ATTN == 41_943_040 and EXPERT == 9_437_184
    # 9 Mamba-2 and 1 attention mixer over 10 layers; the shared expert, the
    # router's 72 outputs, and 10 * 9 / 72 = 1.25 held experts a token
    per_layer = (9 * MAMBA + ATTN) // 10 + 3 * D * FS + D * E + 5 * EXPERT // 4
    assert per_layer == 127_172_608 == costs.layer_params(m, FAM)
    out_proj = D * 100352                                # tied, a multiple of 256
    assert costs.weight_bytes(m, FAM) == 2 * (10 * per_layer + out_proj)


def test_a_step_reads_every_held_expert_and_the_state():
    m = model(NAME, "full")
    held_rest = 10 * (HELD - 1.25) * EXPERT * 2          # 7.75 more experts a layer
    weights_all = costs.weight_bytes(m, FAM) + held_rest
    # 9 x 102.24M + 41.9M + 10 x 18.87M + 90 x 9.44M + router, and the
    # embedding: the reckoning's 4.83 GB of bf16 weights
    assert weights_all == pytest.approx(4.83e9, rel=2e-3)
    flops, byts = costs.decode(m, [0, 4095], FAM)
    state = 128 * 64 * 128                               # heads x head_dim x d_state
    conv = 3 * (8192 + 2 * 128)                          # 3 rows of x, B and C
    ctx = 1 + 4096
    assert byts == weights_all + 2 * 9 * (8 * state + 4 * conv) + 2 * 8 * 128 * 2 * ctx
    assert flops == (2 * 2 * (10 * costs.layer_params(m, FAM) + D * 100352)
                     + 2 * 9 * (5 * state + 2 * 4 * (8192 + 256)) + 4 * 32 * 128 * ctx)
    # one 512-token chunk from position 1024 in a group of 2
    flops, byts = costs.chunk(m, 2, 1024, 512, FAM)
    seen = 1024 * 512 + 512 * 513 // 2
    ssd = 2 * 512 * 512 * 128 + 2 * 512 * 512 * 128 * 64 + 4 * 512 * state
    assert flops == (2 * (2 * 512 * 10 * costs.layer_params(m, FAM) + 2 * D * 100352)
                     + 2 * 9 * (ssd + 2 * 512 * 4 * (8192 + 256))
                     + 2 * 4 * 32 * 128 * seen)
    assert byts == weights_all + 2 * 9 * 8 * state + 2 * 2 * 8 * 128 * 2 * (1024 + 512)


@pytest.mark.parametrize("size", ["full", "cpu_test"])
def test_weight_layout_is_pinned_and_is_the_programs(size):
    m = model(NAME, size)
    assert layout(m) == DATA["layouts"][NAME][size]
    conf = json.loads((Path(harness.BENCH) / "configs" / f"{NAME}.json").read_text())
    harness.check_layout(weights.abstract(m, FAM), harness.program_config(conf, m, FAM))


def test_cpu_test_weights_are_pinned():
    assert digest(model(NAME, "cpu_test"), DATA["seed"]) == DATA["digests"][NAME]


def _run(root, seed, **kw):
    return harness.run(CELL, seed, 1.5, False, t_process=time.perf_counter(),
                       size="cpu_test", require_tpu=False, root=root,
                       log=lambda s: None, **kw)


def test_int8_control_is_not_correct(bench_root):
    sound, control = _run(bench_root, 2), _run(bench_root, 2, control="int8")
    assert sound["correct"] is True and control["correct"] is False
    gap = control["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] > sound["compared"]["max_logit_gap"]["value"]


@pytest.mark.parametrize("fault", [token, state, half], ids=["token", "state", "half"])
def test_planted_fault_is_not_correct(bench_root, fault):
    out = _run(bench_root, 11, fault=fault)
    assert out["correct"] is False
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_check_covers_requests_admitted_together(bench_root, monkeypatch):
    """The check samples several requests, among them requests that were
    admitted in one prefill group with others (they share the group's
    ``begin`` time), and finds them correct."""
    from bench import check, spans

    seen = {}
    sample0, install0 = check.sample, spans.Recorder.install

    def sample(*a, **kw):
        seen["sampled"] = sample0(*a, **kw)
        return seen["sampled"]

    def install(self, engine):
        seen["rec"] = self
        return install0(self, engine)

    monkeypatch.setattr(check, "sample", sample)
    monkeypatch.setattr(spans.Recorder, "install", install)
    out = _run(bench_root, 11)
    admitted = seen["rec"].admitted
    together = [rid for rid, _, _ in seen["sampled"]
                if sum(t == admitted[rid] for t in admitted.values()) > 1]
    assert out["correct"] is True
    assert len(seen["sampled"]) == 8 and together
