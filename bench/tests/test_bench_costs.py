"""The FLOP and byte counts against hand counts for both configurations."""
import json
from pathlib import Path

import pytest

from bench import costs

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_granite_counts():
    m = _model("granite-3-8b-16L")
    # wq + wo: 4096*32*128 each; wk + wv: 4096*8*128 each; SwiGLU 3*4096*12800
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12800
    assert per_layer == 199_229_440 == costs.layer_params(m)
    out_proj = 4096 * 49408                 # vocabulary padded to 256s
    assert costs.weight_bytes(m) == 2 * (16 * per_layer + out_proj)
    # two slots writing positions 0 and 9 attend over 1 and 10 positions
    flops, byts = costs.decode(m, [0, 9])
    attn = 16 * 4 * 32 * 128 * (1 + 10)
    assert flops == 2 * 2 * (16 * per_layer + out_proj) + attn
    kv = 16 * 2 * 8 * 128 * 2 * (1 + 10)    # K and V, bf16, 16 layers
    assert byts == costs.weight_bytes(m) + kv
    # one 256-token chunk from position 512: causal keys 512*256 + 256*257/2
    flops, byts = costs.chunk(m, 1, 512, 256)
    seen = 512 * 256 + 256 * 257 // 2
    assert flops == 2 * 256 * 16 * per_layer + 2 * out_proj + 16 * 4 * 32 * 128 * seen
    assert byts == costs.weight_bytes(m) + 16 * 2 * 8 * 128 * 2 * (512 + 256)


def test_mamba2_counts():
    m = _model("mamba2-780m")
    # wz, wx: 1536x3072; wB, wC: 1536x128; wdt: 1536x48; wo: 3072x1536
    per_layer = 2 * 1536 * 3072 + 2 * 1536 * 128 + 1536 * 48 + 3072 * 1536
    assert per_layer == 14_622_720 == costs.layer_params(m)
    out_proj = 1536 * 50432                 # tied embedding, padded vocab
    assert costs.weight_bytes(m) == 2 * (48 * per_layer + out_proj)
    flops, byts = costs.decode(m, [100, 7, 2000])
    state = 48 * 64 * 128                   # heads x head_dim x state per layer
    conv = 2 * 4 * (3072 + 256)
    assert flops == 3 * (2 * (48 * per_layer + out_proj) + 48 * (5 * state + conv))
    # f32 state read and written, bf16 conv window (3 rows) read and written
    assert byts == costs.weight_bytes(m) + 3 * 48 * (8 * state + 4 * 3 * (3072 + 256))
    flops, _ = costs.chunk(m, 2, 0, 256)
    ssd = 2 * 256 * 256 * 128 + 2 * 256 * 256 * 48 * 64 + 4 * 256 * state
    assert flops == 2 * (2 * 256 * 48 * per_layer + 2 * out_proj
                         + 48 * (ssd + 2 * 256 * 4 * (3072 + 256)))


def test_roofline_takes_the_larger_bound():
    pk = costs.peaks("TPU v5 lite")
    assert costs.least_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert costs.least_seconds(1.0, 819e9, pk) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")
