"""The control of the correctness check comes out not correct: the program
served with its own lower-precision path (int8 weights, and int8 KV pages
where the model has pages) at the configurations' CPU test size, whose
limits were set from sound and control readings at that size (PERF.md)."""
import time

import pytest

from bench import harness


@pytest.mark.parametrize("cell", ["granite8b-decode", "mamba2-chat"])
def test_int8_control_is_not_correct(bench_root, cell):
    kw = dict(t_process=time.perf_counter(), size="cpu_test", require_tpu=False,
              root=bench_root, log=lambda s: None)
    sound = harness.run(cell, 2, 3.0, False, **kw)
    control = harness.run(cell, 2, 3.0, False, control="int8", **kw)
    assert sound["correct"] is True
    assert control["correct"] is False
    gap = control["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] > sound["compared"]["max_logit_gap"]["value"]
