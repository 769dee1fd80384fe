"""The harness loop runs both cells at the configurations' small CPU sizes:
it serves through the scheduler, reports counts and host-clock metrics, and
never a device metric."""
import time

import pytest

from bench import harness
from bench.tests.conftest import all_entries

CELLS = [w["name"] for w in all_entries()["workloads"]]
DEVICE_METRICS = {m["name"] for m in all_entries()["per_layer"]
                  if m["source"] == "device_trace"}


def _run(root, cell, seed, trace=False, **kw):
    return harness.run(cell, seed, 1.5, trace, t_process=time.perf_counter(),
                       size="cpu_test", require_tpu=False, root=root,
                       log=lambda s: None, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_on_the_cpu_and_is_correct(bench_root, cell):
    out = _run(bench_root, cell, 2**31 + 5)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in out["device"] and "breakdown" not in out
    e2e = {m["name"] for m in harness.load_cell(cell, bench_root).end_to_end}
    assert set(out["metrics"]) <= e2e
    assert {"setup_s", "out_tokens_per_s"} <= set(out["metrics"])
    assert list(out)[-1] == "compared"
    assert out["compared"]["max_logit_gap"]["value"] <= out["compared"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cpu_run_reports_no_device_metric(bench_root, cell):
    out = _run(bench_root, cell, 3, trace=True)
    assert out["correct"] is True
    assert not set(out["metrics"]) & DEVICE_METRICS
    assert "busy_s" not in out["device"] and "breakdown" not in out
    per_layer = {m["name"] for m in harness.load_cell(cell, bench_root).per_layer}
    assert set(out["metrics"]) <= per_layer and out["metrics"]


def test_no_tpu_is_refused():
    with pytest.raises(harness.NoChip):
        harness.run(CELLS[0], 1, 1.0, False, t_process=time.perf_counter(),
                    size="cpu_test", require_tpu=True)
