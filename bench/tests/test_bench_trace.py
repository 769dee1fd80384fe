"""The reduction from a profiler trace to busy time, step times and the
breakdown, checked by hand on a small trace kept beside this file."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_synthetic_trace_by_hand():
    r = trace.reduce(json.loads((DATA / "trace_synthetic.json").read_text()))
    # the window is [1000, 11000) ns; ops clipped to it and merged:
    # [1000,1500) + [2000,5000) + [9000,9500) = 500 + 3000 + 500 ns
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4000e-9)
    assert r["kinds"]["decode"] == {"calls": 2, "device_s": pytest.approx(1500e-9)}
    assert r["kinds"]["chunk"] == {"calls": 1, "device_s": pytest.approx(2000e-9)}
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["copy.3"] == pytest.approx(2000e-9)
    assert ops["fusion.1"] == pytest.approx(1100e-9)
    assert ops["fusion.9"] == pytest.approx(500e-9)  # the part inside the window
    assert r["breakdown"]["device_ops"][0][0] == "copy.3"
    # the layer loop's while op spans the ops inside it: busy, not listed
    assert not any(n.startswith("%while") for n, _ in r["breakdown"]["device_ops"])
    # idle gaps [5000,9000), [9500,11000), [1500,2000), named by the host
    # annotation each began in
    assert r["breakdown"]["idle_gaps"] == [
        ["scheduler host code", pytest.approx(4000e-9)],
        ["bench.wait_for_arrival", pytest.approx(1500e-9)],
        ["bench.decode", pytest.approx(500e-9)],
    ]


def test_trace_without_a_device_gives_no_device_numbers():
    tr = {"host": [["bench.window", 0, 100]], "ops": [], "modules": []}
    r = trace.reduce(tr)
    assert r["busy_s"] is None and r["breakdown"]["device_ops"] == []


def test_trace_without_a_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"host": [], "ops": [], "modules": []})
