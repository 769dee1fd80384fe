"""A new workload, configuration, traffic mix, metric or model family is a
file found by its name under the benchmark root: the harness takes one
without a code edit. A model family is its module under ``bench/families``
(weight layout, costs, program fields) and its reference under
``bench/reference``; the harness's own files name no family."""
import json
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness
from bench.metrics import reader

ROOT = Path(__file__).resolve().parents[2]


def _copy_benchmark(tmp: Path) -> dict:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for d in ("configs", "traffic", "metrics", "families", "reference"):
        shutil.copytree(ROOT / "bench" / d, tmp / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((tmp / "BENCHMARK.json").read_text())


def test_new_files_are_found_by_name(tmp_path):
    bj = _copy_benchmark(tmp_path)
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "mamba2-780m.json").read_text())
    conf["name"] = "mamba2-780m-copy"
    (b / "configs" / "mamba2-780m-copy.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "chat-bursty.json").read_text())
    mix["arrivals"] = "poisson"
    (b / "traffic" / "chat-steady.json").write_text(json.dumps(mix))
    (b / "metrics" / "served_requests.py").write_text(
        "def read(run):\n    return len(run.rec.tokens)\n")
    bj["workloads"].append({"name": "copy-steady", "config": "mamba2-780m-copy",
                            "traffic": "chat-steady", "chips": 1, "why": "test"})
    bj["end_to_end"].append({"name": "served_requests", "unit": "requests",
                             "better": "higher", "bound": 0.1, "source": "host_clock",
                             "workloads": ["copy-steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    cell = harness.load_cell("copy-steady", tmp_path)
    assert cell.conf["name"] == "mamba2-780m-copy"
    assert cell.mix["arrivals"] == "poisson"
    assert "served_requests" in {m["name"] for m in cell.end_to_end}
    assert reader("served_requests", b / "metrics") is not None
    out = harness.run("copy-steady", 4, 1.5, False, t_process=time.perf_counter(),
                      size="cpu_test", require_tpu=False, root=tmp_path,
                      log=lambda s: None)
    assert out["metrics"]["served_requests"]["value"] > 0


def _add_granite_of_family(tmp: Path, bj: dict, family: str) -> str:
    """A configuration of granite's sizes whose model names ``family``, and a
    cell of it; returns the cell's name."""
    b = tmp / "bench"
    conf = json.loads((b / "configs" / "granite-3-8b-16L.json").read_text())
    conf["name"] = f"granite-{family}"
    conf["model"]["family"] = conf["reference"] = family
    (b / "configs" / f"granite-{family}.json").write_text(json.dumps(conf))
    bj["workloads"].append({"name": f"{family}-backlog", "config": f"granite-{family}",
                            "traffic": "decode-backlog", "chips": 1, "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bj))
    return f"{family}-backlog"


def test_new_family_is_found_by_name(tmp_path):
    """A family added as two files in a copied root, a copy of ``dense``
    under a new name that serves the program's dense family, is served and
    checked with no other file touched."""
    bj = _copy_benchmark(tmp_path)
    b = tmp_path / "bench"
    for d in ("families", "reference"):
        shutil.copy(b / d / "dense.py", b / d / "dense_copy.py")
        assert not (ROOT / "bench" / d / "dense_copy.py").exists()
    cell = _add_granite_of_family(tmp_path, bj, "dense_copy")
    out = harness.run(cell, 5, 1.5, False, t_process=time.perf_counter(),
                      size="cpu_test", require_tpu=False, root=tmp_path,
                      log=lambda s: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["max_logit_gap"]["value"] <= out["compared"]["max_logit_gap"]["limit"]


def test_family_without_a_file_names_the_file(tmp_path):
    bj = _copy_benchmark(tmp_path)
    cell = _add_granite_of_family(tmp_path, bj, "no_such_family")
    path = (tmp_path / "bench" / "families" / "no_such_family.py").resolve()
    with pytest.raises(FileNotFoundError, match=re.escape(str(path))):
        harness.run(cell, 5, 1.5, False, t_process=time.perf_counter(),
                    size="cpu_test", require_tpu=False, root=tmp_path,
                    log=lambda s: None)


def test_split_metric_is_read_by_its_base_reader():
    assert reader("decode_step_ms.rate").__module__ == reader("decode_step_ms").__module__


def test_per_layer_metric_without_workloads_follows_what_it_moves(tmp_path):
    bj = _copy_benchmark(tmp_path)
    bj["per_layer"].append({"name": "decode_occupancy.all", "unit": "slots",
                            "better": "higher", "source": "program_counter",
                            "layer": "page pool", "moves": "itl_p95_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    for w in bj["workloads"]:
        names = {m["name"] for m in harness.load_cell(w["name"], tmp_path).per_layer}
        assert "decode_occupancy.all" in names


def _bench(cwd, extra_env=None):
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite8b-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_with_no_result():
    p = _bench(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
