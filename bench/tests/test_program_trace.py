"""The program's spans and scopes read from a profiler trace, checked by hand
on a small trace kept beside this file (``data/trace_program.json``)."""
import json
import re
import time
from pathlib import Path

import pytest

from bench import program_trace, trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tr():
    return json.loads((DATA / "trace_program.json").read_text())


@pytest.fixture(scope="module")
def hlo():
    """The decode program's compiled text for the trace's ops."""
    return (DATA / "trace_program.hlo").read_text()


def test_span_self_times_by_hand(tr):
    s = program_trace.reduce(tr)["spans"]
    # the window is [1000, 11000); the third tick is clipped to [10500, 11000)
    # ticks 5000 + 4500 + 500 ns; their children cover 4000, 2200 and 400
    # (prepare 100, dispatch 200, readback clipped to 100); bench.decode
    # between a tick and its engine spans is looked through
    assert s["serve.tick"] == {"count": 3, "total_s": pytest.approx(10000e-9),
                               "self_s": pytest.approx(3400e-9)}
    assert s["engine.decode.readback"]["total_s"] == pytest.approx(3500e-9)
    assert s["serve.idle"]["self_s"] == pytest.approx(2000e-9)  # bench.wait is no child
    # engine.land ran on another thread: no child of the tick it overlaps
    assert s["engine.land"] == {"count": 1, "total_s": pytest.approx(400e-9),
                                "self_s": pytest.approx(400e-9)}
    assert "bench.decode" not in s and "bench.window" not in s


def test_per_step_numbers_by_hand(tr, hlo):
    r = program_trace.reduce(tr, hlo)
    assert r["tick_self_ms"] == pytest.approx(3400e-9 / 3 * 1e3)
    # prepare 200 + 100, dispatch 200 + 200, over two decode calls
    assert r["decode_host_ms"] == pytest.approx(700e-9 / 2 * 1e3)
    # two decode runs touch the window; attention 1000 + 100 (clipped),
    # kv_pages 500 (the layer's slice, by its gather) + 200 (the scatter,
    # scoped inside vmap)
    assert r["decode_attn_ms"] == pytest.approx(1100e-9 / 2 * 1e3)
    assert r["decode_kv_ms"] == pytest.approx(700e-9 / 2 * 1e3)


def test_scopes_of_the_decode_program_by_hand(tr, hlo):
    sc = program_trace.reduce(tr, hlo)["scopes"]
    # the chunk program's attention (fusion.9) and the while op are left out;
    # fusion.1 and fusion.4 count under their users' scopes; copy.5 carries
    # no path and feeds only the loop's tuple
    assert sc == {"kv_pages": pytest.approx(700e-9), "attention": pytest.approx(1100e-9),
                  "proj": pytest.approx(500e-9), "mlp": pytest.approx(300e-9),
                  "logits": pytest.approx(300e-9), None: pytest.approx(200e-9)}


def test_idle_gaps_carry_program_names(tr):
    r = program_trace.reduce(tr)
    # gaps [7300,10900), [5000,6300), [1000,2000), named where each began
    assert r["idle_gaps"] == [["serve.tick", pytest.approx(3600e-9)],
                              ["engine.decode.readback", pytest.approx(1300e-9)],
                              ["serve.tick", pytest.approx(1000e-9)]]
    # the same gaps by trace.reduce, which knows only bench.* annotations
    assert trace.reduce(tr)["breakdown"]["idle_gaps"] == [
        ["scheduler host code", pytest.approx(3600e-9)],
        ["bench.decode", pytest.approx(1300e-9)],
        ["scheduler host code", pytest.approx(1000e-9)]]
    assert r["idle_by_gap_name"] == {"serve.tick": pytest.approx(4600e-9),
                                     "engine.decode.readback": pytest.approx(1300e-9)}
    by = r["idle_by_span"]
    assert by == {"serve.tick": pytest.approx(2200e-9),
                  "bench.wait_for_arrival": pytest.approx(1800e-9),
                  "engine.decode.readback": pytest.approx(400e-9),
                  "engine.decode.dispatch": pytest.approx(400e-9),
                  "engine.decode.prepare": pytest.approx(300e-9),
                  "bench.decode": pytest.approx(200e-9),
                  "serve.gc": pytest.approx(200e-9),
                  "serve.idle": pytest.approx(200e-9),
                  "engine.chunk.dispatch": pytest.approx(200e-9)}
    assert sum(by.values()) == pytest.approx(5900e-9)


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/vmap()/while/body/closed_call/attention/dot_general", "attention"),
    ("jit(f)/vmap(kv_pages)/jit(floor_divide)/rem", "kv_pages"),
    ("jit(f)/logits/proj/rsqrt", "logits"),
    ("jit(f)/vmap()/while/body/dynamic_slice", None),
    (None, None),
])
def test_scope_is_the_first_on_the_path(path, scope):
    assert program_trace.scope_of(path) == scope


def test_scopes_from_compiled_hlo_text(hlo):
    sc = program_trace.hlo_scopes(hlo)
    assert {k: sc[k] for k in ("fusion.1", "gather.1", "fusion.2", "fusion.3", "fusion.4",
                               "bitcast.4", "dot.4", "copy.5", "tuple.3", "fusion.6",
                               "fusion.7", "convert.4")} == {
        "fusion.1": "kv_pages",   # while/body/squeeze: its user gathers pages
        "gather.1": "kv_pages", "fusion.2": "attention", "fusion.3": "proj",
        "fusion.4": "mlp",        # no path: its user's user is mlp's dot
        "bitcast.4": "mlp", "dot.4": "mlp",
        "copy.5": None, "tuple.3": None,  # users lead to no scope
        "fusion.6": "kv_pages", "fusion.7": "logits",
        "convert.4": None,        # a fused computation's root: no users
    }
    # computations are not instructions
    assert not {"body.3", "cond.2", "fused_computation.4", "main.9"} & set(sc)
    assert program_trace.instr_name("%fusion.5 = bf16[16,8]{1,0} fusion(%p)") == "fusion.5"


def test_an_own_scope_wins_over_its_users():
    hlo = """  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(f)/attention/mul"}
  ROOT %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(f)/logits/argmax"}
"""
    assert program_trace.hlo_scopes(hlo) == {"fusion.1": "attention", "fusion.2": "logits"}


def test_trace_without_a_device_gives_spans_only():
    tr = {"spans": [["bench.window", 0, 100, 0, {}], ["serve.tick", 0, 50, 0, {}]],
          "host": [["bench.window", 0, 100]], "ops": [], "modules": []}
    r = program_trace.reduce(tr)
    assert r["tick_self_ms"] == pytest.approx(50e-9 * 1e3)
    assert r["decode_attn_ms"] is None and r["scopes"] == {} and r["idle_gaps"] == []
    assert r["idle_by_gap_name"] == {} and r["idle_by_span"] == {}


def test_a_program_without_scopes_reads_nothing(tr, hlo):
    """A build without the named scopes (a parent commit's) gives paths
    with none of them: its decode time is all outside, and the two scope
    numbers read nothing rather than zero."""
    bare = re.sub(r'op_name="[^"]*"', 'op_name="jit(_paged_decode_impl)/while/body/dot"', hlo)
    r = program_trace.reduce(tr, bare)
    assert r["scopes"] == {None: pytest.approx(3100e-9)}
    assert r["decode_attn_ms"] is None and r["decode_kv_ms"] is None


def test_profile_program_runs_a_cell_at_cpu_size(bench_root):
    """The tool on the CPU: the program's spans come back from a traced run
    of the cell's small stand-in, with no device numbers, and the harness's
    names are its own again afterwards."""
    from bench import harness, profile_program

    build, reduce_trace = harness.build, harness._reduce_trace
    r = profile_program.profile("granite8b-decode", 2718281829, 1.5,
                                t_process=time.perf_counter(), size="cpu_test",
                                require_tpu=False, root=bench_root, log=lambda s: None)
    assert (harness.build, harness._reduce_trace) == (build, reduce_trace)
    assert r["run"]["correct"] and "idle_share" not in r["run"]["metrics"]
    assert r["run"]["metrics"]["sched_host_ms"]["value"] > 0
    spans = r["program"]["spans"]
    n = spans["engine.decode.dispatch"]["count"]
    assert n and spans["engine.decode.prepare"]["count"] == n
    assert spans["engine.decode.readback"]["count"] == n
    assert spans["serve.tick"]["count"] >= n
    assert r["program"]["tick_self_ms"] > 0 and r["program"]["decode_attn_ms"] is None


def test_profile_program_starts_from_the_command_line(tmp_path):
    """The command imports the program and reaches the cell: an unknown
    cell is refused by name, after the imports."""
    import os
    import subprocess
    import sys

    root = Path(__file__).resolve().parents[2]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache")}
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/profile_program.py", "--workload", "no-such-cell",
                        "--seed", "1", "--seconds", "1"],
                       cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "KeyError" in p.stderr and "no-such-cell" in p.stderr, p.stderr[-2000:]


def test_one_read_gives_what_trace_extract_gives(tmp_path):
    """``program_trace.extract`` reads the file once for both reductions:
    ``trace.extract``'s keys come out the same, the program's spans beside."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("serve.tick"):
            with jax.profiler.TraceAnnotation("engine.land", rows=3):
                jax.block_until_ready(jnp.arange(8) * 2)
    jax.profiler.stop_trace()
    both = program_trace.extract(str(tmp_path))
    alone = trace.extract(str(tmp_path))
    assert {k: both[k] for k in alone} == alone
    names = {s[0]: s for s in both["spans"]}
    assert set(names) == {"bench.window", "serve.tick", "engine.land"}
    assert names["engine.land"][4]["rows"] == 3
    assert program_trace.reduce(both)["spans"]["serve.tick"]["count"] == 1
