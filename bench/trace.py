"""From a profiler trace to device busy time, step times and a breakdown.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reduction needs, in a small JSON-able form:

  ops      [name, start_ns, dur_ns] of every operation on the first device
           (the device plane's "XLA Ops" line)
  modules  [name, start_ns, dur_ns] of every compiled program run on it
           (the "XLA Modules" line); a jitted function ``f`` runs as a module
           named ``jit_f`` (followed by an id)
  host     [name, start_ns, dur_ns] of the benchmark's own annotations
           (``bench.*``) on the host threads, on the same clock

``reduce`` turns that into numbers: the traced window (the
``bench.window`` annotation), the union of operation intervals inside it
(busy seconds), device seconds and call counts per module family, the
operations that took most time (control-flow ops, whose events contain
other ops, left out), and the longest idle gaps with the host annotation
they fell in.
"""
from __future__ import annotations

import collections
import glob
import re

# control-flow ops whose events span the ops inside them (a layer scan runs
# as one ``while``): left out of the per-op breakdown, kept in busy time
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s]")

# which compiled program is which engine call (the program's jit names)
MODULE_KINDS = {
    "decode": re.compile(r"^jit__paged_decode_impl"),
    "chunk": re.compile(r"^jit__lambda"),
}


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    out = {"ops": [], "modules": [], "host": []}
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None:
            device = plane.name
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    out[key] += [[e.name, e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.name.startswith("bench.")]
    out["device_plane"] = device
    out["planes"] = {p.name: [l.name for l in p.lines] for p in pd.planes}
    return out


def short_name(name: str) -> str:
    """An op's name and result type, without the HLO text after them."""
    m = re.match(r"(\S+) = (\w+\[[^\]]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(tr: dict, top: int = 10) -> dict:
    win = [h for h in tr["host"] if h[0] == "bench.window"]
    if not win:
        raise ValueError("the trace has no bench.window annotation")
    w0, w1 = win[0][1], win[0][1] + win[0][2]

    def clip(evs):
        return [(n, max(s, w0), min(s + d, w1)) for n, s, d in evs
                if s < w1 and s + d > w0]

    if not tr["ops"] and not tr["modules"]:
        # no device in the trace (a CPU run): nothing here is a device number
        return {"window_s": (w1 - w0) * 1e-9, "busy_s": None,
                "kinds": {k: {"calls": 0, "device_s": 0.0} for k in MODULE_KINDS},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    ops = clip(tr["ops"] or tr["modules"])
    busy = _union([(s, e) for _, s, e in ops])
    busy_ns = sum(e - s for s, e in busy)

    kinds = {k: {"calls": 0, "device_s": 0.0} for k in MODULE_KINDS}
    for name, s, e in clip(tr["modules"]):
        for kind, pat in MODULE_KINDS.items():
            if pat.match(name):
                kinds[kind]["calls"] += 1
                kinds[kind]["device_s"] += (e - s) * 1e-9

    by_op = collections.Counter()
    for name, s, e in ops:
        if not CONTAINER.match(name):
            by_op[short_name(name)] += (e - s) * 1e-9
    device_ops = [[n, t] for n, t in by_op.most_common(top)]

    # idle gaps, each named by the innermost host annotation it began in
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted((s, s + d, n) for n, s, d in tr["host"] if n != "bench.window")

    def doing(t):
        inside = [(e - s, n) for s, e, n in host if s <= t < e]
        return min(inside)[1] if inside else "scheduler host code"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    idle = [[doing(s), (e - s) * 1e-9] for s, e in longest]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "kinds": kinds,
        "breakdown": {"device_ops": device_ops, "idle_gaps": idle},
    }
