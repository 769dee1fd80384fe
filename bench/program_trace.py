"""The serving program's own spans and device scopes in a profiler trace.

The program marks its host phases with ``jax.profiler.TraceAnnotation``s
(``serve.*`` in the scheduler, ``engine.*`` in the engine) and its device
work with ``jax.named_scope``s (``SCOPES``), so a profile of a live server
carries them on the same clock as the device operations. ``trace.py``
reduces a trace to busy time, step times and a breakdown; this module reads
the same trace for the program's spans and scopes.

``extract`` reads the ``.xplane.pb`` once and returns ``trace.extract``'s
dict (so ``trace.reduce`` takes it as it is) with one more key:

  spans      [name, start_ns, dur_ns, thread, stats] of every ``serve.*``,
             ``engine.*`` and ``bench.*`` annotation; ``thread`` numbers the
             host line it ran on, ``stats`` holds its metadata (``rows``)

A v5e trace's op events carry no scope path (their stats are only times),
so scopes come from the decode program's compiled HLO text (``hlo_scopes``),
matched to op events by instruction name (``%fusion.5 = ...`` is
``fusion.5``).

``reduce`` turns a trace and that text into numbers, each clipped to the
``bench.window`` annotation:

  spans      per program span name: count, total seconds and self seconds
             (the span less what its program-span children cover)
  scopes     device seconds of the decode program's operations per scope
             (``hlo_scopes``); ``None`` collects the rest
  idle_gaps  the longest idle gaps, each named by the innermost annotation
             of either kind (program span or ``bench.*``) it began in
  idle_by_gap_name  idle seconds of every gap, summed by that name
  idle_by_span  idle seconds per innermost annotation over each stretch of
             every gap, so a gap that begins in one span and lasts through
             others is split among them
  and the four per-step numbers ``tick_self_ms``, ``decode_host_ms``,
  ``decode_attn_ms`` and ``decode_kv_ms`` (None where the trace lacks what
  they read).
"""
from __future__ import annotations

import bisect
import collections
import glob
import heapq
import re

from bench import trace

SCOPES = ("kv_pages", "proj", "attention", "mlp", "logits")
PROGRAM = ("serve.", "engine.")
ANNOTATIONS = PROGRAM + ("bench.",)
OUTSIDE = "scheduler host code"  # an instant no annotation covers
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_PATH = re.compile(r'op_name="([^"]*)"')


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    out = {"ops": [], "modules": [], "spans": []}
    device, thread = None, 0
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None:
            device = plane.name
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    out[key] += [[e.name, e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [[e.name, e.start_ns, e.duration_ns, thread,
                                  dict(e.stats)]
                                 for e in line.events if e.name.startswith(ANNOTATIONS)]
                thread += 1
    out["host"] = [s[:3] for s in out["spans"] if s[0].startswith("bench.")]
    out["device_plane"] = device
    out["planes"] = {p.name: [l.name for l in p.lines] for p in pd.planes}
    return out


def instr_name(op: str) -> str:
    """An op event's HLO instruction name (``%fusion.5 = ...`` -> ``fusion.5``)."""
    return op.split(" = ")[0].strip().lstrip("%")


def scope_of(path: str | None) -> str | None:
    """The first of ``SCOPES`` on an op_name path. A transform frame wraps
    the scope it was applied in (``vmap(kv_pages)``), so its inside counts."""
    for part in (path or "").split("/"):
        inner = part[part.find("(") + 1:part.rfind(")")] if part.endswith(")") else part
        if inner in SCOPES:
            return inner
    return None


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction name: scope} of a compiled HLO module's text.

    An instruction's scope is the first of ``SCOPES`` on its op_name path;
    a fusion carries the path of its root instruction, so it counts where
    its root does. XLA leaves some instructions with no path (a convert it
    split out of a fusion) or with one that names no scope (the layer
    scan's slice of its per-layer inputs, ``while/body/squeeze``): such an
    instruction takes the scope of the nearest instruction that uses its
    result and has one (breadth first over the users, in text order), so
    the work counts under the sublayer it feeds. One whose users lead to no
    scope has none."""
    scope, users, order = {}, collections.defaultdict(list), []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        path = _PATH.search(rhs)
        scope[name] = scope_of(path.group(1) if path else None)
        order.append(name)
        # every %name before the metadata: operands, and called computations,
        # which no instruction is named after
        for operand in set(re.findall(r"%([\w.\-]+)", rhs.split(" metadata=")[0])):
            users[operand].append(name)
    out = {}
    for name in order:
        found, seen, frontier = scope[name], {name}, [name]
        while found is None and frontier:
            nxt = [u for n in frontier for u in users[n] if u not in seen]
            seen.update(nxt)
            found = next((scope[u] for u in nxt if scope[u]), None)
            frontier = nxt
        out[name] = found
    return out


def _window(tr):
    win = [s for s in tr["spans"] if s[0] == "bench.window"]
    if not win:
        raise ValueError("the trace has no bench.window annotation")
    return win[0][1], win[0][1] + win[0][2]


def _clipped(iv, w0, w1):
    return max(min(iv[1], w1) - max(iv[0], w0), 0)


def span_times(spans, w0: int, w1: int) -> dict:
    """Per program span name: count, total and self seconds in [w0, w1).
    A span's children are the program spans it contains on its own thread;
    ``bench.*`` annotations in between are looked through."""
    prog = sorted((s for s in spans if s[0].startswith(PROGRAM)),
                  key=lambda s: (s[3], s[1], -s[2]))
    children = collections.defaultdict(list)
    stack: list[int] = []
    for i, (_, s0, d, th, _) in enumerate(prog):
        while stack and (prog[stack[-1]][3] != th
                         or prog[stack[-1]][1] + prog[stack[-1]][2] <= s0):
            stack.pop()
        if stack:
            children[stack[-1]].append(i)
        stack.append(i)
    out: dict = {}
    for i, (name, s0, d, _, _) in enumerate(prog):
        iv = (s0, s0 + d)
        total = _clipped(iv, w0, w1)
        if not total:
            continue
        inner = trace._union([(max(prog[j][1], s0), min(prog[j][1] + prog[j][2], s0 + d))
                              for j in children[i]])
        covered = sum(_clipped(c, max(w0, s0), min(w1, s0 + d)) for c in inner)
        o = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        o["count"] += 1
        o["total_s"] += total * 1e-9
        o["self_s"] += (total - covered) * 1e-9
    return out


def innermost_timeline(spans) -> list[tuple[float, float, str]]:
    """[(start, end, name)]: the host's time cut where any annotation starts
    or ends, each piece named by the innermost (the shortest) annotation
    that covers it, or ``OUTSIDE``; the first and last pieces are open."""
    evs = sorted((s, s + d, n) for n, s, d, *_ in spans if n != "bench.window" and d > 0)
    bounds = sorted({t for s, e, _ in evs for t in (s, e)})
    active: list = []  # heap of (duration, end, name); ended ones leave lazily
    pieces = [(float("-inf"), bounds[0] if bounds else float("inf"), OUTSIDE)]
    k = 0
    for t, nxt in zip(bounds, bounds[1:] + [float("inf")]):
        while k < len(evs) and evs[k][0] <= t:
            s, e, n = evs[k]
            heapq.heappush(active, (e - s, e, n))
            k += 1
        while active and active[0][1] <= t:
            heapq.heappop(active)
        pieces.append((t, nxt, active[0][2] if active else OUTSIDE))
    return pieces


def reduce(tr: dict, hlo: str | None = None, top: int = 10) -> dict:
    """The program's spans, and the decode program's scopes by ``hlo`` (its
    compiled text), in the window."""
    w0, w1 = _window(tr)
    spans = span_times(tr["spans"], w0, w1)
    out = {"spans": spans, "scopes": {}, "idle_gaps": [], "idle_by_gap_name": {},
           "idle_by_span": {},
           "tick_self_ms": None, "decode_host_ms": None,
           "decode_attn_ms": None, "decode_kv_ms": None}
    tick = spans.get("serve.tick")
    if tick and tick["count"]:
        out["tick_self_ms"] = tick["self_s"] / tick["count"] * 1e3
    disp = spans.get("engine.decode.dispatch")
    if disp and disp["count"] and "engine.decode.prepare" in spans:
        host = spans["engine.decode.prepare"]["total_s"] + disp["total_s"]
        out["decode_host_ms"] = host / disp["count"] * 1e3
    if not tr["ops"]:
        return out

    # device seconds per scope of the ops that ran inside a decode program run
    decode = trace.MODULE_KINDS["decode"]
    runs = sorted((s, s + d) for n, s, d in tr["modules"] if decode.match(n)
                  and s < w1 and s + d > w0)
    starts = [r[0] for r in runs]
    by_instr = hlo_scopes(hlo) if hlo else {}
    scopes = collections.Counter()
    for name, s, d in tr["ops"]:
        if trace.CONTAINER.match(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s + d > runs[i][1]:
            continue
        scopes[by_instr.get(instr_name(name))] += _clipped((s, s + d), w0, w1) * 1e-9
    out["scopes"] = dict(scopes)
    calls = len(runs)
    if calls and any(scopes[s] for s in SCOPES):
        out["decode_attn_ms"] = scopes["attention"] / calls * 1e3
        out["decode_kv_ms"] = scopes["kv_pages"] / calls * 1e3

    # idle gaps of the device, named by the host's innermost annotation
    busy = trace._union([(max(s, w0), min(s + d, w1)) for _, s, d in tr["ops"]
                         if s < w1 and s + d > w0])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    pieces = innermost_timeline(tr["spans"])
    piece_starts = [p[0] for p in pieces]
    named = [[pieces[bisect.bisect_right(piece_starts, s) - 1][2], (e - s) * 1e-9]
             for s, e in gaps]
    out["idle_gaps"] = sorted(named, key=lambda g: -g[1])[:top]
    by_start = collections.Counter()
    for name, sec in named:
        by_start[name] += sec
    out["idle_by_gap_name"] = dict(by_start.most_common())
    by_span = collections.Counter()
    for s, e in gaps:
        i = bisect.bisect_right(piece_starts, s) - 1
        while i < len(pieces) and pieces[i][0] < e:
            p0, p1, name = pieces[i]
            by_span[name] += (min(p1, e) - max(p0, s)) * 1e-9
            i += 1
    out["idle_by_span"] = dict(by_span.most_common())
    return out
