"""Model FLOPs of the decode and chunk calls (from their shapes, at their
actual positions; see costs.py) over their device time times the chip's
peak, in percent."""
from bench import costs


def read(run):
    if not run.trace:
        return None
    k = run.trace["kinds"]
    dev = k["decode"]["device_s"] + k["chunk"]["device_s"]
    if dev <= 0:
        return None
    flops = sum(costs.decode(run.m, s.positions)[0] for s in run.rec.of("decode"))
    flops += sum(costs.chunk(run.m, s.rows, s.positions[0], s.tokens)[0]
                 for s in run.rec.of("chunk"))
    return 100.0 * flops / (dev * run.peaks["bf16_flops_per_s"])
