"""The decode step's share of its roofline, in percent: the least time the
chip could take for the step's needed FLOPs and bytes (costs.py), summed
over the steps, over the decode program's device time in the trace."""
from bench import costs


def read(run):
    k = run.trace and run.trace["kinds"]["decode"]
    if not k or not k["calls"]:
        return None
    least = sum(costs.least_seconds(*costs.decode(run.m, s.positions, run.fam), run.peaks)
                for s in run.rec.of("decode"))
    return 100.0 * least / k["device_s"]
