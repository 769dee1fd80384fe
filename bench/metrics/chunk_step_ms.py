"""Device milliseconds per prefill chunk step: the chunk program's time on
the device in the trace, over its runs."""


def read(run):
    k = run.trace and run.trace["kinds"]["chunk"]
    return k["device_s"] / k["calls"] * 1e3 if k and k["calls"] else None
