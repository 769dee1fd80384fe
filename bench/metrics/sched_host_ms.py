"""Wall milliseconds per scheduler loop iteration spent outside the engine
calls and outside idle waits for arrivals: the scheduler's own host work."""


def read(run):
    spans = run.rec.spans
    iters = run.rec.iterations()
    if not spans or not iters:
        return None
    end = spans[-1].t1
    inside = run.rec.engine_seconds(end)
    waits = sum(min(b, end) - a for a, b in run.waits if a < end)
    return (end - inside - waits) / iters * 1e3
