"""Mean number of decoding slots per decode step (page pool occupancy as
the decode step sees it)."""


def read(run):
    steps = run.rec.of("decode")
    return sum(s.rows for s in steps) / len(steps) if steps else None
