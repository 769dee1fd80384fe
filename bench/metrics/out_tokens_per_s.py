"""Output tokens the host received inside the window, over the window's
seconds (first tokens and decoded tokens alike)."""


def read(run):
    a, b = run.start_s, run.start_s + run.seconds
    n = sum(1 for toks in run.rec.tokens.values() for t, _ in toks if a < t <= b)
    return n / run.seconds
