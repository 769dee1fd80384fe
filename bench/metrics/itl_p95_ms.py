"""95th percentile of every gap between two consecutive output tokens of
one request, both received inside the window."""
import numpy as np


def read(run):
    lo, hi = run.start_s, run.start_s + run.seconds
    gaps = [b - a for toks in run.rec.tokens.values()
            for (a, _), (b, _) in zip(toks, toks[1:]) if lo < a and b <= hi]
    if len(gaps) < 200:  # fewer than ten beyond the 95th percentile
        return None
    return float(np.percentile(gaps, 95)) * 1e3
