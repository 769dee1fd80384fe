"""95th percentile, over every request due in the window, of the time from
when it was due to when the host received its first token. A request that
got no first token by the end of the run counts with the time to that end,
which is less than it will take."""
import numpy as np


def read(run):
    first = run.rec.first_token_s()
    t = [first.get(it.rid, run.end_s) - it.due_s
         for it in run.items if run.start_s <= it.due_s < run.start_s + run.seconds]
    if len(t) < 200:
        return None
    return float(np.percentile(t, 95)) * 1e3
