"""Process start to the window's start: imports, weights drawn from the
seed, compiled programs loaded from the cache (or compiled), warm-up."""


def read(run):
    return run.setup_s
