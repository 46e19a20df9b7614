"""Device time of the collectives inside the sharded chunk program (its
all_gathers and the convergence psum), averaged over the chips' device
planes, over the passes the window's decomposes ran."""
from bench import collectives, readings


def read(run):
    lo, hi = run.window_ns
    ns = collectives.collective_ns(
        [e for e in run.ops if lo <= e.start_ns <= hi],
        readings.CHUNK_PROGRAM)
    p = readings.passes(run)
    if not ns or not p:
        return None
    return ns / p / 1e6
