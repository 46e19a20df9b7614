"""Device time of the resident fixpoint's chunk program over the passes the
window's decomposes ran."""
from bench import readings


def read(run):
    return readings.fixpoint_ms_per_pass(run)
