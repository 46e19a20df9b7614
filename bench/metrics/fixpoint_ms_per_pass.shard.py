"""Device time of the sharded chunk program over the passes the window's
decomposes ran, averaged over the chips' device planes."""
from bench import readings


def read(run):
    return readings.fixpoint_ms_per_pass(run)
