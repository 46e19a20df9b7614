"""Device time of the resident fixpoint's chunk program over the settle
passes of the window's batches."""
from bench import readings


def read(run):
    return readings.fixpoint_ms_per_pass(run)
