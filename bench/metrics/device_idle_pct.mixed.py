"""Share of the maintenance window in which no operation ran on the device,
from the profiler trace."""
from bench import readings


def read(run):
    return readings.idle_pct(run)
