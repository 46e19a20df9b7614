"""Share of the decompose window in which no operation ran on a chip,
averaged over the chips, from the profiler trace."""
from bench import readings


def read(run):
    return readings.idle_pct(run)
