"""Mean maintenance settle per batch in the window, from the program's
``repro_maintenance_settle_seconds`` histogram."""
from bench import readings


def read(run):
    return readings.histogram_mean_ms(run, "repro_maintenance_settle_seconds")
