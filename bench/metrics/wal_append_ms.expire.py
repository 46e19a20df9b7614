"""Mean WAL append (write, flush and fsync) in the window, from the
program's ``repro_wal_append_seconds`` histogram."""
from bench import readings


def read(run):
    return readings.histogram_mean_ms(run, "repro_wal_append_seconds")
