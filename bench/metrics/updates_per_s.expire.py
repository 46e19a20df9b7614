"""Ops of the acknowledged batches over the window's time."""
from bench import readings


def read(run):
    return readings.ops_per_s(run)
