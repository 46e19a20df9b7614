"""Traces of the resident jit programs in the window, a count, from the
program's ``repro_resident_traces_total`` counter: a retrace in the window
is a compile the batch waits for."""
from bench import spanreads


def read(run):
    return spanreads.counter_delta(run, "repro_resident_traces_total")
