"""Per decompose, the summed time of the program's ``resident.replay``
spans: the host replay of each chunk's planner charges."""
from bench import spanreads


def read(run):
    return spanreads.per_unit_ms(run, "resident.replay")
