"""Per acknowledged batch, the summed time of the program's ``maint.plan``
spans: the grouped settle's planning rounds (adjacency snapshot, candidate
sets and groups, the warm state)."""
from bench import spanreads


def read(run):
    return spanreads.per_unit_ms(run, "maint.plan")
