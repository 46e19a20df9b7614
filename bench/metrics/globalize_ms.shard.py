"""Per decompose, the summed time of the program's ``resident.globalize``
spans: the host's reassembly of the per-shard frontier and cnt slices."""
from bench import spanreads


def read(run):
    return spanreads.per_unit_ms(run, "resident.globalize")
