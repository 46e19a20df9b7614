"""Per acknowledged batch, the summed time of the program's
``engine.merge_buffered`` spans: splicing the buffered edge updates into
the flat adjacency, for the planner and for the structure rebuild."""
from bench import spanreads


def read(run):
    return spanreads.per_unit_ms(run, "engine.merge_buffered")
