"""Per acknowledged batch, the time inside the program's
``maintenance.parallel_settle`` spans that its ``resident.chunk`` spans do
not cover: the settle's host work (applying the ops, planning, the
structure rebuild and upload) and whatever waits between the chunks."""
from bench import spanreads


def read(run):
    return spanreads.uncovered_per_unit_ms(
        run, "maintenance.parallel_settle", "resident.chunk")
