"""The resident fixpoint's share of the device's memory roofline: the least
time the passes' bytes need at the peak HBM bandwidth, over the device time
of the chunk program.  The bytes are the reference SemiCore*'s, so they do
not depend on how the pass is implemented; the share is memory-bound (the
pass does no floating-point work)."""
from bench import readings
from bench.reference import work


def read(run):
    ns = readings.chunk_ns(run)
    done = len(run.units)
    if not ns or not done:
        return None
    _, frontiers = work.semicore_star(run.graph)
    least_s = (done * sum(work.pass_bytes(run.graph, frontiers))
               / readings.peak(run.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ns / 1e9)
