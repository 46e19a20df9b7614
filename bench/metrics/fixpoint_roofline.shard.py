"""The sharded fixpoint's share of the mesh's memory roofline: the least
time the reference SemiCore*'s bytes need at the peak HBM bandwidth of all
the chips that ran the chunk program, over its device time a chip."""
from bench import devtrace, readings
from bench.reference import work


def read(run):
    ns = readings.chunk_ns(run)
    done = len(run.units)
    if not ns or not done:
        return None
    lo, hi = run.window_ns
    chips = devtrace.chips([
        e for e in run.ops if lo <= e.start_ns <= hi and str(
            e.stats.get("hlo_module", "")).startswith(readings.CHUNK_PROGRAM)])
    _, frontiers = work.semicore_star(run.graph)
    least_s = (done * sum(work.pass_bytes(run.graph, frontiers))
               / (chips * readings.peak(run.device_kind, "hbm_bytes_per_s")))
    return 100.0 * least_s / (ns / 1e9)
