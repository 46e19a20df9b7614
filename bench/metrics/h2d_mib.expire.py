"""Per acknowledged batch, the bytes of host arrays the resident runner
turned into device arrays (edge table and node state), in MiB, from the
program's ``repro_resident_h2d_bytes_total`` counter."""
from bench import spanreads


def read(run):
    total = spanreads.counter_delta(run, "repro_resident_h2d_bytes_total")
    if total is None or not run.units:
        return None
    return total / 2**20 / len(run.units)
