"""Per decompose, the MiB the sharded chunk programs' all_gathers return,
from the program's ``repro_shard_allgather_bytes_total``."""
from bench import spanreads


def read(run):
    b = spanreads.counter_delta(run, "repro_shard_allgather_bytes_total")
    if b is None or not run.units:
        return None
    return b / len(run.units) / 2**20
