"""The sharded layout's padding over its real edge slots, from the
program's ``repro_shard_slots_total`` of the structures the window built."""


def read(run):
    d = run.registry_delta
    real = d.get('repro_shard_slots_total{kind="real"}')
    pad = d.get('repro_shard_slots_total{kind="pad"}')
    if not real or pad is None:
        return None
    return 100.0 * pad / real
