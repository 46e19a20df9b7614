"""The window's time over the decomposes it completed."""


def read(run):
    return run.window_s / len(run.units) if run.units else None
