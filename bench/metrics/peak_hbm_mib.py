"""The device allocator's peak bytes in use, read after the window."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2**20
