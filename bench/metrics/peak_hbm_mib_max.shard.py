"""The fullest chip's allocator peak: the largest ``peak_bytes_in_use`` of
the cell's chips after the window (``peak_hbm_mib`` reads the first chip's
alone).  ``None`` where the devices keep no allocator statistics."""


def read(run):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:run.cell["chips"]]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) / 2**20 if peaks else None
