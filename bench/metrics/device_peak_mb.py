"""Device memory: the allocator's peak over the run on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``), in MB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e6
