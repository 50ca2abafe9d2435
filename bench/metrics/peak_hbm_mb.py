"""Peak device memory after the window, ``memory_stats()["peak_bytes_in_use"]``
on the fullest chip, in 10**6 bytes."""


def read(run):
    return run.peak_bytes / 1e6 if run.peak_bytes > 0 else None
