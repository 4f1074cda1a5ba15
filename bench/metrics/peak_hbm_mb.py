"""Peak device memory after the window, in MB (1e6 bytes): the largest
``memory_stats()["peak_bytes_in_use"]`` over the devices."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 1e6
