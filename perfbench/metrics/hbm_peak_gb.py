"""`memory_stats()["peak_bytes_in_use"]` of the chip, read when the window closes and
before the reference runs."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
