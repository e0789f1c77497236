"""Peak bytes in use on the fullest chip (memory_stats), a size record."""


def read(run):
    return run.memory_peak_bytes
