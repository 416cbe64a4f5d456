"""Peak device memory in GiB: the largest ``peak_bytes_in_use`` over the
cell's chips, read after the window and before the checks that follow it."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2 ** 30
