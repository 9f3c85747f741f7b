"""The largest ``peak_bytes_in_use`` over the cell's devices, in GiB, read
after the window and before the reference runs."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 2 ** 30
