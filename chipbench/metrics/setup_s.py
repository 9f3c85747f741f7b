"""Seconds from process start to the first timed step: loading, the
weights made on the device, the ring of batches, compiling or loading
every program from the cache, and the checked first steps."""


def read(run):
    return run.setup_s
