"""Device milliseconds a step of the distribution layer's pushes: the busy
time owned by operations under ``zero.push.*`` (the reduce-scatters while
no compute runs beside them, their waits, and the packing and mean of the
gradients), mean over devices."""

from chipbench.trace import part_ms


def read(run):
    return part_ms(run.trace, "push")
