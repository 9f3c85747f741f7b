"""Device milliseconds a step of the distribution layer's pulls: the busy
time owned by operations under ``zero.pull.*`` and ``zero.regather.*``
(the all-gathers while no compute runs beside them, their waits, and the
unpacking of the gathered buffers), mean over devices."""

from chipbench.trace import part_ms


def read(run):
    return part_ms(run.trace, "pull")
