"""Device milliseconds a step of the sharded optimizer: the busy time owned
by operations under the ``zero.opt`` scope, mean over devices.  Where XLA
fuses the update into the fusion that makes a gradient, that time is the
backward's."""

from chipbench.trace import part_ms


def read(run):
    return part_ms(run.trace, "opt")
