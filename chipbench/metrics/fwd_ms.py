"""Device milliseconds a step of the forward pass: the busy time owned by
operations under a ``zero.fwd.L*`` scope (``trace.owned_time``), mean
over devices."""

from chipbench.trace import part_ms


def read(run):
    return part_ms(run.trace, "fwd")
