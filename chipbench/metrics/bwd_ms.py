"""Device milliseconds a step of the backward pass: the busy time owned by
operations under a ``zero.bwd.L*`` scope (each layer's VJP, its forward
recomputed inside it), mean over devices."""

from chipbench.trace import part_ms


def read(run):
    return part_ms(run.trace, "bwd")
