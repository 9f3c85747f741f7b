"""Device milliseconds a step of operations under no ``zero.*`` scope, own
or inherited (``hlo.op_names``): copies of the step's inputs, the
loss's mean, and any program but the step.  Mean over devices."""

from chipbench.trace import part_ms


def read(run):
    return part_ms(run.trace, "unscoped")
