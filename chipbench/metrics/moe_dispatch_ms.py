"""Device milliseconds a step of the routed layer's token movement: the
busy time owned by operations under ``moe.dispatch`` or ``moe.combine``
(scatter into the experts' buffers and gather back) inside the forward
or backward pass, mean over devices."""

from chipbench.trace import scope_ms, step_part

SCOPES = ("moe.dispatch", "moe.combine")


def read(run):
    return scope_ms(run.trace, lambda path: step_part(path) in ("fwd", "bwd")
                    and any(s in SCOPES for s in path))
