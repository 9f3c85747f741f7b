"""Device milliseconds a step of a looped stack's block applications: the
busy time owned by operations under a ``loop.t*`` scope (pass ``t`` of
the blocks, inside ``zero.fwd.L*`` or ``zero.bwd.L*``) and not under
``loop.exit``, both passes, mean over devices."""

from chipbench.trace import scope_ms


def read(run):
    return scope_ms(run.trace, lambda path: "loop.exit" not in path
                    and any(s.startswith("loop.t") for s in path))
