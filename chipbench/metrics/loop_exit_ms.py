"""Device milliseconds a step of a looped stack's exits: the busy time
owned by operations under ``loop.exit`` (every pass's final norm, head
and exit gate, and the exit objective), forward and backward, mean over
devices."""

from chipbench.trace import scope_ms


def read(run):
    return scope_ms(run.trace, lambda path: "loop.exit" in path)
