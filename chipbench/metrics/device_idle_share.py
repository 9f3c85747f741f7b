"""Percent of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window.  With several
devices, the largest."""

from chipbench.trace import busy, length


def read(run):
    trace = run.trace
    if trace is None or not trace.devices:
        return None
    window = trace.window[1] - trace.window[0]
    return 100.0 * max(1.0 - length(busy(trace, d)) / window
               for d in range(len(trace.devices)))
