"""Milliseconds the host takes to hand one step to the device: the median
duration of the program's ``repro.dispatch`` spans (``ZeroRuntime.step``,
around the call of the jitted step) that lie inside the traced window.
A chip reading: nothing on a trace without a device plane or without
those spans."""

import statistics

SPAN = "repro.dispatch"


def read(run):
    trace = run.trace
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window
    spans = [b - a for name, a, b in trace.host
             if name == SPAN and a >= lo and b <= hi]
    if not spans:
        return None
    return statistics.median(spans) * 1e-6
