"""The whole step's percent of the chips' peak: the operations the traced
steps require (``flops.py``), over the traced window's seconds, the chips
and the chip's bf16 peak (``peaks.json``)."""


def read(run):
    trace = run.trace
    if trace is None or not trace.devices or run.peak_flops is None:
        return None
    return 100.0 * (run.flops_per_step * trace.steps
            / (trace.window_s * run.chips * run.peak_flops))
