"""Milliseconds per step that a device spends in collectives (all-gather,
all-reduce, reduce-scatter, ...; an async one from its start to its
done), as the union of those intervals, mean over devices."""

from chipbench.trace import collective_intervals, length


def read(run):
    trace = run.trace
    if trace is None or not trace.devices:
        return None
    per_device = [length(collective_intervals(trace, d))
                  for d in range(len(trace.devices))]
    if not any(per_device):
        return None
    return sum(per_device) / len(per_device) / trace.steps * 1e-6
