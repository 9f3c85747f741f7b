"""Milliseconds per step of collective time in which no other operation
runs on that device: the communication that compute does not hide, which
DynaComm's segmentation minimises.  Mean over devices."""

from chipbench.trace import collective_intervals, is_collective, length, minus


def read(run):
    trace = run.trace
    if trace is None or not trace.devices:
        return None
    exposed, any_comm = [], False
    for d, ops in enumerate(trace.devices):
        comm = collective_intervals(trace, d)
        any_comm = any_comm or bool(comm)
        compute = [(a, b) for name, a, b in ops if not is_collective(name)]
        exposed.append(length(minus(comm, compute)))
    if not any_comm:
        return None
    return sum(exposed) / len(exposed) / trace.steps * 1e-6
