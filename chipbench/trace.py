"""From a profiler trace to the neutral record the metric readers use.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` reduces it to a
:class:`Trace`:

* ``window``: from the start of the first traced step to the end of the
  last, the steps being the harness's ``StepTraceAnnotation`` spans;
* ``devices``: for each device plane (``/device:TPU:<n>``), the
  operations of its ``XLA Ops`` line as ``(name, start_ns, end_ns)``;
* ``async_ops``: the same for its ``Async XLA Ops`` line, where each
  asynchronous operation (a copy, a collective) is one event from its
  start to its done;
* ``host``: the host's named spans on the thread that drives the steps,
  ``(name, start_ns, end_ns)``.

Operation names are shortened from the HLO text the trace carries to
``%name opcode kind result-type``.  A :class:`Trace` can also be read
from JSON, so a small hand-made one serves as a test fixture.  Interval
arithmetic for the readers is here too.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Op = Tuple[str, float, float]

STEP_SPAN = "train"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")


@dataclasses.dataclass
class Trace:
    window: Interval
    steps: int
    devices: List[List[Op]]
    async_ops: List[List[Op]]
    host: List[Op]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(window=tuple(d["window"]), steps=d["steps"],
                   devices=[[tuple(o) for o in ops] for ops in d["devices"]],
                   async_ops=[[tuple(o) for o in ops]
                              for ops in d["async_ops"]],
                   host=[tuple(o) for o in d["host"]])


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


_OPCODE = re.compile(r"([a-z][\w\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def short_name(text: str) -> str:
    """``%fusion.49 fusion kLoop (f32[60821504], ...)`` from the HLO text
    of an operation; a name that is not HLO text is kept as it is."""
    name, sep, rhs = text.partition(" = ")
    if not sep:
        return text
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rtype, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        rtype, _, rest = rhs.partition(" ")
    rtype = re.sub(r"\{[^{}]*\}", "", re.sub(r"\{[^{}]*\}", "", rtype))
    m = _OPCODE.search(rest)
    kind = _KIND.search(rest)
    parts = [name, m.group(1) if m else "?"]
    if kind:
        parts.append(kind.group(1))
    parts.append(rtype if len(rtype) <= 60 else rtype[:57] + "...")
    return " ".join(parts)


def _events(line, shorten=False):
    for e in line.events:
        start = float(e.start_ns)
        name = short_name(e.name) if shorten else e.name
        yield name, start, start + float(e.duration_ns)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines: Dict[str, Dict[int, List[Op]]] = {OPS_LINE: {}, ASYNC_LINE: {}}
    steps: List[Op] = []
    host: List[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name][int(m.group(1))] = sorted(
                        _events(line, shorten=True), key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ops = list(_events(line))
                mine = [o for o in ops if o[0] == STEP_SPAN]
                if mine:
                    steps, host = mine, ops
    if not steps:
        raise ValueError(f"no {STEP_SPAN!r} step spans in {path}")
    window = (min(s[1] for s in steps), max(s[2] for s in steps))

    def inside(ops):
        return [o for o in ops if o[2] > window[0] and o[1] < window[1]]

    devices = sorted(lines[OPS_LINE])
    return Trace(window=window, steps=len(steps),
                 devices=[inside(lines[OPS_LINE][d]) for d in devices],
                 async_ops=[inside(lines[ASYNC_LINE].get(d, []))
                            for d in devices],
                 host=sorted((o for o in inside(host) if o[0] != STEP_SPAN),
                             key=lambda o: o[1]))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Sequence[Interval], clip: Optional[Interval] = None
          ) -> List[Interval]:
    """Sorted disjoint union of ``intervals``, cut to ``clip``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if clip is not None:
            a, b = max(a, clip[0]), min(b, clip[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def minus(base: Sequence[Interval], cut: Sequence[Interval]
          ) -> List[Interval]:
    """Parts of the disjoint sorted ``base`` not covered by ``cut``."""
    cut = union(cut)
    out, j = [], 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def collective_intervals(trace: Trace, device: int) -> List[Interval]:
    """When the device is in a collective: each asynchronous one from its
    start to its done (its ``Async XLA Ops`` event), each synchronous one
    as it runs."""
    out = [(a, b) for name, a, b in trace.async_ops[device]
           if is_collective(name)]
    out += [(a, b) for name, a, b in trace.devices[device]
            if is_collective(name)]
    return union(out, trace.window)


def busy(trace: Trace, device: int) -> List[Interval]:
    """When an operation runs on the device, collectives in flight
    included."""
    ops = [(a, b) for _, a, b in trace.devices[device]]
    return union(ops + collective_intervals(trace, device), trace.window)


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------


def top_device_ops(trace: Trace, n: int = 10) -> List[List]:
    """Device seconds by operation name, summed over devices and averaged
    over them, largest first."""
    total: Dict[str, float] = {}
    for ops in trace.devices:
        for name, a, b in ops:
            a, b = max(a, trace.window[0]), min(b, trace.window[1])
            if b > a:
                total[name] = total.get(name, 0.0) + (b - a) * 1e-9
    chips = max(len(trace.devices), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / chips] for name, secs in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest idle gaps of device 0 in the window, each named by the
    innermost host span that covers its middle."""
    if not trace.devices:
        return []
    gaps = minus([trace.window], busy(trace, 0))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        covering = [o for o in trace.host if o[1] <= mid <= o[2]]
        label = min(covering, key=lambda o: o[2] - o[1])[0] if covering \
            else "no host span"
        out.append([label, (b - a) * 1e-9])
    return out
