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
  ``(name, start_ns, end_ns)``;
* ``scopes``: for each operation's HLO name (``%fusion.213``), its scope
  path, the ``op_name`` of the instruction's metadata in the compiled
  step's text (``jit(step)/shard_map/zero.bwd.L1/jvp(moe.dispatch)/lt``).
  The trace's own events carry HLO text without metadata, so the path is
  joined to them by name.

Operation names are shortened from the HLO text the trace carries to
``%name opcode kind result-type``.  A :class:`Trace` can also be read
from JSON, so a small hand-made one serves as a test fixture.  Interval
arithmetic for the readers is here too, and the split of the device's
busy time by scope (:func:`owned_time`, :func:`scope_ms`).
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import json
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from chipbench.hlo import op_names

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
    scopes: Dict[str, str] = dataclasses.field(default_factory=dict)

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
                   host=[tuple(o) for o in d["host"]],
                   scopes=d.get("scopes", {}))


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


def hlo_name(op_name: str) -> str:
    """``%fusion.49`` of ``%fusion.49 fusion kLoop f32[8]``."""
    return op_name.split(" ", 1)[0]


def load(path: str, step_text: Optional[str] = None) -> Trace:
    """The trace at ``path``; ``step_text``, the compiled step's HLO text,
    gives the operations their scopes."""
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
    trace = Trace(window=window, steps=len(steps),
                  devices=[inside(lines[OPS_LINE][d]) for d in devices],
                  async_ops=[inside(lines[ASYNC_LINE].get(d, []))
                             for d in devices],
                  host=sorted((o for o in inside(host) if o[0] != STEP_SPAN),
                              key=lambda o: o[1]))
    if step_text:
        join_scopes(trace, step_text)
    return trace


def join_scopes(trace: Trace, step_text: str) -> None:
    """Fill ``trace.scopes`` for the operations it holds, from the
    compiled step's HLO text (``hlo.op_names``)."""
    names = {hlo_name(o[0]) for ops in trace.devices + trace.async_ops
             for o in ops}
    trace.scopes = {k: v for k, v in op_names(step_text).items()
                    if k in names}


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


def owned_time(trace: Trace, device: int) -> Dict[str, float]:
    """The device's busy time (:func:`busy`) split among its operations,
    in ns by operation name: each instant belongs to the operation that
    started last among those running on the compute line, or where none
    runs, to the collective in flight that started last.  The values add
    up to the busy time."""
    events = [(a, b, 1, name) for name, a, b in trace.devices[device]]
    events += [(a, b, 0, name) for name, a, b in trace.async_ops[device]
               if is_collective(name)]
    lo, hi = trace.window
    events = sorted((max(a, lo), min(b, hi), rank, name)
                    for a, b, rank, name in events if min(b, hi) > max(a, lo))
    cuts = sorted({t for a, b, _, _ in events for t in (a, b)})
    out: Dict[str, float] = {}
    running: List[Tuple] = []      # (-rank, -start, end, name): owner first
    i = 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(events) and events[i][0] <= t0:
            a, b, rank, name = events[i]
            heapq.heappush(running, (-rank, -a, b, name))
            i += 1
        while running and running[0][2] <= t0:
            heapq.heappop(running)
        if running:
            name = running[0][3]
            out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_path(trace: Trace, name: str) -> List[str]:
    """The scopes of the operation ``name``, outermost first, each with
    JAX's transformation wrappers taken off (``transpose(jvp(moe.combine))``
    is ``moe.combine``); empty where the compiled step has none for it."""
    out = []
    for part in trace.scopes.get(hlo_name(name), "").split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part:
            out.append(part)
    return out


#: a ZeRO re-pull is pull work
_PARTS = {"regather": "pull"}


def step_part(path: Sequence[str]) -> str:
    """The part of the ZeRO step that a scope path lies in, by its
    outermost ``zero.*`` scope: ``fwd``, ``bwd``, ``opt``, ``pull``
    (``zero.pull.*`` and ``zero.regather.*``), ``push``; ``unscoped``
    under none."""
    for scope in path:
        if scope.startswith("zero."):
            part = scope.split(".")[1]
            return _PARTS.get(part, part)
    return "unscoped"


def scope_ms(trace: Optional[Trace],
             select: Callable[[List[str]], bool]) -> Optional[float]:
    """Milliseconds a step, mean over devices, of the busy time owned
    (:func:`owned_time`) by operations whose scope path ``select`` takes;
    None where the trace has no scopes or no operation is taken."""
    if trace is None or not trace.devices or not trace.scopes:
        return None
    total, found = 0.0, False
    for d in range(len(trace.devices)):
        for name, ns in owned_time(trace, d).items():
            if select(scope_path(trace, name)):
                total += ns
                found = True
    if not found:
        return None
    return total / len(trace.devices) / trace.steps * 1e-6


def part_ms(trace: Optional[Trace], part: str) -> Optional[float]:
    """:func:`scope_ms` of one part of the step (:func:`step_part`)."""
    return scope_ms(trace, lambda path: step_part(path) == part)


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
