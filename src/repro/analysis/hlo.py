"""Structured HLO-text parser (layer 1 of ``repro.analysis``).

Promotes the regex scraping that used to live in
``repro.launch.hlo_analysis`` (and was copy-pasted across the slow-test
helpers) into a typed walk: every instruction definition becomes an
:class:`HloInstruction` with opcode, result type, and operand edges, and
the module knows how to resolve a bare operand name back to its
definition so operand-byte accounting works for both printer styles XLA
uses (bare ``%name`` operands vs inline-typed
``f32[2,128]{1,0} %name``).

Collective accounting rules fixed here (previously subtly wrong):

* async ``-start`` / ``-done`` pairs count **once** — the ``-start``
  carries the operand, the ``-done`` only consumes the start's tuple and
  is skipped entirely;
* tuple-typed operands (and tuple-typed defs a bare operand resolves to)
  sum **all** leaves.

A DynaComm segment is counted by the scope its collectives carry in
their ``op_name`` metadata (``zero.pull.b{i}``, ``zero.regather.b{i}``,
``zero.push.b{i}``), not by the collectives themselves: a segment of
sharded parameter leaves moves one collective per leaf
(:func:`segment_counts`).

Pure stdlib on purpose: parsing an HLO dump must not import jax, so the
lint/verify CLI and the golden-fixture tests stay import-light.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple, Union

__all__ = [
    "COLLECTIVES", "DTYPE_BYTES", "HloInstruction", "HloModule",
    "parse_hlo", "type_bytes", "collective_counts", "collective_summary",
    "segment_counts",
]

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# "  %name = dtype[dims]{layout} opcode(operands...), attrs" — tuple-typed
# results allowed; ROOT prefix optional.  TPU layouts carry parentheses
# (``{1,0:T(8,128)(2,1)}``), so a tuple type ends at its *matching* ')'.
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s+(?P<opcode>[\w\-]+)\((?P<rest>.*)$")
_LEAF_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
_NAME_RE = re.compile(r"^%?([\w.\-]+)$")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SEGMENT_RE = re.compile(r"(?:^|/)zero\.(pull|regather|push)\.b(\d+)(?:/|$)")


def type_bytes(type_str: str) -> int:
    """Bytes of an HLO type string; tuple types sum **all** leaves.

    E.g. ``'bf16[8,128]{1,0}'`` → 2048, ``'(f32[4], f32[8])'`` → 48.
    Unknown dtypes (and token/opaque leaves) contribute zero.
    """
    total = 0
    for dtype, dims in _LEAF_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _split_top_level(s: str) -> List[str]:
    """Split a comma-separated list at depth 0 of ``()[]{}`` nesting."""
    parts: List[str] = []
    depth = 0
    cur: List[str] = []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        parts.append(tail)
    return parts


def _split_type(s: str) -> Optional[Tuple[str, str]]:
    """``(result type, remainder)`` of a definition's right-hand side."""
    if not s.startswith("("):
        head, _, tail = s.partition(" ")
        return head, " " + tail
    depth = 0
    for i, ch in enumerate(s):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return s[:i + 1], s[i + 1:]
    return None


def _operand_region(rest: str) -> str:
    """The operand list of ``opcode(<rest>`` up to its matching ')'
    (everything after it is attributes like ``replica_groups={...}``)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


@dataclasses.dataclass(frozen=True)
class HloInstruction:
    """One instruction definition line of an HLO module dump."""

    name: str
    result_type: str
    opcode: str
    operands: Tuple[str, ...]      # raw operand tokens, attrs stripped
    line: int                      # 1-based line number in the dump
    is_root: bool = False
    op_name: str = ""              # the metadata's scope path, if any

    @property
    def base_opcode(self) -> str:
        """Opcode with any async ``-start``/``-done`` suffix stripped."""
        for suffix in ("-start", "-done"):
            if self.opcode.endswith(suffix):
                return self.opcode[:-len(suffix)]
        return self.opcode

    @property
    def is_async_done(self) -> bool:
        return self.opcode.endswith("-done")

    @property
    def is_collective(self) -> bool:
        """True for the collective op itself; ``-done`` halves are not
        (they only consume the ``-start`` tuple — counting both would
        double-count the pair)."""
        return self.base_opcode in COLLECTIVES and not self.is_async_done

    def operand_names(self) -> Tuple[str, ...]:
        """Bare instruction names referenced by the operand tokens."""
        names = []
        for tok in self.operands:
            m = _NAME_RE.match(tok.split()[-1]) if tok else None
            if m:
                names.append(m.group(1))
        return tuple(names)


@dataclasses.dataclass(frozen=True)
class HloModule:
    """All instruction definitions of an HLO dump, with name resolution."""

    instructions: Tuple[HloInstruction, ...]
    by_name: Dict[str, HloInstruction]

    def find(self, opcode: str) -> Tuple[HloInstruction, ...]:
        """Instructions whose *base* opcode matches (``-done`` included)."""
        return tuple(i for i in self.instructions if i.base_opcode == opcode)

    def collectives(self) -> Tuple[HloInstruction, ...]:
        """Collective ops, each async pair counted once (via its -start)."""
        return tuple(i for i in self.instructions if i.is_collective)

    def operand_bytes(self, instr: HloInstruction) -> int:
        """Total bytes of an instruction's operands.

        Inline-typed operand tokens are read directly; bare ``%name``
        tokens resolve against the definition map (tuple-typed defs sum
        all leaves).  Unresolvable tokens (literals, parameters of
        called computations) contribute zero.
        """
        total = 0
        for tok in instr.operands:
            b = type_bytes(tok)
            if b == 0:
                m = _NAME_RE.match(tok)
                if m and m.group(1) in self.by_name:
                    b = type_bytes(self.by_name[m.group(1)].result_type)
            total += b
        return total


def parse_hlo(text: str) -> HloModule:
    """Parse an HLO module dump (``compiled.as_text()``) line-by-line."""
    instructions: List[HloInstruction] = []
    by_name: Dict[str, HloInstruction] = {}
    for lineno, line in enumerate(text.splitlines(), start=1):
        d = _DEF_RE.match(line)
        typed = _split_type(line[d.end():]) if d else None
        m = _OPCODE_RE.match(typed[1]) if typed else None
        if not m:
            continue
        operands = tuple(_split_top_level(_operand_region(m.group("rest"))))
        op_name = _OP_NAME_RE.search(line)
        instr = HloInstruction(
            name=d.group("name"), result_type=typed[0],
            opcode=m.group("opcode"), operands=operands, line=lineno,
            is_root=line.lstrip().startswith("ROOT"),
            op_name=op_name.group(1) if op_name else "")
        instructions.append(instr)
        by_name[instr.name] = instr
    return HloModule(instructions=tuple(instructions), by_name=by_name)


ModuleOrText = Union[HloModule, str]


def _as_module(m: ModuleOrText) -> HloModule:
    return m if isinstance(m, HloModule) else parse_hlo(m)


def collective_counts(module_or_text: ModuleOrText) -> Dict[str, int]:
    """Per-kind collective counts (all kinds present, zeros included);
    async pairs count once."""
    module = _as_module(module_or_text)
    counts = {k: 0 for k in COLLECTIVES}
    for instr in module.collectives():
        counts[instr.base_opcode] += 1
    return counts


def collective_summary(module_or_text: ModuleOrText
                       ) -> Dict[str, List[Tuple[HloInstruction, int]]]:
    """Per-kind list of ``(instruction, operand_bytes)`` for every
    collective (async pairs once, via the ``-start``)."""
    module = _as_module(module_or_text)
    out: Dict[str, List[Tuple[HloInstruction, int]]] = \
        {k: [] for k in COLLECTIVES}
    for instr in module.collectives():
        out[instr.base_opcode].append((instr, module.operand_bytes(instr)))
    return out


def segment_counts(module_or_text: ModuleOrText) -> Tuple[int, int]:
    """``(pulls, pushes)``: the distinct segment scopes that hold a
    collective of their kind — ``zero.pull.b{i}`` and
    ``zero.regather.b{i}`` an all-gather, ``zero.push.b{i}`` a
    reduce-scatter or all-reduce — by each collective's ``op_name``.
    However many collectives a segment moves, it counts once; a step with
    no collective (one device) reads ``(0, 0)``."""
    kinds = {"pull": ("all-gather",), "regather": ("all-gather",),
             "push": ("reduce-scatter", "all-reduce")}
    seen = set()
    for instr in _as_module(module_or_text).collectives():
        m = _SEGMENT_RE.search(instr.op_name)
        if m and instr.base_opcode in kinds[m.group(1)]:
            seen.add(m.groups())
    pulls = sum(1 for part, _ in seen if part != "push")
    return pulls, len(seen) - pulls
