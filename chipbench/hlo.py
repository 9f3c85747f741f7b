"""Collectives in a compiled step's HLO text, with their operand bytes,
and each instruction's scope path.

A copy of the parsing rules of the program's ``repro.analysis.hlo``, kept
here so that no later change to the program can change how the benchmark
counts:

* an instruction's result type may be a tuple, and TPU layouts carry
  parentheses (``{1,0:T(8,128)(2,1)}``), so a tuple type ends at its
  matching ``)``;
* an async ``-start``/``-done`` pair counts once, by its ``-start``;
* an operand given by bare name takes the bytes of its definition.

XLA's TPU backend compiles a reduce-scatter over the data axis as an
all-reduce of the whole operand and a slice, so a push is a reduce-scatter
or an all-reduce above ``SMALL_BYTES`` (the scalar loss mean is the small
all-reduce of every step).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
SMALL_BYTES = 1024

_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\s+([\w\-]+)\((.*)$")
_LEAF = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
_NAME = re.compile(r"^%?([\w.\-]+)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s.*\{\s*$")
_REF = re.compile(r"%[\w.\-]+")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|branch_computations|"
                     r"true_computation|false_computation)=\{?"
                     r"(%[\w.\-]+(?:\s*,\s*%[\w.\-]+)*)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _LEAF.findall(type_str):
        if dtype in DTYPE_BYTES:
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * DTYPE_BYTES[dtype]
    return total


def _matching(s: str, start: int) -> int:
    """Index of the ``)`` closing the ``(`` just before ``start``."""
    depth = 1
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(s)


def _split_top(s: str) -> List[str]:
    parts, depth, cur = [], 0, []
    for ch in s:
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if "".join(cur).strip():
        parts.append("".join(cur).strip())
    return parts


def collectives(text: str) -> Dict[str, List[int]]:
    """Per kind, the operand bytes of each collective in ``text``."""
    defs: Dict[str, str] = {}
    found = []
    for line in text.splitlines():
        d = _DEF.match(line)
        if not d:
            continue
        rhs = line[d.end():]
        if rhs.startswith("("):
            end = _matching(rhs, 1)
            rtype, rest = rhs[:end + 1], rhs[end + 1:]
        else:
            rtype, _, rest = rhs.partition(" ")
            rest = " " + rest
        m = _OPCODE.match(rest)
        if not m:
            continue
        defs[d.group(1)] = rtype
        opcode = m.group(1)
        if opcode.endswith("-done"):
            continue
        base = opcode[:-len("-start")] if opcode.endswith("-start") else opcode
        if base in KINDS:
            args = m.group(2)
            found.append((base, _split_top(args[:_matching(args, 0)])))
    out: Dict[str, List[int]] = {k: [] for k in KINDS}
    for base, operands in found:
        total = 0
        for tok in operands:
            b = type_bytes(tok)
            if b == 0:
                n = _NAME.match(tok)
                if n and n.group(1) in defs:
                    b = type_bytes(defs[n.group(1)])
            total += b
        out[base].append(total)
    return out


def pulls_and_pushes(text: str) -> Dict[str, int]:
    """Counts and bytes of the ZeRO pulls (all-gathers) and pushes."""
    c = collectives(text)
    pushes = c["reduce-scatter"] + [b for b in c["all-reduce"]
                                    if b > SMALL_BYTES]
    return {"pulls": len(c["all-gather"]), "pull_bytes": sum(c["all-gather"]),
            "pushes": len(pushes), "push_bytes": sum(pushes),
            "small_all_reduces": sum(b <= SMALL_BYTES
                                     for b in c["all-reduce"])}


def op_names(text: str) -> Dict[str, str]:
    """The scope path of each instruction (``%name``) of an HLO module's
    text: the ``op_name`` of its metadata.  The compiler drops the
    metadata on some instructions it makes (a reduce-scatter rewritten as
    an all-reduce, a relayout loop, a copy); such an instruction takes the
    path of the first of its operands that has one, or else that of the
    instruction that calls its computation (a loop's body that of the
    loop).  Instructions are listed after their operands, as HLO text is."""
    own: Dict[str, Optional[str]] = {}
    home: Dict[str, str] = {}            # instruction -> its computation
    callers: Dict[str, str] = {}         # computation -> first caller
    operands: Dict[str, List[str]] = {}
    computation = ""
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            computation = c.group(1)
            continue
        d = _DEF.match(line)
        if not d:
            continue
        name, rhs = "%" + d.group(1), line[d.end():]
        rest = rhs[_matching(rhs, 1) + 1:] if rhs.startswith("(") \
            else rhs.partition(" ")[2]          # past the result type
        m = _OPCODE.match(" " + rest)
        args = m.group(2)[:_matching(m.group(2), 0)] if m else ""
        meta = _OP_NAME.search(rest)
        own[name] = meta.group(1) if meta else None
        home[name] = computation
        operands[name] = _REF.findall(args)
        for called in _CALLED.findall(rest):
            for callee in _REF.findall(called):
                callers.setdefault(callee, name)
    from_operands: Dict[str, Optional[str]] = {}
    for name in own:
        from_operands[name] = own[name] or next(
            (from_operands[o] for o in operands[name]
             if from_operands.get(o)), None)

    def resolved(name: str, seen=()) -> Optional[str]:
        if from_operands[name] or name in seen:
            return from_operands[name]
        caller = callers.get(home[name])
        return resolved(caller, seen + (name,)) if caller else None

    return {name: path for name in own
            for path in [resolved(name)] if path}
