"""Post-compile HLO analysis: collective traffic + roofline terms.

``collective_bytes`` sums operand bytes of every all-gather / all-reduce
/ reduce-scatter / all-to-all / collective-permute — the quantity
``cost_analysis`` does not report.  The parsing lives in the structured
walker of :mod:`repro.analysis.hlo` (this module used to carry its own
regex scraper; ``repro.analysis`` promoted it, fixing the async
``-start``/``-done`` double count and tuple-operand leaf summing on the
way).  ``roofline`` combines collective bytes with HLO FLOPs/bytes into
the three terms of EXPERIMENTS.md §Roofline.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from repro.analysis.hlo import collective_summary
from repro.core.netmodel import (TPU_HBM_BW, TPU_ICI_BW_PER_LINK,
                                 TPU_PEAK_FLOPS_BF16)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind operand bytes summed over the module (per device),
    plus a ``"_counts"`` entry with per-kind instruction counts.

    Both XLA printer styles are handled (bare ``%name`` operands and
    inline-typed ``f32[1,16]{1,0} %name``); async ``-start``/``-done``
    pairs count once, tuple-typed operands sum all leaves.
    """
    summary = collective_summary(hlo_text)
    out: Dict[str, int] = {kind: sum(b for _, b in entries)
                           for kind, entries in summary.items()}
    out["_counts"] = {kind: len(entries)
                     for kind, entries in summary.items()}
    return out


@dataclasses.dataclass(frozen=True)
class Roofline:
    flops: float                  # per-device HLO FLOPs
    hbm_bytes: float              # per-device HLO bytes accessed
    coll_bytes: float             # per-device collective operand bytes
    coll_detail: Dict[str, int]
    chips: int

    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=lambda kv: terms[kv])

    @property
    def bound_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline(*, flops: float, hbm_bytes: float, coll: Dict[str, int],
             chips: int, peak_flops: float = TPU_PEAK_FLOPS_BF16,
             hbm_bw: float = TPU_HBM_BW,
             ici_bw: float = TPU_ICI_BW_PER_LINK) -> Roofline:
    """FLOPs/bytes from ``cost_analysis`` are PER-DEVICE for a partitioned
    module, so each term divides by a single chip's capability; ``chips``
    is retained for reporting."""
    coll_total = sum(v for k, v in coll.items() if not k.startswith("_"))
    return Roofline(
        flops=flops, hbm_bytes=hbm_bytes, coll_bytes=coll_total,
        coll_detail=coll, chips=chips,
        compute_s=flops / peak_flops,
        memory_s=hbm_bytes / hbm_bw,
        collective_s=coll_total / ici_bw,
    )
