"""``repro.analysis`` — static analysis for the DynaComm reproduction.

Two layers:

* **IR analyzers** (:mod:`repro.analysis.hlo`,
  :mod:`repro.analysis.conformance`) — a structured HLO-text walker and
  the schedule-conformance passes proving a compiled step contains
  exactly the collectives its ``BucketPlan`` prescribes, with operand
  and wire bytes matching the ``FlatSpec``/``Compressor`` byte math;
* **AST lints** (:mod:`repro.analysis.lints`) — repo-specific
  determinism hazards (unseeded RNG, wall-clock in event loops,
  order-sensitive param-tree walks, hard-coded Pallas ``interpret=``,
  deprecated import aliases).

CLI: ``python -m repro.analysis lint src/`` and
``python -m repro.analysis verify --config <runtime config>``.

This package deliberately imports neither jax nor numpy at the top
level (``repro.analysis.runtime_verify``, which drives a built runtime,
is imported lazily by the CLI), so lints and fixture-based conformance
stay usable in import-light contexts.
"""

from repro.analysis.conformance import (expected_ag_bytes,
                                        expected_ar_bytes,
                                        expected_rs_bytes,
                                        independent_wire_bytes,
                                        segment_wire_bytes, verify_cache,
                                        verify_fleet_membership,
                                        verify_no_collectives,
                                        verify_push_ledger,
                                        verify_schedule, verify_wire_model)
from repro.analysis.findings import (Finding, findings_to_json,
                                     render_findings)
from repro.analysis.hlo import (COLLECTIVES, DTYPE_BYTES, HloInstruction,
                                HloModule, collective_counts,
                                collective_summary, parse_hlo,
                                segment_counts, type_bytes)
from repro.analysis.lints import (LINT_CODES, LintConfig, lint_file,
                                  lint_paths, lint_source)

__all__ = [
    "COLLECTIVES", "DTYPE_BYTES", "Finding", "HloInstruction", "HloModule",
    "LINT_CODES", "LintConfig", "collective_counts", "collective_summary",
    "expected_ag_bytes", "expected_ar_bytes", "expected_rs_bytes",
    "findings_to_json",
    "independent_wire_bytes", "lint_file", "lint_paths", "lint_source",
    "parse_hlo", "render_findings", "segment_counts", "segment_wire_bytes",
    "type_bytes",
    "verify_cache", "verify_fleet_membership", "verify_no_collectives",
    "verify_push_ledger", "verify_schedule", "verify_wire_model",
]
