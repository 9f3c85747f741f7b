"""Tests for ``repro.analysis``: the structured HLO parser over golden
fixtures, schedule-conformance over all four scheduling strategies,
mutation self-tests (corrupted plans / tampered HLO / lying compressors
must be flagged), the AST determinism lints, and the CLI."""

import json
import os
import pathlib
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

from repro.analysis import (collective_counts, collective_summary,
                            lint_paths, lint_source, parse_hlo,
                            segment_counts, type_bytes,
                            verify_cache, verify_fleet_membership,
                            verify_no_collectives, verify_push_ledger,
                            verify_schedule, verify_wire_model)
from repro.analysis.conformance import (INT8_TILE, expected_ag_bytes,
                                        expected_ar_bytes,
                                        expected_rs_bytes,
                                        independent_wire_bytes,
                                        segment_wire_bytes)
from repro.analysis.findings import Finding, findings_to_json
from repro.analysis.lints import LintConfig
from repro.core import plan_from_decision, random_costs, schedule
from repro.core.buckets import BucketPlan

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"
FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "hlo"
CONFIGS = REPO / "examples" / "runtime_configs"

STRATEGIES = ("sequential", "lbl", "ibatch", "dynacomm")


def fixture(name):
    return (FIXTURES / name).read_text()


# ---------------------------------------------------------------------------
# HLO parser over golden fixtures (no compile)
# ---------------------------------------------------------------------------

class TestHloParser:

    def test_type_bytes(self):
        assert type_bytes("f32[2,3]{1,0}") == 24
        assert type_bytes("bf16[8,128]") == 2048
        assert type_bytes("f32[]") == 4
        assert type_bytes("pred[7]") == 7
        # tuple types sum all leaves
        assert type_bytes("(f32[4]{0}, f32[8]{0})") == 48
        assert type_bytes("(f32[2,2], s8[3])") == 19

    def test_inline_operands_fixture(self):
        mod = parse_hlo(fixture("inline_operands.txt"))
        counts = collective_counts(mod)
        assert counts == {"all-gather": 1, "all-reduce": 1,
                          "reduce-scatter": 2, "all-to-all": 0,
                          "collective-permute": 0}
        summary = collective_summary(mod)
        assert [b for _, b in summary["all-gather"]] == [4 * 721536]
        assert sorted(b for _, b in summary["reduce-scatter"]) == \
            [4 * 2 * 128, 4 * 2 * 721408]
        assert [b for _, b in summary["all-reduce"]] == [4]
        # non-collective instructions are parsed too
        assert mod.find("fusion")[0].name == "fusion.7"

    def test_bare_operands_resolved_via_defs(self):
        # the second printer style: operands are bare %names whose types
        # come from the defining instruction, even when defined later
        mod = parse_hlo(fixture("bare_operands.txt"))
        summary = collective_summary(mod)
        assert [b for _, b in summary["all-gather"]] == [4 * 16]
        assert [b for _, b in summary["reduce-scatter"]] == [4 * 4 * 8]
        assert [b for _, b in summary["all-reduce"]] == [4]

    def test_async_pairs_count_once(self):
        # -start carries the operand and counts; -done consumes the
        # start's tuple and must not double-count
        mod = parse_hlo(fixture("async_pairs.txt"))
        counts = collective_counts(mod)
        assert counts["all-gather"] == 1
        assert counts["reduce-scatter"] == 1
        assert counts["all-reduce"] == 1
        summary = collective_summary(mod)
        assert [b for _, b in summary["all-gather"]] == [4 * 64]
        assert [b for _, b in summary["reduce-scatter"]] == [4 * 4 * 32]
        assert [b for _, b in summary["all-reduce"]] == [4 * 2 * 2]
        done = [i for i in mod.instructions if i.is_async_done]
        assert len(done) == 3 and not any(i.is_collective for i in done)

    def test_segment_counts_by_scope(self):
        """A segment counts once however many collectives it moves, by
        the scope in their ``op_name``; a collective of the other kind,
        or under no segment scope, counts for none."""
        def op(name, kind, scope):
            return (f"  %{name} = f32[8]{{0}} {kind}(f32[4]{{0}} %p), "
                    f"metadata={{op_name=\"jit(step)/shard_map/{scope}\"}}")
        text = "\n".join([
            "HloModule m", "ENTRY %e (p: f32[4]) {",
            op("ag.1", "all-gather", "zero.pull.b0/all_gather"),
            op("ag.2", "all-gather", "zero.pull.b0/all_gather"),
            op("ag.3", "all-gather-start", "zero.pull.b1/all_gather"),
            op("ag.4", "all-gather", "zero.regather.b0/all_gather"),
            op("rs.1", "reduce-scatter", "zero.push.b0/reduce_scatter"),
            op("rs.2", "reduce-scatter", "zero.push.b0/reduce_scatter"),
            op("ar.1", "all-reduce", "zero.push.b1/psum"),
            op("ar.2", "all-reduce", "psum"),
            op("ag.5", "all-gather", "zero.push.b2/all_gather"),
            op("ag.6", "all-gather", "zero.fwd.L1/all_gather"),
            "  ROOT %p = f32[4] parameter(0)", "}"])
        assert parse_hlo(text).by_name["ag.1"].op_name == \
            "jit(step)/shard_map/zero.pull.b0/all_gather"
        assert segment_counts(text) == (3, 2)
        assert segment_counts(fixture("inline_operands.txt")) == (0, 0)

    def test_tpu_layouts_parsed(self):
        # TPU layouts carry parentheses ({0:T(1024)}, {1,0:T(8,128)(2,1)}),
        # inside plain and tuple result types alike
        mod = parse_hlo(fixture("tpu_layouts.txt"))
        counts = collective_counts(mod)
        assert counts["all-gather"] == 1 and counts["all-reduce"] == 2
        summary = collective_summary(mod)
        assert [b for _, b in summary["all-gather"]] == [4 * 15205376]
        assert sorted(b for _, b in summary["all-reduce"]) == \
            [4, 4 * 4 * 40372736]
        fusion = mod.find("fusion")[0]
        assert fusion.name == "fusion.493"
        assert type_bytes(fusion.result_type) == 2 * 2 * 16777216
        assert mod.find("tuple")[0].operands[1] == "%all-gather.17"

    def test_collective_bytes_contract(self):
        # launch.hlo_analysis.collective_bytes keeps its dict contract on
        # top of the structured walker
        from repro.launch.hlo_analysis import collective_bytes
        out = collective_bytes(fixture("async_pairs.txt"))
        assert out["all-gather"] == 4 * 64
        assert out["reduce-scatter"] == 4 * 4 * 32
        assert out["all-reduce"] == 16
        assert out["all-to-all"] == 0
        assert out["_counts"]["all-gather"] == 1
        assert out["_counts"]["reduce-scatter"] == 1


# ---------------------------------------------------------------------------
# schedule conformance over synthesized HLO (single process, no compile)
# ---------------------------------------------------------------------------

def fake_specs(num_layers, axis_size=2, base=256):
    """FlatSpec stand-ins: ``total`` deliberately not axis-aligned so
    ``padded`` differs, exercising the padded-vs-total distinction."""
    specs = []
    for l in range(num_layers):
        total = base * (l + 1) + 3
        padded = -(-total // axis_size) * axis_size
        specs.append(SimpleNamespace(total=total, padded=padded,
                                     axis_size=axis_size))
    return specs


def synth_hlo(specs, plan, *, zero3=False, extra_lines=()):
    """Emit module text with exactly the collectives the plan
    prescribes, using the empirically pinned operand shapes."""
    axis = specs[0].axis_size
    lines = ["HloModule synth", "", "ENTRY %main.1 (p: f32[1,1]) {"]
    n = 0

    def gather(bucket):
        nonlocal n
        n += 1
        shard = sum(specs[l].padded // axis for l in bucket)
        lines.append(
            f"  %all-gather.{n} = f32[{axis},{shard}] "
            f"all-gather(f32[1,{shard}] %concat.{n}), "
            f"replica_groups={{{{0,1}}}}, dimensions={{0}}")

    for bucket in plan.forward:
        gather(bucket)
    if zero3:
        num_layers = len(specs)
        for bucket in plan.backward:
            if any(0 < l < num_layers - 1 for l in bucket):
                gather(bucket)
    for bucket in plan.backward:
        n += 1
        shard = sum(specs[l].padded for l in bucket) // axis
        lines.append(
            f"  %reduce-scatter.{n} = f32[1,{shard}] "
            f"reduce-scatter(f32[{axis},{shard}] %grad.{n}), "
            f"replica_groups={{{{0,1}}}}, dimensions={{0}}, "
            f"to_apply=%sum")
    lines.append("  %all-reduce.loss = f32[] all-reduce(f32[] %l), "
                 "to_apply=%sum")
    lines.extend(extra_lines)
    lines.append("  ROOT %tuple.99 = f32[1,1] copy(f32[1,1] %p)")
    lines.append("}")
    return "\n".join(lines)


def fake_leaf_specs(num_layers, axis_size=2):
    """FlatSpec stand-ins with leaf shapes, for the leaves layout: per
    layer a matrix split on dimension 0, one whose rows the axis does not
    divide (split on dimension 1), and a ``(1,)`` bias held whole."""
    specs = []
    for l in range(num_layers):
        shapes = ((8 * (l + 1), 4), (3, 2 * (l + 2)), (1,))
        specs.append(SimpleNamespace(
            shapes=shapes, axis_size=axis_size,
            total=sum(a * b for a, b in shapes[:2]) + 1))
    return specs


def _f32(shape):
    return f"f32[{','.join(map(str, shape))}]"


def synth_leaves_hlo(specs, plan, *, zero3=False, extra_lines=()):
    """Module text with exactly the collectives a leaves state's pulls and
    pushes issue: an all-gather of each split leaf's shard per pull, a
    reduce-scatter of each split leaf's gradient and an all-reduce of
    each whole one per push."""
    axis = specs[0].axis_size
    lines = ["HloModule synth", "", "ENTRY %main.1 (p: f32[1,1]) {"]
    n = 0

    def split(shape):
        d = next((d for d, k in enumerate(shape) if k % axis == 0), None)
        if d is None:
            return None
        return d, tuple(k // axis if i == d else k
                        for i, k in enumerate(shape))

    def gather(bucket):
        nonlocal n
        for l in bucket:
            for shape in specs[l].shapes:
                if split(shape) is None:
                    continue
                d, shard = split(shape)
                n += 1
                lines.append(
                    f"  %all-gather.{n} = {_f32(shape)} all-gather("
                    f"{_f32(shard)} %p.{n}), replica_groups={{{{0,1}}}}, "
                    f"dimensions={{{d}}}")

    for bucket in plan.forward:
        gather(bucket)
    if zero3:
        for bucket in plan.backward:
            if any(0 < l < len(specs) - 1 for l in bucket):
                gather(bucket)
    for bucket in plan.backward:
        for l in bucket:
            for shape in specs[l].shapes:
                n += 1
                if split(shape) is None:
                    lines.append(
                        f"  %all-reduce.{n} = {_f32(shape)} all-reduce("
                        f"{_f32(shape)} %g.{n}), to_apply=%sum")
                    continue
                d, shard = split(shape)
                lines.append(
                    f"  %reduce-scatter.{n} = {_f32(shard)} reduce-scatter("
                    f"{_f32(shape)} %g.{n}), replica_groups={{{{0,1}}}}, "
                    f"dimensions={{{d}}}, to_apply=%sum")
    lines.append("  %all-reduce.loss = f32[] all-reduce(f32[] %l), "
                 "to_apply=%sum")
    lines.extend(extra_lines)
    lines.append("  ROOT %tuple.99 = f32[1,1] copy(f32[1,1] %p)")
    lines.append("}")
    return "\n".join(lines)


def plan_for(strat, num_layers=8):
    costs = random_costs(num_layers, seed=0, dt=1e-3)
    f, b = schedule(costs, strat)
    return plan_from_decision(f, b, num_layers)


def flat_wire():
    """A compressor: the trainer holds the flat wire only with one, and
    the conformance byte math follows it."""
    from repro.compress.compressor import make_compressor
    return make_compressor("int8")


class TestConformance:
    """The flat wire's byte math: one all-gather per forward bucket, one
    reduce-scatter per backward bucket (a compressor's step)."""

    @pytest.mark.parametrize("strat", STRATEGIES)
    @pytest.mark.parametrize("zero3", [False, True],
                             ids=["zero", "zero3"])
    def test_all_strategies_conform(self, strat, zero3):
        plan = plan_for(strat)
        specs = fake_specs(8)
        hlo = synth_hlo(specs, plan, zero3=zero3)
        assert verify_schedule(hlo, plan, specs, zero3=zero3,
                               compressor=flat_wire()) == []

    @pytest.mark.parametrize("strat", STRATEGIES)
    @pytest.mark.parametrize("scheme", ["int8", "topk"])
    def test_ps_wire_model_exact(self, strat, scheme):
        # the repo's own Compressor accounting must match the
        # independent byte formulas exactly, per backward segment
        from repro.compress.compressor import make_compressor
        kwargs = {"topk_fraction": 0.01} if scheme == "topk" else {}
        comp = make_compressor(scheme, **kwargs)
        plan = plan_for(strat)
        specs = fake_specs(8)
        assert verify_wire_model(specs, plan, comp) == []
        hlo = synth_hlo(specs, plan)
        assert verify_schedule(hlo, plan, specs, compressor=comp) == []

    def test_corrupted_plan_flagged(self):
        plan = plan_for("dynacomm")
        specs = fake_specs(8)
        hlo = synth_hlo(specs, plan)
        # merge the first two forward buckets: fewer gathers prescribed
        # than compiled, and the byte multiset shifts
        corrupted = BucketPlan(
            forward=(plan.forward[0] + plan.forward[1],)
            + plan.forward[2:],
            backward=plan.backward)
        findings = verify_schedule(hlo, corrupted, specs,
                                   compressor=flat_wire())
        assert findings
        assert {f.code for f in findings} <= {
            "SCHED-AG-COUNT", "SCHED-AG-BYTES"}

    def test_tampered_bytes_flagged(self):
        plan = plan_for("sequential")
        specs = fake_specs(8)
        hlo = synth_hlo(specs, plan)
        first_rs = next(line for line in hlo.splitlines()
                        if "reduce-scatter" in line)
        shard = int(re.search(r"f32\[2,(\d+)\]", first_rs).group(1))
        tampered = hlo.replace(
            first_rs, first_rs.replace(f"[2,{shard}]", f"[2,{shard + 7}]"))
        assert tampered != hlo
        codes = {f.code for f in verify_schedule(tampered, plan, specs,
                                                 compressor=flat_wire())}
        assert "SCHED-RS-BYTES" in codes

    def test_stray_collectives_flagged(self):
        plan = plan_for("lbl")
        specs = fake_specs(8)
        hlo = synth_hlo(specs, plan, extra_lines=[
            "  %all-to-all.50 = f32[2,64] all-to-all(f32[2,64] %x.1), "
            "replica_groups={{0,1}}, dimensions={0}",
            "  %all-reduce.51 = f32[1,4096] all-reduce(f32[1,4096] %g.9), "
            "to_apply=%sum",
        ])
        findings = verify_schedule(hlo, plan, specs, compressor=flat_wire())
        assert [f.code for f in findings] == ["SCHED-STRAY-COLLECTIVE"] * 2
        flagged = {f.detail["opcode"] for f in findings}
        assert flagged == {"all-to-all", "all-reduce"}

    def test_single_device_only_stray_checks(self):
        # axis_size == 1: XLA elides the plan's collectives, so counts
        # and bytes are skipped — but big stray traffic is still flagged
        plan = plan_for("dynacomm")
        specs = fake_specs(8, axis_size=1)
        assert verify_schedule("HloModule m\nENTRY %e (p: f32[1]) {\n"
                               "  ROOT %p = f32[1] parameter(0)\n}",
                               plan, specs) == []
        big = ("HloModule m\nENTRY %e (p: f32[1]) {\n"
               "  %all-reduce.1 = f32[4096] all-reduce(f32[4096] %g), "
               "to_apply=%sum\n"
               "  ROOT %p = f32[1] parameter(0)\n}")
        codes = {f.code for f in verify_schedule(big, plan, specs)}
        assert codes == {"SCHED-STRAY-COLLECTIVE"}

    def test_verify_no_collectives(self):
        clean = ("HloModule m\nENTRY %e (p: f32[8]) {\n"
                 "  %all-reduce.1 = f32[] all-reduce(f32[] %l), "
                 "to_apply=%sum\n"
                 "  ROOT %p = f32[8] parameter(0)\n}")
        assert verify_no_collectives(clean) == []
        findings = verify_no_collectives(fixture("inline_operands.txt"))
        assert findings
        assert all(f.code == "SCHED-STRAY-COLLECTIVE" for f in findings)

    def test_expected_byte_math(self):
        plan = plan_for("ibatch", num_layers=6)
        specs = fake_specs(6, axis_size=2)
        ag = expected_ag_bytes(specs, plan, compressor=flat_wire())
        assert len(ag) == len(plan.forward)
        assert ag[0] == 4 * sum(specs[l].padded // 2
                                for l in plan.forward[0])
        rs = expected_rs_bytes(specs, plan, compressor=flat_wire())
        assert len(rs) == len(plan.backward)
        assert rs[-1] == 4 * sum(specs[l].padded
                                 for l in plan.backward[-1])
        extra = expected_ag_bytes(specs, plan, zero3=True,
                                  compressor=flat_wire())
        mid = sum(1 for b in plan.backward if any(0 < l < 5 for l in b))
        assert len(extra) == len(plan.forward) + mid


class TestLeavesConformance:
    """The leaves layout's byte math: per split leaf one all-gather of its
    shard in a pull and one reduce-scatter of its gradient in a push, an
    all-reduce of each leaf held whole (a step with no compressor)."""

    @pytest.mark.parametrize("strat", STRATEGIES)
    @pytest.mark.parametrize("zero3", [False, True],
                             ids=["zero", "zero3"])
    def test_all_strategies_conform(self, strat, zero3):
        plan = plan_for(strat)
        specs = fake_leaf_specs(8)
        hlo = synth_leaves_hlo(specs, plan, zero3=zero3)
        assert verify_schedule(hlo, plan, specs, zero3=zero3) == []

    def test_expected_byte_math(self):
        plan = plan_for("ibatch", num_layers=6)
        specs = fake_leaf_specs(6)
        ag = expected_ag_bytes(specs, plan)
        first = plan.forward[0]
        # two split leaves a layer, each gathered from its half
        assert ag[:2 * len(first)] == [
            n for l in first for n in (4 * 8 * (l + 1) * 4 // 2,
                                       4 * 3 * 2 * (l + 2) // 2)]
        rs = expected_rs_bytes(specs, plan)
        assert len(rs) == 2 * 6
        assert sorted(rs) == sorted(
            n for l in range(6) for n in (4 * 8 * (l + 1) * 4,
                                          4 * 3 * 2 * (l + 2)))
        assert expected_ar_bytes(specs, plan) == [4] * 6
        assert expected_ar_bytes(specs, plan, compressor=flat_wire()) == []
        mid = sum(1 for b in plan.backward if any(0 < l < 5 for l in b))
        extra = expected_ag_bytes(specs, plan, zero3=True)
        assert len(extra) == 2 * 6 + 2 * sum(
            len(b) for b in plan.backward if any(0 < l < 5 for l in b))
        assert mid >= 1

    def test_flat_byte_math_does_not_pass_a_leaves_step(self):
        plan = plan_for("dynacomm")
        specs = fake_leaf_specs(8)
        for l, spec in enumerate(specs):
            spec.padded = -(-spec.total // 2) * 2
        hlo = synth_leaves_hlo(specs, plan)
        codes = {f.code for f in verify_schedule(hlo, plan, specs,
                                                 compressor=flat_wire())}
        assert {"SCHED-AG-COUNT", "SCHED-RS-COUNT"} <= codes

    def test_missing_leaf_and_stray_all_reduce_flagged(self):
        plan = plan_for("lbl")
        specs = fake_leaf_specs(8)
        hlo = synth_leaves_hlo(specs, plan, extra_lines=[
            "  %all-reduce.51 = f32[1,4096] all-reduce(f32[1,4096] %g.9), "
            "to_apply=%sum"])
        first_ag = next(line for line in hlo.splitlines()
                        if "all-gather(" in line)
        hlo = hlo.replace(first_ag + "\n", "")
        codes = [f.code for f in verify_schedule(hlo, plan, specs)]
        assert sorted(codes) == ["SCHED-AG-BYTES", "SCHED-AG-COUNT",
                                 "SCHED-STRAY-COLLECTIVE"]


class TestWireModel:

    def test_int8_tile_pinned_to_kernel(self):
        # conformance re-derives the int8 layout independently; this pin
        # is the one place the two constants are allowed to meet
        from repro.kernels.compress.ops import TILE
        assert INT8_TILE == TILE

    def test_independent_formulas(self):
        assert independent_wire_bytes(None, 4096.0) == 4096.0
        int8 = SimpleNamespace(scheme="int8")
        n = 4096 / 4
        assert independent_wire_bytes(int8, 4096.0) == n + 4.0 * 2
        topk = SimpleNamespace(scheme="topk", fraction=0.01)
        assert independent_wire_bytes(topk, 4096.0) == 8.0 * 11
        # floor: at least one (index, value) pair
        assert independent_wire_bytes(topk, 4.0) == 8.0

    def test_lying_compressor_flagged(self):
        class Lying:
            scheme = "int8"
            segment_overhead_bytes = 0.0

            def wire_bytes(self, logical_bytes):
                return logical_bytes   # claims no compression happened

        plan = plan_for("dynacomm")
        specs = fake_specs(8)
        findings = verify_wire_model(specs, plan, Lying())
        assert findings
        assert all(f.code == "SCHED-WIRE-BYTES" for f in findings)


# ---------------------------------------------------------------------------
# cache + ledger audits (fake doubles, mutation-style)
# ---------------------------------------------------------------------------

class FakeCache:

    def __init__(self, plans, traces=None, counts=None):
        self.plans = list(plans)
        self.traces = len(self.plans) if traces is None else traces
        self._counts = counts or {}

    def hlo_counts(self, plan):
        if plan in self._counts:
            return self._counts[plan]
        return (len(plan.forward), len(plan.backward))


class TestCacheAudit:

    def test_clean_cache(self):
        plans = [plan_for(s) for s in ("sequential", "dynacomm")]
        assert verify_cache(FakeCache(plans)) == []

    def test_retrace_flagged(self):
        plans = [plan_for("sequential")]
        findings = verify_cache(FakeCache(plans, traces=3))
        assert [f.code for f in findings] == ["SCHED-CACHE-RETRACE"]

    def test_count_mismatch_flagged(self):
        plan = plan_for("lbl")
        cache = FakeCache([plan], counts={plan: (0, 0)})
        findings = verify_cache(cache)
        assert [f.code for f in findings] == ["SCHED-CACHE-COUNTS"]

    def test_single_device_accepts_elided_or_degenerate(self):
        # one device: XLA may elide the collectives or compile them as
        # degenerate ops — both pass, anything else is flagged
        plan = plan_for("lbl")
        specs = fake_specs(8, axis_size=1)
        assert verify_cache(FakeCache([plan], counts={plan: (0, 0)}),
                            specs=specs) == []
        assert verify_cache(FakeCache([plan]), specs=specs) == []
        partial = FakeCache([plan], counts={plan: (1, 0)})
        findings = verify_cache(partial, specs=specs)
        assert [f.code for f in findings] == ["SCHED-CACHE-COUNTS"]


class TestPushLedgerAudit:

    def _setup(self, scheme="int8"):
        from repro.compress.compressor import make_compressor
        kwargs = {"topk_fraction": 0.01} if scheme == "topk" else {}
        comp = make_compressor(scheme, **kwargs) if scheme != "none" \
            else None
        plans = {0: plan_for("dynacomm"), 1: plan_for("sequential")}
        specs = fake_specs(8)
        return comp, plans, specs

    def _ledger_for(self, plans, specs, comp, segments_by_worker):
        pushed, wire, n_push = {}, {}, 0
        for w, nseg in segments_by_worker.items():
            bwd = plans[w].backward
            pushed[w] = sum(
                sum(specs[l].total * 4 for l in bwd[i % len(bwd)])
                for i in range(nseg))
            wire[w] = sum(
                segment_wire_bytes(specs, bwd[i % len(bwd)], comp)
                for i in range(nseg))
            n_push += nseg
        return SimpleNamespace(pushed_bytes=pushed,
                               pushed_wire_bytes=wire,
                               num_pushes=n_push)

    @pytest.mark.parametrize("scheme", ["none", "int8", "topk"])
    def test_clean_ledger(self, scheme):
        comp, plans, specs = self._setup(scheme)
        # worker 0: two full iterations + a partial; worker 1: one full
        nseg = {0: 2 * len(plans[0].backward) + 1,
                1: len(plans[1].backward)}
        ledger = self._ledger_for(plans, specs, comp, nseg)
        assert verify_push_ledger(ledger, plans, specs, comp) == []

    def test_undecomposable_bytes_flagged(self):
        comp, plans, specs = self._setup()
        ledger = self._ledger_for(plans, specs, comp,
                                  {0: len(plans[0].backward)})
        ledger.pushed_bytes[0] += 1
        findings = verify_push_ledger(ledger, plans, specs, comp)
        # the broken decomposition also desyncs the message count
        assert findings
        assert all(f.code == "SCHED-LEDGER" for f in findings)
        assert any("decompose" in f.message for f in findings)

    def test_wire_mismatch_flagged(self):
        comp, plans, specs = self._setup()
        ledger = self._ledger_for(plans, specs, comp,
                                  {0: len(plans[0].backward)})
        ledger.pushed_wire_bytes[0] -= 1
        findings = verify_push_ledger(ledger, plans, specs, comp)
        assert any("wire bytes" in f.message for f in findings)
        assert all(f.code == "SCHED-LEDGER" for f in findings)

    def test_message_count_mismatch_flagged(self):
        comp, plans, specs = self._setup()
        ledger = self._ledger_for(plans, specs, comp, {0: 3, 1: 2})
        ledger.num_pushes += 1
        findings = verify_push_ledger(ledger, plans, specs, comp)
        assert any("push messages" in f.message for f in findings)


class TestElasticLedgerAudit:
    """verify_push_ledger over FleetTrainer-style push *histories*: a
    worker that was re-planned mid-run maps to ``(plan, full_iterations,
    extra_segments)`` entries instead of one plan."""

    def _setup(self, scheme="none"):
        from repro.compress.compressor import make_compressor
        comp = make_compressor(scheme) if scheme != "none" else None
        plan_a, plan_b = plan_for("dynacomm"), plan_for("sequential")
        specs = fake_specs(8)
        return comp, plan_a, plan_b, specs

    def _ledger_for(self, history_by_worker, specs, comp):
        pushed, wire, n_push = {}, {}, 0
        for w, history in history_by_worker.items():
            logical = wb = 0
            for plan, full, extra in history:
                seg_l = [sum(specs[l].total * 4 for l in b)
                         for b in plan.backward]
                seg_w = [segment_wire_bytes(specs, b, comp)
                         for b in plan.backward]
                logical += full * sum(seg_l) + sum(seg_l[:extra])
                wb += full * sum(seg_w) + sum(seg_w[:extra])
                n_push += full * len(seg_l) + extra
            pushed[w], wire[w] = logical, wb
        return SimpleNamespace(pushed_bytes=pushed,
                               pushed_wire_bytes=wire,
                               num_pushes=n_push)

    @pytest.mark.parametrize("scheme", ["none", "int8"])
    def test_clean_history(self, scheme):
        comp, plan_a, plan_b, specs = self._setup(scheme)
        # re-planned after 2 iterations, then crashed 1 segment into an
        # iteration under the new plan — the departed ledger closes
        histories = {0: ((plan_a, 2, 0), (plan_b, 3, 1))}
        ledger = self._ledger_for(histories, specs, comp)
        assert verify_push_ledger(ledger, histories, specs, comp) == []

    def test_mixed_elastic_and_static_workers(self):
        comp, plan_a, plan_b, specs = self._setup()
        histories = {0: ((plan_a, 1, 0), (plan_b, 1, 0)),
                     1: plan_a}          # static worker: one plain plan
        pushed = self._ledger_for({0: histories[0]}, specs, comp)
        seg_l = [sum(specs[l].total * 4 for l in b)
                 for b in plan_a.backward]
        pushed.pushed_bytes[1] = sum(seg_l)
        pushed.pushed_wire_bytes[1] = sum(
            segment_wire_bytes(specs, b, comp) for b in plan_a.backward)
        pushed.num_pushes += len(plan_a.backward)
        assert verify_push_ledger(pushed, histories, specs, comp) == []

    def test_history_byte_mismatch_flagged(self):
        comp, plan_a, plan_b, specs = self._setup()
        histories = {0: ((plan_a, 2, 0), (plan_b, 1, 2))}
        ledger = self._ledger_for(histories, specs, comp)
        ledger.pushed_bytes[0] += 4
        findings = verify_push_ledger(ledger, histories, specs, comp)
        assert findings
        assert all(f.code == "SCHED-LEDGER" for f in findings)
        assert any("push history" in f.message for f in findings)

    def test_history_wire_mismatch_flagged(self):
        comp, plan_a, plan_b, specs = self._setup("int8")
        histories = {0: ((plan_a, 2, 1),)}
        ledger = self._ledger_for(histories, specs, comp)
        ledger.pushed_wire_bytes[0] -= 1
        findings = verify_push_ledger(ledger, histories, specs, comp)
        assert any("wire bytes" in f.message for f in findings)
        assert all(f.code == "SCHED-LEDGER" for f in findings)


class TestFleetMembershipAudit:
    """verify_fleet_membership over crafted run logs + roster history."""

    @staticmethod
    def _event(worker, t, version, staleness):
        return SimpleNamespace(worker=worker, sim_time=t, version=version,
                               result=SimpleNamespace(staleness=staleness))

    @staticmethod
    def _log(events):
        return SimpleNamespace(accepted=list(events))

    def test_clean_run(self):
        log = self._log([
            self._event(0, 0.1, 0, 0),
            self._event(7, 0.6, 5, 1),    # joined at v5, pushes from v5
            self._event(0, 0.7, 6, 2),
        ])
        joined = {0: (0.0, 0), 7: (0.5, 5)}
        departed = {1: (0.4, "crash")}
        assert verify_fleet_membership(log, joined, departed,
                                       staleness_bound=2) == []

    def test_staleness_breach_flagged(self):
        log = self._log([self._event(0, 0.1, 0, 3)])
        findings = verify_fleet_membership(log, {0: (0.0, 0)}, {},
                                           staleness_bound=2)
        assert [f.code for f in findings] == ["FLEET-STALENESS"]

    def test_commit_before_join_flagged(self):
        log = self._log([self._event(7, 0.3, 5, 0)])
        findings = verify_fleet_membership(log, {7: (0.5, 5)}, {},
                                           staleness_bound=2)
        assert [f.code for f in findings] == ["FLEET-MEMBER"]
        assert "before its join" in findings[0].message

    def test_push_older_than_join_version_flagged(self):
        log = self._log([self._event(7, 0.6, 3, 1)])
        findings = verify_fleet_membership(log, {7: (0.5, 5)}, {},
                                           staleness_bound=2)
        assert [f.code for f in findings] == ["FLEET-MEMBER"]
        assert "older than the head at its join" in findings[0].message

    def test_commit_after_departure_flagged(self):
        log = self._log([self._event(1, 0.9, 8, 0)])
        findings = verify_fleet_membership(log, {1: (0.0, 0)},
                                           {1: (0.4, "crash")},
                                           staleness_bound=2)
        assert [f.code for f in findings] == ["FLEET-MEMBER"]
        assert "after its departure" in findings[0].message

    def test_never_joined_flagged(self):
        log = self._log([self._event(9, 0.2, 1, 0)])
        findings = verify_fleet_membership(log, {0: (0.0, 0)}, {},
                                           staleness_bound=2)
        assert [f.code for f in findings] == ["FLEET-MEMBER"]
        assert "never joined" in findings[0].message


# ---------------------------------------------------------------------------
# AST lints: each seeded hazard fires; suppression works; src/ is clean
# ---------------------------------------------------------------------------

def codes(source, path="src/repro/some/module.py", config=None):
    return [f.code for f in lint_source(source, path, config)]


class TestLints:

    def test_global_random_draw(self):
        assert codes("import random\nrandom.random()\n") == ["DET-RANDOM"]
        assert codes("import random\nrandom.shuffle(xs)\n") == \
            ["DET-RANDOM"]

    def test_numpy_global_random(self):
        assert codes("import numpy as np\nnp.random.rand(3)\n") == \
            ["DET-RANDOM"]
        assert codes("import numpy.random as npr\nnpr.standard_normal()\n"
                     ) == ["DET-RANDOM"]

    def test_seeded_constructions_are_safe(self):
        assert codes("import numpy as np\n"
                     "rng = np.random.default_rng(0)\nrng.random()\n") == []
        assert codes("import random\nr = random.Random(0)\n") == []

    def test_unseeded_ctor(self):
        assert codes("import random\nr = random.Random()\n") == \
            ["DET-RANDOM"]
        assert codes("import numpy as np\n"
                     "rng = np.random.default_rng()\n") == ["DET-RANDOM"]

    def test_from_import_draw(self):
        assert codes("from random import random\n") == ["DET-RANDOM"]
        assert codes("from numpy.random import rand\n") == ["DET-RANDOM"]
        assert codes("from random import Random\n") == []

    def test_wall_clock_scoped_to_deterministic_modules(self):
        src = "import time\nt = time.time()\n"
        assert codes(src, path="src/repro/ps/async_mode.py") == \
            ["DET-WALL-CLOCK"]
        assert codes(src, path="src/repro/core/simulator.py") == \
            ["DET-WALL-CLOCK"]
        # the fleet event engine and everything feeding it must stay
        # wall-clock-free (bit-reproducibility at scale)
        for mod in ("engine", "membership", "drift", "trainer"):
            assert codes(src, path=f"src/repro/fleet/{mod}.py") == \
                ["DET-WALL-CLOCK"], mod
        # wall clock is fine in profiling / launch code
        assert codes(src, path="src/repro/launch/bench.py") == []

    def test_wall_clock_datetime_and_from_import(self):
        assert codes("from datetime import datetime\n"
                     "t = datetime.now()\n",
                     path="src/repro/core/simulator.py") == \
            ["DET-WALL-CLOCK"]
        assert codes("from time import monotonic\n",
                     path="src/repro/ps/server.py") == ["DET-WALL-CLOCK"]

    def test_dict_order_walks(self):
        assert codes("for k, v in params.items():\n    pass\n") == \
            ["DET-DICT-ORDER"]
        assert codes("xs = [k for k in grad_tree.keys()]\n") == \
            ["DET-DICT-ORDER"]
        # sorted() canonicalizes the walk
        assert codes("for k in sorted(params.keys()):\n    pass\n") == []
        # non-param-tree dicts are out of scope
        assert codes("for k, v in cache.items():\n    pass\n") == []

    def test_kernel_interpret(self):
        call = "pl.pallas_call(kern, interpret=True)\n"
        assert codes(call, path="src/repro/kernels/foo/foo.py") == \
            ["KERNEL-INTERPRET"]
        assert codes(call, path="src/repro/dist/zero.py") == []
        default = "def op(x, interpret: bool = False):\n    return x\n"
        assert codes(default, path="src/repro/kernels/foo/ops.py") == \
            ["KERNEL-INTERPRET"]
        ok = "def op(x, interpret=None):\n    return x\n"
        assert codes(ok, path="src/repro/kernels/foo/ops.py") == []

    def test_noqa_suppression(self):
        assert codes("import random\nrandom.random()  # noqa\n") == []
        assert codes("import random\n"
                     "random.random()  # noqa: DET-RANDOM\n") == []
        # an unrelated code does not suppress
        assert codes("import random\n"
                     "random.random()  # noqa: DET-DICT-ORDER\n") == \
            ["DET-RANDOM"]

    def test_parse_error_reported(self):
        assert codes("def broken(:\n") == ["PARSE-ERROR"]

    def test_src_tree_is_clean(self):
        # the CI gate: the repo's own sources produce zero findings
        findings = lint_paths([str(SRC)])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_custom_config_scoping(self):
        cfg = LintConfig(deterministic_modules=("sim/loop.py",),
                         kernel_dirs=("fastpath",))
        assert codes("import time\ntime.time()\n",
                     path="pkg/sim/loop.py", config=cfg) == \
            ["DET-WALL-CLOCK"]
        assert codes("f(interpret=False)\n",
                     path="pkg/fastpath/k.py", config=cfg) == \
            ["KERNEL-INTERPRET"]


# ---------------------------------------------------------------------------
# findings serialization
# ---------------------------------------------------------------------------

class TestFindings:

    def test_json_roundtrip(self):
        fs = [Finding(code="SCHED-AG-COUNT", message="m",
                      detail={"expected": 3, "observed": 2}),
              Finding(code="DET-RANDOM", message="n", severity="warning",
                      path="a.py", line=7)]
        doc = json.loads(findings_to_json(fs, command="lint"))
        assert doc["num_findings"] == 2
        assert doc["num_errors"] == 1
        assert doc["command"] == "lint"
        assert doc["findings"][0]["detail"] == {"expected": 3,
                                                "observed": 2}
        assert doc["findings"][1]["path"] == "a.py"

    def test_format_includes_location(self):
        f = Finding(code="DET-RANDOM", message="msg", path="a.py", line=3)
        assert f.format() == "a.py:3: error[DET-RANDOM] msg"


# ---------------------------------------------------------------------------
# in-process runtime verification (1 device; the subprocess CLI sweep
# below covers the forged-2-device paths)
# ---------------------------------------------------------------------------

class TestVerifyRuntimeInProcess:

    def _verify(self, name, **kwargs):
        from repro.analysis.runtime_verify import verify_runtime
        from repro.runtime.config import RuntimeConfig
        config = RuntimeConfig.load(str(CONFIGS / name))
        findings, info = verify_runtime(config, **kwargs)
        assert findings == [], "\n".join(f.format() for f in findings)
        return info

    def test_local(self):
        info = self._verify("local.json")
        assert info["checked"] == ["no-collectives"]

    def test_static_ps(self):
        info = self._verify("ps.json")
        assert "ledger" in info["checked"]
        assert info["steps_run"] == 1

    def test_dynamic_cache(self):
        info = self._verify("zero_bandwidth_shift.json")
        assert info["plans_seen"] >= 1
        assert info["traces"] == info["plans_seen"]

    def test_async_int8_exact_wire(self):
        info = self._verify("ps_async_int8.json")
        assert info["compression"] == "int8"
        assert "push-ledger" in info["checked"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cli(*args, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=str(REPO))


class TestCli:

    def test_main_in_process(self, tmp_path, capsys):
        # the entry point itself, without a subprocess: lint a hazard,
        # then verify the cheapest config with --devices 0 (leave the
        # already-initialized jax alone)
        from repro.analysis.cli import main
        bad = tmp_path / "bad.py"
        bad.write_text("import random\nrandom.random()\n")
        out_json = tmp_path / "lint.json"
        assert main(["lint", str(bad), "--json", str(out_json)]) == 1
        assert json.loads(out_json.read_text())["num_errors"] == 1
        assert "DET-RANDOM" in capsys.readouterr().out
        assert main(["verify", "--config", str(CONFIGS / "local.json"),
                     "--devices", "0"]) == 0
        assert "no findings" in capsys.readouterr().out

    def test_lint_clean_tree_exits_zero(self, tmp_path):
        out_json = tmp_path / "findings.json"
        res = run_cli("lint", str(SRC), "--json", str(out_json))
        assert res.returncode == 0, res.stdout + res.stderr
        assert "no findings" in res.stdout
        doc = json.loads(out_json.read_text())
        assert doc["num_findings"] == 0
        assert doc["command"] == "lint"

    def test_lint_hazard_exits_nonzero(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import random\nrandom.random()\n")
        out_json = tmp_path / "findings.json"
        res = run_cli("lint", str(bad), "--json", str(out_json))
        assert res.returncode == 1
        assert "DET-RANDOM" in res.stdout
        doc = json.loads(out_json.read_text())
        assert doc["num_errors"] == 1
        assert doc["findings"][0]["code"] == "DET-RANDOM"

    def test_verify_local_config(self, tmp_path):
        # the cheapest config: single-jit local step, no collectives
        out_json = tmp_path / "verify.json"
        res = run_cli("verify", "--config",
                      str(CONFIGS / "local.json"), "--json", str(out_json))
        assert res.returncode == 0, res.stdout + res.stderr
        doc = json.loads(out_json.read_text())
        assert doc["num_findings"] == 0
        assert doc["command"] == "verify"

    @pytest.mark.slow
    @pytest.mark.parametrize("config", sorted(
        p.name for p in CONFIGS.glob("*.json")))
    def test_verify_all_smoke_configs(self, config, tmp_path):
        out_json = tmp_path / "verify.json"
        res = run_cli("verify", "--config", str(CONFIGS / config),
                      "--json", str(out_json))
        assert res.returncode == 0, res.stdout + res.stderr
        assert json.loads(out_json.read_text())["num_findings"] == 0
