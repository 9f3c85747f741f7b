"""Parameter-server subsystem tests.

Single-device: topology/sharding, per-topology cost projection (asymmetric
per-link Δt), per-worker scheduling + consensus, the PS discrete-event
simulator + timeline rendering, the versioned server (segmented pulls,
staleness gate, eviction), and bounded-staleness async training on the
smoke CNN.

Multi-device (4 forged host devices via subprocess): sync-mode PSTrainer
bit-identity against ZeroTrainer and the one-pull + one-push-per-segment
HLO transfer structure, for all four strategies.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LayerCosts, TopologyCosts, backward_time,
                        consensus_decision, decision_from_plan, dp_backward,
                        iteration_time, plan_from_decision, random_costs,
                        schedule, schedule_topology, simulate_ps_iteration)
from repro.core.viz import render_ps_timeline
from repro.models.cnn import small_cnn_init, small_cnn_loss
from repro.optim import sgd
from repro.ps import (AsyncPSTrainer, PSServer, PSTopology, StaleVersion,
                      asymmetric_link)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------


class TestPSTopology:
    def test_uniform_builder(self):
        topo = PSTopology.uniform(2, 3, down_bps=10e9, up_bps=1e9)
        assert topo.num_servers == 2 and topo.num_workers == 3
        assert topo.links[0].down.bandwidth_bps == 10e9
        assert topo.links[0].up.bandwidth_bps == 1e9

    def test_validation(self):
        link = asymmetric_link(10e9, 1e9)
        with pytest.raises(ValueError, match="num_servers"):
            PSTopology(num_servers=0, links=(link,), worker_flops=(1e9,))
        with pytest.raises(ValueError, match="at least one worker"):
            PSTopology(num_servers=1, links=(), worker_flops=())
        with pytest.raises(ValueError, match="worker_flops"):
            PSTopology(num_servers=1, links=(link,), worker_flops=(1e9, 1e9))
        with pytest.raises(ValueError, match="positive"):
            PSTopology(num_servers=1, links=(link,), worker_flops=(0.0,))
        with pytest.raises(TypeError, match="network interface"):
            from repro.ps import LinkModel
            LinkModel(down=object(), up=object())

    def test_contiguous_shard_ownership(self):
        topo = PSTopology.uniform(3, 1)
        shards = [topo.shard_of_layer(l, 7) for l in range(7)]
        assert shards == sorted(shards)              # contiguous blocks
        assert set(shards) == {0, 1, 2}              # every shard owns some
        union = sum((topo.layers_of_shard(s, 7) for s in range(3)), ())
        assert sorted(union) == list(range(7))       # exact partition
        with pytest.raises(ValueError):
            topo.shard_of_layer(7, 7)
        with pytest.raises(ValueError):
            topo.layers_of_shard(3, 7)

    def test_owner_of_bucket(self):
        topo = PSTopology.uniform(2, 1)
        assert topo.owner_of_bucket((0, 1), 4) == 0
        assert topo.owner_of_bucket((3, 2), 4) == 1
        with pytest.raises(ValueError, match="empty"):
            topo.owner_of_bucket((), 4)

    def test_worker_costs_asymmetric(self):
        """pt/Δt from the downlink, gt/Δt_bwd from the uplink, fc/bc from
        the worker's own compute rate."""
        topo = PSTopology(
            num_servers=1,
            links=(asymmetric_link(10e9, 1e9),
                   asymmetric_link(10e9, 1e9, rtt_s=0.1)),
            worker_flops=(1e10, 2e10))
        pb, ff = [8e6, 8e6], [1e9, 1e9]
        c0 = topo.worker_costs(0, param_bytes=pb, flops_fwd=ff)
        np.testing.assert_allclose(c0.pt, 8e6 * 8 / 10e9)
        np.testing.assert_allclose(c0.gt, 8e6 * 8 / 1e9)   # 10x slower up
        np.testing.assert_allclose(c0.fc, 0.1)
        np.testing.assert_allclose(c0.bc, 0.2)             # default 2x fwd
        assert c0.dt == topo.links[0].down.dt
        assert c0.dt_push == topo.links[0].up.dt
        c1 = topo.worker_costs(1, param_bytes=pb, flops_fwd=ff)
        np.testing.assert_allclose(c1.fc, 0.05)            # 2x faster worker
        assert c1.dt_push > c0.dt_push                     # 0.1s RTT uplink
        with pytest.raises(ValueError, match="worker 2"):
            topo.worker_costs(2, param_bytes=pb, flops_fwd=ff)


# ---------------------------------------------------------------------------
# per-topology cost model + scheduling
# ---------------------------------------------------------------------------


class TestTopologyCosts:
    def _topo(self):
        return TopologyCosts(workers=(
            random_costs(6, seed=0),
            random_costs(6, seed=0, comp_scale=5.0, comm_scale=2.0)))

    def test_validation(self):
        with pytest.raises(ValueError, match="at least one"):
            TopologyCosts(workers=())
        with pytest.raises(ValueError, match="layer count"):
            TopologyCosts(workers=(random_costs(4), random_costs(5)))

    def test_makespan_is_straggler_time(self):
        topo = self._topo()
        d = schedule(topo.workers[0], "dynacomm")
        times = topo.iteration_times(*d)
        assert topo.makespan(*d) == max(times)
        assert topo.straggler(*d) == int(np.argmax(times))

    def test_per_worker_plans_differ_under_heterogeneity(self):
        topo = self._topo()
        decisions = schedule_topology(topo, "dynacomm")
        assert len(decisions) == 2
        assert decisions[0] != decisions[1]

    def test_consensus_minimizes_makespan_over_candidates(self):
        topo = self._topo()
        decision, makespan = consensus_decision(topo, "dynacomm")
        assert makespan == topo.makespan(*decision)
        for cand in schedule_topology(topo, "dynacomm"):
            assert makespan <= topo.makespan(*cand) + 1e-12


class TestAsymmetricDt:
    def test_dt_push_defaults_to_dt(self):
        c = random_costs(4, seed=1)
        assert c.dt_bwd is None and c.dt_push == c.dt

    def test_backward_time_uses_push_dt(self):
        base = random_costs(4, seed=1)
        asym = LayerCosts(pt=base.pt, fc=base.fc, bc=base.bc, gt=base.gt,
                          dt=base.dt, dt_bwd=base.dt * 3)
        segs = ((1, 4),)
        fwd_same = base.scaled()  # forward unaffected by dt_bwd
        assert backward_time(asym, segs) == pytest.approx(
            backward_time(base, segs) + 2 * base.dt)
        from repro.core import forward_time
        assert forward_time(asym, ((1, 4),)) == forward_time(fwd_same,
                                                             ((1, 4),))

    def test_dp_backward_optimal_under_asymmetric_dt(self):
        """The DP's objective must equal f_m when Δt_push != Δt_pull
        (the DPResult constructor asserts this internally) and beat the
        symmetric-Δt decision when the push overhead dominates."""
        base = random_costs(8, seed=3, dt=1e-4)
        asym = LayerCosts(pt=base.pt, fc=base.fc, bc=base.bc, gt=base.gt,
                          dt=base.dt, dt_bwd=5e-2)
        res = dp_backward(asym)
        assert res.time == pytest.approx(backward_time(asym, res.segments))
        # expensive per-push overhead forces fewer, larger segments
        assert len(res.segments) <= len(dp_backward(base).segments)

    def test_validation(self):
        with pytest.raises(ValueError, match="dt_bwd"):
            LayerCosts(pt=[1.0], fc=[1.0], bc=[1.0], gt=[1.0], dt=0.1,
                       dt_bwd=-1.0)


# ---------------------------------------------------------------------------
# PS simulator + rendering
# ---------------------------------------------------------------------------


class TestPSSimulator:
    def _topo(self):
        return TopologyCosts(workers=(random_costs(5, seed=0),
                                      random_costs(5, seed=0,
                                                   comp_scale=3.0)))

    def test_shared_decision_broadcasts(self):
        topo = self._topo()
        d = schedule(topo.workers[0], "dynacomm")
        tl = simulate_ps_iteration(topo, d)
        assert tl.num_workers == 2
        assert tl.makespan == pytest.approx(topo.makespan(*d))
        assert tl.straggler == topo.straggler(*d)

    def test_per_worker_decisions(self):
        topo = self._topo()
        decisions = schedule_topology(topo, "dynacomm")
        tl = simulate_ps_iteration(topo, decisions)
        for w, wtl in enumerate(tl.workers):
            assert wtl.total == pytest.approx(
                iteration_time(topo.workers[w], *decisions[w]))
        waits = tl.barrier_waits
        assert min(waits) == 0.0                       # straggler never waits
        assert waits[tl.straggler] == 0.0

    def test_decision_count_mismatch_rejected(self):
        topo = self._topo()
        d = schedule(topo.workers[0], "dynacomm")
        with pytest.raises(ValueError, match="decisions"):
            simulate_ps_iteration(topo, [d, d, d])

    def test_render_ps_timeline(self):
        topo = self._topo()
        d = schedule(topo.workers[0], "dynacomm")
        text = render_ps_timeline(topo, d, width=60)
        lines = text.splitlines()
        assert "makespan" in lines[0] and "straggler" in lines[0]
        # one header + link lane + compute lane per worker
        assert len(lines) == 1 + 3 * topo.num_workers
        assert sum("barrier wait" in l for l in lines) == topo.num_workers
        assert sum(l.strip().startswith("link") for l in lines) == 2
        # the straggler's reported wait is zero
        straggler_header = lines[1 + 3 * tlstraggler(topo, d)]
        assert "wait 0.0000s" in straggler_header


def tlstraggler(topo, d):
    return simulate_ps_iteration(topo, d).straggler


class TestDecisionPlanRoundTrip:
    @pytest.mark.parametrize("strategy", ["sequential", "lbl", "dynacomm"])
    def test_round_trip(self, strategy):
        costs = random_costs(7, seed=2)
        decision = schedule(costs, strategy)
        plan = plan_from_decision(*decision, 7)
        assert decision_from_plan(plan) == decision


# ---------------------------------------------------------------------------
# the versioned server
# ---------------------------------------------------------------------------


def _make_server(num_layers=4, staleness=1, size=6):
    from repro.dist.collectives import make_flat_spec, flatten_tree
    topo = PSTopology.uniform(2, 2)
    trees = [{"w": jnp.arange(size, dtype=jnp.float32) + l}
             for l in range(num_layers)]
    specs = [make_flat_spec(t, 1) for t in trees]
    flats = [flatten_tree(t, s) for t, s in zip(trees, specs)]
    server = PSServer(specs, topo, sgd(0.5), flats,
                      staleness_bound=staleness)
    return server, specs


def _grads(specs, bucket, value=1.0):
    return {l: jnp.full((specs[l].padded,), value, jnp.float32)
            for l in bucket}


class TestPSServer:
    def test_versioned_pull_is_snapshot_consistent(self):
        """A pull pinned at version v is unaffected by a concurrent push."""
        server, specs = _make_server()
        v, first = server.pull_bucket((0, 1), worker=0)
        assert v == 0
        # another worker pushes everything → version bumps
        for bucket in ((3, 2), (1, 0)):
            server.push_bucket(1, 0, bucket, _grads(specs, bucket))
        assert server.version == 1
        # worker 0 finishes its segmented pull at the pinned version
        v2, rest = server.pull_bucket((2, 3), version=v, worker=0)
        assert v2 == v
        np.testing.assert_array_equal(rest[2], jnp.arange(6) + 2)  # pre-push
        _, head = server.pull_bucket((2, 3), worker=0)
        assert not np.array_equal(head[2], rest[2])                # post-push

    def test_segmented_push_commits_once_complete(self):
        server, specs = _make_server()
        assert server.push_bucket(0, 0, (3, 2), _grads(specs, (3, 2))) is None
        res = server.push_bucket(0, 0, (1, 0), _grads(specs, (1, 0)))
        assert res is not None and res.accepted and res.staleness == 0
        assert res.version == server.version == 1

    def test_staleness_gate(self):
        server, specs = _make_server(staleness=1)

        def push_all(worker, version):
            res = None
            for bucket in ((3, 2), (1, 0)):
                res = server.push_bucket(worker, version, bucket,
                                         _grads(specs, bucket))
            return res

        assert push_all(0, 0).accepted                 # staleness 0
        assert push_all(1, 0).accepted                 # staleness 1 == k
        res = push_all(2, 0)                           # staleness 2 > k
        assert not res.accepted and res.staleness == 2
        assert server.version == 2                     # rejected: no apply
        assert server.ledger.rejected_pushes == 1

    def test_snapshot_eviction(self):
        server, specs = _make_server(staleness=0)
        for v in range(2):
            for bucket in ((3, 2), (1, 0)):
                server.push_bucket(0, v, bucket, _grads(specs, bucket))
        assert server.snapshot_versions == (2,)        # only head retained
        with pytest.raises(StaleVersion, match="evicted"):
            server.pull_bucket((0,), version=0)

    def test_ledger_and_bytes(self):
        server, specs = _make_server()
        nbytes = server.segment_bytes((0, 1))
        assert nbytes == specs[0].total * 4 + specs[1].total * 4
        server.pull_bucket((0, 1), worker=0)
        server.pull_bucket((2, 3), worker=0)
        assert server.ledger.num_pulls == 2
        assert server.ledger.pulled_bytes[0] == server.segment_bytes((0, 1)) \
            + server.segment_bytes((2, 3))

    def test_validation(self):
        server, specs = _make_server()
        with pytest.raises(ValueError, match="empty"):
            server.pull_bucket(())
        with pytest.raises(ValueError, match="lacks grads"):
            server.push_bucket(0, 0, (0, 1), _grads(specs, (0,)))
        server.push_bucket(0, 0, (0,), _grads(specs, (0,)))
        with pytest.raises(ValueError, match="twice"):
            server.push_bucket(0, 0, (0,), _grads(specs, (0,)))
        with pytest.raises(ValueError, match="staleness_bound"):
            _make_server(staleness=-1)


# ---------------------------------------------------------------------------
# bounded-staleness async training (smoke CNN)
# ---------------------------------------------------------------------------


def _cnn_loss(layers, batch):
    return small_cnn_loss({"layers": layers}, batch["images"],
                          batch["labels"])


def _fixed_batch(*_):
    """One fixed batch for every worker: loss must strictly improve."""
    r = np.random.default_rng(7)
    return {"images": jnp.asarray(r.normal(size=(8, 32, 32, 3)), jnp.float32),
            "labels": jnp.asarray(r.integers(0, 10, size=(8,)), jnp.int32)}


def _async_trainer(k, workers=3, flops=None, optimizer=None,
                   throttle="reject", plan=None):
    params = small_cnn_init(jax.random.PRNGKey(0))
    L = len(params["layers"])
    if plan is None:
        plan = plan_from_decision(((1, 3), (4, L)), ((4, L), (1, 3)), L)
    topo = PSTopology(
        num_servers=2,
        links=tuple(asymmetric_link(10e9, 1e9) for _ in range(workers)),
        worker_flops=flops or (1e10,) * workers)
    return AsyncPSTrainer(init_layers=params["layers"], loss_fn=_cnn_loss,
                          optimizer=optimizer or sgd(0.05), topology=topo,
                          plan=plan, staleness=k, throttle=throttle)


def _slow_worker_trainer(k, throttle):
    """The starvation fixture: 4 workers, worker 3 at 1/4 compute rate
    (iteration durations 1, 1, 1, 4 simulated seconds)."""
    return _async_trainer(k, workers=4, flops=(4e10, 4e10, 4e10, 1e10),
                          throttle=throttle)


class TestAsyncBoundedStaleness:
    @pytest.mark.parametrize("k", [0, 1, 2])
    def test_staleness_bound_respected(self, k):
        log = _async_trainer(k).run(12, _fixed_batch)
        assert len(log.accepted) == 12
        assert log.max_staleness <= k
        for e in log.events:
            if not e.result.accepted:
                assert e.result.staleness > k

    def test_k_equal_workers_minus_one_never_rejects(self):
        """Homogeneous workers commit round-robin; k = W-1 absorbs the
        window exactly."""
        log = _async_trainer(2, workers=3).run(12, _fixed_batch)
        assert log.num_rejected == 0

    def test_smoke_cnn_converges(self):
        from repro.optim import adamw
        log = _async_trainer(1, optimizer=adamw(1e-2)).run(30, _fixed_batch)
        losses = log.losses
        assert losses[-1] < losses[0] * 0.55

    def test_deterministic(self):
        l1 = _async_trainer(1).run(10, _fixed_batch).losses
        l2 = _async_trainer(1).run(10, _fixed_batch).losses
        assert l1 == l2

    def test_heterogeneous_durations_from_flops(self):
        """Without explicit costs, the simulated clock scales with
        worker_flops: the 2x-slower worker commits half as often."""
        log = _async_trainer(3, workers=2, flops=(2e10, 1e10)).run(
            12, _fixed_batch)
        by_worker = [sum(1 for e in log.accepted if e.worker == w)
                     for w in range(2)]
        assert by_worker[0] > by_worker[1] > 0

    def test_k0_serializes(self):
        """k=0: every accepted gradient was computed at the head version."""
        log = _async_trainer(0, workers=2).run(8, _fixed_batch)
        assert all(e.result.staleness == 0 for e in log.accepted)
        assert log.num_rejected > 0       # the concurrent pull gets dropped

    def test_plan_must_cover_model(self):
        from repro.core import BucketPlan
        params = small_cnn_init(jax.random.PRNGKey(0))
        plan = plan_from_decision(((1, 2),), ((1, 2),), 2)
        topo = PSTopology.uniform(1, 1)
        with pytest.raises(ValueError, match="forward buckets cover"):
            AsyncPSTrainer(init_layers=params["layers"], loss_fn=_cnn_loss,
                           optimizer=sgd(0.05), topology=topo, plan=plan,
                           staleness=1)
        # backward gaps are rejected up front too (not via a late assert)
        L = len(params["layers"])
        partial = BucketPlan(forward=(tuple(range(L)),),
                             backward=((L - 1, L - 2),))
        with pytest.raises(ValueError, match="backward buckets cover"):
            AsyncPSTrainer(init_layers=params["layers"], loss_fn=_cnn_loss,
                           optimizer=sgd(0.05), topology=topo, plan=partial,
                           staleness=1)


# ---------------------------------------------------------------------------
# SSP wait-at-barrier throttling (ISSUE 4)
# ---------------------------------------------------------------------------


class TestSSPThrottle:
    def test_reject_starves_slow_worker(self):
        """The documented ROADMAP failure mode: at k=1, a 4x-slower worker
        always commits > k versions behind the head the fast workers keep
        advancing — every one of its pushes is evicted, it NEVER
        contributes a gradient."""
        log = _slow_worker_trainer(1, "reject").run(16, _fixed_batch)
        assert log.accepted_by_worker().get(3, 0) == 0
        assert log.num_rejected > 0
        # its attempts were real: rejections from worker 3 are on record
        assert any(e.worker == 3 and not e.result.accepted
                   for e in log.events)

    def test_wait_lets_every_worker_contribute(self):
        """Same fleet, wait throttle: fast workers block at the barrier
        instead; the slow worker lands >= 1 accepted push, nothing is
        ever rejected, and the staleness bound still holds."""
        log = _slow_worker_trainer(1, "wait").run(16, _fixed_batch)
        by_worker = log.accepted_by_worker()
        for w in range(4):
            assert by_worker.get(w, 0) >= 1, f"worker {w} starved"
        assert log.num_rejected == 0
        assert log.max_staleness <= 1
        assert log.total_wait_s > 0        # somebody actually waited

    @pytest.mark.parametrize("k", [0, 1, 2])
    def test_wait_never_violates_bound(self, k):
        log = _slow_worker_trainer(k, "wait").run(12, _fixed_batch)
        assert log.max_staleness <= k
        assert log.num_rejected == 0
        assert len(log.accepted) == 12

    def test_wait_k0_serializes_without_recompute(self):
        """k=0 under wait is sequential SGD like reject mode, but via
        blocking: no rejections, no wasted recomputation."""
        log = _async_trainer(0, workers=2, throttle="wait").run(
            8, _fixed_batch)
        assert all(e.result.staleness == 0 for e in log.accepted)
        assert log.num_rejected == 0

    def test_wait_commits_in_version_order_within_window(self):
        """SSP's invariant, machine-checked: at every accepted commit the
        gradient's compute version is within k of the *post-commit* head
        (PushResult.version), monotone head growth, one bump per commit."""
        log = _slow_worker_trainer(1, "wait").run(16, _fixed_batch)
        heads = [e.result.version for e in log.events]
        assert heads == list(range(1, len(log.events) + 1))
        for e in log.events:
            assert e.result.version - e.version <= 1 + 1  # head bump + k

    def test_throttle_validation(self):
        with pytest.raises(ValueError, match="throttle"):
            _async_trainer(1, throttle="drop")

    def test_run_resumes_without_reset(self):
        """run(reset=False) continues the same event loop: cumulative log,
        advancing simulated clock, no re-priming of batches."""
        tr = _slow_worker_trainer(1, "wait")
        first = tr.run(6, _fixed_batch)
        t1 = first.makespan
        second = tr.run(6, _fixed_batch, reset=False)
        assert second is first                    # one cumulative log
        assert len(second.accepted) == 12
        assert second.makespan > t1               # the clock kept going

    def test_resume_drains_barrier_entries_left_by_push_target(self):
        """A run whose push target is reached while another completed
        worker stands *eligible* at the barrier must not defer that
        commit to the next queue completion on resume: it commits at the
        clock it became eligible, with the SSP wait it actually paid.

        2 workers with durations (1, 4), k=1: worker 0 commits at t=1,
        blocks at the barrier from t=2 on its second push; worker 1
        commits at t=4 (target of 2 reached), which is exactly when
        worker 0's entry becomes eligible."""
        tr = _async_trainer(1, workers=2, flops=(4e10, 1e10),
                            throttle="wait")
        first = tr.run(2, _fixed_batch)
        assert [e.sim_time for e in first.events] == [1.0, 4.0]
        log = tr.run(1, _fixed_batch, reset=False)
        e = log.events[-1]
        assert e.worker == 0
        assert e.sim_time == 4.0            # not worker 1's next finish (8)
        assert e.wait_s == pytest.approx(2.0)   # blocked t=2..4, no more
        assert e.result.accepted and e.result.staleness <= 1


class TestAsyncDeterminism:
    """Two runs with the same seed/topology must be bit-identical — the
    whole event sequence, not just the losses (ISSUE 4 satellite)."""

    @staticmethod
    def _trace(log):
        return [(e.worker, e.sim_time, e.version, e.result.accepted,
                 e.result.staleness, e.result.version, e.loss, e.retries,
                 e.wait_s) for e in log.events]

    @pytest.mark.parametrize("throttle", ["reject", "wait"])
    def test_bit_identical_runs(self, throttle):
        a = _slow_worker_trainer(1, throttle).run(12, _fixed_batch)
        b = _slow_worker_trainer(1, throttle).run(12, _fixed_batch)
        assert self._trace(a) == self._trace(b)
        assert a.losses == b.losses


class TestPerWorkerPlans:
    """Asynchronous planning mode: each worker runs its own decomposition
    (``schedule_topology``), which the server's per-(worker, version)
    accumulation supports without changes."""

    def _plans(self):
        params = small_cnn_init(jax.random.PRNGKey(0))
        L = len(params["layers"])
        coarse = plan_from_decision(((1, L),), ((1, L),), L)
        fine = plan_from_decision(((1, 3), (4, L)), ((4, L), (1, 3)), L)
        return L, coarse, fine

    def test_distinct_plans_run_and_respect_bound(self):
        _, coarse, fine = self._plans()
        tr = _async_trainer(1, workers=3, plan=[coarse, fine, fine])
        log = tr.run(9, _fixed_batch)
        assert log.max_staleness <= 1
        assert tr.plans == (coarse, fine, fine)
        with pytest.raises(ValueError, match="per-worker"):
            tr.plan                     # no single shared plan to return

    def test_plan_count_must_match_workers(self):
        _, coarse, fine = self._plans()
        with pytest.raises(ValueError, match="plans for 3"):
            _async_trainer(1, workers=3, plan=[coarse, fine])

    def test_set_plans_swaps_between_runs(self):
        _, coarse, fine = self._plans()
        tr = _async_trainer(1, workers=3, plan=coarse)
        tr.run(3, _fixed_batch)
        tr.set_plans(fine)
        log = tr.run(3, _fixed_batch, reset=False)
        assert tr.plan == fine
        assert len(log.accepted) == 6


class TestDynamicAsyncPS:
    """Per-worker re-planning across topology epochs (the dynamic-PS
    combination, async side)."""

    def _schedule(self, factor=8.0):
        from repro.ps import uplink_degradation
        base = PSTopology(
            num_servers=2,
            links=tuple(asymmetric_link(1e9, 100e6) for _ in range(3)),
            worker_flops=(1e9, 1e9, 2.5e8))
        return uplink_degradation(base, factor=factor, at_epoch=1)

    def _driver(self, throttle="wait"):
        from repro.ps import DynamicAsyncPSTrainer
        from repro.ps.dynamic import profiles_from_specs
        from repro.dist.collectives import make_flat_spec
        params = small_cnn_init(jax.random.PRNGKey(0))
        specs = [make_flat_spec(t, 1) for t in params["layers"]]
        return DynamicAsyncPSTrainer(
            init_layers=params["layers"], loss_fn=_cnn_loss,
            optimizer=sgd(0.05), topology=self._schedule(),
            pushes_per_epoch=6, staleness=1, throttle=throttle,
            profiles=profiles_from_specs(specs, flops_per_param=1000.0))

    def test_replans_on_epoch_boundaries(self):
        dyn = self._driver()
        log = dyn.run(3, _fixed_batch)
        assert dyn.epoch == 3
        assert len(log.accepted) == 18            # cumulative across epochs
        assert [e.epoch for e in dyn.events] == [0, 1, 2]
        assert [e.at_push for e in dyn.events] == [0, 6, 12]
        # the uplink degradation at epoch 1 re-segments the plans...
        assert dyn.events[1].plan_changed
        # ...and the heterogeneous fleet genuinely plans per worker
        assert len(set(dyn.events[0].worker_plans)) > 1
        assert log.max_staleness <= 1

    def test_wait_throttle_carries_across_replans(self):
        dyn = self._driver(throttle="wait")
        log = dyn.run(2, _fixed_batch)
        assert log.num_rejected == 0
        by_worker = log.accepted_by_worker()
        for w in range(3):
            assert by_worker.get(w, 0) >= 1

    def test_run_pushes_exact_total_with_partial_epoch(self):
        """run_pushes honours the exact requested total: whole epochs of
        pushes_per_epoch with a re-plan on each boundary, then a partial
        final epoch for the remainder."""
        dyn = self._driver()
        log = dyn.run_pushes(14, _fixed_batch)     # 6 + 6 + 2
        assert len(log.accepted) == 14
        assert [e.epoch for e in dyn.events] == [0, 1, 2]
        assert [e.at_push for e in dyn.events] == [0, 6, 12]
        assert log.max_staleness <= 1

    def test_validation(self):
        from repro.ps import DynamicAsyncPSTrainer
        params = small_cnn_init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="pushes_per_epoch"):
            DynamicAsyncPSTrainer(
                init_layers=params["layers"], loss_fn=_cnn_loss,
                optimizer=sgd(0.05), topology=self._schedule(),
                pushes_per_epoch=0)
        with pytest.raises(ValueError, match="num_pushes"):
            self._driver().run_pushes(0, _fixed_batch)


# ---------------------------------------------------------------------------
# multi-device sync-mode checks (subprocess, 4 forged devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestPSTrainerMultiDevice:
    @pytest.fixture(scope="class")
    def result(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "helpers",
                                          "ps_trainer_check.py")],
            capture_output=True, text=True, env=env, timeout=1200)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_losses_bit_identical_to_zero_trainer(self, result):
        """Sync-mode PS == ZeRO on the same plan, to the bit."""
        for strat, r in result["strategies"].items():
            assert r["losses"] == r["zero_losses"], strat

    def test_one_pull_one_push_per_segment(self, result):
        """HLO transfers == 2x segment count: one pull per forward
        segment, one push per backward segment (each the collectives of
        its leaves, under its own bucket scope), all strategies."""
        for strat, r in result["strategies"].items():
            assert r["pulled"] == r["fwd_segments"], (strat, r)
            assert r["pushed"] == r["bwd_segments"], (strat, r)
            assert r["pulled"] + r["pushed"] == \
                r["fwd_segments"] + r["bwd_segments"], (strat, r)

    def test_strategies_produce_distinct_segmentations(self, result):
        s = result["strategies"]
        assert s["sequential"]["fwd_segments"] == 1
        assert s["lbl"]["fwd_segments"] > s["sequential"]["fwd_segments"]

    def test_consensus_is_min_over_candidates(self, result):
        c = result["consensus"]
        assert c["makespan"] == pytest.approx(min(c["candidate_makespans"]))

    def test_dynacomm_beats_sequential_makespan(self, result):
        s = result["strategies"]
        assert s["dynacomm"]["makespan"] <= s["sequential"]["makespan"]
