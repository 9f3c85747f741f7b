"""Compressed push-pull: kernels, Compressor algebra, cost-model
re-segmentation, trainer threading, and wire accounting.

Kernel tests run the Pallas path in interpret mode and assert bit-exact
agreement with the pure-jnp oracles (the production CPU path), so the
TPU kernels and the jnp math can never drift apart.  Training tests
exercise the error-feedback residuals end-to-end on the smoke CNN.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.compress import (Compressor, Int8Compressor, TopKCompressor,
                            make_compressor)
from repro.kernels.compress.ops import (TILE, aligned, densify,
                                        dequantize_unpack, quantize_pack,
                                        sparsify, topk_indices)
from repro.kernels.compress.ref import (densify_ref, dequantize_unpack_ref,
                                        quantize_pack_ref, sparsify_ref)


def _segments(lengths, seed=0):
    key = jax.random.PRNGKey(seed)
    lmax = max(lengths)
    rows = [jnp.pad(jax.random.normal(jax.random.fold_in(key, i), (n,)),
                    (0, lmax - n))
            for i, n in enumerate(lengths)]
    return jnp.stack(rows), tuple(lengths)


# ---------------------------------------------------------------------------
# kernels vs oracles (bit-exact, interpret mode)
# ---------------------------------------------------------------------------


class TestQuantizeKernels:
    @pytest.mark.parametrize("lengths", [
        (512,), (512, 1024), (2048, 512, 512, 1024), (512,) * 7,
    ])
    def test_quantize_pack_matches_ref(self, lengths):
        segs, alens = _segments(lengths)
        payload, scales = quantize_pack(segs, alens)
        payload_ref, scales_ref = quantize_pack_ref(segs, alens)
        assert payload.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(payload),
                                      np.asarray(payload_ref))
        np.testing.assert_array_equal(np.asarray(scales),
                                      np.asarray(scales_ref))
        out = dequantize_unpack(payload, scales, alens, segs.shape[1])
        out_ref = dequantize_unpack_ref(payload_ref, scales_ref, alens,
                                        segs.shape[1])
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))

    def test_quantization_error_bounded_per_tile(self):
        segs, alens = _segments((1024, 512))
        out = dequantize_unpack(*quantize_pack(segs, alens), alens,
                                segs.shape[1])
        err = np.abs(np.asarray(out) - np.asarray(segs))
        tiles = np.asarray(segs).reshape(2, -1, TILE)
        absmax = np.abs(tiles).max(axis=2, keepdims=True)
        bound = np.broadcast_to(absmax / 127.0 * 0.5 + 1e-6,
                                tiles.shape).reshape(2, -1)
        assert (err <= bound).all()

    def test_zero_tile_stays_zero(self):
        segs = jnp.zeros((1, 512))
        payload, scales = quantize_pack(segs, (512,))
        out = dequantize_unpack(payload, scales, (512,), 512)
        np.testing.assert_array_equal(np.asarray(out), np.zeros((1, 512)))

    def test_padding_rows_zeroed(self):
        """Positions past a row's aligned length decode to exact zeros."""
        segs, alens = _segments((512, 1536))
        out = dequantize_unpack(*quantize_pack(segs, alens), alens,
                                segs.shape[1])
        np.testing.assert_array_equal(np.asarray(out)[0, 512:],
                                      np.zeros(1024))

    def test_bad_inputs_raise_value_error(self):
        from repro.kernels.compress.compress import (
            dequantize_unpack_pallas, quantize_pack_pallas)
        good = jnp.ones((2, 512))
        with pytest.raises(ValueError, match="float32"):
            quantize_pack_pallas(good.astype(jnp.bfloat16), (512, 512))
        with pytest.raises(ValueError, match="multiple of"):
            quantize_pack_pallas(jnp.ones((2, 100)), (512, 512))
        with pytest.raises(ValueError, match="aligned lengths"):
            quantize_pack_pallas(good, (512,))
        with pytest.raises(ValueError, match="must be \\(K, Lmax\\)"):
            quantize_pack_pallas(jnp.ones((512,)), (512,))
        payload, scales = quantize_pack_ref(good, (512, 512))
        with pytest.raises(ValueError, match="payload"):
            dequantize_unpack_pallas(payload[:-1], scales, (512, 512), 512)
        with pytest.raises(ValueError, match="scales"):
            dequantize_unpack_pallas(payload, scales[:-1], (512, 512), 512)


class TestTopKKernels:
    @pytest.mark.parametrize("lengths,k", [
        ((512,), 5), ((512, 1024), 32), ((256, 700, 513), 17),
    ])
    def test_sparsify_densify_match_refs(self, lengths, k):
        segs, _ = _segments(lengths, seed=3)
        idx = topk_indices(segs, lengths, k)
        vals = sparsify(segs, idx)
        vals_ref = sparsify_ref(segs, idx)
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(vals_ref))
        dense = densify(vals, idx, segs.shape[1])
        dense_ref = densify_ref(vals_ref, idx, segs.shape[1])
        np.testing.assert_array_equal(np.asarray(dense),
                                      np.asarray(dense_ref))

    def test_topk_selects_largest_magnitudes(self):
        row = jnp.asarray([[0.1, -5.0, 0.0, 3.0, -0.2, 2.0]])
        idx = topk_indices(row, (6,), 3)
        assert sorted(np.asarray(idx)[0].tolist()) == [1, 3, 5]
        dense = densify_ref(sparsify_ref(row, idx), idx, 6)
        np.testing.assert_array_equal(
            np.asarray(dense), [[0.0, -5.0, 0.0, 3.0, 0.0, 2.0]])

    def test_topk_short_rows_pad_minus_one(self):
        """Rows with fewer valid positions than k pad indices with -1,
        which sparsify/densify treat as 'no coordinate'."""
        segs = jnp.asarray([[1.0, 2.0, 0.0, 0.0]])
        idx = topk_indices(segs, (2,), 3)
        assert np.asarray(idx)[0].tolist() == [-1, 0, 1]
        dense = densify_ref(sparsify_ref(segs, idx), idx, 4)
        np.testing.assert_array_equal(np.asarray(dense),
                                      [[1.0, 2.0, 0.0, 0.0]])

    def test_topk_tie_breaks_to_lower_index(self):
        segs = jnp.asarray([[2.0, 2.0, 2.0, 1.0]])
        idx = topk_indices(segs, (4,), 2)
        assert np.asarray(idx)[0].tolist() == [0, 1]

    def test_bad_inputs_raise_value_error(self):
        from repro.kernels.compress.compress import (densify_pallas,
                                                     sparsify_pallas)
        segs = jnp.ones((2, 16))
        idx = jnp.zeros((2, 4), jnp.int32)
        with pytest.raises(ValueError, match="out of range"):
            topk_indices(segs, (16, 16), 0)
        with pytest.raises(ValueError, match="lengths"):
            topk_indices(segs, (16,), 4)
        with pytest.raises(ValueError, match="indices must be"):
            sparsify_pallas(segs, jnp.zeros((3, 4), jnp.int32))
        with pytest.raises(ValueError, match="integer"):
            sparsify_pallas(segs, idx.astype(jnp.float32))
        with pytest.raises(ValueError, match="indices must be"):
            densify_pallas(jnp.ones((3, 4)), idx, 16)


# ---------------------------------------------------------------------------
# Compressor algebra
# ---------------------------------------------------------------------------


class TestCompressor:
    def test_int8_wire_ratio(self):
        comp = Int8Compressor()
        # 1 byte per element + one fp32 scale per TILE ⇒ just under 4x
        assert comp.ratio(4 * TILE * 64) == pytest.approx(
            4.0 / (1.0 + 4.0 / TILE), rel=1e-12)
        assert comp.ratio(4 * TILE * 64) > 3.5
        np.testing.assert_allclose(
            comp.wire_bytes(np.asarray([4.0 * TILE, 8.0 * TILE])),
            [TILE + 4.0, 2 * TILE + 8.0])

    def test_topk_wire_ratio(self):
        comp = TopKCompressor(fraction=0.05)
        n = 10_000
        assert comp.wire_bytes(4.0 * n) == 8.0 * np.ceil(0.05 * n)
        assert comp.ratio(4.0 * n) == pytest.approx(
            4.0 * n / (8.0 * np.ceil(0.05 * n)))
        assert comp.segment_overhead_bytes == 8.0

    def test_identity_compressor(self):
        comp = Compressor()
        flat = jnp.arange(8.0)
        np.testing.assert_array_equal(np.asarray(comp.roundtrip(flat)),
                                      np.asarray(flat))
        assert comp.ratio(1234.0) == 1.0

    def test_kernel_and_ref_paths_bit_identical(self):
        flat = jax.random.normal(jax.random.PRNGKey(5), (1000,))
        for scheme, kw in (("int8", {}), ("topk", {"topk_fraction": 0.1})):
            a = make_compressor(scheme, use_kernel=True, **kw).roundtrip(flat)
            b = make_compressor(scheme, use_kernel=False, **kw).roundtrip(flat)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_error_feedback_algebra_exact(self):
        """compressed + residual' == flat + residual, exactly (the
        residual is literally what the wire dropped)."""
        comp = Int8Compressor(error_feedback=True)
        flat = jax.random.normal(jax.random.PRNGKey(1), (700,))
        residual = jax.random.normal(jax.random.PRNGKey(2), (700,)) * 1e-3
        compressed, new_res = comp.feedback_roundtrip(flat, residual)
        np.testing.assert_array_equal(
            np.asarray(compressed + new_res), np.asarray(flat + residual))

    def test_make_compressor_validation(self):
        with pytest.raises(ValueError, match="unknown compression scheme"):
            make_compressor("gzip")
        with pytest.raises(ValueError, match="topk_fraction"):
            make_compressor("int8", topk_fraction=0.1)
        with pytest.raises(ValueError, match="topk_fraction"):
            make_compressor("none", topk_fraction=0.1)
        with pytest.raises(ValueError, match="requires topk_fraction"):
            make_compressor("topk")
        with pytest.raises(ValueError, match="in \\(0, 1\\]"):
            make_compressor("topk", topk_fraction=1.5)

    def test_use_kernel_auto_detects_backend(self):
        from repro._compat.pallas import default_interpret
        comp = make_compressor("int8")
        # off-TPU the auto route is the jnp math; on TPU the fused kernels
        assert comp.use_kernel == (not default_interpret())
        assert default_interpret() == (jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# hypothesis properties
# ---------------------------------------------------------------------------

_floats = st.floats(-100.0, 100.0)
_vec = st.integers(1, 900).flatmap(
    lambda n: st.lists(_floats, min_size=n, max_size=n))
_Lvec = lambda L: st.lists(st.floats(0.0, 100.0), min_size=L, max_size=L)
_inst = st.integers(2, 8).flatmap(
    lambda L: st.tuples(_Lvec(L), _Lvec(L), _Lvec(L), _Lvec(L),
                        st.floats(0.0, 10.0)))


class TestCompressProperties:
    @settings(max_examples=50, deadline=None)
    @given(_vec)
    def test_int8_error_within_one_quantum_of_tile_absmax(self, values):
        flat = jnp.asarray(values, jnp.float32)
        out = np.asarray(Int8Compressor().roundtrip(flat))
        n = len(values)
        tiles = np.zeros((aligned(n),), np.float32)
        tiles[:n] = np.asarray(flat)
        tiles = tiles.reshape(-1, TILE)
        absmax = np.abs(tiles).max(axis=1)
        err = np.abs(out - np.asarray(flat))
        for t in range(tiles.shape[0]):
            lo, hi = t * TILE, min((t + 1) * TILE, n)
            if hi > lo:
                # per-element error ≤ half a quantum = absmax / (2·127)
                assert err[lo:hi].max() <= absmax[t] / 127.0 * 0.51 + 1e-6

    @settings(max_examples=50, deadline=None)
    @given(_inst, st.floats(0.05, 1.0), st.floats(0.05, 1.0))
    def test_makespan_monotone_in_compression_ratio(self, tup, r1, r2):
        """A strictly better push ratio can never worsen the DP optimum
        (costs shrink pointwise, so the optimal schedule's time does
        too) — the guarantee that lets the planner trust compressed gt."""
        from repro.core import LayerCosts, dp_backward
        pt, fc, bc, gt, dt = tup
        c = LayerCosts(pt=np.array(pt), fc=np.array(fc), bc=np.array(bc),
                       gt=np.array(gt), dt=dt)
        hi, lo = max(r1, r2), min(r1, r2)
        t_hi = dp_backward(c.compressed(gt_ratio=hi)).time
        t_lo = dp_backward(c.compressed(gt_ratio=lo)).time
        assert t_lo <= t_hi + 1e-9

    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 400), st.integers(1, 400))
    def test_int8_wire_bytes_monotone_and_below_fp32(self, a, b):
        comp = Int8Compressor()
        small, big = 4.0 * min(a, b), 4.0 * max(a, b)
        assert comp.wire_bytes(small) <= comp.wire_bytes(big)
        # one element is 1 int8 byte plus its tile's 4-byte scale: the
        # only size at which int8 is not smaller than fp32
        if big == 4.0:
            assert comp.wire_bytes(big) == 5.0
        else:
            assert comp.wire_bytes(big) < big


# ---------------------------------------------------------------------------
# cost model + planning under compression
# ---------------------------------------------------------------------------


class TestCompressedPlanning:
    def _topology(self, workers=4):
        from repro.ps import PSTopology, asymmetric_link
        return PSTopology(
            num_servers=2,
            links=tuple(asymmetric_link(10e9, 0.2e9) for _ in range(workers)),
            worker_flops=(1e10,) * workers)

    def _profiles(self):
        from repro.models.cnn import PAPER_CNNS
        return PAPER_CNNS["vgg19"](batch=32)

    def test_compressed_costs_shrink_gt_only(self):
        topo = self._topology()
        plain = topo.topology_costs(self._profiles())
        comp = topo.topology_costs(self._profiles(),
                                   compressor=Int8Compressor())
        for w in range(topo.num_workers):
            assert (comp.workers[w].gt < plain.workers[w].gt).all()
            np.testing.assert_array_equal(comp.workers[w].pt,
                                          plain.workers[w].pt)
            np.testing.assert_array_equal(comp.workers[w].fc,
                                          plain.workers[w].fc)

    def test_consensus_makespan_drops_under_int8(self):
        from repro.core.scheduler import consensus_decision
        topo = self._topology()
        _, plain = consensus_decision(topo.topology_costs(self._profiles()),
                                      "dynacomm")
        _, compressed = consensus_decision(
            topo.topology_costs(self._profiles(),
                                compressor=Int8Compressor()),
            "dynacomm")
        assert compressed < plain

    def test_topk_header_lands_in_dt_bwd(self):
        topo = self._topology(workers=1)
        comp = TopKCompressor(fraction=0.01)
        costs = topo.topology_costs(self._profiles(), compressor=comp)
        plain = topo.topology_costs(self._profiles())
        link_up = topo.links[0].up
        expect = link_up.dt + link_up.transfer_time(8.0)
        assert costs.workers[0].dt_bwd == pytest.approx(expect)
        assert plain.workers[0].dt_bwd == pytest.approx(link_up.dt)

    def test_layer_costs_compressed_validation(self):
        from repro.core import LayerCosts
        c = LayerCosts(pt=np.ones(3), fc=np.ones(3), bc=np.ones(3),
                       gt=np.ones(3), dt=0.1)
        with pytest.raises(ValueError, match="gt_ratio"):
            c.compressed(gt_ratio=0.0)
        with pytest.raises(ValueError, match="pt_ratio"):
            c.compressed(pt_ratio=1.5)
        with pytest.raises(ValueError, match="dt_bwd_extra"):
            c.compressed(dt_bwd_extra=-1.0)


# ---------------------------------------------------------------------------
# trainers end-to-end (smoke CNN + reduced text arch)
# ---------------------------------------------------------------------------


def _cnn_loss(layers, batch):
    from repro.models.cnn import small_cnn_loss
    return small_cnn_loss({"layers": layers}, batch["images"],
                          batch["labels"])


def _fixed_batch(*_):
    r = np.random.default_rng(7)
    return {"images": jnp.asarray(r.normal(size=(8, 32, 32, 3)), jnp.float32),
            "labels": jnp.asarray(r.integers(0, 10, size=(8,)), jnp.int32)}


def _async_trainer(compressor, optimizer=None, workers=3, staleness=1):
    from repro.core import plan_from_decision
    from repro.models.cnn import small_cnn_init
    from repro.optim import sgd
    from repro.ps import AsyncPSTrainer, PSTopology, asymmetric_link
    params = small_cnn_init(jax.random.PRNGKey(0))
    L = len(params["layers"])
    plan = plan_from_decision(((1, 3), (4, L)), ((4, L), (1, 3)), L)
    topo = PSTopology(
        num_servers=2,
        links=tuple(asymmetric_link(10e9, 1e9) for _ in range(workers)),
        worker_flops=(1e10,) * workers)
    return AsyncPSTrainer(init_layers=params["layers"], loss_fn=_cnn_loss,
                          optimizer=optimizer or sgd(0.02), topology=topo,
                          plan=plan, staleness=staleness,
                          compressor=compressor)


class TestCompressedAsyncTraining:
    def test_int8_ef_final_loss_within_2pct_of_fp32(self):
        base = _async_trainer(None).run(30, _fixed_batch).losses
        i8 = _async_trainer(make_compressor("int8")).run(
            30, _fixed_batch).losses
        assert base[-1] < base[0] * 0.55          # both actually train
        assert abs(i8[-1] - base[-1]) <= 0.02 * abs(base[-1])

    def test_topk_ef_converges(self):
        tr = _async_trainer(make_compressor("topk", topk_fraction=0.1))
        losses = tr.run(30, _fixed_batch).losses
        assert losses[-1] < losses[0] * 0.75

    def test_push_wire_ratio_exceeds_3_5x_at_int8(self):
        tr = _async_trainer(make_compressor("int8"))
        tr.run(12, _fixed_batch)
        led = tr.server.ledger
        assert led.compression_ratio("push") > 3.5
        # pulls stay fp32
        assert led.compression_ratio("pull") == pytest.approx(1.0)
        assert sum(led.pushed_wire_bytes.values()) < \
            sum(led.pushed_bytes.values())

    def test_scheme_none_is_normalized_away(self):
        tr = _async_trainer(make_compressor("none"))
        assert tr.compressor is None
        assert tr.server.compressor is None

    def test_residuals_reset_with_loop(self):
        tr = _async_trainer(make_compressor("int8"))
        tr.run(6, _fixed_batch)
        assert tr._residuals
        tr.reset_loop()
        assert not tr._residuals

    def test_dynamic_async_replans_with_compressed_costs(self):
        from repro.models.cnn import small_cnn_init
        from repro.optim import sgd
        from repro.ps import DynamicAsyncPSTrainer, PSTopology, \
            asymmetric_link, uplink_degradation
        params = small_cnn_init(jax.random.PRNGKey(0))
        topo = uplink_degradation(
            PSTopology(num_servers=2,
                       links=tuple(asymmetric_link(10e9, 1e9)
                                   for _ in range(3)),
                       worker_flops=(1e10,) * 3),
            factor=4.0, at_epoch=1)
        tr = DynamicAsyncPSTrainer(
            init_layers=params["layers"], loss_fn=_cnn_loss,
            optimizer=sgd(0.02), topology=topo, pushes_per_epoch=4,
            staleness=1, compressor=make_compressor("int8"))
        log = tr.run_pushes(8, _fixed_batch)
        assert len(log.accepted) == 8
        assert tr.compressor is not None
        # every epoch's planning costs carry the compressed gt
        c0 = tr.costs_for_epoch(0)
        plain = topo.topology_at(0).topology_costs(tr._profiles)
        assert (c0.workers[0].gt < plain.workers[0].gt).all()


class TestCompressedSyncTraining:
    def _trainer(self, compressor):
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core.buckets import BucketPlan
        from repro.models import num_sched_layers
        from repro.optim import sgd
        from repro.ps import PSTopology, PSTrainer
        cfg = get_config("granite-3-2b").reduced()
        Ls = num_sched_layers(cfg)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        plan = BucketPlan(forward=(tuple(range(Ls)),),
                          backward=(tuple(range(Ls - 1, -1, -1)),))
        return cfg, PSTrainer(cfg=cfg, mesh=mesh, plan=plan,
                              optimizer=sgd(0.05),
                              topology=PSTopology.uniform(2, 1),
                              compressor=compressor)

    def _batch(self, cfg):
        key = jax.random.PRNGKey(3)
        toks = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def test_int8_ef_state_carries_residuals_and_trains(self):
        cfg, tr = self._trainer(make_compressor("int8"))
        state = tr.init_state(jax.random.PRNGKey(0))
        assert "residuals" in state
        assert len(state["residuals"]) == tr.num_layers
        for l, spec in enumerate(tr.specs):
            assert state["residuals"][l].shape == (1, spec.padded)
        step = jax.jit(tr.build_train_step())
        batch = self._batch(cfg)
        losses = []
        for _ in range(4):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        # residuals are live: after a step they hold quantization error
        assert float(jnp.abs(state["residuals"][0]).max()) > 0

    def test_no_error_feedback_keeps_state_shape(self):
        _, tr = self._trainer(make_compressor("int8", error_feedback=False))
        state = tr.init_state(jax.random.PRNGKey(0))
        assert "residuals" not in state

    def test_wire_byte_views(self):
        _, tr = self._trainer(make_compressor("int8"))
        logical = tr.transfer_bytes()
        wire = tr.transfer_wire_bytes()
        assert wire["pull"] == logical["pull"]
        assert 3.5 < logical["push"] / wire["push"] < 4.0

    def test_from_topology_plans_with_compressed_costs(self):
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.optim import sgd
        from repro.ps import PSTopology, PSTrainer, asymmetric_link
        cfg = get_config("granite-3-2b").reduced()
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        topo = PSTopology(num_servers=2,
                          links=(asymmetric_link(10e9, 0.05e9),),
                          worker_flops=(1e10,))
        shape = InputShape("t", 16, 4, "train")
        tr = PSTrainer.from_topology(cfg, mesh, topo, sgd(0.05), shape,
                                     compressor=make_compressor("int8"))
        assert tr.compressor is not None
        costs = tr.topology_costs(shape)
        plain = topo.topology_costs(
            __import__("repro.models.profiles",
                       fromlist=["layer_profiles"]).layer_profiles(cfg, shape))
        assert (costs.workers[0].gt < plain.workers[0].gt).all()


# ---------------------------------------------------------------------------
# TransferLedger wire accounting
# ---------------------------------------------------------------------------


class TestLedgerWireAccounting:
    def _ledger(self):
        from repro.ps.server import TransferLedger
        return TransferLedger()

    def test_wire_defaults_to_logical(self):
        led = self._ledger()
        led.record_push(0, 1000)
        led.record_pull(0, 500)
        assert led.pushed_wire_bytes[0] == 1000
        assert led.pulled_wire_bytes[0] == 500
        assert led.compression_ratio("push") == 1.0

    def test_per_worker_and_direction_ratios(self):
        led = self._ledger()
        led.record_push(0, 1000, wire_bytes=250)
        led.record_push(1, 1000, wire_bytes=500)
        led.record_pull(0, 1000, wire_bytes=1000)
        assert led.compression_ratio("push", worker=0) == 4.0
        assert led.compression_ratio("push", worker=1) == 2.0
        assert led.compression_ratio("push") == pytest.approx(2000 / 750)
        assert led.compression_ratio("pull") == 1.0

    def test_empty_ledger_ratio_is_one(self):
        assert self._ledger().compression_ratio("push") == 1.0

    def test_bad_direction_raises(self):
        with pytest.raises(ValueError, match="direction"):
            self._ledger().compression_ratio("sideways")


# ---------------------------------------------------------------------------
# runtime config + launcher threading
# ---------------------------------------------------------------------------


class TestCompressionConfig:
    def test_validation(self):
        from repro.runtime import CompressionConfig
        with pytest.raises(ValueError, match="unknown compression scheme"):
            CompressionConfig(scheme="gzip")
        with pytest.raises(ValueError, match="topk_fraction"):
            CompressionConfig(scheme="topk")
        with pytest.raises(ValueError, match="in \\(0, 1\\]"):
            CompressionConfig(scheme="topk", topk_fraction=2.0)
        with pytest.raises(ValueError, match="topk_fraction"):
            CompressionConfig(scheme="int8", topk_fraction=0.1)

    def test_build(self):
        from repro.runtime import CompressionConfig
        assert CompressionConfig().build() is None
        comp = CompressionConfig(scheme="topk", topk_fraction=0.05,
                                 error_feedback=False).build()
        assert comp.scheme == "topk"
        assert comp.fraction == 0.05
        assert comp.error_feedback is False

    def test_json_round_trip(self):
        from repro.runtime import (CompressionConfig, RuntimeConfig,
                                   ScheduleConfig, TopologyConfig)
        cfg = RuntimeConfig(
            runtime="ps",
            schedule=ScheduleConfig(topology=TopologyConfig()),
            compression=CompressionConfig(scheme="int8"))
        assert RuntimeConfig.from_json(cfg.to_json()) == cfg
        assert cfg.compression.enabled

    def test_compression_rejected_on_non_ps_runtimes(self):
        from repro.runtime import CompressionConfig, RuntimeConfig
        with pytest.raises(ValueError, match="ps-\\*"):
            RuntimeConfig(runtime="zero",
                          compression=CompressionConfig(scheme="int8"))
        with pytest.raises(ValueError, match="ps-\\*"):
            RuntimeConfig(runtime="local",
                          compression=CompressionConfig(scheme="int8"))

    def test_launcher_flags_map_to_config(self):
        import argparse
        from repro.launch.train import config_from_flags
        args = argparse.Namespace(
            runtime="ps", staleness=1, arch="granite-3-2b", reduced=True,
            batch=4, seq=16, optimizer="adamw", lr=3e-4,
            strategy="dynacomm", steps_per_epoch=20, drift_detect=False,
            async_planning=False, plan_cache_size=256,
            bw_gbps=10.0, bw_shift_gbps=None, shift_epoch=1,
            cost_source="analytic", ps_servers=2, ps_workers=3,
            down_gbps=10.0, up_gbps=1.0, up_shift_gbps=None,
            worker_flops=1e10, throttle="reject", aggregate=False,
            compress="topk", topk_fraction=0.02, no_error_feedback=True,
            fleet_schedule=None, workers_per_shard=0)
        cfg = config_from_flags(args)
        assert cfg.runtime == "ps-async"        # staleness upgrades
        assert cfg.compression.scheme == "topk"
        assert cfg.compression.topk_fraction == 0.02
        assert cfg.compression.error_feedback is False
        args.compress = "int8"
        cfg = config_from_flags(args)
        assert cfg.compression.scheme == "int8"
        assert cfg.compression.topk_fraction is None


# ---------------------------------------------------------------------------
# fit() eval hook
# ---------------------------------------------------------------------------


class TestEvalHook:
    def test_eval_every_validation(self):
        from repro.runtime.adapters import RuntimeAdapter
        with pytest.raises(ValueError, match="eval_every"):
            RuntimeAdapter._check_eval(lambda: 0.0, 0)
        RuntimeAdapter._check_eval(None, 0)     # no eval_fn: fine

    def test_sync_runtime_records_eval_events(self):
        from repro.configs import get_config
        from repro.runtime import EvalEvent, RuntimeConfig, build_runtime
        cfg = RuntimeConfig(runtime="local", reduced=True, batch=2, seq=16)
        vocab = get_config(cfg.arch).reduced().vocab_size

        def batch_fn(i):
            r = np.random.default_rng(i)
            toks = jnp.asarray(r.integers(0, vocab, (2, 16)), jnp.int32)
            return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

        rt = build_runtime(cfg, data=batch_fn)
        evals = []
        rt.fit(4, eval_fn=lambda: evals.append(1) or 0.25, eval_every=2)
        events = [e for e in rt.events if isinstance(e, EvalEvent)]
        assert len(events) == len(evals) == 2
        assert [e.unit for e in events] == [2, 4]
        assert all(e.loss == 0.25 for e in events)
