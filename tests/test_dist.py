"""Distribution-layer tests.

Single-device: flat-spec plumbing, sharding rules, bucket plans.
Multi-device (4 forged host devices, via subprocess so the main pytest
process keeps its single-device jax): the DynaComm ZeRO trainer's
structural and numerical claims.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, get_config
from repro.dist.collectives import (flatten_tree, make_flat_spec,
                                    unflatten_tree)
from repro.dist.sharding import param_pspec
from repro.models import init_params, sched_layer_trees

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFlatSpecs:
    @pytest.mark.parametrize("axis_size", [2, 4, 8])
    def test_flatten_roundtrip(self, axis_size):
        cfg = get_config("gemma2-2b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        for tree in sched_layer_trees(params):
            spec = make_flat_spec(tree, axis_size)
            assert spec.padded % axis_size == 0
            flat = flatten_tree(tree, spec)
            assert flat.shape == (spec.padded,)
            back = unflatten_tree(flat, spec)
            for a, b in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(back)):
                assert a.dtype == b.dtype
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-7)


class TestBucketValidation:
    """gather/reduce-scatter bucket preconditions fail fast with clear
    messages instead of index-erroring (or silently mixing shard layouts)."""

    def _specs(self, axis_sizes):
        tree = {"w": jnp.ones((4, 4))}
        return [make_flat_spec(tree, a) for a in axis_sizes]

    def test_empty_bucket_rejected(self):
        from repro.dist.collectives import (gather_bucket,
                                            reduce_scatter_bucket)
        specs = self._specs([2, 2])
        with pytest.raises(ValueError, match="empty bucket"):
            gather_bucket([jnp.ones(8)] * 2, specs, (), "data")
        with pytest.raises(ValueError, match="empty bucket"):
            reduce_scatter_bucket({}, specs, (), "data")

    def test_unknown_layer_rejected(self):
        from repro.dist.collectives import gather_bucket
        specs = self._specs([2, 2])
        with pytest.raises(ValueError, match="unknown layers"):
            gather_bucket([jnp.ones(8)] * 2, specs, (0, 5), "data")

    def test_mixed_axis_size_rejected(self):
        from repro.dist.collectives import (gather_bucket,
                                            reduce_scatter_bucket)
        specs = self._specs([2, 4])
        with pytest.raises(ValueError, match="mixes axis sizes"):
            gather_bucket([jnp.ones(8), jnp.ones(4)], specs, (0, 1), "data")
        grads = {l: {"w": jnp.ones((4, 4))} for l in (0, 1)}
        with pytest.raises(ValueError, match="mixes axis sizes"):
            reduce_scatter_bucket(grads, specs, (0, 1), "data")


class TestShardingRules:
    def test_canonical_dims(self):
        kw = dict(model_axis="model", data_axes=("data",), model_size=16,
                  data_size=16)
        # mlp up: (d, f) → f over model, d over data
        spec = param_pspec("layers/0/mlp/up", (2048, 8192), **kw)
        assert spec == jax.sharding.PartitionSpec("data", "model")
        # wo: (q_dim, d) → model on dim0
        spec = param_pspec("layers/0/attn/wo", (4096, 2048), **kw)
        assert spec == jax.sharding.PartitionSpec("model", "data")
        # norm scale: indivisible → replicated
        spec = param_pspec("layers/0/norm1", (17,), **kw)
        assert spec == jax.sharding.PartitionSpec(None,)

    def test_stacked_offset(self):
        kw = dict(model_axis="model", data_axes=("data",), model_size=16,
                  data_size=16, dim_offset=1)
        spec = param_pspec("stack/0/mlp/up", (40, 2048, 8192), **kw)
        assert spec == jax.sharding.PartitionSpec(None, "data", "model")

    def test_indivisible_falls_back(self):
        kw = dict(model_axis="model", data_axes=("data",), model_size=16,
                  data_size=16)
        # kv proj with kv_dim 8 (< 16): replicate model, data on dim0
        spec = param_pspec("layers/0/attn/wk", (2048, 8), **kw)
        assert spec == jax.sharding.PartitionSpec("data", None)

    @pytest.mark.parametrize("arch", sorted(ARCHITECTURES))
    def test_all_full_configs_get_specs(self, arch):
        """Every full-size param leaf gets a valid, divisible spec."""
        from repro.dist.sharding import params_shardings
        from jax.sharding import Mesh
        import numpy as np

        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda k: init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0))
        devs = np.array(jax.devices() * 1)

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        def rule(path, leaf):
            ps = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                          for p in path)
            spec = param_pspec(ps, tuple(leaf.shape), model_axis="model",
                               data_axes=("data",), model_size=16,
                               data_size=16)
            for dim, ax in enumerate(spec):
                if ax is not None:
                    assert leaf.shape[dim] % 16 == 0, (arch, ps, leaf.shape)
            return spec

        jax.tree_util.tree_map_with_path(rule, shapes)


class TestOneDeviceStateLayout:
    """On a one-device data axis with no compressor the ZeRO state is the
    parameter leaves (no wire, so no pack or unpack); a compressor keeps
    the flat wire format there."""

    B1 = 0.9

    def _trainer(self, compress):
        from jax.sharding import Mesh
        from repro.compress import make_compressor
        from repro.core import plan_from_decision, random_costs, schedule
        from repro.dist.zero import ZeroTrainer
        from repro.models import num_sched_layers
        from repro.optim import adamw
        cfg = get_config("granite-3-2b").reduced()
        Ls = num_sched_layers(cfg)
        plan = plan_from_decision(
            *schedule(random_costs(Ls, seed=0, dt=1e-3), "dynacomm"), Ls)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        compressor = None if compress is None else make_compressor(compress)
        return cfg, ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan,
                                optimizer=adamw(1e-3, b1=self.B1),
                                compressor=compressor)

    @pytest.mark.parametrize("compress,layout",
                             [(None, "leaves"), ("int8", "flat")])
    def test_params_from_state_is_init_params(self, compress, layout):
        cfg, tr = self._trainer(compress)
        assert tr.layout == layout
        key = jax.random.PRNGKey(7)
        state = tr.init_state(key)
        # init_state draws the weights inside jit, which may round
        # differently from eager dispatch: compare like with like
        want = jax.jit(lambda k: init_params(cfg, k))(key)
        got = tr.params_from_state(state)
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(want)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the benchmark's reading of the first moment as a parameter tree
        mu = state["opt"].mu
        moment = tr.params_from_state(
            {"flat_params": [m / (1.0 - self.B1) for m in mu]})
        assert jax.tree_util.tree_structure(moment) == \
            jax.tree_util.tree_structure(want)
        assert [x.shape for x in jax.tree_util.tree_leaves(moment)] == \
            [x.shape for x in jax.tree_util.tree_leaves(want)]

    @pytest.mark.parametrize("compress,packs",
                             [(None, False), ("int8", True)])
    def test_one_device_step_packs_only_with_a_wire(self, compress, packs):
        """No 1-D f32 value as large as a sched layer's flat buffer is left
        in the compiled leaves step (the flat one is the control)."""
        import re
        cfg, tr = self._trainer(compress)
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
        state = tr.init_state(jax.random.PRNGKey(0))
        text = jax.jit(tr.build_train_step()).lower(
            state, batch).compile().as_text()
        # a layer whose flat buffer is its one 1-D leaf is no pack
        smallest = min(s.padded for s in tr.specs
                       if s.num_leaves > 1 or len(s.shapes[0]) > 1)
        big = sorted({int(n) for n in re.findall(r"\bf32\[(\d+)\]", text)
                      if int(n) >= smallest})
        assert bool(big) == packs, (tr.layout, smallest, big)


class TestFourDeviceStateShards:
    """On a four-device data axis the ZeRO state holds each weight once:
    every leaf split on a dimension the axis divides (the vocabulary
    tables on their width, since 49155 rows do not split four ways), but
    the one leaf no dimension divides.  Shapes only, at published widths
    and depths: the mesh is abstract, nothing is allocated."""

    #: leaves of the final sched layer (norm, head, exit gate) held whole
    HELD_WHOLE = {"granite-3-2b": set(), "granite-moe-1b-a400m": set(),
                  "ouro-2.6b": {"gate_bias"}}

    @pytest.mark.parametrize("arch", sorted(HELD_WHOLE))
    def test_each_weight_held_once_in_shards(self, arch):
        import math
        from jax.sharding import AbstractMesh
        from repro.dist.zero import ZeroTrainer
        from repro.models import num_sched_layers
        from repro.optim import adamw
        from repro.runtime.replan import sequential_plan
        cfg = get_config(arch)
        tr = ZeroTrainer(cfg=cfg, mesh=AbstractMesh((4,), ("data",)),
                         plan=sequential_plan(num_sched_layers(cfg)),
                         optimizer=adamw(1e-3))
        assert tr.layout == "leaves"
        state = tr.state_structs()
        params = jax.eval_shape(lambda k: init_params(cfg, k),
                                jax.random.PRNGKey(0))
        paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path) for path, _ in
                 jax.tree_util.tree_flatten_with_path(
                     sched_layer_trees(params))[0]]
        # sched_layer_trees lists a layer's leaves in the state's order
        leaves = state["flat_params"]
        assert len(paths) == len(leaves)
        last = num_sched_layers(cfg) - 1
        held_whole = {f"{last}/{k}" for k in self.HELD_WHOLE[arch]}
        assert {p for p, s in zip(paths, leaves)
                if "data" not in tuple(s.sharding.spec)} == held_whole
        table = leaves[0]
        assert table.shape[0] == cfg.vocab_size
        assert "data" in tuple(table.sharding.spec)
        # the moments split as their parameters do
        for mu, p in zip(state["opt"].mu, leaves):
            assert mu.sharding.spec == p.sharding.spec
        held = sum(math.prod(s.sharding.shard_shape(s.shape))
                   for s in leaves)
        total = sum(math.prod(s.shape) for s in leaves)
        kept = sum(math.prod(s.shape) for p, s in zip(paths, leaves)
                   if p in held_whole)
        assert 4 * held == total + 3 * kept
        assert kept <= 1

    @pytest.mark.parametrize("arch,axis,whole", [
        ("granite-3-2b", 4, 0), ("ouro-2.6b", 4, 1), ("granite-3-2b", 3, None)])
    def test_leaves_held_whole_are_reported(self, arch, axis, whole):
        """Building the trainer says how many leaves (and bytes) the axis
        leaves unsplit: none for granite on four devices, Ouro's gate bias,
        and on three devices most of granite, whose widths 3 divides not."""
        import warnings
        from jax.sharding import AbstractMesh
        from repro.dist.zero import ZeroTrainer
        from repro.models import num_sched_layers
        from repro.optim import adamw
        from repro.runtime.replan import sequential_plan
        cfg = get_config(arch)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            ZeroTrainer(cfg=cfg, mesh=AbstractMesh((axis,), ("data",)),
                        plan=sequential_plan(num_sched_layers(cfg)),
                        optimizer=adamw(1e-3))
        said = [str(w.message) for w in caught if "held whole" in str(w.message)]
        if whole == 0:
            assert said == []
            return
        assert len(said) == 1
        count = int(said[0].split(" of ")[0])
        if whole is not None:
            assert count == whole
            assert "(4 of " in said[0]
        else:
            assert count > 10


@pytest.mark.slow
class TestZeroTrainerMultiDevice:
    @pytest.fixture(scope="class")
    def result(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "helpers",
                                          "zero_trainer_check.py")],
            capture_output=True, text=True, env=env, timeout=1200)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_collective_counts_match_buckets(self, result):
        """Each bucket's pull is an all-gather of each of its split
        leaves, its push a reduce-scatter of each (an all-reduce of each
        leaf held whole), every one under its own bucket's scope; the
        compressor's flat wire keeps one collective per bucket."""
        for strat, r in result["strategies"].items():
            assert r["scopes"] == r["expected_scopes"], (strat, r)
            assert len([k for k in r["scopes"] if k.startswith("pull.")]) \
                == r["fwd_buckets"], (strat, r)
            assert len([k for k in r["scopes"] if k.startswith("push.")]) \
                == r["bwd_buckets"], (strat, r)
        r = result["int8"]
        assert r["layout"] == "flat"
        assert r["ag"] == r["fwd_buckets"], r
        assert r["rs"] == r["bwd_buckets"], r

    def test_losses_bit_identical_across_schedules(self, result):
        """Paper Fig. 10 'accuracy untouched', strengthened to exactness."""
        seqs = [r["losses"] for r in result["strategies"].values()]
        for other in seqs[1:]:
            assert other == seqs[0]

    def test_matches_single_device_reference(self, result):
        ref = result["reference_losses"]
        dyn = result["strategies"]["dynacomm"]["losses"]
        np.testing.assert_allclose(dyn, ref, rtol=2e-5)

    def test_layout_follows_mesh_and_compressor(self, result):
        assert result["layouts"] == {"4dev": "leaves", "1dev": "leaves",
                                     "1dev_int8": "flat"}

    def test_one_device_leaves_match_four_devices(self, result):
        """The leaves layout on one device trains as the sharded leaves on
        four do, on the same global batch."""
        np.testing.assert_allclose(
            result["one_device_losses"],
            result["strategies"]["dynacomm"]["losses"], rtol=2e-5)

    def test_push_without_exchange_is_caught(self, result):
        """A push that exchanges nothing, planted through the module-level
        ``zero.reduce_scatter_bucket``, does not follow the one-device
        run; the real push does, to fp32 roundoff."""
        r = result["no_exchange"]
        assert r["four_device_gap"] < 1e-4, r
        assert r["fault_gap"] > 1e-3, r
        assert r["fault_gap"] > 100 * r["four_device_gap"], r

    def test_four_device_state_reads_back_as_init(self, result):
        """``params_from_state`` of a ``jax.device_get`` copy of the
        sharded leaves equals ``init_params``, bit for bit."""
        assert result["round_trip"] == {
            "granite-3-2b": True, "granite-moe-1b-a400m": True,
            "ouro-2.6b": True}

    def test_bucket_structure_differs(self, result):
        s = result["strategies"]
        assert s["sequential"]["fwd_buckets"] == 1
        assert s["lbl"]["fwd_buckets"] > s["dynacomm"]["fwd_buckets"] >= 1 \
            or s["dynacomm"]["fwd_buckets"] >= 1

    def test_zero3_regather_mode(self, result):
        """ZeRO-3: backward re-pulls appear per D_b bucket; math unchanged."""
        z3 = result["zero3"]
        assert z3["scopes"] == z3["expected_scopes"]
        assert z3["losses"] == result["strategies"]["dynacomm"]["losses"]
