"""The benchmark's HLO collective count and its traffic generator."""

import numpy as np

from chipbench import data, hlo

TPU_HLO = """\
%all-gather-start.1 = (f32[1,512]{1,0:T(1,128)}, f32[4,512]{1,0:T(4,128)}) all-gather-start(f32[1,512]{1,0:T(1,128)} %p.1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
%all-gather-done.1 = f32[4,512]{1,0:T(4,128)} all-gather-done((f32[1,512]{1,0:T(1,128)}, f32[4,512]{1,0:T(4,128)}) %all-gather-start.1)
%all-reduce.2 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0:T(1024)} %g), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add
%all-reduce.3 = f32[]{:T(128)} all-reduce(f32[]{:T(128)} %loss), to_apply=%add
%reduce-scatter.4 = f32[256]{0} reduce-scatter(%g2), dimensions={0}
%g2 = f32[1024]{0} parameter(0)
ROOT %all-gather.5 = f32[4,8]{1,0} all-gather(%p2), dimensions={0}
%p2 = f32[1,8]{1,0} parameter(1)
"""


def test_collectives_with_tpu_layouts():
    # the async pair counts once, by its start; bare operands resolve to
    # their definitions, wherever those lie
    assert hlo.collectives(TPU_HLO) == {
        "all-gather": [2048, 32], "all-reduce": [4096, 4],
        "reduce-scatter": [4096], "all-to-all": [],
        "collective-permute": []}


def test_pushes_are_reduce_scatters_and_large_all_reduces():
    assert hlo.pulls_and_pushes(TPU_HLO) == {
        "pulls": 2, "pull_bytes": 2080, "pushes": 2, "push_bytes": 8192,
        "small_all_reduces": 1}


TRAFFIC = {"batch_per_chip": 8, "seq": 64, "zipf_exponent": 1.0,
           "labels": "permuted", "ring": 6}


def test_ring_is_a_function_of_the_seed():
    seed = 2 ** 31 + 12345
    a = data.make_ring(TRAFFIC, 1000, 4, seed)
    b = data.make_ring(TRAFFIC, 1000, 4, seed)
    c = data.make_ring(TRAFFIC, 1000, 4, seed + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    tokens, labels = a
    assert tokens.shape == labels.shape == (6, 32, 64)
    assert tokens.dtype == labels.dtype == np.int32
    assert 0 <= tokens.min() and tokens.max() < 1000


def test_rows_differ_and_labels_follow_tokens():
    tokens, labels = data.make_ring(TRAFFIC, 1000, 1, 7)
    rows = tokens.reshape(-1, 64)
    assert len({r.tobytes() for r in rows}) == len(rows)
    # one fixed successor per token
    pairs = {}
    for t, l in zip(tokens.ravel(), labels.ravel()):
        assert pairs.setdefault(t, l) == l


def test_tokens_follow_zipf():
    tokens, _ = data.make_ring(dict(TRAFFIC, ring=50), 1000, 1, 3)
    counts = np.bincount(tokens.ravel(), minlength=1000)
    share = counts[0] / counts.sum()
    expected = 1.0 / np.sum(1.0 / np.arange(1, 1001))
    assert abs(share - expected) < 0.01
    assert counts[0] > counts[1] > counts[9]


def test_global_batch_is_the_chips_rows():
    assert data.global_batch({"batch_per_chip": 8}, 1) == 8
    assert data.global_batch({"batch_per_chip": 8}, 4) == 32
