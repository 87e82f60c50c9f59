"""The data-parallel learner (``tree_learner=data``) over four chips of the
virtual CPU mesh, through ``engine.train``, held to the serial learner and
to the plain reference bench/reference_data_parallel.py.

Each chip partitions its own rows; a leaf's count is the int32 psum of the
chips' partition counts, and a histogram the psum of the chips' parts. On
gradients and hessians that are multiples of 2**-10
every float32 sum is exact in any order, so the mesh grows the serial
learner's trees split for split, and its counts are equal to them, not
close.
"""
import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
from bench import reference_data_parallel as dp_ref
from lightgbm_tpu.analysis import jaxpr_audit
from lightgbm_tpu.core import grow
from lightgbm_tpu.obs import trace

CHIPS = 4
N = 2000                     # 500 rows a chip: no padding
BASE = {"num_leaves": 15, "min_data_in_leaf": 5, "max_bin": 31,
        "learning_rate": 0.3, "verbosity": -1}
MESH = {"tree_learner": "data", "mesh_shape": [CHIPS]}


def table(seed, n=N):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6))
    X[rng.random((n, 6)) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1]) ** 2
         + rng.normal(size=n) > 0.5).astype(np.float64)
    return X, y


def dyadic_logloss(preds, data):
    """The log loss's gradient and hessian rounded to multiples of 2**-10."""
    y = data.get_label()
    p = 1.0 / (1.0 + np.exp(-np.asarray(preds, np.float64)))
    g = np.round((p - y) * 1024) / 1024
    h = np.maximum(np.round(p * (1 - p) * 1024), 1) / 1024
    return g.astype(np.float32), h.astype(np.float32)


def train(X, y, extra, rounds=3, fobj=dyadic_logloss):
    params = dict(BASE, **extra)
    if fobj is None:
        params["objective"] = "binary"
    return lgb.train(params, lgb.Dataset(X, y, params=dict(params)),
                     num_boost_round=rounds, fobj=fobj)


def newest():
    return max((s["id"] for s in trace.recorded_spans()), default=-1)


def spans_after(mark, name):
    return [s["counts"] for s in trace.recorded_spans()
            if s["id"] > mark and s["name"] == name]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_mesh_grows_the_serial_trees_with_equal_counts(seed):
    X, y = table(seed)
    serial = train(X, y, {})._impl.models
    mesh = train(X, y, MESH)
    assert mesh._impl.plan.partition_on_mesh
    assert mesh._impl.grow_params.all_rows_in_bag
    for a, b in zip(serial, mesh._impl.models):
        m = a.num_leaves_actual
        assert m == b.num_leaves_actual > 8
        for field in ("split_feature", "threshold_bin", "default_left",
                      "left_child", "right_child", "internal_count"):
            np.testing.assert_array_equal(getattr(a, field)[:m - 1],
                                          getattr(b, field)[:m - 1], field)
        np.testing.assert_array_equal(a.leaf_count[:m], b.leaf_count[:m])
        np.testing.assert_allclose(a.leaf_value[:m], b.leaf_value[:m],
                                   rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("seed", [4, 5])
def test_the_mesh_against_the_reference_and_its_shares(seed):
    """Every row routed by its raw value over the whole table; the four
    chips' blocks of rows add up, leaf by leaf, to the model's counts
    exactly and to its leaves' hessian sums and values."""
    X, y = table(seed)
    bst = train(X, y, MESH, fobj=None)
    params = dict(BASE, objective="binary")
    trees = dp_ref.parse_trees(bst.model_to_string())
    search = {"cells": 64, "search_rows": N, "min_data": 5,
              "zero_as_missing": False}
    got = dp_ref.readings(X, y, trees, params["learning_rate"], 0.0,
                          dp_ref.ref.draw_nodes(seed, trees, 3), search)
    assert got["count_mismatch"] == 0
    assert got["leaf_value_gap"] < 1e-4 and got["split_gain_gap"] < 1e-3
    shared = dp_ref.shares(X, y, trees, params["learning_rate"], 0.0, CHIPS)
    for t, per, host in zip(trees, shared, bst._impl.models):
        m = t["num_leaves"]
        assert per["count"].shape == (CHIPS, m)
        # each chip's share is its own block of rows
        assert (per["count"].sum(axis=1) == N // CHIPS).all()
        np.testing.assert_array_equal(per["count"].sum(axis=0),
                                      t["leaf_count"])
        np.testing.assert_allclose(per["hess"].sum(axis=0),
                                   host.leaf_weight[:m], rtol=2e-5)


def test_the_whole_tables_sums_are_the_shards_sums():
    X, y = table(6)
    trees = dp_ref.parse_trees(train(X, y, {}, fobj=None).model_to_string())
    one, four = (dp_ref.shares(X, y, trees, 0.3, 0.0, s) for s in (1, 4))
    for a, b in zip(one, four):
        np.testing.assert_array_equal(a["count"][0], b["count"].sum(axis=0))
        for k in ("grad", "hess"):
            np.testing.assert_allclose(a[k][0], b[k].sum(axis=0),
                                       rtol=1e-12, atol=1e-12)
    assert dp_ref.shard_bounds(10, 4) == [(0, 3), (3, 6), (6, 9), (9, 10)]


def test_set_up_says_the_mesh_counts_are_the_partitions_integers():
    X, y = table(7)
    cases = (({}, N, 0, 0), (MESH, N, CHIPS, 1),
             # a row count the mesh does not divide is padded: float counts
             (MESH, N - 1, CHIPS, 0))
    for extra, n, chips, exact in cases:
        mark = newest()
        bst = lgb.Booster(params=dict(BASE, objective="binary", **extra),
                          train_set=lgb.Dataset(X[:n], y[:n]))
        setup, = spans_after(mark, "train.setup")
        assert setup["mesh_chips"] == chips, extra
        assert setup["mesh_exact_counts"] == exact, (extra, n)
        del bst


def test_a_mesh_block_carries_its_work_and_its_exchange():
    """The chips' rows and tiles summed, the exchange as one chip issues
    it: the root's three sums and its histogram, then a split's two (the
    counts as int32 and the smaller child's histogram)."""
    X, y = table(8)
    params = dict(BASE, objective="binary", tpu_row_chunk=128, **MESH)
    bst = lgb.Booster(params=dict(params), train_set=lgb.Dataset(
        X, y, params=dict(params)))
    gbdt = bst._impl
    mark = newest()
    gbdt.train_many(1)
    gbdt.train_many(1)
    trees = dp_ref.parse_trees(bst.model_to_string())
    blocks = spans_after(mark, "train.block")
    assert len(blocks) == 2
    hist_bytes = gbdt.xb.shape[1] * gbdt.num_bins * 3 * 4
    splits = BASE["num_leaves"] - 1
    for got, t in zip(blocks, trees):
        assert got["split_rows"] == int(t["internal_count"].sum())
        assert got["hist_rows"] >= N
        assert got["partition_tiles"] >= -(-N // 128)
        assert got["exchanges"] == 4 + 2 * splits
        assert got["exchange_bytes"] == 3 * 4 + hist_bytes \
            + splits * (2 * 4 + hist_bytes)
    assert set(grow.EXCHANGE_COUNTS) <= set(blocks[0])


def test_the_mesh_block_psums_counts_as_int32_and_histograms_as_float32():
    X, y = table(9)
    params = dict(BASE, objective="binary", **MESH)
    bst = lgb.train(params, lgb.Dataset(X, y, params=dict(params)),
                    num_boost_round=1)
    b = bst._impl
    jaxpr = jax.make_jaxpr(b._build_run_block())(*b.train_block_sds(1))
    schedule = jaxpr_audit.collective_schedule(jaxpr)
    ops = [(c["primitive"], tuple(c["operands"])) for c in schedule]
    assert ("psum", ("int32[2]",)) in ops
    hist = "float32[%d,%d,3]" % (b.xb.shape[1], b.num_bins)
    summed = [o for p, o in ops if p == "psum" and hist in o]
    assert summed == [(hist,), (hist,)], ops   # the root's, a split's
    assert not [o for p, o in ops if p == "all_gather" and hist in o]


# the one-chip exact train block's structural fingerprint: the mesh's
# integer counts do not move it. Measured anew when its leaf ids moved to
# row space and its packed rows to one select a byte (4042c53b... /
# 18602e2b... until then): the one-chip program routes every row a split,
# the mesh's maps ranges to rows once a tree
ONE_CHIP_BLOCK = {
    "exact": "14c64dc8d77422ae83e9fde4495cf5007daff2807de8992eb6cc08ba6b889085",
    "exact_cat":
        "3dd561c87f3df7d44e9eeac25593c0a0d1f8b450f72ab0e4b1dff05b9f65c033",
}


@pytest.mark.parametrize("case", sorted(ONE_CHIP_BLOCK))
def test_the_one_chip_block_is_unchanged(case):
    rng = np.random.RandomState(0)
    X = rng.randn(512, 6)
    X[rng.rand(512, 6) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + X[:, 1] ** 2 > 0.5).astype(float)
    extra = {}
    if case == "exact_cat":
        X[:, 5] = rng.randint(0, 7, 512)
        extra = {"categorical_feature": "5"}
    p = dict(objective="binary", num_leaves=15, verbosity=-1, **extra)
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), num_boost_round=2)
    b = bst._impl
    b.models
    block = int(getattr(b, "_last_block_len", 0) or 1)
    got = jaxpr_audit.structural_fingerprint(
        jax.make_jaxpr(b._build_run_block())(*b.train_block_sds(block)))
    assert got == ONE_CHIP_BLOCK[case]


def test_each_chip_holds_its_own_rows_of_the_bins():
    X, y = table(10)
    params = dict(BASE, objective="binary", **MESH)
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    xb_host = np.asarray(ds._binned.X_binned)
    bst = lgb.Booster(params=dict(params), train_set=ds)
    b = bst._impl
    devices = list(b.mesh.devices.flat)
    shards = sorted(b.xb.addressable_shards,
                    key=lambda s: devices.index(s.device))
    assert len(shards) == CHIPS
    per = N // CHIPS
    for i, s in enumerate(shards):
        assert s.index[0] == slice(i * per, (i + 1) * per)
        np.testing.assert_array_equal(np.asarray(s.data),
                                      xb_host[i * per:(i + 1) * per])
