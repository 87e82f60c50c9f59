"""The exact grower over all rows on one device keeps every row's leaf id in
row space (partition.route_in_row_space): one streaming pass a split over
the split column of the feature-major copy of the bins, where that copy
fits beside the table (partition.row_space_fits). Elsewhere the ids come
from the final ranges of ``order`` once a tree (leaf_id_from_partition).

Both put every row in the same leaf, so the grower's leaf ids agree row
for row, and the trees, the scores and the model text to the bit. Each
case trains twice: on the CPU, which reports no memory limit and so takes
row space, and with the limit read as just what the copy needs, with no
room to spare, which falls back. Small sizes, seeded; every comparison is
exact.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.core import partition
from lightgbm_tpu.core.grow import grow_tree

BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
        "verbosity": -1}
ROUNDS = 3
N = 2000


def _nan_and_zeros(rng):
    X = rng.normal(size=(N, 6))
    y = X[:, 0] + 0.5 * X[:, 1] ** 2 + rng.normal(size=N) > 0.5
    X[rng.random((N, 6)) < 0.15] = np.nan
    X[rng.random((N, 6)) < 0.25] = 0.0
    return X, y


def _categorical(rng):
    X, _ = _nan_and_zeros(rng)
    X[:, 0] = rng.integers(0, 40, N)
    y = (np.isin(X[:, 0], [3, 7, 11, 19, 23, 31]) * 2.0
         + 0.5 * np.nan_to_num(X[:, 1]) + 0.3 * rng.normal(size=N)) > 0.6
    return X, y


def _three_classes(rng):
    X, _ = _nan_and_zeros(rng)
    return X, np.digitize(np.nan_to_num(X[:, 0] + X[:, 2]), [-0.5, 0.5])


def _bundled(rng):
    # five mutually exclusive sparse columns (one active a row, or none):
    # EFB stores them as one
    which = rng.integers(0, 8, N)
    X = np.zeros((N, 6))
    vals = rng.integers(1, 9, N).astype(np.float64)
    for c in range(5):
        X[which == c, c] = vals[which == c]
    X[:, 5] = rng.normal(size=N)
    return X, X[:, 0] - X[:, 2] + X[:, 5] + 0.3 * rng.normal(size=N) > 0.3


def _no_limit(rows, cols):
    return None


def _room_to_spare(rows, cols):
    return 2 * partition.row_space_bytes(rows, cols)


def _no_room_to_spare(rows, cols):
    # what the copy needs, and not the tenth of the device left beside it
    return partition.row_space_bytes(rows, cols)


# case: (data, parameters, the limit the row-space side reads)
CASES = {
    "nan_and_zero_bins": (_nan_and_zeros, {}, _no_limit),
    "zero_as_missing": (_nan_and_zeros, {"zero_as_missing": True},
                        _no_limit),
    "categorical": (_categorical, {"categorical_feature": "0",
                                   "min_data_per_group": 5,
                                   "cat_smooth": 1.0}, _no_limit),
    "efb_bundled": (_bundled, {"enable_bundle": True}, _no_limit),
    "tpu_tile_loop": (_nan_and_zeros, {"tpu_hist_impl": "pallas_interpret"},
                      _no_limit),
    "multiclass_vmapped": (_three_classes, {"objective": "multiclass",
                                            "num_class": 3}, _no_limit),
    "bagging": (_nan_and_zeros, {"bagging_fraction": 0.6,
                                 "bagging_freq": 1}, _no_limit),
    "cegb_split_and_coupled": (_nan_and_zeros, {
        "cegb_penalty_split": 0.001,
        "cegb_penalty_feature_coupled": [0.5] * 6}, _no_limit),
    "limit_with_room": (_nan_and_zeros, {}, _room_to_spare),
}


def _train(monkeypatch, X, y, params, limit):
    """Trained with the device's limit read as ``limit`` of the table's
    rows and stored columns."""
    ds = lgb.Dataset(X, y.astype(np.float64), params=dict(params)).construct()
    table = ds._binned
    monkeypatch.setattr(partition, "device_bytes_limit", lambda device: limit(
        table.num_data, table.num_columns))
    return lgb.train(params, ds, num_boost_round=ROUNDS)


def _grow(gbdt, grad, hess, bins_by_col):
    n = gbdt.xb.shape[0]
    f = gbdt.feature_meta.num_bin.shape[0]
    fn = jax.jit(functools.partial(grow_tree, params=gbdt.grow_params))
    return fn(gbdt.xb, grad, hess, jnp.ones((n,), jnp.float32),
              gbdt.feature_meta, jnp.ones((f,), bool),
              cegb=gbdt._cegb_state, bins_by_col=bins_by_col)


@pytest.mark.parametrize("case", sorted(CASES))
def test_row_space_leaf_ids_are_the_row_partitions(case, monkeypatch):
    make, extra, limit = CASES[case]
    X, y = make(np.random.default_rng(45))
    params = dict(BASE, **extra)
    rowspace = _train(monkeypatch, X, y, params, limit)
    fallback = _train(monkeypatch, X, y, params, _no_room_to_spare)
    rs, fb = rowspace._impl, fallback._impl
    assert rs.plan.row_space_ids and rs._rowspace_bins() is not None
    # a byte a stored column and row, rows in whole lanes
    assert rs._bins_by_col.nbytes == rs.xb.shape[1] * 2 * 1024
    assert not fb.plan.row_space_ids and fb._bins_by_col is None
    assert fb._rowspace_bins() is None
    assert rowspace.model_to_string() == fallback.model_to_string()
    np.testing.assert_array_equal(np.asarray(rs.scores),
                                  np.asarray(fb.scores))

    # one tree grown both ways from the same gradients: the row-space ids
    # are leaf_id_from_partition's, row for row
    rng = np.random.default_rng(451)
    grad = jnp.asarray(0.5 - (y == 1) + 0.1 * rng.normal(size=N),
                       jnp.float32)
    hess = jnp.asarray(rng.uniform(0.2, 0.3, N), jnp.float32)
    by_rows = _grow(rs, grad, hess, rs._bins_by_col)
    by_ranges = _grow(rs, grad, hess, None)
    assert by_rows.leaf_id.dtype == jnp.int32
    assert by_rows.leaf_id.shape == (N,)
    np.testing.assert_array_equal(np.asarray(by_rows.leaf_id),
                                  np.asarray(by_ranges.leaf_id))
    jax.tree.map(np.testing.assert_array_equal, by_rows.tree,
                 by_ranges.tree)
    tree = by_rows.tree
    live = int(tree.num_leaves)
    assert live > 5
    np.testing.assert_array_equal(
        np.bincount(np.asarray(by_rows.leaf_id), minlength=live),
        np.asarray(tree.leaf_count)[:live])
    if case == "categorical":
        assert np.asarray(tree.is_categorical)[:live - 1].any()
    if case == "efb_bundled":
        assert rs.grow_params.with_efb and rs.xb.shape[1] < 6
