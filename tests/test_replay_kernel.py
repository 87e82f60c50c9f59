"""The replay's kernel (``core/replay.py`` ``forest_total_kernel``).

Its node decision, two compares on the bin code (``goes_right`` with the
node's ``flip_code``), against ``_bin_go_left`` at every code; the kernel
in Pallas's interpreter against ``forest_total`` bit for bit, scores and
total, over a table with NaN- and zero-missing columns for sets of 1, 7
and 50 trees of 2, 31 and 255 leaves and a tree of no node, at a row count
that is not a multiple of the tile; and where a booster takes it: only
over the feature-major copy of a table whose stored columns each hold one
numerical feature, which the plan's ``replay_kernel`` and
``dart_replay_kernel_trees`` on ``train.block`` report.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import HostTree
from lightgbm_tpu.core import replay as replay_mod
from lightgbm_tpu.core.grow import _bin_go_left
from lightgbm_tpu.core.partition import bins_by_column
from lightgbm_tpu.core.split import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from lightgbm_tpu.obs import trace

CODES = np.arange(256, dtype=np.int32)


@pytest.mark.parametrize("default_left", [False, True],
                         ids=["right", "left"])
@pytest.mark.parametrize("missing_type", [MISSING_NONE, MISSING_ZERO,
                                          MISSING_NAN],
                         ids=["none", "zero", "nan"])
@pytest.mark.parametrize("num_bin", [2, 17, 255, 256])
def test_two_compares_decide_as_bin_go_left(missing_type, default_left,
                                            num_bin):
    """Every code 0-255 at thresholds from the first bin to the last, and
    the default bin below, at, and above the threshold."""
    for thr in sorted({0, 1, num_bin // 2, num_bin - 2, num_bin - 1}):
        for default_bin in sorted({0, max(thr - 1, 0), thr,
                                   min(thr + 1, num_bin - 1), num_bin - 1}):
            flip = int(replay_mod.flip_code(
                np.int32(missing_type), np.int32(num_bin),
                np.int32(default_bin), np.int32(thr), default_left))
            want = np.asarray(_bin_go_left(
                jnp.asarray(CODES), jnp.int32(thr), jnp.bool_(default_left),
                jnp.int32(missing_type), jnp.int32(num_bin),
                jnp.int32(default_bin), None, jnp.zeros(8, jnp.uint32)))
            got = ~np.asarray(replay_mod.goes_right(CODES, thr, flip))
            assert np.array_equal(got, want), (thr, default_bin, flip)


ROWS = 33 * 1024 - 100      # 33 rows of the copy: a tile of 32, one short


@pytest.fixture(scope="module")
def table():
    """A binned table of NaN-missing, zero-missing and complete columns,
    its layout and its feature-major copy."""
    rng = np.random.RandomState(5)
    x = rng.randn(ROWS, 5)
    x[:, 0][rng.rand(ROWS) < 0.2] = np.nan
    x[:, 1][rng.rand(ROWS) < 0.3] = 0.0
    x[:, 2] = np.round(x[:, 2] * 3)          # few bins, zero among them
    y = (x[:, 3] > 0).astype(np.float64)
    params = {"verbosity": -1, "max_bin": 255}
    ds = lgb.Dataset(x, y, params=params).construct()._binned
    layout = replay_mod.TableLayout(ds)
    assert layout.single
    bins = jax.jit(bins_by_column)(jnp.asarray(ds.X_binned))
    return layout, bins, ds


def _random_tree(rng, leaves, layout, features):
    """A tree of ``leaves`` leaves whose node t splits one of the leaves
    0..t at a threshold drawn from the column's bins, its missing type,
    default direction and leaf values drawn."""
    ht = HostTree(leaves)
    nn = leaves - 1
    ht.num_leaves_actual = leaves
    if nn:
        f = rng.randint(0, features, nn)
        ht.split_feature[:] = f
        ht.split_leaf[:] = [rng.randint(0, t + 1) for t in range(nn)]
        ht.threshold_bin[:] = [rng.randint(0, layout.num_bin[j])
                               for j in f]
        ht.missing_type[:] = rng.randint(0, 3, nn)
        ht.default_left[:] = rng.rand(nn) < 0.5
    ht.leaf_value[:] = rng.randn(leaves)
    return ht


@pytest.mark.parametrize("count,leaves,classes", [
    (count, leaves, 1) for count in (1, 7, 50) for leaves in (2, 31, 255)]
    + [(7, 31, 3)])
def test_the_kernel_is_the_passes_to_the_bit(table, count, leaves, classes):
    """The set of 7 holds a tree of no node; three classes take turns."""
    layout, bins, ds = table
    rng = np.random.RandomState(count * 1000 + leaves)
    trees = [_random_tree(rng, leaves, layout, ds.num_features)
             for _ in range(count)]
    if count == 7:
        trees[3] = _random_tree(rng, 1, layout, ds.num_features)
    stacked = layout.stack(trees, [d % classes for d in range(count)])
    assert stacked.split_leaf.shape[0] == {1: 1, 7: 8, 50: 64}[count]
    scores = jnp.asarray(rng.randn(ROWS, classes).astype(np.float32))
    program = replay_mod.replay_program("lgbm.kernel_test")
    args = (scores, stacked, np.int32(count), np.float32(-0.5), bins)
    statics = dict(feature_major=True, packed=False, pairs=False,
                   categorical=False)
    want = program(*args, **statics)
    got = program(*args, kernel=True, interpret=True, **statics)
    for g, w in zip(got, want):
        assert g.shape == (ROWS, classes)
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_the_kernel_walks_tiles_and_slabs_of_any_size(table):
    """Tiles of 8 copy rows in slabs of 4 (five tiles, the last short),
    and a tile of the whole copy in one slab."""
    layout, bins, ds = table
    rng = np.random.RandomState(3)
    trees = [_random_tree(rng, 31, layout, ds.num_features)
             for _ in range(5)]
    stacked = jax.tree_util.tree_map(jnp.asarray,
                                     layout.stack(trees, [0] * 5))
    want = replay_mod.forest_total(
        stacked, np.int32(5), bins, feature_major=True, packed=False,
        pairs=False, categorical=False, num_classes=1, num_data=ROWS)
    for tile, slab, unroll in [(8, 4, 3), (64, 8, 1)]:
        got = replay_mod.forest_total_kernel(
            stacked, np.int32(5), bins, num_classes=1, num_data=ROWS,
            interpret=True, row_tile=tile, slab=slab, unroll=unroll)
        assert np.array_equal(np.asarray(got), np.asarray(want))


def _X(n=400, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4)
    x[rng.rand(n, 4) < 0.15] = np.nan
    x[:, 1][rng.rand(n) < 0.3] = 0.0
    y = ((np.nan_to_num(x[:, 0]) + 0.3 * rng.randn(n)) > 0).astype(float)
    return x, y


def _sparse(x):
    """Two columns, each zero in 90% of the rows and never non-zero in
    one row together: EFB bundles them."""
    rng = np.random.RandomState(1)
    n = x.shape[0]
    hot = rng.randint(0, 10, n)
    sparse = np.zeros((n, 2))
    sparse[hot == 1, 0] = rng.rand(int((hot == 1).sum())) + 1
    sparse[hot == 2, 1] = rng.rand(int((hot == 2).sum())) + 1
    return np.concatenate([x, sparse], axis=1)


BASE = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbosity": -1, "boosting": "dart", "drop_rate": 0.5,
        "skip_drop": 0.2, "max_drop": 3, "drop_seed": 7,
        "tpu_hist_impl": "pallas_interpret"}
PATHS = {
    # (extra params, table, the kernel engages)
    "plain": ({}, lambda x: x, True),
    "cpu-scatter": ({"tpu_hist_impl": "scatter"}, lambda x: x, False),
    # boosting=gbdt holds the copy for its leaf ids in row space
    # (TrainPlan.row_space_ids), and a replay over the table reads it
    "gbdt": ({"boosting": "gbdt"}, lambda x: x, True),
    # no copy where CEGB's lazy cost keeps the ids split by split
    "gbdt-no-copy": ({"boosting": "gbdt",
                      "cegb_penalty_feature_lazy": [0.1] * 4},
                     lambda x: x, False),
    "pairs": ({"tree_growth": "frontier", "tpu_bin_packing": "nibble"},
              lambda x: np.concatenate(
                  [x, np.random.RandomState(2).randint(0, 6, (len(x), 2))],
                  axis=1), False),
    "categorical": ({"categorical_feature": "4"},
                    lambda x: np.concatenate(
                        [x, np.random.RandomState(2).randint(
                            0, 9, (len(x), 1))], axis=1), False),
    "efb": ({}, _sparse, False),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_where_the_kernel_engages(case):
    extra, make, engages = PATHS[case]
    x, y = _X()
    params = dict(BASE, **extra)
    bst = lgb.Booster(params=dict(params),
                      train_set=lgb.Dataset(make(x), y, params=dict(params)))
    gbdt = bst._impl
    assert gbdt.plan.replay_kernel == engages
    layout = gbdt._replay_layout_of()
    assert layout.single == (case not in ("pairs", "categorical", "efb"))
    if case == "pairs":
        assert layout.pairs
    if case == "efb":
        assert np.asarray(gbdt.train_data.feature_layout()[2]).any()
    assert gbdt._replay_input()[1]["kernel"] == engages
    # a validation cache's row-major table always takes the passes
    assert not gbdt._replay_input(xb=gbdt.xb)[1]["kernel"]
    gbdt.train_many(6)
    blocks = [s["counts"] for s in trace.recorded_spans()
              if s["name"] == "train.block"][-6:]
    if params["boosting"] == "dart":
        dropped = sum(c["dart_dropped"] for c in blocks)
        assert dropped > 0
        assert sum(c["dart_replay_kernel_trees"] for c in blocks) == \
            (dropped if engages else 0)
    np.testing.assert_allclose(np.asarray(gbdt.scores)[:, 0],
                               bst.predict(make(x), raw_score=True),
                               rtol=1e-5, atol=2e-6)
