"""Round-4 batched-growth kernel + routing units.

Pins two things the end-to-end batched tests cannot isolate:
- build_histogram_slots6 (parent-slot x 6-channel joint kernel) against
  a per-slot numpy reference, including inactive rows and absent slots;
- the dense one-hot routing (route_split_rows) on an EFB-BUNDLED
  dataset under batched growth — the decode_bundle_value path rides
  sel_k one-hot selects there, which no dense-data test exercises.
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.boosting import create_boosting

from conftest import make_binary
from test_efb import _exclusive_groups


def test_slots6_matches_per_slot_reference():
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram_pallas import build_histogram_slots6

    r = np.random.RandomState(11)
    n, f, b, k = 5000, 6, 64, 4
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    slot = r.randint(-1, k, n).astype(np.int32)   # -1 = inactive
    slot[slot == k - 1] = -1                      # leave slot k-1 ABSENT
    sel = (r.rand(n) > 0.4).astype(np.float32)
    vals = r.randn(3, n).astype(np.float32)
    out = np.asarray(build_histogram_slots6(
        jnp.asarray(xb), jnp.asarray(slot), jnp.asarray(sel),
        jnp.asarray(vals), num_bins=b, n_slots=k, row_tile=512,
        interpret=True))
    assert out.shape == (k, f, b, 6)
    for s in range(k):
        m = slot == s
        ref = np.zeros((f, b, 6), np.float32)
        for ch in range(6):
            w = sel[m] if ch < 3 else 1.0 - sel[m]
            v = vals[ch % 3, m] * w
            for j in range(f):
                np.add.at(ref[j, :, ch], xb[m, j], v)
        np.testing.assert_allclose(out[s], ref, rtol=5e-2, atol=5e-2)


def _booster(X, y, params, grow=None):
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    bst = create_boosting(cfg, ds, create_objective(cfg), [])
    if grow:
        # a grower's path no configuration reaches on one device
        bst.grow_params = bst.grow_params._replace(**grow)
    return bst, ds


def _train(X, y, params, rounds=4, grow=None):
    bst, ds = _booster(X, y, params, grow)
    for _ in range(rounds):
        bst.train_one_iter()
    return bst, ds


def _train_on_dyadic_gradients(X, y, params, rounds=4, replay=None):
    """The log loss's gradients and hessians rounded to multiples of 2**-10,
    handed to the booster from outside: over 3,000 rows every float32 sum of
    them is exact in any order, and so is parent - smaller. ``replay`` is
    another run's list of (gradients, hessians), to grow trees from the very
    same numbers. Returns (booster, dataset, that list)."""
    bst, ds = _booster(X, y, dict(params, objective="none"))
    used = []
    for i in range(rounds):
        if replay is not None:
            g, h = replay[i]
        else:
            score = bst.predict(X, raw_score=True) if i else np.zeros(len(y))
            p = 1.0 / (1.0 + np.exp(-score))
            g = np.round((p - y) * 1024) / 1024
            h = np.maximum(np.round(p * (1 - p) * 1024), 1) / 1024
        used.append((g, h))
        bst.train_one_iter(g.astype(np.float32), h.astype(np.float32))
    return bst, ds, used


def _first_parting(models0, models1):
    """(tree of models0, tree of models1, node) at the first split the two
    boosters make differently; None where every tree is the same."""
    for t0, t1 in zip(models0, models1):
        differ = np.flatnonzero(
            (np.asarray(t0.split_feature) != np.asarray(t1.split_feature))
            | (np.asarray(t0.threshold_bin) != np.asarray(t1.threshold_bin)))
        if len(differ):
            return t0, t1, differ[0]   # later trees grow from other scores
    return None


_EFB_BASE = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
             "min_data_in_leaf": 5, "tpu_hist_impl": "scatter"}


@pytest.mark.parametrize("seed", [3, 4, 5, 6, 7])
def test_batched_routing_on_efb_bundles(seed):
    """Batched growth over an EFB-bundled dataset: K=1 must reproduce
    exact growth's split structure (the routing's decode_bundle_value
    path through the one-hot selects), split for split in every tree, and
    K=4 must stay accurate.

    The two growers price a leaf by different float32 sums: the batched
    one sums both children of a split from their rows, the exact one sums
    the smaller child and takes the larger as parent - smaller
    (serial_tree_learner.cpp:383-397). So that the comparison holds the
    routing and not the order of a sum, the gradients are multiples of
    2**-10: every sum is exact, both growers see the same histograms to
    the bit, and an exact tie between two columns (seeds 3 and 7 have one)
    falls the same way in both. What float32 gradients do to near ties is
    the next test's."""
    X, y = _exclusive_groups(seed=seed)
    be, ds_e, grads = _train_on_dyadic_gradients(
        X, y, dict(_EFB_BASE, tree_growth="exact"))
    assert ds_e.num_columns < X.shape[1], "test requires real bundling"
    b1, _, _ = _train_on_dyadic_gradients(
        X, y, dict(_EFB_BASE, tree_growth="batched", tree_batch_splits=1),
        replay=grads)
    for t0, t1 in zip(be.models, b1.models):
        assert np.asarray(t0.split_feature).size > 20
        np.testing.assert_array_equal(np.asarray(t0.split_feature),
                                      np.asarray(t1.split_feature))
        np.testing.assert_array_equal(np.asarray(t0.threshold_bin),
                                      np.asarray(t1.threshold_bin))
        np.testing.assert_array_equal(np.asarray(t0.internal_count),
                                      np.asarray(t1.internal_count))
    b4, _, _ = _train_on_dyadic_gradients(
        X, y, dict(_EFB_BASE, tree_growth="batched", tree_batch_splits=4))
    p0 = be.predict(X[:400], raw_score=True)
    p4 = b4.predict(X[:400], raw_score=True)
    # different split ORDER is fine; the models must agree in quality
    auc = lambda p: float(
        (np.argsort(np.argsort(p))[y[:400] > 0].sum()
         - (y[:400] > 0).sum() * ((y[:400] > 0).sum() + 1) / 2)
        / max((y[:400] > 0).sum() * (400 - (y[:400] > 0).sum()), 1))
    assert auc(p0) > 0.8
    assert abs(auc(p0) - auc(p4)) < 0.05


@pytest.mark.parametrize("seed", [3, 4, 5, 6, 7])
def test_exact_and_batched_part_at_a_near_tie_only(seed):
    """The same tables under the objective's own float32 gradients, where
    a small leaf's bins carry the rounding of its ancestors' larger sums
    in the exact grower and not in the batched one. Two columns whose cuts
    take nearly the same rows of a 14- to 40-row leaf then have gains that
    agree to 1e-7 ... 2e-5, and the argmax may fall either way, after
    which the trees part (seed 3: tree 3's last split, 22 rows, gains
    1.8355485 and 1.8355483; seed 7: tree 0, node 26, 40 rows, 1.7452593
    and 1.7452917). Up to that node the trees are the same, and at it
    both growers split the same rows for the same gain to 1e-4: a tie
    within the subtraction's rounding, not another split search."""
    X, y = _exclusive_groups(seed=seed)
    be, _ = _train(X, y, dict(_EFB_BASE, tree_growth="exact"))
    b1, _ = _train(X, y, dict(_EFB_BASE, tree_growth="batched",
                              tree_batch_splits=1))
    parting = _first_parting(be.models, b1.models)
    if parting is not None:
        t0, t1, at = parting
        np.testing.assert_allclose(np.asarray(t0.split_gain)[at],
                                   np.asarray(t1.split_gain)[at], rtol=1e-4)
        assert np.asarray(t0.internal_count)[at] == \
            np.asarray(t1.internal_count)[at]
        assert np.asarray(t0.internal_count)[at] <= 64


@pytest.mark.parametrize("seed", [3, 4, 5, 6, 7])
def test_partition_and_masked_paths_grow_the_same_trees(seed):
    """The exact grower over the row partition (a pass that splits the
    leaf's range, a pass over the smaller child's) and over masked
    full-data passes: both build the smaller child and subtract for its
    sibling, in other row orders, and grow the same trees on the bundled
    tables above, split for split."""
    X, y = _exclusive_groups(seed=seed)
    params = dict(_EFB_BASE, tree_growth="exact")
    bp, _ = _train(X, y, params)
    assert bp.grow_params.use_partition
    bm, _ = _train(X, y, params, grow={"use_partition": False})
    assert _first_parting(bp.models, bm.models) is None
    for t0, t1 in zip(bp.models, bm.models):
        np.testing.assert_array_equal(np.asarray(t0.internal_count),
                                      np.asarray(t1.internal_count))
        np.testing.assert_allclose(np.asarray(t0.leaf_value),
                                   np.asarray(t1.leaf_value),
                                   rtol=1e-4, atol=1e-5)
