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


def _train(X, y, params, rounds=4):
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    b = cfg, ds
    bst = create_boosting(cfg, ds, create_objective(cfg), [])
    for _ in range(rounds):
        bst.train_one_iter()
    return bst, ds


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_batched_routing_on_efb_bundles(seed):
    """Batched growth over an EFB-bundled dataset: K=1 must reproduce
    exact growth's split structure (the routing's decode_bundle_value
    path through the one-hot selects), and K=4 must stay accurate.

    The seeds are tables on which no node has an exact tie: two columns
    whose cuts take other rows of the same gradients (the same count of
    each label out of the same earlier leaves) have one gain in exact
    arithmetic. The two growers sum a leaf's histogram in different row
    orders, and since the split scan sums both children from the bins
    the argmax of such a tie may fall either way, after which the trees
    part (seed 3: tree 3's last split, 22 rows, columns 22 and 28, 5
    rows to the right either way, gains 1.8355482 and 1.8355483; seed 7:
    tree 0, node 26)."""
    X, y = _exclusive_groups(seed=seed)
    base = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
            "min_data_in_leaf": 5, "tpu_hist_impl": "scatter"}
    be, ds_e = _train(X, y, dict(base, tree_growth="exact"))
    assert ds_e.num_columns < X.shape[1], "test requires real bundling"
    b1, _ = _train(X, y, dict(base, tree_growth="batched",
                              tree_batch_splits=1))
    for t0, t1 in zip(be.models, b1.models):
        np.testing.assert_array_equal(np.asarray(t0.split_feature),
                                      np.asarray(t1.split_feature))
        np.testing.assert_array_equal(np.asarray(t0.threshold_bin),
                                      np.asarray(t1.threshold_bin))
    b4, _ = _train(X, y, dict(base, tree_growth="batched",
                              tree_batch_splits=4))
    p0 = be.predict(X[:400], raw_score=True)
    p4 = b4.predict(X[:400], raw_score=True)
    # different split ORDER is fine; the models must agree in quality
    auc = lambda p: float(
        (np.argsort(np.argsort(p))[y[:400] > 0].sum()
         - (y[:400] > 0).sum() * ((y[:400] > 0).sum() + 1) / 2)
        / max((y[:400] > 0).sum() * (400 - (y[:400] > 0).sum()), 1))
    assert abs(auc(p0) - auc(p4)) < 0.05


@pytest.mark.parametrize("seed", [3, 7])
def test_exact_and_batched_at_an_exact_tie_only(seed):
    """The tables the test above leaves out: up to the first node the two
    growers split differently the trees are the same, and at that node
    both record the same gain to two float32 ulps: a tie, not another
    split search."""
    X, y = _exclusive_groups(seed=seed)
    base = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
            "min_data_in_leaf": 5, "tpu_hist_impl": "scatter"}
    be, _ = _train(X, y, dict(base, tree_growth="exact"))
    b1, _ = _train(X, y, dict(base, tree_growth="batched",
                              tree_batch_splits=1))
    for t0, t1 in zip(be.models, b1.models):
        f0, f1 = np.asarray(t0.split_feature), np.asarray(t1.split_feature)
        differ = np.flatnonzero(
            (f0 != f1) | (np.asarray(t0.threshold_bin)
                          != np.asarray(t1.threshold_bin)))
        if len(differ):
            break   # later trees are grown from other scores
    else:
        pytest.fail("seed %d has no tie any more: move it to the test above"
                    % seed)
    at = differ[0]
    np.testing.assert_allclose(np.asarray(t0.split_gain)[at],
                               np.asarray(t1.split_gain)[at], rtol=2.4e-7)
    assert np.asarray(t0.internal_count)[at] == \
        np.asarray(t1.internal_count)[at]
