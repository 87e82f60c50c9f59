"""Batched-frontier growth (core/grow_batched.py, tree_growth=batched).

Contract being pinned:
- batch size 1 reproduces the exact leaf-wise algorithm (same split
  sequence, same node numbering — the reference's tree.cpp:49-67);
- larger batches trade exact best-first ordering for per-step
  parallelism with near-identical model quality (the GPU learner's
  documented-deviation stance, GPU-Performance.rst:132-139);
- the data-parallel mesh path matches single-device batched growth;
- order-dependent features (forced splits, CEGB) refuse loudly.
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.log import LightGBMError
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.metrics import create_metric
from lightgbm_tpu.boosting import create_boosting

from conftest import make_binary, make_multiclass


def _train(X, y, params, rounds=20, **ds_kw):
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y, **ds_kw)
    mets = [m for m in (create_metric(n, cfg) for n in (cfg.metric or []))
            if m]
    b = create_boosting(cfg, ds, create_objective(cfg), mets)
    for _ in range(rounds):
        if b.train_one_iter():
            break
    return b


def _tree_structures(booster, num=3):
    """(split_feature, threshold, split_leaf) tuples of the first trees."""
    return [(t.split_feature.copy(), t.threshold_bin.copy(),
             t.split_leaf.copy()) for t in booster.models[:num]]


def test_batch_one_matches_exact_structure():
    """K=1 batched growth is the exact algorithm: identical split
    sequences on tie-free data."""
    X, y = make_binary(n=3000)
    base = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
            "min_data_in_leaf": 5}
    be = _train(X, y, dict(base, tree_growth="exact"), rounds=5)
    bb = _train(X, y, dict(base, tree_growth="batched",
                           tree_batch_splits=1), rounds=5)
    for (f1, t1, l1), (f2, t2, l2) in zip(_tree_structures(be),
                                          _tree_structures(bb)):
        np.testing.assert_array_equal(f1, f2)
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(l1, l2)


@pytest.mark.parametrize("kb", [4, 16])
@pytest.mark.slow
@pytest.mark.slow
def test_batched_quality_close_to_exact(kb):
    X, y = make_binary(n=4000)
    base = {"objective": "binary", "num_leaves": 63, "metric": "auc",
            "verbosity": -1}
    be = _train(X, y, dict(base, tree_growth="exact"), rounds=15)
    bb = _train(X, y, dict(base, tree_growth="batched",
                           tree_batch_splits=kb), rounds=15)
    auc_e = dict((m, v) for _, m, v, _ in be.get_eval_at(0))["auc"]
    auc_b = dict((m, v) for _, m, v, _ in bb.get_eval_at(0))["auc"]
    assert auc_b > 0.95
    assert abs(auc_e - auc_b) < 0.02


def test_batched_fills_leaf_budget():
    """A learnable problem must still grow to the num_leaves budget —
    batching must not strand capacity (the prefix-mask bookkeeping)."""
    X, y = make_binary(n=4000)
    b = _train(X, y, {"objective": "binary", "num_leaves": 33,
                      "tree_growth": "batched", "tree_batch_splits": 8,
                      "min_data_in_leaf": 2, "verbosity": -1}, rounds=2)
    assert b.models[0].num_leaves == 33


@pytest.mark.parametrize("sampling", [
    {},
    {"bagging_fraction": 0.6, "bagging_freq": 1},
    # learning_rate 0.5: GOSS samples from iteration 2 on (1 / rate)
    {"boosting": "goss", "learning_rate": 0.5},
], ids=["plain", "bagging", "goss"])
def test_batched_predict_matches_train_scores(sampling):
    """Rows a tree was not grown on (out of the bag, dropped by GOSS) still
    reach the right leaf: the held scores are what predict gives."""
    X, y = make_binary(n=1500)
    b = _train(X, y, dict({"objective": "binary", "tree_growth": "batched",
                           "tree_batch_splits": 8, "verbosity": -1},
                          **sampling), rounds=8)
    assert b.grow_params.batch_splits == 8
    assert b.grow_params.all_rows_in_bag == (not sampling)
    pred = b.predict(X, raw_score=True)
    np.testing.assert_allclose(pred, np.asarray(b.scores)[:, 0],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
@pytest.mark.slow
def test_batched_multiclass():
    X, y = make_multiclass()
    base = {"objective": "multiclass", "num_class": 4,
            "metric": "multi_logloss", "verbosity": -1}
    be = _train(X, y, dict(base, tree_growth="exact"), rounds=15)
    bb = _train(X, y, dict(base, tree_growth="batched",
                           tree_batch_splits=8), rounds=15)
    ll_e = dict((m, v) for _, m, v, _ in be.get_eval_at(0))["multi_logloss"]
    ll_b = dict((m, v) for _, m, v, _ in bb.get_eval_at(0))["multi_logloss"]
    assert ll_b < ll_e + 0.05


def test_batched_data_parallel_matches_single_device():
    """Eight-device data-parallel batched growth must reproduce the
    single-device model (the collective is one psum per step)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    X, y = make_binary(n=2048)
    base = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
            "tree_growth": "batched", "tree_batch_splits": 8}
    b1 = _train(X, y, dict(base), rounds=5)
    b8 = _train(X, y, dict(base, tree_learner="data", num_machines=1,
                           mesh_shape=[8]), rounds=5)
    p1 = b1.predict(X[:200], raw_score=True)
    p8 = b8.predict(X[:200], raw_score=True)
    np.testing.assert_allclose(p1, p8, rtol=2e-4, atol=2e-4)


def test_batched_monotone_constraints_hold():
    r = np.random.RandomState(5)
    n = 3000
    X = r.randn(n, 4).astype(np.float32)
    y = (X[:, 0] + 0.3 * r.randn(n)).astype(np.float32)
    b = _train(X, y, {"objective": "regression", "verbosity": -1,
                      "tree_growth": "batched", "tree_batch_splits": 8,
                      "monotone_constraints": [1, 0, 0, 0]}, rounds=20)
    grid = np.zeros((50, 4), np.float32)
    grid[:, 0] = np.linspace(-2.5, 2.5, 50)
    pred = b.predict(grid, raw_score=True)
    assert np.all(np.diff(pred) >= -1e-6)


def test_batched_refuses_order_dependent_features(tmp_path):
    X, y = make_binary(n=500)
    with pytest.raises(LightGBMError, match="batched"):
        _train(X, y, {"objective": "binary", "tree_growth": "batched",
                      "verbosity": -1,
                      "cegb_penalty_feature_coupled": [0.1] * X.shape[1],
                      "cegb_tradeoff": 1.0}, rounds=1)
    with pytest.raises(LightGBMError, match="batched"):
        _train(X, y, {"objective": "binary", "tree_growth": "batched",
                      "tree_learner": "voting", "verbosity": -1}, rounds=1)


def test_batched_slot_kernel_end_to_end():
    """Batched growth through the slot-extended Pallas kernel (interpret
    mode) must match the scatter-based combined-index build."""
    X, y = make_binary(n=1200, f=6)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "tree_growth": "batched", "tree_batch_splits": 4}
    bs = _train(X, y, dict(base, tpu_hist_impl="scatter"), rounds=3)
    bp = _train(X, y, dict(base, tpu_hist_impl="pallas_interpret"), rounds=3)
    ps = bs.predict(X[:300], raw_score=True)
    pp = bp.predict(X[:300], raw_score=True)
    np.testing.assert_allclose(ps, pp, rtol=2e-4, atol=2e-4)
