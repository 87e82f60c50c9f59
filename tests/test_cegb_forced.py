"""Forced splits (forcedsplits_filename) and CEGB penalties.

Reference semantics: SerialTreeLearner::ForceSplits
(src/treelearner/serial_tree_learner.cpp:593-751) splits a BFS-predetermined
(feature, threshold) chain before best-first growth takes over; CEGB
(:484-504, :533-539) subtracts feature-acquisition costs from candidate
gains.
"""
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture()
def xy():
    rng = np.random.RandomState(7)
    X = rng.randn(2000, 5).astype(np.float32)
    y = (((X[:, 0] > 0.3) & (X[:, 1] < 0.2)) | (X[:, 2] > 0)).astype(
        np.float32)
    return X, y


def _used_features(bst):
    used = set()
    for t in bst._impl.models:
        for i in range(t.num_nodes):
            if t.split_leaf[i] >= 0:
                used.add(int(t.split_feature[i]))
    return used


def test_forced_splits_structure(tmp_path, xy):
    X, y = xy
    fpath = tmp_path / "forced.json"
    fpath.write_text(json.dumps(
        {"feature": 0, "threshold": 0.3,
         "left": {"feature": 1, "threshold": 0.2},
         "right": {"feature": 3, "threshold": -0.5}}))
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1,
                     "forcedsplits_filename": str(fpath)},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    for t in [bst._impl.models[0], bst._impl.models[1]]:
        # node 0: root forced on feature 0 near 0.3
        assert t.split_leaf[0] == 0
        assert t.split_feature[0] == 0
        assert abs(t.threshold[0] - 0.3) < 0.25
        # node 1: BFS order -> root's LEFT child (leaf 0) on feature 1
        assert t.split_leaf[1] == 0
        assert t.split_feature[1] == 1
        assert abs(t.threshold[1] - 0.2) < 0.25
        # node 2: root's RIGHT child (leaf 1) on feature 3
        assert t.split_leaf[2] == 1
        assert t.split_feature[2] == 3


def test_forced_splits_survive_model_roundtrip(tmp_path, xy):
    X, y = xy
    fpath = tmp_path / "forced.json"
    fpath.write_text(json.dumps({"feature": 4, "threshold": 0.0}))
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                     "forcedsplits_filename": str(fpath)},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    mpath = tmp_path / "model.txt"
    bst.save_model(str(mpath))
    loaded = lgb.Booster(model_file=str(mpath))
    np.testing.assert_allclose(loaded.predict(X[:100]), bst.predict(X[:100]),
                               rtol=1e-6)
    assert bst._impl.models[0].split_feature[0] == 4


def test_forced_split_categorical_rejected(tmp_path, xy):
    X, y = xy
    X[:, 1] = np.round(np.abs(X[:, 1]) * 3)
    fpath = tmp_path / "forced.json"
    fpath.write_text(json.dumps({"feature": 1, "threshold": 1.0}))
    with pytest.raises(lgb.LightGBMError):
        lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                   "forcedsplits_filename": str(fpath),
                   "categorical_feature": [1]},
                  lgb.Dataset(X, label=y, categorical_feature=[1]),
                  num_boost_round=1)


def test_cegb_coupled_penalty_gates_features(xy):
    X, y = xy
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                     "cegb_tradeoff": 1.0,
                     "cegb_penalty_feature_coupled": [1e9, 1e9, 0.0, 1e9,
                                                      1e9]},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    assert _used_features(bst) <= {2}


def test_cegb_split_penalty_prunes(xy):
    X, y = xy
    kw = {"objective": "binary", "num_leaves": 31, "verbosity": -1}
    free = lgb.train(dict(kw), lgb.Dataset(X, label=y), num_boost_round=1)
    pen = lgb.train(dict(kw, cegb_penalty_split=0.05),
                    lgb.Dataset(X, label=y), num_boost_round=1)
    assert pen._impl.models[0].num_leaves_actual \
        < free._impl.models[0].num_leaves_actual


def test_cegb_lazy_prefers_paid_rows(xy):
    X, y = xy
    # with a steep lazy penalty the model should stick to few features:
    # re-splitting a feature whose rows already paid is cheaper
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1,
                     "cegb_penalty_feature_lazy": [0.01] * 5},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    free = lgb.train({"objective": "binary", "num_leaves": 15,
                      "verbosity": -1},
                     lgb.Dataset(X, label=y), num_boost_round=3)
    assert len(_used_features(bst)) <= len(_used_features(free))


@pytest.mark.slow
def test_forced_splits_match_on_data_parallel_mesh(tmp_path, xy):
    """Forced splits now ride the fused sharded partition path (the leaf
    rebuild runs straight-line + psum, grow.py leaf_hist): an 8-shard
    data-parallel run must reproduce the serial forced-split model."""
    X, y = xy
    path = str(tmp_path / "forced.json")
    with open(path, "w") as f:
        json.dump({"feature": 2, "threshold": 0.1,
                   "left": {"feature": 3, "threshold": -0.2}}, f)
    kw = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "forcedsplits_filename": path}
    serial = lgb.train(dict(kw), lgb.Dataset(X, label=y), num_boost_round=4)
    dp = lgb.train(dict(kw, tree_learner="data", mesh_shape=[8]),
                   lgb.Dataset(X, label=y), num_boost_round=4)
    assert dp._impl.plan.partition_on_mesh   # not the masked fallback
    ps = serial.predict(X[:300], raw_score=True)
    pd = dp.predict(X[:300], raw_score=True)
    np.testing.assert_allclose(ps, pd, rtol=1e-5, atol=1e-5)
    # the forced structure is present in both
    t0s = serial._impl.models[0]
    t0d = dp._impl.models[0]
    assert t0s.split_feature[0] == t0d.split_feature[0] == 2


def test_cegb_lazy_matches_on_data_parallel_mesh(xy):
    """Lazy CEGB's unpaid-row psum runs straight-line (no cond) on the
    sharded partition path; acquisition state threads through the
    shard_map with row_used sharded. 8-shard result == serial result."""
    X, y = xy
    kw = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "cegb_tradeoff": 0.5,
          "cegb_penalty_split": 1e-5,
          "cegb_penalty_feature_lazy": [0.001] * 5}
    serial = lgb.train(dict(kw), lgb.Dataset(X, label=y), num_boost_round=4)
    dp = lgb.train(dict(kw, tree_learner="data", mesh_shape=[8]),
                   lgb.Dataset(X, label=y), num_boost_round=4)
    assert dp._impl.plan.partition_on_mesh
    ps = serial.predict(X[:300], raw_score=True)
    pd = dp.predict(X[:300], raw_score=True)
    np.testing.assert_allclose(ps, pd, rtol=1e-5, atol=1e-5)
