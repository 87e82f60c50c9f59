"""Frontier-wave growth (core/grow_frontier.py, tree_growth=frontier).

Contract being pinned:
- when the num_leaves cap never binds, frontier growth performs exactly
  the split SET of the exact leaf-wise algorithm (each leaf's best split
  depends only on its own rows), so the golden structure matches — node
  NUMBERING differs (wave order vs global best-first order), so the
  comparison is the canonical multiset of splits plus predictions;
- on capped workloads quality stays close to exact (same documented
  approximation stance as tree_growth=batched);
- the data-parallel mesh path (one psum per WAVE) matches single-device;
- order-dependent features (forced splits, CEGB, voting) refuse loudly.
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.log import LightGBMError
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.metrics import create_metric
from lightgbm_tpu.boosting import create_boosting

from conftest import make_binary


def _train(X, y, params, rounds=10, **ds_kw):
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y, **ds_kw)
    mets = [m for m in (create_metric(n, cfg) for n in (cfg.metric or []))
            if m]
    b = create_boosting(cfg, ds, create_objective(cfg), mets)
    for _ in range(rounds):
        if b.train_one_iter():
            break
    return b


def _canonical_splits(booster, num=3):
    """Order-independent view of each tree: sorted (feature, threshold_bin)
    multiset + sorted (leaf_count, leaf_value) multiset."""
    out = []
    for t in booster.models[:num]:
        nn = t.num_leaves - 1
        splits = sorted(zip(t.split_feature[:nn].tolist(),
                            t.threshold_bin[:nn].tolist()))
        leaves = sorted(zip(t.leaf_count[:t.num_leaves].tolist(),
                            np.round(t.leaf_value[:t.num_leaves],
                                     5).tolist()))
        out.append((splits, leaves))
    return out


def _golden_data():
    """Strong-signal, shallow golden dataset: no near-tie gains at any
    node (verified over seeds), so fp summation-order differences between
    the per-leaf and frontier histogram paths cannot flip an argmax."""
    rng = np.random.default_rng(0)
    n = 600
    X = rng.normal(size=(n, 6))
    logit = (1.5 * X[:, 0] + 1.0 * X[:, 1] - 0.8 * X[:, 2]
             + 0.5 * X[:, 3] * X[:, 4])
    y = (logit + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X.astype(np.float32), y


def test_frontier_golden_structure_matches_exact():
    """Uncapped growth: the frontier split SET is identical to exact
    (ISSUE 2 acceptance: identical split structure on a golden dataset)."""
    X, y = _golden_data()
    base = {"objective": "binary", "num_leaves": 64, "max_depth": 4,
            "min_data_in_leaf": 40, "verbosity": -1}
    be = _train(X, y, dict(base, tree_growth="exact"), rounds=3)
    bf = _train(X, y, dict(base, tree_growth="frontier"), rounds=3)
    assert _canonical_splits(be) == _canonical_splits(bf)
    pe = be.predict(X, raw_score=True)
    pf = bf.predict(X, raw_score=True)
    np.testing.assert_allclose(pe, pf, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_frontier_quality_close_to_exact_capped():
    """When the leaf cap binds, wave truncation is approximate best-first
    (same stance as batched K>1): quality must stay close."""
    X, y = make_binary(n=4000)
    base = {"objective": "binary", "num_leaves": 63, "metric": "auc",
            "verbosity": -1}
    be = _train(X, y, dict(base, tree_growth="exact"), rounds=15)
    bf = _train(X, y, dict(base, tree_growth="frontier"), rounds=15)
    auc_e = dict((m, v) for _, m, v, _ in be.get_eval_at(0))["auc"]
    auc_f = dict((m, v) for _, m, v, _ in bf.get_eval_at(0))["auc"]
    assert auc_f > 0.95
    assert abs(auc_e - auc_f) < 0.02


def test_frontier_fills_leaf_budget():
    """A learnable problem must still grow to the num_leaves budget —
    the wave's prefix-mask bookkeeping must not strand capacity."""
    X, y = make_binary(n=4000)
    b = _train(X, y, {"objective": "binary", "num_leaves": 33,
                      "tree_growth": "frontier", "min_data_in_leaf": 2,
                      "verbosity": -1}, rounds=2)
    assert b.models[0].num_leaves == 33


@pytest.mark.slow
def test_frontier_sweeps_scale_with_depth():
    """The whole point: dataset sweeps per tree = max leaf depth + 1,
    not num_leaves - 1 (ISSUE 2 acceptance)."""
    from lightgbm_tpu.profiling import frontier_tree_stats
    X, y = make_binary(n=2000)
    b = _train(X, y, {"objective": "binary", "num_leaves": 31,
                      "tree_growth": "frontier", "verbosity": -1},
               rounds=2)
    stats = frontier_tree_stats(b.models[0], b.grow_params)
    waves = stats["waves"]
    # a 31-leaf tree needs at least ceil(log2(31)) = 5 waves and at most
    # 30 (degenerate chain); on this learnable workload it must be far
    # below the per-leaf sweep count
    assert 5 <= waves <= 30
    assert stats["sweeps_per_tree"] == waves + 1
    assert stats["sweeps_per_tree"] < b.models[0].num_leaves - 1


@pytest.mark.slow
def test_frontier_predict_matches_train_scores():
    X, y = make_binary(n=1500)
    b = _train(X, y, {"objective": "binary", "tree_growth": "frontier",
                      "verbosity": -1}, rounds=8)
    pred = b.predict(X, raw_score=True)
    np.testing.assert_allclose(pred, np.asarray(b.scores)[:, 0],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_frontier_data_parallel_matches_single_device():
    """Eight-device data-parallel frontier growth must reproduce the
    single-device model (the collective is one psum per WAVE)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    # the tie-free golden config: psum reordering across shards perturbs
    # gains in the last ulp, which on a near-tie workload can flip a deep
    # argmax and cascade — the same fp sensitivity every grower has under
    # sharding, not a frontier property
    X, y = _golden_data()
    base = {"objective": "binary", "num_leaves": 64, "max_depth": 4,
            "min_data_in_leaf": 40, "verbosity": -1,
            "tree_growth": "frontier"}
    b1 = _train(X, y, dict(base), rounds=5)
    b8 = _train(X, y, dict(base, tree_learner="data", num_machines=1,
                           mesh_shape=[8]), rounds=5)
    assert _canonical_splits(b1, num=5) == _canonical_splits(b8, num=5)
    p1 = b1.predict(X[:200], raw_score=True)
    p8 = b8.predict(X[:200], raw_score=True)
    np.testing.assert_allclose(p1, p8, rtol=2e-4, atol=2e-4)


def test_frontier_refuses_order_dependent_features():
    X, y = make_binary(n=500)
    with pytest.raises(LightGBMError, match="frontier"):
        _train(X, y, {"objective": "binary", "tree_growth": "frontier",
                      "verbosity": -1,
                      "cegb_penalty_feature_coupled": [0.1] * X.shape[1],
                      "cegb_tradeoff": 1.0}, rounds=1)
    # the explicit feature-parallel learner needs grow_tree's fp context
    with pytest.raises(LightGBMError, match="frontier"):
        _train(X, y, {"objective": "binary", "tree_growth": "frontier",
                      "tree_learner": "feature", "verbosity": -1}, rounds=1)
    # voting rides the frontier waves now (parallel/learners.py) but still
    # refuses batched growth, whose commit loop has no election seam
    with pytest.raises(LightGBMError, match="voting"):
        _train(X, y, {"objective": "binary", "tree_growth": "batched",
                      "tree_learner": "voting", "verbosity": -1}, rounds=1)


@pytest.mark.slow
def test_frontier_slot_kernel_end_to_end():
    """Frontier growth through the Pallas slot kernel (interpret mode)
    must match the scatter frontier build."""
    X, y = make_binary(n=1200, f=6)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "tree_growth": "frontier"}
    bs = _train(X, y, dict(base, tpu_hist_impl="scatter"), rounds=3)
    bp = _train(X, y, dict(base, tpu_hist_impl="pallas_interpret"),
                rounds=3)
    ps = bs.predict(X[:300], raw_score=True)
    pp = bp.predict(X[:300], raw_score=True)
    np.testing.assert_allclose(ps, pp, rtol=2e-4, atol=2e-4)


def test_config_validates_growth_and_hist_impl():
    """ISSUE 2 satellite: unknown tree_growth / tpu_hist_impl values fail
    loudly at config time."""
    with pytest.raises(LightGBMError, match="tree_growth"):
        Config({"tree_growth": "levelwise"})
    with pytest.raises(LightGBMError, match="tpu_hist_impl"):
        Config({"tpu_hist_impl": "palas"})
    # the full-f32 kernel variant went at PR 31: refused by name, with the
    # spellings that are left (gpu_use_dp is the double-precision route)
    for suffix in ("highest", "highest_interpret"):
        gone = "pallas_" + suffix
        with pytest.raises(
                LightGBMError,
                match="tpu_hist_impl should be one of auto/matmul/scatter/"
                      "pallas/pallas_interpret, got %s$" % gone):
            Config({"tpu_hist_impl": gone})
    # the alias from the issue spelling resolves to the canonical name
    assert Config({"tree_grow_mode": "frontier"}).tree_growth == "frontier"
    assert Config({"tpu_hist_impl": " Scatter "}).tpu_hist_impl == "scatter"
