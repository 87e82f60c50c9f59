"""Multi-device training on the virtual 8-device CPU mesh (SURVEY.md §4:
the tests the reference never had — distributed paths exercised without a
cluster)."""
import numpy as np
import jax
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.metrics import create_metric
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.parallel import mesh as mesh_mod

from conftest import make_binary


def _train(params, X, y, rounds=8):
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    b = create_boosting(cfg, ds, create_objective(cfg),
                        [create_metric("auc", cfg)])
    for _ in range(rounds):
        if b.train_one_iter():
            break
    return b


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_build_mesh_shapes():
    cfg = Config({"tree_learner": "data"})
    m = mesh_mod.build_mesh(cfg)
    assert m is not None and m.shape["data"] == 8
    cfg = Config({"tree_learner": "feature"})
    m = mesh_mod.build_mesh(cfg)
    assert m is not None and m.shape["feature"] == 8
    cfg = Config({"mesh_shape": [4]})
    m = mesh_mod.build_mesh(cfg)
    assert m.shape["data"] == 4
    cfg = Config({})
    assert mesh_mod.build_mesh(cfg) is None


@pytest.mark.slow
def test_data_parallel_matches_serial():
    """Data-parallel (rows sharded over 8 devices) must reproduce serial
    results: histograms are f32 sums so allow tiny drift
    (data_parallel_tree_learner.cpp semantics via GSPMD)."""
    X, y = make_binary(n=2000)
    serial = _train({"objective": "binary", "metric": "auc",
                     "verbosity": -1}, X, y)
    dp = _train({"objective": "binary", "metric": "auc",
                 "tree_learner": "data", "verbosity": -1}, X, y)
    auc_s = dict((m, v) for _, m, v, _ in serial.get_eval_at(0))["auc"]
    auc_d = dict((m, v) for _, m, v, _ in dp.get_eval_at(0))["auc"]
    assert abs(auc_s - auc_d) < 1e-3
    ps = serial.predict(X[:200], raw_score=True)
    pd = dp.predict(X[:200], raw_score=True)
    np.testing.assert_allclose(ps, pd, rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_data_parallel_uneven_rows():
    """Row count not divisible by 8: padding must not change results."""
    X, y = make_binary(n=2005)  # 2005 % 8 != 0
    dp = _train({"objective": "binary", "metric": "auc",
                 "tree_learner": "data", "verbosity": -1}, X, y, rounds=5)
    auc = dict((m, v) for _, m, v, _ in dp.get_eval_at(0))["auc"]
    assert auc > 0.9
    # leaf counts must total the real (unpadded) row count
    t = dp.models[0]
    assert int(t.leaf_count[:t.num_leaves_actual].sum()) == 2005


@pytest.mark.slow
def test_data_parallel_uses_sharded_partition():
    """tree_learner=data rides the explicit shard_map partition path (each
    device partitions its local rows; only child histograms psum) whenever
    forced splits / CEGB are absent — and still matches serial training."""
    X, y = make_binary(n=2000)
    dp = _train({"objective": "binary", "metric": "auc",
                 "tree_learner": "data", "verbosity": -1}, X, y)
    assert dp._partition_on_mesh
    assert dp.grow_params.partition_on_mesh
    serial = _train({"objective": "binary", "metric": "auc",
                     "verbosity": -1}, X, y)
    np.testing.assert_allclose(serial.predict(X[:200], raw_score=True),
                               dp.predict(X[:200], raw_score=True),
                               rtol=1e-3, atol=1e-3)
    # CEGB and forced-split configs STAY on the fused partition path now:
    # the forced rebuild runs straight-line + psum and CEGB state threads
    # through the shard_map (equivalence vs serial is pinned in
    # test_cegb_forced.py::test_*_match*_on_data_parallel_mesh)
    dp3 = _train({"objective": "binary", "tree_learner": "data",
                  "cegb_tradeoff": 0.0, "cegb_penalty_split": 5.0,
                  "verbosity": -1}, X, y, rounds=2)
    assert dp3._partition_on_mesh
    import json, tempfile, os
    fs = {"feature": 0, "threshold": float(np.median(X[:, 0]))}
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(fs, f)
        path = f.name
    try:
        dp2 = _train({"objective": "binary", "tree_learner": "data",
                      "forcedsplits_filename": path, "verbosity": -1},
                     X, y, rounds=2)
        assert dp2._partition_on_mesh
    finally:
        os.unlink(path)


@pytest.mark.slow
def test_feature_parallel_matches_serial():
    X, y = make_binary(n=1500)
    serial = _train({"objective": "binary", "metric": "auc",
                     "verbosity": -1}, X, y, rounds=5)
    fp = _train({"objective": "binary", "metric": "auc",
                 "tree_learner": "feature", "verbosity": -1}, X, y, rounds=5)
    auc_s = dict((m, v) for _, m, v, _ in serial.get_eval_at(0))["auc"]
    auc_f = dict((m, v) for _, m, v, _ in fp.get_eval_at(0))["auc"]
    assert abs(auc_s - auc_f) < 1e-3


@pytest.mark.slow
def test_voting_parallel_close_to_serial():
    """PV-Tree voting (voting_parallel_tree_learner.cpp) is approximate —
    the elected candidate set can miss the global best — but with top_k >=
    num_features it must contain every feature and match data-parallel."""
    X, y = make_binary(n=1600)
    serial = _train({"objective": "binary", "metric": "auc",
                     "verbosity": -1}, X, y, rounds=5)
    vp = _train({"objective": "binary", "metric": "auc",
                 "tree_learner": "voting", "top_k": 20,  # > 10 features
                 "verbosity": -1}, X, y, rounds=5)
    auc_s = dict((m, v) for _, m, v, _ in serial.get_eval_at(0))["auc"]
    auc_v = dict((m, v) for _, m, v, _ in vp.get_eval_at(0))["auc"]
    assert abs(auc_s - auc_v) < 1e-3
    ps = serial.predict(X[:200], raw_score=True)
    pv = vp.predict(X[:200], raw_score=True)
    np.testing.assert_allclose(ps, pv, rtol=1e-3, atol=1e-3)


def test_voting_parallel_small_top_k():
    """With a tight top_k the vote compresses comm; accuracy should still be
    in the same ballpark (PV-Tree's claim)."""
    X, y = make_binary(n=1600)
    vp = _train({"objective": "binary", "metric": "auc",
                 "tree_learner": "voting", "top_k": 3,
                 "verbosity": -1}, X, y, rounds=8)
    auc = dict((m, v) for _, m, v, _ in vp.get_eval_at(0))["auc"]
    assert auc > 0.9


@pytest.mark.slow
def test_data_parallel_through_python_api():
    X, y = make_binary(n=1600)
    bst = lgb.train({"objective": "binary", "tree_learner": "data",
                     "metric": "auc", "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, bst.predict(X)) > 0.9


def test_grow_tree_explicit_psum_path():
    """The shard_map/axis_name path in grow_tree (manual collectives used by
    the voting learner) matches the unsharded result."""
    from functools import partial
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from lightgbm_tpu.core.grow import grow_tree, GrowParams
    from lightgbm_tpu.core.split import SplitParams, FeatureMeta

    r = np.random.RandomState(0)
    n, f, b = 512, 6, 16
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = np.ones(n, np.float32)
    meta = FeatureMeta(
        num_bin=jnp.full((f,), b, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
        penalty=jnp.ones((f,), jnp.float32),
        monotone=jnp.zeros((f,), jnp.int32))
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
                     min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                     min_gain_to_split=0.0, max_cat_threshold=32,
                     cat_smooth=10.0, cat_l2=10.0, max_cat_to_onehot=4,
                     min_data_per_group=100)
    params = GrowParams(num_leaves=15, num_bins=b, max_depth=-1, split=sp,
                        row_chunk=16384, hist_impl="scatter")
    ones = np.ones(n, np.float32)
    fmask = jnp.ones((f,), bool)

    tree_ref, leaf_ref = jax.jit(
        lambda xbj, gj, hj, mj: grow_tree(xbj, gj, hj, mj, meta, fmask,
                                          params)[:2])(xb, g, h, ones)

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    fn = shard_map(
        lambda xbj, gj, hj, mj: grow_tree(xbj, gj, hj, mj, meta, fmask,
                                          params, axis_name="data")[:2],
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=(jax.tree.map(lambda _: P(), tree_ref), P("data")),
        check_vma=False)
    tree_dp, leaf_dp = jax.jit(fn)(xb, g, h, ones)

    assert int(tree_dp.num_leaves) == int(tree_ref.num_leaves)
    np.testing.assert_array_equal(np.asarray(leaf_dp), np.asarray(leaf_ref))
    np.testing.assert_allclose(np.asarray(tree_dp.leaf_value),
                               np.asarray(tree_ref.leaf_value),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_goss_under_mesh_uses_real_counts():
    """GOSS top-k must size its threshold from the REAL row count, not the
    mesh-padding-inflated one (goss.hpp:87-135): padded rows carry
    |g*h| = 0, so with correct counts the sampled multiplier set matches a
    serial run closely. n is chosen to NOT divide 8 so padding exists."""
    X, y = make_binary(n=1501)
    params = {"objective": "binary", "metric": "auc", "boosting": "goss",
              "top_rate": 0.3, "other_rate": 0.2, "learning_rate": 0.1,
              "verbosity": -1}
    meshed = _train(dict(params, tree_learner="data"), X, y, rounds=12)
    assert meshed.num_data > 1501  # padding really happened
    serial = _train(params, X, y, rounds=12)
    auc_m = dict((m, v) for _, m, v, _ in meshed.get_eval_at(0))["auc"]
    auc_s = dict((m, v) for _, m, v, _ in serial.get_eval_at(0))["auc"]
    # GOSS sampling is stochastic; equal-count semantics keep AUC in step
    assert auc_m > 0.9
    assert abs(auc_m - auc_s) < 0.05


@pytest.mark.slow
def test_explicit_feature_parallel_engaged_and_matches():
    """The EXPLICIT feature-parallel learner (bin-balanced column
    assignment + argmax-allreduce of split structs, grow.sync_best_split —
    feature_parallel_tree_learner.cpp:30-60) is the default for
    tree_learner=feature and reproduces serial predictions; forced splits
    fall back to the GSPMD learner."""
    import json
    import os
    import tempfile
    X, y = make_binary(n=1500)
    serial = _train({"objective": "binary", "verbosity": -1}, X, y,
                    rounds=4)
    fp = _train({"objective": "binary", "tree_learner": "feature",
                 "verbosity": -1}, X, y, rounds=4)
    assert fp._explicit_fp and fp._fp_capture is not None
    ps = serial.predict(X[:300], raw_score=True)
    pf = fp.predict(X[:300], raw_score=True)
    np.testing.assert_allclose(ps, pf, rtol=2e-4, atol=2e-4)

    fs = {"feature": 0, "threshold": float(np.median(X[:, 0]))}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(fs, f)
        path = f.name
    try:
        fp2 = _train({"objective": "binary", "tree_learner": "feature",
                      "forcedsplits_filename": path, "verbosity": -1},
                     X, y, rounds=2)
        assert not fp2._explicit_fp
    finally:
        os.unlink(path)


def test_sync_best_split_broadcasts_winner():
    """sync_best_split = SyncUpGlobalBestSplit: every rank ends up with
    the max-gain rank's full struct, including bool/uint32 fields."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from lightgbm_tpu.core.grow import sync_best_split
    from lightgbm_tpu.core.split import BestSplit
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, ("f",))
    d = len(devs)

    def make(rank):
        r = rank.astype(jnp.float32)
        return BestSplit(
            gain=jnp.where(rank == 2, 9.0, r),   # rank 2 wins
            feature=rank * 10, threshold=rank + 1,
            default_left=(rank % 2) == 0,
            left_sum_grad=r, left_sum_hess=r, left_count=r,
            right_sum_grad=r, right_sum_hess=r, right_count=r,
            left_output=r, right_output=r,
            is_categorical=rank == 2,
            cat_bitset=jnp.full((8,), rank.astype(jnp.uint32) + 7,
                                jnp.uint32))

    out = jax.jit(jax.shard_map(
        lambda _: jax.tree.map(
            lambda a: a[None],
            sync_best_split(make(jax.lax.axis_index("f")), "f")),
        mesh=mesh, in_specs=(P("f"),), out_specs=P("f"),
        check_vma=False))(jnp.zeros((d,)))
    # every rank holds rank 2's struct
    assert np.all(np.asarray(out.gain) == 9.0)
    assert np.all(np.asarray(out.feature) == 20)
    assert np.all(np.asarray(out.threshold) == 3)
    assert np.all(np.asarray(out.is_categorical))
    assert np.all(np.asarray(out.cat_bitset) == 9)


def _voting_construction(n_dev=8, m=200, f=10, flip=0.2, seed=3):
    """Data where the GLOBAL best feature (0) is nobody's LOCAL top-1:
    feature 1+d predicts y perfectly on device d's contiguous row shard
    and is noise elsewhere; feature 0 is a flip-noised copy of y
    everywhere. Rows land on devices in contiguous blocks (device_put of
    the leading axis), so shard d = rows [d*m, (d+1)*m)."""
    r = np.random.RandomState(seed)
    n = n_dev * m
    y = (r.rand(n) < 0.5).astype(np.float32)
    X = (r.rand(n, f) < 0.5).astype(np.float64)
    flips = r.rand(n) < flip
    X[:, 0] = np.where(flips, 1.0 - y, y)
    for d in range(n_dev):
        sl = slice(d * m, (d + 1) * m)
        X[sl, 1 + d] = y[sl]
    # premise: per-shard corr ranks the local feature first, feature 0
    # second; global corr ranks feature 0 first
    for d in range(n_dev):
        sl = slice(d * m, (d + 1) * m)
        cors = [abs(np.corrcoef(X[sl, j], y[sl])[0, 1]) for j in range(f)]
        assert np.argmax(cors) == 1 + d, (d, cors)
        assert np.argsort(cors)[-2] == 0, (d, cors)
    gcors = [abs(np.corrcoef(X[:, j], y)[0, 1]) for j in range(f)]
    assert np.argmax(gcors) == 0, gcors
    return X, y


def test_voting_elects_global_best_not_local_top1():
    """GlobalVoting semantics (voting_parallel_tree_learner.cpp:166-196):
    with top_k=2 each device proposes its local top-2 = [its private
    feature, feature 0]; feature 0 wins the vote 8-to-1 and — once the
    elected candidates' histograms are globally summed — the root split.
    A learner that globally reduced nothing (pure local best) would split
    on a private feature; one that skipped the vote and reduced all
    features would also pass, which is what the comm test below pins."""
    X, y = _voting_construction()
    b = _train({"objective": "binary", "metric": "auc",
                "tree_learner": "voting", "top_k": 2,
                "num_leaves": 4, "min_data_in_leaf": 5,
                "verbosity": -1}, X, y, rounds=1)
    root_feat = int(b.models[0].split_feature[0])
    assert root_feat == 0, \
        "root split used feature %d, not the vote-elected global best" \
        % root_feat


def test_voting_reduces_only_elected_histograms():
    """Comm accounting for PV-Tree: the only >=2-D tensors crossing the
    mesh are the elected candidates' histograms — [2*top_k, B, ...] —
    never a full [F, B, ...] histogram (the O(top_k*B) vs O(F*B) claim,
    voting_parallel_tree_learner.cpp:251-360)."""
    import jax.lax as _lax
    X, y = _voting_construction(m=201, f=12, seed=5)  # fresh shapes: retrace
    top_k = 3
    recorded = []
    orig = _lax.psum

    def recording_psum(x, axis_name, **kw):
        for leaf in jax.tree.leaves(x):
            recorded.append(tuple(getattr(leaf, "shape", ())))
        return orig(x, axis_name, **kw)

    _lax.psum = recording_psum
    try:
        b = _train({"objective": "binary", "metric": "auc",
                    "tree_learner": "voting", "top_k": top_k,
                    "num_leaves": 4, "min_data_in_leaf": 5,
                    "verbosity": -1}, X, y, rounds=1)
    finally:
        _lax.psum = orig
    assert recorded, "nothing traced through psum — patching went stale"
    big = [s for s in recorded if len(s) >= 2]
    n_cols = 12  # all 12 features are non-trivial 0/1 columns
    assert all(s[0] == 2 * top_k for s in big), big
    assert not any(s[0] >= n_cols for s in big), \
        "a full-width histogram crossed the mesh: %r" % (big,)
    # and the elected reduction itself must have happened
    assert any(s[0] == 2 * top_k for s in big), big


@pytest.mark.slow
def test_voting_on_2d_mesh_slow_axis():
    """Multi-slice-shaped config: a [4, 2] (data x feature) mesh with the
    PV-Tree vote riding the SLOW (data) axis — the deployment the voting
    learner exists for (ICI-cheap elected-candidate psum across slices).
    Election semantics must hold with 4 data shards, and the result must
    match the 1-D mesh voting run."""
    X, y = _voting_construction(n_dev=4, m=400)
    b2d = _train({"objective": "binary", "metric": "auc",
                  "tree_learner": "voting", "top_k": 2,
                  "mesh_shape": [4, 2], "num_leaves": 4,
                  "min_data_in_leaf": 5, "verbosity": -1}, X, y, rounds=2)
    assert b2d.mesh is not None and b2d.mesh.shape["data"] == 4 \
        and b2d.mesh.shape["feature"] == 2
    assert int(b2d.models[0].split_feature[0]) == 0
    b1d = _train({"objective": "binary", "metric": "auc",
                  "tree_learner": "voting", "top_k": 2,
                  "mesh_shape": [4], "num_leaves": 4,
                  "min_data_in_leaf": 5, "verbosity": -1}, X, y, rounds=2)
    np.testing.assert_allclose(
        b2d.predict(X[:300], raw_score=True),
        b1d.predict(X[:300], raw_score=True), rtol=1e-5, atol=1e-5)
