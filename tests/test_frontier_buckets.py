"""Wave-width-adaptive frontier histograms + persistent compile cache (PR 4).

Contracts pinned here:
- the shared pow-2 bucketing module (lightgbm_tpu/bucketing.py) is the
  single source of truth for serving row buckets AND frontier wave widths,
  with the frontier cap clamped by max_depth (frontier <= 2^(d-1));
- bucketed frontier growth is STRUCTURE-IDENTICAL to fixed-width growth —
  same splits, same node numbering, same leaf values — on dense, EFB,
  categorical, and sharded skewed inputs (the lax.switch over the width
  ladder only changes padding, never the committed top_k prefix);
- one bucketed frontier pass equals per-leaf build_histogram per slot, at
  every ladder width and on both hist impls;
- a grown tree's wave occupancy is counted (profiling.frontier_tree_stats)
  and its occupancy-weighted slot-sweep count stays within 2x of
  num_leaves; the compile-cache counters are there to read;
- training performs zero XLA backend compiles after the warmup ladder;
- checkpoint resume stays byte-identical with tree_growth=frontier.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback, engine
from lightgbm_tpu.bucketing import (frontier_max_width, pow2_bucket,
                                    pow2_ladder, wave_width_bucket,
                                    wave_width_ladder)
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.log import LightGBMError
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.boosting import create_boosting

from conftest import make_binary


def _train(X, y, params, rounds=3, **ds_kw):
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y, **ds_kw)
    b = create_boosting(cfg, ds, create_objective(cfg), [])
    for _ in range(rounds):
        if b.train_one_iter():
            break
    return b


def _golden_data():
    """Same tie-free dataset as test_grow_frontier._golden_data."""
    rng = np.random.default_rng(0)
    n = 600
    X = rng.normal(size=(n, 6))
    logit = (1.5 * X[:, 0] + 1.0 * X[:, 1] - 0.8 * X[:, 2]
             + 0.5 * X[:, 3] * X[:, 4])
    y = (logit + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X.astype(np.float32), y


def _assert_same_trees(bb, bf, num=3):
    """Bucketed and fixed-width must agree on NUMBERING, not just the split
    multiset — the stable top_k prefix is width-independent."""
    for tb, tf in zip(bb.models[:num], bf.models[:num]):
        assert tb.num_leaves == tf.num_leaves
        nn = tb.num_leaves - 1
        np.testing.assert_array_equal(np.asarray(tb.split_feature[:nn]),
                                      np.asarray(tf.split_feature[:nn]))
        np.testing.assert_array_equal(np.asarray(tb.threshold_bin[:nn]),
                                      np.asarray(tf.threshold_bin[:nn]))
        np.testing.assert_array_equal(np.asarray(tb.left_child[:nn]),
                                      np.asarray(tf.left_child[:nn]))
        np.testing.assert_array_equal(
            np.asarray(tb.leaf_count[:tb.num_leaves]),
            np.asarray(tf.leaf_count[:tf.num_leaves]))
        np.testing.assert_allclose(
            np.asarray(tb.leaf_value[:tb.num_leaves]),
            np.asarray(tf.leaf_value[:tf.num_leaves]), rtol=1e-6, atol=1e-9)


# --------------------------------------------------------- bucketing unit
def test_pow2_bucket_and_ladder():
    assert [pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert pow2_bucket(100, cap=30) == 30
    assert pow2_bucket(3, min_bucket=16) == 16
    # the ladder always ends exactly at the (possibly non-pow-2) cap
    assert pow2_ladder(1, 30) == [1, 2, 4, 8, 16, 30]
    assert pow2_ladder(16, 16) == [16]
    # every bucket the bucket function can return is on the ladder
    for n in range(1, 31):
        assert pow2_bucket(n, cap=30) in pow2_ladder(1, 30)


def test_frontier_max_width_clamps_by_depth():
    # the satellite bugfix: a depth-d tree's frontier holds <= 2^(d-1)
    # leaves, so 255 leaves at max_depth=3 never needs more than 4 lanes
    assert frontier_max_width(255, 3) == 4
    assert frontier_max_width(255) == 254
    assert frontier_max_width(255, -1) == 254
    assert frontier_max_width(31, 1) == 1
    assert frontier_max_width(2, 10) == 1
    assert wave_width_ladder(255, 3) == [1, 2, 4]
    assert wave_width_ladder(64, 4) == [1, 2, 4, 8]
    assert wave_width_ladder(31) == [1, 2, 4, 8, 16, 30]
    # occupancy accounting mirrors the switch: live snaps up, never past cap
    assert wave_width_bucket(5, 31) == 8
    assert wave_width_bucket(20, 31) == 30
    assert wave_width_bucket(20, 255, 3) == 4


def test_serving_buckets_ride_shared_module():
    from lightgbm_tpu.serving.predictor import bucket_rows, bucket_sizes
    assert bucket_rows(5) == pow2_bucket(5, 16, 4096)
    assert bucket_sizes(16, 100) == pow2_ladder(16, 100)
    with pytest.raises(LightGBMError):
        bucket_rows(0)


# ------------------------------------------------------------ config knobs
def test_config_compile_cache_and_bucketing_knobs(tmp_path):
    assert Config({}).tpu_frontier_bucketing is True
    assert Config({"frontier_bucketing": False}).tpu_frontier_bucketing \
        is False
    d = str(tmp_path / "cache")
    for alias in ("compile_cache_dir", "compilation_cache_dir",
                  "jax_compilation_cache_dir"):
        assert Config({alias: d}).compile_cache_dir == d
    f = tmp_path / "a_file"
    f.write_text("x")
    with pytest.raises(LightGBMError, match="compile_cache_dir"):
        Config({"compile_cache_dir": str(f)})


# --------------------------------------------------- per-wave hist property
@pytest.mark.parametrize("impl", ["matmul", "scatter"])
def test_bucketed_wave_hist_matches_per_leaf(impl):
    """One frontier pass at ANY ladder width == per-leaf build_histogram
    per slot; the padding lanes stay exactly zero."""
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram import (build_histogram,
                                             build_histogram_frontier)
    r = np.random.RandomState(1)
    n, f, bins, live = 512, 4, 16, 5
    xb = jnp.asarray(r.randint(0, bins, (n, f)), jnp.uint8)
    slot = jnp.asarray(r.randint(-1, live, n), jnp.int32)  # -1 = inactive
    g = jnp.asarray(r.randn(n), jnp.float32)
    h = jnp.asarray(r.rand(n) + 0.5, jnp.float32)
    mask = jnp.asarray((r.rand(n) < 0.8), jnp.float32)
    for width in wave_width_ladder(live + 1):     # 1, 2, 4, 5
        if width < live:
            continue                               # caller-guaranteed fit
        hist = np.asarray(build_histogram_frontier(
            xb, slot, g, h, mask, bins, num_slots=width, impl=impl))
        assert hist.shape == (width, f, bins, 3)
        for k in range(live):
            ref = np.asarray(build_histogram(
                xb, g, h, mask * (np.asarray(slot) == k), bins, impl=impl))
            np.testing.assert_allclose(hist[k], ref, rtol=1e-5, atol=1e-5)
        assert not hist[live:].any()


# ----------------------------------------------- structure identity golden
def test_bucketed_matches_fixed_width_dense():
    X, y = _golden_data()
    base = {"objective": "binary", "num_leaves": 64, "max_depth": 4,
            "min_data_in_leaf": 40, "verbosity": -1,
            "tree_growth": "frontier"}
    bf = _train(X, y, dict(base, tpu_frontier_bucketing=False))
    bb = _train(X, y, dict(base))                  # bucketing is the default
    _assert_same_trees(bb, bf)
    np.testing.assert_array_equal(bb.predict(X, raw_score=True),
                                  bf.predict(X, raw_score=True))
    # and both still match exact growth (the pre-existing golden contract)
    be = _train(X, y, dict(base, tree_growth="exact"))
    np.testing.assert_allclose(be.predict(X, raw_score=True),
                               bb.predict(X, raw_score=True),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
@pytest.mark.slow
def test_bucketed_matches_fixed_width_efb():
    """Exclusive sparse blocks: EFB bundling rewrites the column layout the
    wave sweeps, so pin identity on the bundled path too."""
    r = np.random.RandomState(3)
    n, groups, per = 1500, 4, 5
    X = np.zeros((n, groups * per))
    for gidx in range(groups):
        which = r.randint(0, per + 1, n)
        vals = r.randint(1, 9, n).astype(np.float64)
        for k in range(per):
            X[which == k, gidx * per + k] = vals[which == k]
    y = ((X[:, 0] + X[:, per] - X[:, 2 * per] + 0.5 * r.randn(n))
         > 1.0).astype(np.float32)
    base = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
            "verbosity": -1, "tree_growth": "frontier"}
    bf = _train(X, y, dict(base, tpu_frontier_bucketing=False))
    bb = _train(X, y, dict(base))
    _assert_same_trees(bb, bf)


def test_bucketed_matches_fixed_width_categorical():
    r = np.random.RandomState(5)
    n = 800
    cat = r.randint(0, 12, n)
    x2 = r.randn(n)
    effect = np.where(np.isin(cat, [1, 3, 5, 8]), 2.0, -2.0)
    y = (effect + 0.5 * x2 + 0.3 * r.randn(n) > 0).astype(np.float64)
    X = np.column_stack([cat.astype(np.float64), x2])
    base = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
            "tree_growth": "frontier", "categorical_feature": "0",
            "min_data_per_group": 10}
    bf = _train(X, y, dict(base, tpu_frontier_bucketing=False))
    bb = _train(X, y, dict(base))
    _assert_same_trees(bb, bf)


@pytest.mark.slow
def test_bucketed_matches_fixed_width_sharded_skewed():
    """Row-sorted 8-shard data parallel: most (slot, shard) pairs own zero
    rows, the regime where the switch must still pick ONE width on every
    device (the live count derives from the psum'd gains, so it is
    replicated) and the branch-local psum stays a uniform collective.

    Slow-marked like the other 8-device mesh golden test
    (test_frontier_data_parallel_matches_single_device): three frontier
    trainings under shard_map are compile-heavy on the CPU mesh."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    X, y = _golden_data()
    order = np.argsort(X[:, 0], kind="stable")
    X, y = X[order], y[order]
    base = {"objective": "binary", "num_leaves": 64, "max_depth": 4,
            "min_data_in_leaf": 40, "verbosity": -1,
            "tree_growth": "frontier", "tree_learner": "data",
            "num_machines": 1, "mesh_shape": [8]}
    bf = _train(X, y, dict(base, tpu_frontier_bucketing=False))
    bb = _train(X, y, dict(base))
    _assert_same_trees(bb, bf)
    p1 = _train(X, y, {k: v for k, v in base.items()
                       if k not in ("tree_learner", "num_machines",
                                    "mesh_shape")})
    np.testing.assert_allclose(p1.predict(X[:200], raw_score=True),
                               bb.predict(X[:200], raw_score=True),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@pytest.mark.slow
def test_max_depth_clamp_end_to_end():
    """Regression for the clamp bugfix: with a binding max_depth the wave
    ladder tops out at 2^(d-1), and the grown trees respect the depth cap
    with structure identical to the unclamped-fixed-width path."""
    X, y = make_binary(n=800)
    base = {"objective": "binary", "num_leaves": 255, "max_depth": 3,
            "min_data_in_leaf": 20, "verbosity": -1,
            "tree_growth": "frontier"}
    bb = _train(X, y, dict(base))
    bf = _train(X, y, dict(base, tpu_frontier_bucketing=False))
    _assert_same_trees(bb, bf)
    for t in bb.models:
        # depth-3 tree holds <= 8 leaves (num_leaves is the capacity)
        assert t.num_leaves_actual <= 2 ** 3
    # the widths the grower dispatches are the clamped ladder
    assert wave_width_ladder(255, 3) == [1, 2, 4]
    assert list(bb.warmup_wave_ladder()["widths"]) == [1, 2, 4]


# --------------------------------------------- occupancy + compile metrics
@pytest.mark.slow
def test_tree_stats_report_occupancy_and_cache_counters():
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        frontier_tree_stats)
    X, y = make_binary(n=2000)
    b = _train(X, y, {"objective": "binary", "num_leaves": 15,
                      "tree_growth": "frontier", "verbosity": -1}, rounds=2)
    stats = frontier_tree_stats(b.models[0], b.grow_params)
    occ = stats["wave_occupancy"]
    assert 0.0 < occ <= 1.0
    paid = stats["slot_sweeps_per_tree"]
    fixed = stats["slot_sweeps_fixed_width"]
    # the ISSUE 4 acceptance bar: occupancy-weighted slot-sweeps within 2x
    # of num_leaves, strictly below the fixed-width waves * (num_leaves-1)
    assert paid <= 2 * 15
    assert paid < fixed
    cache = compile_cache_stats()
    assert "persistent_cache_hits" in cache
    assert "persistent_cache_misses" in cache


@pytest.mark.slow
@pytest.mark.slow
def test_zero_recompiles_after_warmup_in_process(tmp_path):
    """The measured invariant the cache work exists for: after one
    train_many block (which pre-warms the wave ladder — the eager ladder
    runs in compile_cache_dir mode), further blocks perform ZERO XLA
    backend compiles — across iterations AND trees."""
    import jax
    from lightgbm_tpu.profiling import backend_compile_count
    X, y = make_binary(n=500)
    cfg = Config({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  "tree_growth": "frontier",
                  "compile_cache_dir": str(tmp_path / "cache")})
    # enable_compile_cache redirects the process-wide persistent cache;
    # restore conftest's shared cache dir afterwards
    saved_dir = jax.config.jax_compilation_cache_dir
    saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
    saved_sz = jax.config.jax_persistent_cache_min_entry_size_bytes
    try:
        ds = BinnedDataset.from_matrix(X, cfg, label=y)
        b = create_boosting(cfg, ds, create_objective(cfg), [])
        b.train_many(2)
        jax.block_until_ready(b.scores)
        floor = backend_compile_count()
        b.train_many(2)
        jax.block_until_ready(b.scores)
        assert backend_compile_count() - floor == 0
        warm = getattr(b, "_ladder_warmup", None)
        assert warm and list(warm["widths"]) == wave_width_ladder(7)
    finally:
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved_min)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          saved_sz)


# ---------------------------------------------------- checkpoint identity
@pytest.mark.slow
@pytest.mark.slow
def test_checkpoint_resume_byte_identical_frontier(tmp_path):
    """Checkpoint/resume must stay byte-identical when the frontier grower
    (bucketed by default) is the training path."""
    r = np.random.RandomState(7)
    X = r.randn(400, 6)
    y = (X[:, 0] + X[:, 1] * 2 + 0.3 * r.randn(400) > 0).astype(np.float64)
    params = dict(objective="binary", num_leaves=7, learning_rate=0.2,
                  min_data_in_leaf=5, verbosity=-1, tree_growth="frontier")

    def run(ckpt_dir, rounds, resume=False):
        ds = lgb.Dataset(X, label=y, params=dict(params))
        return engine.train(dict(params), ds, num_boost_round=rounds,
                            callbacks=[callback.checkpoint(ckpt_dir,
                                                           period=1)],
                            resume_from=(ckpt_dir if resume else None),
                            verbose_eval=False)

    golden = run(str(tmp_path / "g"), 4)
    run(str(tmp_path / "i"), 2)                    # "preempted" at 2
    resumed = run(str(tmp_path / "i"), 4, resume=True)
    assert golden.model_to_string() == resumed.model_to_string()
