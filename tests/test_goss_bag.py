"""boosting=goss where the exact grower runs over the row partition on one
device: the sampler makes a bag, the partition starts from it, and the rows
out of it are routed and scored and cost no histogram pass.

The program is held to bench/reference_goss.py (numpy, float64, imports
nothing of the program), which follows the unsampled trees, then judges
each sampled tree on its bag and each bag against its own |g*h|. Small
sizes, seeded. Tolerances, and why:

- counts, bag sizes, rows on the wrong side of the threshold: 0. They are
  integers; the reference leaves rows within a relative 1e-4 of its own
  threshold unjudged (the program's |g*h| is float32 of float32 scores).
- leaf values 1e-4, split gains 1e-3 (relative to the larger of the
  reference's value and the tree's median): float32 sums of a few thousand
  rows against float64 read 1e-6 ... 1e-5; a weight left out of the others
  reads 0.1 and more.
- scores 1e-5: the float32 running sum of a handful of leaf values.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from bench import reference_goss as ref
from lightgbm_tpu import callback, engine
from lightgbm_tpu.obs import trace

BASE = {"objective": "binary", "boosting": "goss", "num_leaves": 15,
        "learning_rate": 0.5, "min_data_in_leaf": 5, "verbosity": -1,
        "top_rate": 0.2, "other_rate": 0.1}
WARMUP = 2          # int(1 / 0.5)
ROUNDS = 5


def dense(rng, n):
    X = rng.normal(size=(n, 6))
    return X, X[:, 0] + 0.5 * X[:, 1] ** 2 + rng.normal(size=n) > 0.5


def nan_and_zeros(rng, n):
    X, y = dense(rng, n)
    X[rng.random((n, 6)) < 0.15] = np.nan
    X[rng.random((n, 6)) < 0.25] = 0.0
    return X, y


def ties(rng, n):
    # four values a column: rows that share a cell and a label share
    # |g*h| to the last bit, so every threshold lies inside a tie
    X = rng.integers(0, 4, size=(n, 4)).astype(np.float64)
    return X, X[:, 0] + X[:, 1] + rng.normal(size=n) > 3.0


CASES = {
    "dense": (dense, {}, False),
    "nan_and_zeros": (nan_and_zeros, {}, False),
    "ties_at_the_threshold": (ties, {}, False),
    "rates_sum_to_one": (dense, {"top_rate": 0.6, "other_rate": 0.4}, False),
    "weights": (dense, {}, True),
}


def grow(X, y, params, rounds, weight=None):
    """Iteration by iteration through train_many, keeping each sampled
    iteration's bag and the rows its histogram passes saw."""
    ds = lgb.Dataset(X, y.astype(np.float64), weight=weight,
                     params=dict(params))
    bst = lgb.Booster(params=dict(params), train_set=ds)
    gbdt = bst._impl
    bags = {}
    while gbdt.iter_ < rounds:
        gbdt.train_many(1)
        if gbdt.last_bag is not None:
            it, code = gbdt.last_bag
            bags[it] = np.asarray(code)
    trees = ref.parse_trees(bst.model_to_string())
    hist_rows = {s["counts"]["start_iter"]: s["counts"]["hist_rows"]
                 for s in trace.recorded_spans()[-4 * rounds:]
                 if s["name"] == "train.block" and "hist_rows" in s["counts"]}
    return bst, trees, bags, hist_rows


@pytest.fixture(scope="module", params=sorted(CASES))
def grown(request):
    make, extra, weighted = CASES[request.param]
    rng = np.random.default_rng(33)
    n = 4000
    X, y = make(rng, n)
    weight = rng.uniform(0.5, 2.0, n) if weighted else None
    params = dict(BASE, **extra)
    bst, trees, bags, hist_rows = grow(X, y, params, ROUNDS, weight)
    followed = ref.follow(
        X.astype(np.float32), y.astype(np.float32), trees, bags,
        (params["top_rate"], params["other_rate"]), params["learning_rate"],
        0.0, ref.draw_nodes(1, trees, sorted(bags), 3),
        {"cells": 64, "min_data": 5, "search_rows": n,
         "zero_as_missing": False}, row_weight=weight)
    return {"name": request.param, "X": X, "y": y, "params": params,
            "bst": bst, "trees": trees, "bags": bags, "followed": followed,
            "hist_rows": hist_rows, "got": ref.readings(trees, followed)}


def test_the_partition_starts_from_the_bag(grown):
    gbdt = grown["bst"]._impl
    assert gbdt._goss_bag and gbdt.grow_params.all_rows_in_bag
    assert sorted(grown["bags"]) == list(range(WARMUP, ROUNDS))


def test_each_bag_is_the_top_and_an_exact_draw_of_the_rest(grown):
    n = len(grown["y"])
    p = grown["params"]
    top_cnt, other_cnt, _ = ref.bag_counts(n, p["top_rate"], p["other_rate"])
    for code in grown["bags"].values():
        assert (code == ref.BAG_TOP).sum() == top_cnt
        assert (code == ref.BAG_OTHER).sum() == other_cnt
    assert grown["got"]["bag_count_gap"] == 0
    assert grown["got"]["bag_top_missed"] == 0
    # p >= 1e-4 over the 8 tests the three bags make
    assert grown["got"]["bag_uniformity"] < 4.0
    # two bags do not share their others
    a, b = (grown["bags"][i] == ref.BAG_OTHER for i in (WARMUP, WARMUP + 1))
    assert (a & b).sum() < 0.5 * a.sum() or p["other_rate"] > 0.3


def test_counts_are_the_bags_integers_routed_by_raw_value(grown):
    assert grown["got"]["count_mismatch"] == 0
    for i, code in grown["bags"].items():
        tree = grown["trees"][i]
        assert tree["internal_count"][0] == (code > 0).sum()
        assert tree["leaf_count"].sum() == (code > 0).sum()
    for tree in grown["trees"][:WARMUP]:
        assert tree["internal_count"][0] == len(grown["y"])


def test_leaf_values_and_gains_are_the_weighted_sums_over_the_bag(grown):
    got = grown["got"]
    assert got["leaf_value_gap"] < 1e-4, got
    assert got["split_gain_gap"] < 1e-3, got
    assert got["split_order_gap"] < 1e-3, got


def test_every_row_in_the_bag_or_out_takes_every_trees_score(grown):
    X, bst = grown["X"].astype(np.float32), grown["bst"]
    scores = np.asarray(bst._impl.scores)[:, 0]
    gap = ref.score_gap(X, grown["trees"], scores, np.arange(len(scores)))
    assert gap < 1e-5
    out = grown["bags"][ROUNDS - 1] == ref.OUT_OF_BAG
    if grown["params"]["top_rate"] < 0.5:
        assert out.sum() > 0.5 * len(scores)
    np.testing.assert_allclose(bst.predict(grown["X"], raw_score=True),
                               scores, rtol=1e-5, atol=1e-6)


def test_the_kernel_sees_the_bag_and_its_smaller_children_only(grown):
    """Rows fed to the histogram kernel a tree = the bag (the root's pass)
    + the smaller child's in-bag count of every split."""
    assert sorted(grown["hist_rows"]) == list(range(WARMUP, ROUNDS))
    for i, seen in grown["hist_rows"].items():
        t = grown["trees"][i]
        count = lambda c: t["leaf_count"][-c - 1] if c < 0 \
            else t["internal_count"][c]
        smaller = sum(min(count(int(l)), count(int(r)))
                      for l, r in zip(t["left_child"], t["right_child"]))
        assert seen == t["internal_count"][0] + smaller
        assert seen < len(grown["y"]) or grown["params"]["top_rate"] > 0.5


def test_ties_at_the_threshold_go_to_the_lower_row_ids():
    rng = np.random.default_rng(34)
    X, y = ties(rng, 4000)
    _, trees, bags, _ = grow(X, y, BASE, WARMUP + 1)
    followed_scores = np.full(len(y), ref.init_score(y.astype(np.float64)))
    cols = ref.Columns(X)
    for t in trees[:WARMUP]:
        followed_scores += t["leaf_value"][ref.route(cols, t, n=len(y))]
    followed_scores -= ref.init_score(y.astype(np.float64))   # tree 0 holds it
    p = ref.sigmoid(followed_scores)
    gh = np.abs((p - y) * p * (1 - p))
    top_cnt = ref.bag_counts(len(y), 0.2, 0.1)[0]
    thr = np.partition(gh, len(y) - top_cnt)[len(y) - top_cnt]
    tied = np.flatnonzero(np.abs(gh - thr) <= 1e-4 * thr)
    top = bags[WARMUP][tied] == ref.BAG_TOP
    assert 1 < len(tied) and 0 < top.sum() < len(tied)    # a real tie
    assert not top[np.argmin(top):].any()     # tops first, by row id


def test_unsampled_iterations_are_boosting_gbdts():
    rng = np.random.default_rng(35)
    X, y = dense(rng, 1500)
    params = dict(BASE, learning_rate=0.1, num_leaves=7)
    goss = lgb.train(params, lgb.Dataset(X, y.astype(float), params=params),
                     num_boost_round=12)
    plain = dict(params, boosting="gbdt")
    gbdt = lgb.train(plain, lgb.Dataset(X, y.astype(float), params=plain),
                     num_boost_round=10)
    split = lambda b: b.model_to_string().split("end of trees")[0] \
        .split("Tree=")[1:]
    assert split(goss)[:10] == split(gbdt)
    assert goss._impl._sampled_regime
    counts = [s["counts"] for s in trace.recorded_spans()
              if s["name"] == "train.block"][-3:]
    # train_many cut its blocks at the switch: 10 unsampled, 2 sampled
    assert [(c["count"], c.get("goss_active")) for c in counts[:2]] == \
        [(10, 0), (2, 1)]
    assert [t.internal_count[0] for t in goss._impl.models[9:11]] == \
        [1500, 450]


def test_compile_block_readies_the_sampled_program_and_runs_nothing():
    from lightgbm_tpu.profiling import compile_cache_stats
    rng = np.random.default_rng(36)
    X, y = dense(rng, 1000)
    params = dict(BASE, num_leaves=7)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, y.astype(float), params=params))
    gbdt = bst._impl
    gbdt.train_many(WARMUP)
    before = np.asarray(gbdt.scores).copy()
    gbdt.compile_block(1)
    assert gbdt._sampled_regime and gbdt.iter_ == WARMUP
    np.testing.assert_array_equal(before, np.asarray(gbdt.scores))
    compiles = compile_cache_stats()["backend_compiles"]
    gbdt.train_many(1)
    assert compile_cache_stats()["backend_compiles"] == compiles
    assert gbdt.models[WARMUP].internal_count[0] == 300


@pytest.mark.parametrize("kill_at", [1, 2, 3])
def test_resume_across_the_switch_is_byte_identical(tmp_path, kill_at):
    rng = np.random.default_rng(37)
    X, y = dense(rng, 600)
    params = dict(BASE, num_leaves=5)

    def train(ckpt, rounds, resume=False):
        ds = lgb.Dataset(X, label=y.astype(float), params=dict(params))
        return engine.train(dict(params), ds, num_boost_round=rounds,
                            callbacks=[callback.checkpoint(ckpt, period=1)],
                            resume_from=ckpt if resume else None,
                            verbose_eval=False)
    golden = train(str(tmp_path / "g"), 5)
    train(str(tmp_path / "i"), kill_at)
    resumed = train(str(tmp_path / "i"), 5, resume=True)
    assert golden.model_to_string() == resumed.model_to_string()
