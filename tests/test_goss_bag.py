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
    # every block counts its trees' work; the sampled ones' are read here
    hist_rows = {s["counts"]["start_iter"]: s["counts"]["hist_rows"]
                 for s in trace.recorded_spans()[-4 * rounds:]
                 if s["name"] == "train.block"
                 and s["counts"].get("goss_active") == 1}
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


# ---- PR 34: a tree on a bag routes ALL rows in row space -----------------

def _numerical(rng, n):
    return dense(rng, n), {}


def _nan_missing(rng, n):
    X, y = dense(rng, n)
    X[rng.random((n, 6)) < 0.3] = np.nan
    return (X, y), {}


def _zero_missing(rng, n):
    X, y = dense(rng, n)
    X[rng.random((n, 6)) < 0.3] = 0.0
    return (X, y), {"zero_as_missing": True}


def _categorical(rng, n):
    X, y = dense(rng, n)
    X[:, 0] = rng.integers(0, 40, n)
    y = (np.isin(X[:, 0], [3, 7, 11, 19, 23, 31]) * 2.0 + 0.5 * X[:, 1]
         + 0.3 * rng.normal(size=n)) > 0.6
    return (X, y), {"categorical_feature": "0", "min_data_per_group": 5,
                    "cat_smooth": 1.0}


def _efb_bundled(rng, n):
    # five mutually exclusive sparse columns (one active a row, or none,
    # integer values: few bins): EFB stores them as one
    which = rng.integers(0, 8, n)
    X = np.zeros((n, 6))
    vals = rng.integers(1, 9, n).astype(np.float64)
    for c in range(5):
        X[which == c, c] = vals[which == c]
    X[:, 5] = rng.normal(size=n)
    y = X[:, 0] - X[:, 2] + X[:, 5] + 0.3 * rng.normal(size=n) > 0.3
    return (X, y), {"enable_bundle": True}


ROUTING_CASES = {"numerical": _numerical, "nan_missing": _nan_missing,
                 "zero_missing": _zero_missing, "categorical": _categorical,
                 "efb_bundled": _efb_bundled}


@pytest.mark.parametrize("case", sorted(ROUTING_CASES))
def test_sampled_leaf_ids_are_every_rows_leaf_by_raw_value(case):
    """The sampled program's own leaf_id_out, rows in the bag and out of
    it, against the grown tree walked by raw value (the predictor: real
    thresholds, missing directions and category sets, no bins)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(341)
    n = 3000
    (X, y), extra = ROUTING_CASES[case](rng, n)
    params = dict(BASE, **extra)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, y.astype(np.float64), params=dict(params)))
    gbdt = bst._impl
    gbdt.train_many(WARMUP)
    gbdt.compile_block(1)          # the sampled program is current now
    assert gbdt._sampled_regime and gbdt._rowspace_bins() is not None
    # the key train_many hands the next iteration's sampler
    gkey = jax.random.split(jax.random.split(gbdt._bag_key, 2)[1])[1]
    f = gbdt.train_data.num_features
    out = jax.jit(gbdt._iter_core)(
        *gbdt._iter_capture, gbdt.scores, jnp.ones((n,), jnp.float32),
        jnp.ones((f,), bool), jnp.zeros((n, 1), jnp.float32),
        jnp.ones((n, 1), jnp.float32), jnp.float32(gbdt.shrinkage_rate),
        jnp.float32(1.0), gkey, None, gbdt._stopped_dev,
        gbdt._rowspace_bins())
    leaf_ids, code = np.asarray(out[1][0]), np.asarray(out[-1][1])
    gbdt.train_many(1)             # the same iteration, for its tree
    np.testing.assert_array_equal(code, np.asarray(gbdt.last_bag[1]))
    by_value = bst.predict(X, pred_leaf=True)[:, WARMUP]
    assert leaf_ids.dtype == np.int32 and leaf_ids.shape == (n,)
    np.testing.assert_array_equal(leaf_ids, by_value)
    out_of_bag = code == ref.OUT_OF_BAG
    assert out_of_bag.sum() > 0.6 * n
    assert len(np.unique(leaf_ids[out_of_bag])) > 3
    tree = gbdt.models[WARMUP]
    np.testing.assert_array_equal(
        np.bincount(leaf_ids[~out_of_bag], minlength=tree.num_leaves),
        tree.leaf_count[:tree.num_leaves])
    # the case is the case: a split of the kind it names was routed
    text = bst.model_to_string().split("Tree=%d" % WARMUP)[1] \
        .split("Tree=")[0]
    decisions = [int(d) for d in text.split("decision_type=")[1]
                 .split("\n")[0].split()]
    if case == "categorical":
        assert any(d & 1 for d in decisions)
    if case == "nan_missing":
        assert any((d >> 2) & 3 == 2 for d in decisions)
    if case == "zero_missing":
        assert any((d >> 2) & 3 == 1 for d in decisions)
    if case == "efb_bundled":
        assert gbdt.grow_params.with_efb and gbdt.xb.shape[1] < f
        used = {int(v) for v in text.split("split_feature=")[1]
                .split("\n")[0].split()}
        assert used & {0, 1, 2, 3, 4}


def test_thirteen_goss_iterations_write_the_model_recorded_before_pr34():
    """Ten unsampled trees and three on a bag: the model text is the one
    the parent of PR 34 (f003618, second range a leaf through the tile
    loop) wrote on this data, recorded there as a hash."""
    import hashlib
    rng = np.random.default_rng(340)
    n = 3000
    X = rng.normal(size=(n, 6))
    y = X[:, 0] + 0.5 * X[:, 1] ** 2 + rng.normal(size=n) > 0.5
    X[rng.random((n, 6)) < 0.15] = np.nan
    X[rng.random((n, 6)) < 0.25] = 0.0
    X[:, 5] = rng.integers(0, 12, n)
    params = dict(BASE, learning_rate=0.1, categorical_feature="5")
    bst = lgb.train(params, lgb.Dataset(X, y.astype(float), params=params),
                    num_boost_round=13)
    assert bst._impl._goss_bag and bst._impl._sampled_regime
    text = bst.model_to_string().split("end of trees")[0]
    assert text.count("Tree=") == 13 and "cat_threshold" in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "ef15b5478ab1dca8dbbed928239eb2ceab317316c6e016e11b16bbddc7048f04"


def test_rowspace_routing_is_counted_where_the_bag_is_and_nowhere_else():
    rng = np.random.default_rng(342)
    X, y = dense(rng, 1100)
    for boosting, extra in (("goss", {}), ("gbdt", {}),
                            ("goss", {"tree_growth": "frontier"})):
        params = dict(BASE, boosting=boosting, **extra)
        bst = lgb.Booster(params=params, train_set=lgb.Dataset(
            X, y.astype(float), params=dict(params)))
        gbdt = bst._impl
        plan = gbdt.plan
        if boosting == "goss" and not extra:
            assert gbdt._goss_bag and plan.goss_bag and plan.copy
            # one byte a stored bin, rows padded to whole lanes
            assert gbdt._bins_by_col.nbytes == 6 * 2 * 1024
            assert gbdt._rowspace_bins() is None     # unsampled so far
            assert not plan.row_space_ids
        else:
            assert not gbdt._goss_bag and not plan.goss_bag
        if boosting == "gbdt":
            # over all rows the ids live in row space, by the same copy
            assert plan.row_space_ids and plan.copy
            assert gbdt._bins_by_col.nbytes == 6 * 2 * 1024
            assert gbdt._rowspace_bins() is gbdt._bins_by_col
        elif extra:
            assert not plan.row_space_ids and not plan.copy
            assert gbdt._bins_by_col is None


def _rows_indexed_outside_tile_loops(jaxpr, rows):
    """Names of the gathers and scatters with at least ``rows`` indices
    that sit in ``jaxpr`` outside every ``while`` (the tile loops)."""
    import jax
    found = []
    for e in jaxpr.eqns:
        name = e.primitive.name
        if name == "while":
            continue
        if (name == "gather" or name.startswith("scatter")) \
                and int(np.prod(e.invars[1].aval.shape[:-1])) >= rows:
            found.append(name)
        for sub in jax.core.jaxprs_in_params(e.params):
            found += _rows_indexed_outside_tile_loops(sub, rows)
    return found


def _prims_under(jaxpr, scope, inside=False):
    """Primitive names of every equation under ``scope``, with all that
    such an equation nests (a nested trace starts its name stack anew)."""
    import jax
    for e in jaxpr.eqns:
        under = inside or scope in str(e.source_info.name_stack)
        if under:
            yield e.primitive.name
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _prims_under(sub, scope, under)


def test_the_sampled_split_loop_indexes_rows_only_in_the_bags_tile_loops():
    """Jaxpr audit of grow_tree on a bag: inside the split loop no gather
    and no scatter over a tile's worth of rows or more outside the two
    tile loops (the bag's partition pass and the smaller child's pass),
    nothing indexed under lgbm.route_only at all, and no
    leaf_id_from_partition (its prefix sum and scatter) anywhere."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core import partition
    from lightgbm_tpu.core.grow import grow_tree
    rng = np.random.default_rng(343)
    n = 2600
    X, y = dense(rng, n)
    bst = lgb.Booster(params=dict(BASE), train_set=lgb.Dataset(
        X, y.astype(float), params=dict(BASE)))
    gbdt = bst._impl
    chunk = 256
    params = gbdt.grow_params._replace(row_chunk=chunk)

    def grow(xb, g, h, in_bag, meta, cols):
        bag = partition.bag_partition(in_bag, chunk) if cols is not None \
            else None
        return grow_tree(xb, g, h, in_bag.astype(jnp.float32), meta,
                         jnp.ones((6,), bool), params, bag=bag,
                         bins_by_col=cols)

    args = (gbdt.xb, jnp.ones((n,)), jnp.ones((n,)),
            jnp.arange(n) % 3 == 0, gbdt.feature_meta)

    def audit(cols):
        jaxpr = jax.make_jaxpr(grow)(*args, cols).jaxpr
        loops = [e for e in jaxpr.eqns if e.primitive.name == "scan"
                 and e.params["length"] == params.num_leaves - 1]
        assert len(loops) == 1
        body = loops[0].params["jaxpr"].jaxpr
        return jaxpr, body

    jaxpr, body = audit(gbdt._bins_by_col)
    assert _rows_indexed_outside_tile_loops(body, chunk) == []
    routed = list(_prims_under(body, "lgbm.route_only"))
    assert routed.count("cond") == 1    # a dead split takes the empty branch
    assert "select_n" in routed and "dynamic_slice" in routed
    indexed = ("gather", "scatter", "sort", "while", "cumsum")
    assert not [p for p in routed if p.startswith(indexed)]
    ids = list(_prims_under(jaxpr, "lgbm.leaf_ids"))
    assert ids and not [p for p in ids if p.startswith(indexed)]
    # the walk does see what it guards against: without a bag the ids come
    # from leaf_id_from_partition, a prefix sum and a scatter through order
    plain, plain_body = audit(None)
    assert _rows_indexed_outside_tile_loops(plain_body, chunk) == []
    assert "scatter" in _rows_indexed_outside_tile_loops(plain, chunk)
    ids = list(_prims_under(plain, "lgbm.leaf_ids"))
    assert "cumsum" in ids and "scatter" in ids
