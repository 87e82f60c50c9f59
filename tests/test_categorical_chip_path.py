"""Categorical splits on the path the chip runs (the exact grower over the
row partition), at small sizes on the CPU: the program against the plain
reference that knows a categorical node (bench/reference_categorical.py,
through the benchmark job's own ``judge``), the gather-free membership
test against the one it replaced, the jaxpr of the routing, the finder
over the categorical columns alone against the finder over every column,
the native categorical binner against numpy, and the numerical train
block's lowered text against the recorded one.
"""
import hashlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from bench.jobs import categorical_window as job
from lightgbm_tpu.core import split as split_mod
from lightgbm_tpu.core.grow import (MISSING_NAN, MISSING_NONE, MISSING_ZERO,
                                    _bin_go_left)
from lightgbm_tpu.io.binning import BinMapper, BinType

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
          "max_cat_threshold": 32, "cat_l2": 10.0, "cat_smooth": 10.0,
          "max_cat_to_onehot": 4, "min_data_per_group": 100}
# float32 sums of a few thousand rows against float64. Over the six cases
# the worst leaf read 1e-6 ... 5.7e-5 and the tree's median leaf 2e-7 ...
# 1.7e-6; the worst split 1.3e-4 ... 5.3e-3 (a late split on a noise column,
# whose gain is a small difference of large terms) and the median split
# 1.2e-6 ... 2.1e-5. With cat_l2 left out of the reference the same four
# read 0.28, 0.015, 0.5 and 0.0034: each limit lies between, four times
# over the one and 20 times under the other at the least
# (test_the_reference_tells_cat_l2). node_regret is not held over nodes
# drawn from the seed: these nodes hold a few hundred rows, and all that
# reads there is a noise column's 64 rank-spaced cut points against the
# program's 63 bins (0.1-0.6; the benchmark's rehearsal limit is 0.5 for
# the same reason). It is held AT the categorical nodes, where the
# reference searches upstream's candidates exactly and read 1e-13
CHECK = {"follow_trees": 3, "sample_rows": 1000000, "regret_nodes": 6,
         "grid_cells": 64, "search_rows": 1000000,
         "limits": {"count_mismatch": 0, "leaf_value_gap": 3e-4,
                    "leaf_value_gap_median": 2e-5, "split_gain_gap": 0.02,
                    "split_gain_gap_median": 1e-4,
                    "split_order_gap": 0.01, "score_gap": 3e-5}}
CATEGORICAL_NODE_REGRET = 1e-4


def label(rng, logit):
    return (rng.random(len(logit)) < 1 / (1 + np.exp(-logit))).astype(
        np.float32)


def zipf_ids(rng, n, k):
    return np.minimum((k + 1) ** rng.random(n) - 1, k - 1).astype(np.int64)


def both_branches(rng, n):
    a, b = zipf_ids(rng, n, 40), zipf_ids(rng, n, 3)
    t = rng.standard_normal(40)[a] + np.array([0.8, -0.6, 0.1])[b]
    X = np.column_stack([rng.standard_normal(n), a, b]).astype(np.float64)
    return X, label(rng, t), "1,2", {}


def nan_negative_unseen(rng, n):
    a = zipf_ids(rng, n, 60).astype(np.float64)
    t = rng.standard_normal(60)[a.astype(int)]
    a[rng.random(n) < 0.1] = np.nan
    a[rng.random(n) < 0.05] = -3.0
    X = np.column_stack([a, rng.standard_normal(n)])
    # 15 bins keep 14 of the 60 categories: the others are ids unseen by
    # every node's set
    return X, label(rng, np.nan_to_num(t)), "0", {"max_bin": 16}


def zero_most_frequent(rng, n):
    a = zipf_ids(rng, n, 25)               # id 0 is the most frequent
    t = 1.2 * rng.standard_normal(25)[a]
    X = np.column_stack([a, rng.standard_normal(n)]).astype(np.float64)
    return X, label(rng, t), "0", {}


def three_categories_one_over_half(rng, n):
    a = rng.choice(3, n, p=[0.62, 0.25, 0.13])
    t = np.array([-0.7, 0.9, 0.2])[a] + 0.3 * rng.standard_normal(n)
    X = np.column_stack([rng.standard_normal(n), a]).astype(np.float64)
    return X, label(rng, t), "1", {}


def sparse_ids_to_10m(rng, n):
    ids = np.sort(rng.choice(10_000_000, 30, replace=False))
    ids[-1] = 9_999_999
    a = zipf_ids(rng, n, 30)
    t = 1.1 * rng.standard_normal(30)[a]
    X = np.column_stack([ids[a], rng.standard_normal(n)]).astype(np.float64)
    return X, label(rng, t), "0", {}


def tied_with_a_numerical_column(rng, n):
    a = (rng.random(n) < 0.4).astype(np.int64)      # two categories
    t = 1.5 * a - 0.5 + 0.4 * rng.standard_normal(n)
    # the same two values as numbers: the same candidate at the same gain
    X = np.column_stack([a, a, rng.standard_normal(n)]).astype(np.float64)
    return X, label(rng, t), "0", {}


CASES = {f.__name__: f for f in (
    both_branches, nan_negative_unseen, zero_most_frequent,
    three_categories_one_over_half, sparse_ids_to_10m,
    tied_with_a_numerical_column)}


def train(case, rounds=3, **more):
    rng = np.random.default_rng(35)
    X, y, cat, extra = CASES[case](rng, 4000)
    params = dict(PARAMS, categorical_feature=cat, **extra)
    params.update(more)
    bst = lgb.train(params, lgb.Dataset(X, y, params=dict(params)),
                    num_boost_round=rounds)
    return X, y, params, bst


def judged(X, y, params, bst, text=None):
    ctx = {"seed": 35, "workload": {"check": CHECK},
           "config": {"params": params}}
    return job.judge(ctx, X.astype(np.float32), y,
                     text or bst.model_to_string(num_iteration=-1),
                     np.asarray(bst._impl.scores)[:, 0])


def regret_at_categorical_nodes(X, y, params, text):
    """The reference's regret over the judged trees' categorical nodes
    (six a tree at most, the earliest)."""
    ref = job.reference_categorical
    ctx = {"seed": 35, "workload": {"check": CHECK},
           "config": {"params": params}}
    trees = ref.parse_trees(text)[:CHECK["follow_trees"]]
    nodes = [[int(k) for k in np.flatnonzero(t["decision_type"] & 1)[:6]]
             for t in trees]
    followed = ref.follow(X.astype(np.float32), y, trees,
                          params["learning_rate"], 0.0, nodes,
                          job.search_of(ctx, text))
    return max(f["node_regret"] for f in followed)


def categorical_splits(text):
    """(column, words) of every categorical node of a model text."""
    out = []
    for t in job.reference_categorical.parse_trees(text):
        for k in np.flatnonzero(t["decision_type"] & 1):
            out.append((int(t["split_feature"][k]),
                        job.reference_categorical.node_words(t, k)))
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_program_against_the_reference_that_knows_a_category(case):
    """Counts equal to rows routed by raw value, leaf values and gains
    within CHECK's tolerances, scores equal to the sum of the trees'
    leaves, on the exact grower over the row partition."""
    X, y, params, bst = train(case)
    g = bst._impl.grow_params
    assert g.use_partition and g.with_categorical == len(
        params["categorical_feature"].split(","))
    text = bst.model_to_string(num_iteration=-1)
    splits = categorical_splits(text)
    assert splits, "no categorical split was grown"
    compared, correct = judged(X, y, params, bst, text)
    assert compared["count_mismatch"]["value"] == 0, compared
    assert correct, compared
    assert regret_at_categorical_nodes(X, y, params, text) \
        <= CATEGORICAL_NODE_REGRET
    cols = {c for c, _ in splits}
    if case == "both_branches":
        assert cols == {1, 2}        # sorted-subset and one-vs-rest
    if case == "sparse_ids_to_10m":
        # a node's words run to the largest id going left, no further
        # (upstream's layout: 9,999,999 going left is 312,500 words, 0.6 MB
        # of text a node; label-encoded ids keep it to a few words)
        for _, w in splits:
            assert len(w) == 1 or w[-1] != 0
        assert max(len(w) for _, w in splits) == 9_999_999 // 32 + 1
        assert min(len(w) for _, w in splits) < 9_999_999 // 32 + 1
        assert len(text) < 20_000_000
    if case == "nan_negative_unseen":
        assert bst._impl.feature_meta.num_bin[0] <= 16
    again = lgb.Booster(model_str=text)
    np.testing.assert_array_equal(bst.predict(X), again.predict(X))


def test_the_reference_tells_cat_l2():
    """The tolerances can fail: with cat_l2 left out of the children's
    values (the reference told 0) a sorted-subset leaf is off by far more
    than leaf_value_gap allows."""
    X, y, params, bst = train("both_branches")
    compared, correct = judged(X, y, dict(params, cat_l2=0.0), bst)
    assert not correct
    for name in ("leaf_value_gap", "leaf_value_gap_median",
                 "split_gain_gap", "split_gain_gap_median"):
        assert compared[name]["value"] > 20 * CHECK["limits"][name]
    assert compared["count_mismatch"]["value"] == 0


# ------------------------------------------------------- the membership test
def old_bin_go_left(col, threshold, default_left, missing_type, num_bin,
                    default_bin, is_cat, cat_bitset):
    """core/grow.py ``_bin_go_left`` as it stood before PR 35: the set's
    word looked up through a gather."""
    coli = col.astype(jnp.int32)
    is_missing = jnp.where(
        missing_type == MISSING_NAN, coli == num_bin - 1,
        jnp.where(missing_type == MISSING_ZERO, coli == default_bin, False))
    numerical = jnp.where(is_missing, default_left, coli <= threshold)
    word = cat_bitset[coli >> 5]
    categorical = ((word >> (coli & 31).astype(jnp.uint32)) & 1) == 1
    return jnp.where(is_cat, categorical, numerical)


@pytest.mark.parametrize("missing_type", [MISSING_NONE, MISSING_ZERO,
                                          MISSING_NAN])
def test_the_gather_free_test_is_the_old_one(missing_type):
    """All 256 bins x 200 random sets, categorical and numerical splits."""
    rng = np.random.default_rng(missing_type)
    bins = jnp.arange(256, dtype=jnp.uint8)
    sets = rng.integers(0, 2 ** 32, size=(200, 8), dtype=np.uint64).astype(
        np.uint32)
    sets[:20] &= rng.integers(0, 2 ** 32, size=(20, 8), dtype=np.uint64) \
        .astype(np.uint32) & 0x11111111        # sparse sets too
    for i, words in enumerate(sets):
        args = (bins, jnp.int32(rng.integers(0, 255)), jnp.asarray(i % 2 == 0),
                jnp.int32(missing_type), jnp.int32(rng.integers(2, 257)),
                jnp.int32(rng.integers(0, 255)), jnp.asarray(i % 3 != 0),
                jnp.asarray(words))
        np.testing.assert_array_equal(np.asarray(_bin_go_left(*args)),
                                      np.asarray(old_bin_go_left(*args)))


def _prims_under(jaxpr, scope, inside=False):
    """Primitive names of every equation under ``scope``, with all that
    such an equation nests."""
    for e in jaxpr.eqns:
        under = inside or scope in str(e.source_info.name_stack)
        if under:
            yield e.primitive.name
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _prims_under(sub, scope, under)


def test_the_categorical_train_block_routes_without_a_gather():
    X, y, params, bst = train("both_branches", rounds=1)
    g = bst._impl
    assert g.grow_params.with_categorical == 2
    jaxpr = jax.make_jaxpr(g._build_run_block())(*g.train_block_sds(1)).jaxpr
    routed = list(_prims_under(jaxpr, "lgbm.route_rows"))
    assert "select_n" in routed and "shift_right_logical" in routed
    assert not [p for p in routed if p.startswith(("gather", "scatter"))]
    # the walk does see what it guards against
    old = jax.make_jaxpr(lambda c, w: old_bin_go_left(
        c, 3, True, 0, 9, 0, True, w))(jnp.zeros(8, jnp.int32),
                                       jnp.zeros(8, jnp.uint32)).jaxpr
    assert "gather" in [e.primitive.name for e in old.eqns]
    # and the finder carries its own scope inside the split search's
    searched = list(_prims_under(jaxpr, "lgbm.split_search_cat"))
    assert "sort" in searched and "while" not in searched


# ------------------------------------------------------------- the finder
def _finder_inputs(rng, f=9, b=64):
    num_bin = np.array([64, 64, 4, 30, 64, 3, 64, 12, 64], np.int32)
    is_cat = np.array([0, 1, 1, 1, 0, 1, 0, 1, 0], bool)
    cnt = rng.integers(0, 400, size=(f, b)).astype(np.float32)
    cnt *= np.arange(b)[None, :] < num_bin[:, None]
    cnt[:, 0] += 50
    g = (rng.standard_normal((f, b)) * np.sqrt(cnt) * 0.3).astype(np.float32)
    # every column's bins hold the same rows in all: one leaf's totals
    total = cnt[0].sum()
    cnt *= (total / cnt.sum(axis=1))[:, None]
    hist = np.stack([g, cnt * 0.2, cnt], axis=-1)
    meta = split_mod.FeatureMeta(
        num_bin=jnp.asarray(num_bin), missing_type=jnp.zeros(f, jnp.int32),
        default_bin=jnp.zeros(f, jnp.int32), is_categorical=jnp.asarray(is_cat),
        penalty=jnp.ones(f, jnp.float32), monotone=jnp.zeros(f, jnp.int32))
    sp = split_mod.SplitParams(
        lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0, min_data_in_leaf=20,
        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
        max_cat_threshold=32, cat_smooth=10.0, cat_l2=10.0,
        max_cat_to_onehot=4, min_data_per_group=100)
    return jnp.asarray(hist), meta, sp


def _sorted_subset_as_before(hist_f, num_bin, sp, sum_grad, sum_hess,
                             num_data):
    """One column's sorted-subset search as core/split.py had it before
    PR 35: two argsorts a direction and a scan over every bin."""
    b = hist_f.shape[0]
    bins = jnp.arange(b, dtype=jnp.int32)
    sum_hess = sum_hess + 2 * split_mod.K_EPSILON
    shift = split_mod.leaf_split_gain(sum_grad, sum_hess, sp.lambda_l1,
                                      sp.lambda_l2, sp.max_delta_step) \
        + sp.min_gain_to_split
    is_real = (bins >= 1) & (bins < num_bin)
    g = jnp.where(is_real, hist_f[:, 0], 0.0)
    h = jnp.where(is_real, hist_f[:, 1], 0.0)
    c = jnp.where(is_real, hist_f[:, 2], 0.0)
    elig = is_real & (c >= sp.cat_smooth)
    n_elig = jnp.sum(elig.astype(jnp.int32))
    ctr = g / (h + sp.cat_smooth)
    max_num_cat = jnp.minimum(sp.max_cat_threshold, (n_elig + 1) // 2)

    def one_direction(key):
        order = jnp.argsort(key)
        gs, hs, cs = g[order], h[order], c[order]
        pg, ph, pc = jnp.cumsum(gs), jnp.cumsum(hs) + split_mod.K_EPSILON, \
            jnp.cumsum(cs)
        i = jnp.arange(b, dtype=jnp.int32)
        in_range = (i < max_num_cat) & (i < n_elig)
        left_ok = (pc >= sp.min_data_in_leaf) \
            & (ph >= sp.min_sum_hessian_in_leaf)
        rc, rh = num_data - pc, sum_hess - ph
        stop = (rc < sp.min_data_in_leaf) | (rc < sp.min_data_per_group) \
            | (rh < sp.min_sum_hessian_in_leaf)
        alive = jnp.cumsum((left_ok & stop).astype(jnp.int32)) == 0
        can = in_range & alive & left_ok

        def gstep(cnt_group, inp):
            cs_i, can_i = inp
            cnt_group = cnt_group + cs_i
            do_eval = can_i & (cnt_group >= sp.min_data_per_group)
            return jnp.where(do_eval, 0.0, cnt_group), do_eval

        _, do_eval = jax.lax.scan(gstep, jnp.asarray(0.0), (cs, can))
        gain2, _, _ = split_mod._split_gains_l2(
            pg, ph, sum_grad - pg, sum_hess - ph, sp,
            sp.lambda_l2 + sp.cat_l2, -jnp.inf, jnp.inf)
        gain2 = jnp.where(do_eval & (gain2 > shift), gain2, -jnp.inf)
        ib = jnp.argmax(gain2)
        member = (jnp.argsort(order) <= ib) & elig
        return gain2[ib], member

    ga, ma = one_direction(jnp.where(elig, ctr, jnp.inf))
    gd, md = one_direction(jnp.where(elig, -ctr, jnp.inf))
    return jnp.where(ga >= gd, ga, gd) - shift, jnp.where(ga >= gd, ma, md)


@pytest.mark.parametrize("seed", range(4))
def test_the_finder_over_the_categorical_columns_alone(seed):
    """The same per-feature splits and sets as the finder run over every
    column and masked afterwards, and the sorted-subset search (one sort a
    direction, max_cat_threshold steps) finds what the search over every
    bin found."""
    hist, meta, sp = _finder_inputs(np.random.default_rng(seed))
    tot = jnp.sum(hist[0], axis=0)
    mask = jnp.ones(hist.shape[0], bool)

    def run(with_categorical):
        return split_mod.per_feature_split_merged(
            hist, meta, sp, tot[0], tot[1], tot[2], mask,
            with_categorical=with_categorical)

    (pf_all, sets_all), (pf_cat, sets_cat) = run(True), run(5)
    for a, b in zip(pf_all, pf_cat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(sets_all), np.asarray(sets_cat))
    assert np.isfinite(np.asarray(pf_cat.gain)[[1, 3, 7]]).all()
    best_all = split_mod.find_best_split(hist, meta, sp, tot[0], tot[1],
                                         tot[2], mask, with_categorical=True)
    best_cat = split_mod.find_best_split(hist, meta, sp, tot[0], tot[1],
                                         tot[2], mask, with_categorical=5)
    for a, b in zip(best_all, best_cat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for j in (1, 3, 7):                  # the sorted-subset columns
        gain, member = _sorted_subset_as_before(
            hist[j], meta.num_bin[j], sp, tot[0], tot[1], tot[2])
        np.testing.assert_allclose(float(gain), float(pf_cat.gain[j]),
                                   rtol=1e-6)
        bits = np.asarray(sets_cat[j])
        got = ((bits[:, None] >> np.arange(32, dtype=np.uint32)) & 1) \
            .astype(bool).reshape(-1)[:hist.shape[1]]
        np.testing.assert_array_equal(got, np.asarray(member))


# ------------------------------------------------------------- the binner
def test_the_native_categorical_binner_is_the_numpy_path():
    from lightgbm_tpu.native import bin_categorical_native
    rng = np.random.default_rng(5)
    m = BinMapper()
    sample = np.concatenate([zipf_ids(rng, 50000, 3000).astype(np.float64),
                             [9_999_999.0] * 400])
    m.find_bin(sample[sample != 0], total_sample_cnt=len(sample), max_bin=255,
               bin_type=BinType.CATEGORICAL)
    assert m.num_bin == 255 and m.categories_seen > 254
    keys = np.array(sorted(m.categorical_2_bin), np.int64)
    vals = np.array([m.categorical_2_bin[k] for k in keys], np.int32)
    values = np.concatenate([
        zipf_ids(rng, 200000, 6000).astype(np.float64),
        [np.nan, np.inf, -np.inf, -1.0, -0.5, 0.0, 0.9, 1.0, 1.5, 2.0 ** 40,
         1e19, -1e19, 9_999_999.0, 9_999_999.5, 1e7]])
    rng.shuffle(values)
    by_numpy = BinMapper._categorical_bins_numpy(values, keys, vals)
    # the loop the vector forms replace: upstream's ValueToBin, a value
    by_loop = np.array([m.value_to_bin(v) if v > -1 or v != v else 0
                        for v in values[:5000]], np.int32)
    np.testing.assert_array_equal(by_numpy[:5000], by_loop)
    native = bin_categorical_native(values, keys, vals)
    if native is None:
        pytest.skip("no native library here: the numpy path is the path")
    np.testing.assert_array_equal(native, by_numpy)
    np.testing.assert_array_equal(m.values_to_bins(values), by_numpy)
    assert by_numpy.max() == 254 and (by_numpy == 0).any()


def test_id_zero_the_most_frequent_category_keeps_a_bin_of_its_own():
    """_find_bin_categorical's swap: bin 0 is the catch-all, so id 0 is
    binned like any other kept category, most frequent or not."""
    m = BinMapper()
    vals = np.repeat([0.0, 1.0, 2.0], [600, 300, 100])
    m.find_bin(vals[vals != 0], total_sample_cnt=1000, max_bin=255,
               bin_type=BinType.CATEGORICAL)
    assert m.bin_2_categorical[:2] == [1, 0] and m.num_bin == 4
    assert list(m.values_to_bins(np.array([0.0, 1.0, 2.0, 3.0, np.nan]))) \
        == [2, 1, 3, 0, 0]


# ------------------------------------------------ spans, counts, the model
def test_the_spans_and_counts_a_categorical_table_leaves():
    from lightgbm_tpu.obs import trace
    X, y, params, bst = train("both_branches", rounds=2)
    bst.model_to_string()                      # the trees are fetched
    spans = trace.recorded_spans()
    last = lambda name: [s for s in spans if s["name"] == name][-1]
    cat = last("ingest.bin_categorical")
    assert cat["counts"]["columns"] == 2 and cat["counts"]["values"] == 8000
    # the 99% coverage cut may drop the rarest of the 40
    assert 40 <= cat["counts"]["categories_kept"] \
        <= cat["counts"]["categories_seen"] == 40 + 3
    by_id = {s["id"]: s for s in spans}
    assert by_id[cat["parent"]]["name"] == "ingest.bin_columns"
    setup = last("train.setup")["counts"]
    assert setup["features_categorical"] == 2
    assert setup["cat_route_gather_free"] == 1
    block = [s for s in spans if s["name"] == "train.block"][-1]
    text = bst.model_to_string(num_iteration=-1)
    per_tree = [int((t["decision_type"] & 1).sum())
                for t in job.reference_categorical.parse_trees(text)]
    # one block of two iterations: its trees' categorical splits
    assert block["counts"]["cat_splits"] == sum(per_tree) > 0


def test_a_table_without_categories_counts_none():
    from lightgbm_tpu.obs import trace
    rng = np.random.default_rng(1)
    X = rng.standard_normal((500, 3))
    p = {"objective": "binary", "num_leaves": 4, "verbosity": -1}
    bst = lgb.train(p, lgb.Dataset(X, (X[:, 0] > 0).astype(float), params=p),
                    num_boost_round=1)
    bst.model_to_string()
    spans = trace.recorded_spans()
    setup = [s for s in spans if s["name"] == "train.setup"][-1]["counts"]
    assert setup["features_categorical"] == 0
    assert setup["cat_route_gather_free"] == 0
    assert "cat_splits" not in [s for s in spans
                                if s["name"] == "train.block"][-1]["counts"]
    assert bst._impl.grow_params.with_categorical == 0


# ----------------------------------------------- the packed row's width
@pytest.mark.parametrize("cols,width", [(5, 64), (39, 64), (51, 64),
                                        (52, 64), (53, 65), (67, 79)])
def test_the_packed_row_is_no_narrower_than_the_gather_wants(cols, width):
    """Under MIN_PACKED_WIDTH the packed rows are filled with zero bytes
    (the v5e gathers a narrower row through a path four times as slow); a
    table of 52 columns or more is packed as it was. Either way the tile's
    rows and values are the table's."""
    from lightgbm_tpu.core import partition
    rng = np.random.default_rng(cols)
    n = 777
    xb = jnp.asarray(rng.integers(0, 255, (n, cols), dtype=np.uint8))
    vals = jnp.asarray(rng.standard_normal((n, 3)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, 512, dtype=np.int32))
    gather = partition.make_row_gather(xb, vals)
    rows, v = gather(idx)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(xb)[idx])
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vals)[idx])
    gathered = [e for e in jax.make_jaxpr(gather)(idx).jaxpr.eqns
                if e.primitive.name == "gather"]
    assert [e.outvars[0].aval.shape for e in gathered] == [(512, width)]


# --------------------------------------------- the numerical block, unmoved
GOLDEN = os.path.join(ROOT, "tests", "golden",
                      "train_block_numerical_cpu.sha256")


def numerical_block_text():
    rng = np.random.default_rng(35)
    X = rng.standard_normal((3000, 52))    # 52 + 12 bytes: packed as it was
    X[rng.random(3000) < 0.2, 1] = np.nan
    p = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
         "verbosity": -1}
    bst = lgb.train(p, lgb.Dataset(X, (X[:, 0] > 0).astype(float), params=p),
                    num_boost_round=1)
    g = bst._impl
    assert g.grow_params.with_categorical == 0
    return jax.jit(g._build_run_block()).lower(*g.train_block_sds(1)).as_text()


def test_the_numerical_train_block_lowers_to_the_recorded_program():
    """The block of a table WITHOUT categorical columns is the program the
    parent of PR 35 lowered (the three cells the benchmark had run it): its
    lowered text on the CPU, recorded from the parent's checkout by this
    very function. A PR that means to change that block records it anew
    (``python tests/test_categorical_chip_path.py``) and says so."""
    with open(GOLDEN) as f:
        want = f.read().split()[0]
    assert hashlib.sha256(numerical_block_text().encode()).hexdigest() == want


if __name__ == "__main__":
    print(hashlib.sha256(numerical_block_text().encode()).hexdigest())
