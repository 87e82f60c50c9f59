"""The program against the plain reference that knows a missing value
(bench/reference_clicklog.py) on seeded click-log-shaped data
(bench/generators/clicklog.py), at a small size on the CPU; and the train
block's independence of the data's values: two tables whose zero bins,
missing types and bin counts differ lower to one HLO text.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax

import lightgbm_tpu as lgb
from bench.generators import clicklog
from bench.jobs import clicklog_window as job

with open(os.path.join(ROOT, "bench", "configs",
                       "criteo-1of64-clicklog.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "bench", "workloads",
                       "criteo_clicklog_train.json")) as f:
    CHECK = json.load(f)["rehearsal"]["check"]
DATA = dict(CONFIG["rehearsal"]["data"], rows=4000)
PARAMS = {k: v for k, v in CONFIG["rehearsal"]["params"].items()
          if k != "tpu_hist_impl"}
COUNTS = DATA["count_cols"]

# what each case changes of the rehearsal's data and parameters
CASES = {
    "nan_only": ({"count_zero_share": [1e-6] * COUNTS}, {}),
    "zero_heavy": ({"count_nan_share": [0.0] * COUNTS,
                    "pair_unseen_share": [0.0] * len(
                        DATA["pair_unseen_share"]),
                    "count_zero_share": [0.6 + 0.025 * r
                                         for r in range(COUNTS)]}, {}),
    "both": ({}, {}),
    "three_bins": ({"short_distinct": [3, 3]}, {}),
    "zero_as_missing": ({}, {"zero_as_missing": True}),
}


def grow(seed, data, params, rounds=3):
    X, y = clicklog.generate(seed, **data)
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    bst = lgb.train(params, ds, num_boost_round=rounds)
    return X, y, bst


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_agrees_with_the_reference_on(case):
    data_over, params_over = CASES[case]
    data, params = dict(DATA, **data_over), dict(PARAMS, **params_over)
    X, y, bst = grow(29, data, params)
    meta = bst._impl.feature_meta
    missing = np.bincount(np.asarray(meta.missing_type), minlength=3)
    # the case is what its name says
    want = {"nan_only": missing[2] > 0, "zero_heavy": missing[0] == 67,
            "both": missing[2] > 0 and missing[0] > 0,
            "three_bins": int(np.asarray(meta.num_bin).min()) <= 4,
            "zero_as_missing": missing[1] == 67}[case]
    assert want, (missing, np.asarray(meta.num_bin))
    ctx = {"seed": 29, "workload": {"check": CHECK},
           "config": {"params": params}}
    compared, correct = job.judge(
        ctx, X.astype(np.float32), y, bst.model_to_string(num_iteration=-1),
        np.asarray(bst._impl.scores)[:, 0])
    assert compared["count_mismatch"]["value"] == 0, compared
    assert correct, compared


def test_a_split_on_missingness_alone_is_written_and_read_back():
    """The last numeric bin of a column with a NaN bin is bounded by +inf:
    a split there (NaN against the rest) has to reach the model text."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3000, 3))
    gone = rng.random(3000) < 0.4
    y = (gone ^ (rng.random(3000) < 0.05)).astype(np.float32)
    x[gone, 0] = np.nan
    params = dict(PARAMS, num_leaves=4)
    bst = lgb.train(params, lgb.Dataset(x, y, params=dict(params)),
                    num_boost_round=1)
    tree = job.reference_clicklog.parse_trees(
        bst.model_to_string(num_iteration=-1))[0]
    assert tree["split_feature"][0] == 0 and tree["threshold"][0] == 1e300
    assert (int(tree["decision_type"][0]) >> 2) & 3 == 2
    again = lgb.Booster(model_str=bst.model_to_string(num_iteration=-1))
    assert np.array_equal(bst.predict(x), again.predict(x))


def block_hlo(seed):
    X, y, bst = grow(seed, dict(DATA, rows=3000), PARAMS, rounds=1)
    g = bst._impl
    meta = {k: np.asarray(getattr(g.feature_meta, k))
            for k in ("default_bin", "missing_type", "num_bin")}
    text = jax.jit(g._build_run_block()).lower(
        *g.train_block_sds(1)).as_text()
    return meta, text


def test_two_tables_lower_to_one_train_block():
    (meta_a, text_a), (meta_b, text_b) = block_hlo(11), block_hlo(12)
    # the two tables differ in each piece of metadata the block reads
    for k in meta_a:
        assert not np.array_equal(meta_a[k], meta_b[k]), k
    assert text_a == text_b


def test_a_leaf_of_more_than_2_to_24_rows_is_counted_exactly():
    """A float32 histogram count cannot hold an odd number past 2**24
    (16,777,216): the tree's counts are integers, taken from the row
    partition where every row is in the bag."""
    big = (1 << 24) + 1
    x = np.concatenate([np.zeros(big), np.ones(100000),
                        np.full(100000, 2.0)])[:, None]
    y = np.concatenate([np.zeros(big), np.ones(100000), np.zeros(100000)])
    y[:50000] = 1.0
    params = {"objective": "binary", "num_leaves": 2, "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(x, y, params=dict(params)),
                    num_boost_round=1)
    tree = bst._impl.models[0]
    assert list(tree.leaf_count) == [big, 200000]
    assert list(tree.internal_count) == [big + 200000]
