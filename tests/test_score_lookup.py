"""The score update's leaf-value lookup (``core/tree.py`` ``leaf_value_of``):
selects up to ``SELECT_LOOKUP_MAX`` leaves, a gather above, the table's entry
bit for bit either way; the score update's jaxpr on either side of the
bound, and a ``train_many`` block's scores against a numpy restatement.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis.jaxpr_audit import iter_eqns
from lightgbm_tpu.core.tree import (SELECT_LOOKUP_MAX, leaf_value_of,
                                    predict_tree_leaves_raw)

BOUND = SELECT_LOOKUP_MAX


def _ids(leaves, dtype, n=4096, seed=0):
    """Every leaf, random ids in range, and ids past the table's end (and
    -1, which the gather wraps to the last entry) where the dtype holds
    them."""
    rng = np.random.default_rng(seed + leaves)
    ids = [np.arange(leaves), rng.integers(0, leaves, n)]
    top = np.iinfo(dtype).max
    out = [i for i in (leaves, leaves + 7, 1 << 20) if i <= top]
    if np.iinfo(dtype).min < 0:
        out.append(-1)
    ids.append(np.asarray(out, np.int64))
    return np.concatenate(ids).astype(dtype)


def _gathers(jaxpr):
    return sum(e.primitive.name == "gather" for e in iter_eqns(jaxpr))


# a uint8 id holds 256 leaves at most
@pytest.mark.parametrize("leaves,itype", [
    (leaves, itype) for itype in ("int32", "uint8")
    for leaves in (1, 2, 31, 255, BOUND, BOUND + 1)
    if itype == "int32" or leaves <= 256])
@pytest.mark.parametrize("ftype", ["float32", "float64"])
def test_the_lookup_is_the_tables_entry_bit_for_bit(leaves, itype, ftype):
    with jax.enable_x64(ftype == "float64"):
        rng = np.random.default_rng(leaves)
        table = jnp.asarray(rng.standard_normal(leaves).astype(ftype))
        ids = jnp.asarray(_ids(leaves, np.dtype(itype)))
        got = leaf_value_of(table, ids)
        assert got.dtype == table.dtype and got.shape == ids.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(table[ids]))
        n_gather = _gathers(jax.make_jaxpr(leaf_value_of)(table, ids))
        assert n_gather == (leaves > BOUND)


@pytest.mark.parametrize("leaves", [2, 31, 255, BOUND + 1])
def test_the_lookup_vmapped_over_classes(leaves):
    """Under the score update's vmap over classes the table is batched."""
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.standard_normal((3, leaves)).astype(np.float32))
    ids = jnp.asarray(np.stack([_ids(leaves, np.dtype(np.int32), seed=c)
                                for c in range(3)]))
    got = jax.vmap(leaf_value_of)(table, ids)
    i = np.asarray(ids)
    i = np.where((i < 0) | (i >= leaves), leaves - 1, i)
    want = np.take_along_axis(np.asarray(table), i, axis=1)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("bad", [-5, -2, 300, 1 << 30])
def test_an_id_outside_the_table_takes_the_last_entry(bad):
    table = jnp.arange(255, dtype=jnp.float32)
    ids = jnp.asarray([bad, 0, 254], jnp.int32)
    assert list(np.asarray(leaf_value_of(table, ids))) == [254.0, 0.0, 254.0]


def _train_args(num_leaves):
    X = np.random.RandomState(0).randn(300, 4)
    y = ((X[:, 0] > 0) | (X[:, 1] > 1)).astype(float)
    p = {"objective": "binary", "num_leaves": num_leaves, "verbose": -1}
    return p, lgb.Dataset(X, y, params=p)


def _score_update_gathers(g):
    """Gathers in the train block under the scope lgbm.score_update."""
    jaxpr = jax.make_jaxpr(g._build_run_block())(*g.train_block_sds(1))
    return sum(e.primitive.name == "gather"
               and "lgbm.score_update" in str(e.source_info.name_stack)
               for e in iter_eqns(jaxpr))


@pytest.mark.parametrize("num_leaves", [4, 255, BOUND + 1])
def test_the_score_update_gathers_only_above_the_bound(num_leaves):
    """The block's score update holds no gather at L <= the bound and one
    above it (tree.leaf_value_of looks a leaf's value up by selects up to
    SELECT_LOOKUP_MAX leaves)."""
    g = lgb.train(*_train_args(num_leaves), num_boost_round=1)._impl
    assert g.grow_params.num_leaves == num_leaves
    assert _score_update_gathers(g) == int(num_leaves > BOUND)


# a train_many block's scores = scores + leaf_value[leaf] x rate, on
# each booster that trains on the device; the rate 0.5 leaves the host's
# float64 leaf values exact in float32, so the restatement is bit for bit
@pytest.mark.parametrize("extra", [
    {},
    {"tpu_hist_impl": "pallas_interpret"},
    {"objective": "multiclass", "num_class": 3},
    {"tree_growth": "frontier"},
    {"tree_growth": "batched"},
], ids=["exact", "pallas", "multiclass", "frontier", "batched"])
def test_a_block_adds_each_rows_leaf_value(extra):
    rng = np.random.RandomState(3)
    X = rng.randn(600, 5)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    if extra.get("num_class"):
        y = y + (X[:, 3] > 0.5)
    p = dict({"objective": "binary", "num_leaves": 31, "learning_rate": 0.5,
              "min_data_in_leaf": 5, "verbose": -1}, **extra)
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), num_boost_round=1)
    g = bst._impl
    before = np.asarray(g.scores)
    g.train_many(1)
    after = np.asarray(g.scores)
    trees = g.models[-g.num_tree_per_iteration:]
    want = before.copy()
    for c, ht in enumerate(trees):
        # each row's leaf by its raw values, as predict finds it
        table = jax.tree.map(jnp.asarray,
                             ht.predict_table(ht.num_nodes, ht.num_leaves))
        leaf = np.asarray(predict_tree_leaves_raw(
            table, jnp.asarray(X, jnp.float32)))
        lv = ht.leaf_value.astype(np.float32)
        assert np.array_equal(lv.astype(np.float64), ht.leaf_value)
        want[:, c] = before[:, c] + lv[leaf]
    assert max(ht.num_leaves_actual for ht in trees) > 2
    np.testing.assert_array_equal(after, want)
