"""The device's work counts on every ``train.block`` span, and set-up under
spans from the process's start.

The exact grower over the row partition adds up, once a split and on the
device, what its two tile loops walk (core/grow.py ``WORK_COUNTS``);
the counts leave the block beside the trees and join the block's span where
the host fetches them. Here a toy booster's counts are held to what its own
MODEL TEXT says (the tree's ``internal_count`` and ``leaf_count``, read by
bench/reference_goss.py's parser, which imports nothing of the program),
one iteration a block, at a toy ``tpu_row_chunk``. Counts and structure
only: no time is read.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from bench.reference_goss import parse_trees
from lightgbm_tpu.core import grow
from lightgbm_tpu.obs import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 256
N = 3000
BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 31,
        "min_data_in_leaf": 5, "verbosity": -1, "tpu_row_chunk": CHUNK}


# the root, then its left child, then its right: the third step's leaf is no
# child of the second's
FORCED = {"feature": 0, "threshold": 0.0,
          "left": {"feature": 1, "threshold": 0.0},
          "right": {"feature": 2, "threshold": 0.0}}


def numeric(rng):
    X = rng.normal(size=(N, 6))
    X[rng.random((N, 6)) < 0.1] = np.nan
    return X, (X[:, 0] + 0.5 * np.nan_to_num(X[:, 1]) ** 2
               + rng.normal(size=N) > 0.5).astype(np.float64)


def with_categories(rng):
    X, y = numeric(rng)
    X[:, 4] = rng.integers(0, 40, size=N)
    X[:, 5] = rng.integers(0, 3, size=N)
    return X, ((y > 0) ^ (X[:, 4] % 7 == 0)).astype(np.float64)


def three_classes(rng):
    X, _ = numeric(rng)
    return X, rng.integers(0, 3, size=N).astype(np.float64)


# name -> (data, parameters over BASE, rounds, which counts a block carries)
CASES = {
    "plain": (numeric, {}, 3, "tiles"),
    "goss_bag": (numeric, {"boosting": "goss", "learning_rate": 0.5,
                           "top_rate": 0.2, "other_rate": 0.1}, 4, "tiles"),
    "categorical": (with_categories, {"categorical_feature": "4,5",
                                      "min_data_per_group": 10}, 3, "tiles"),
    "multiclass": (three_classes, {"objective": "multiclass",
                                   "num_class": 3}, 2, "tiles"),
    # two slots: a split misses unless its leaf is a child of the one before
    "capped_pool": (numeric, {"histogram_pool_size": 1e-9}, 3, "tiles"),
    # a forced step reads its leaf's histogram twice: two passes on a miss
    "capped_pool_forced": (numeric, {"histogram_pool_size": 1e-9,
                                     "forcedsplits_filename": FORCED}, 2,
                           "tiles"),
    # growers with no tile: what their trees say, and nothing else
    "frontier": (numeric, {"tree_growth": "frontier"}, 2, "trees"),
    "batched": (numeric, {"tree_growth": "batched"}, 2, "trees"),
}


def spans_after(mark):
    """The spans recorded since ``newest()`` read ``mark`` (by id: the ring
    may be full, and then its length says nothing)."""
    return [s for s in trace.recorded_spans() if s["id"] > mark]


def newest():
    return max((s["id"] for s in trace.recorded_spans()), default=-1)


def tiles(rows):
    return -(-int(rows) // CHUNK)


def said_by(tree, root_rows, pool_slots=None, forced=0):
    """The work counts as a tree of the model text says them. Under a pool
    of two slots (the children of the last split) every other leaf's
    histogram is built again from its rows when it is split, and once more
    where the step is one of the ``forced`` first."""
    count = lambda c: tree["leaf_count"][-c - 1] if c < 0 \
        else tree["internal_count"][c]
    children = list(zip(tree["left_child"], tree["right_child"]))
    walked = [min(count(int(l)), count(int(r))) for l, r in children]
    if pool_slots is not None:
        assert pool_slots == 2
        for i in range(1, len(children)):
            if i not in children[i - 1]:
                walked += [int(tree["internal_count"][i])] * (1 + (i < forced))
    return {"splits": tree["num_leaves"] - 1,
            "split_rows": int(tree["internal_count"].sum()),
            "partition_tiles": sum(tiles(c) for c in tree["internal_count"]),
            "hist_rows": root_rows + int(sum(walked)),
            "hist_tiles": sum(tiles(c) for c in walked)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_blocks_work_counts_are_what_its_trees_say(case, tmp_path):
    make, extra, rounds, kind = CASES[case]
    X, y = make(np.random.default_rng(38))
    params = dict(BASE, **extra)
    forced = 3 if "forcedsplits_filename" in params else 0
    if forced:
        (tmp_path / "forced.json").write_text(json.dumps(FORCED))
        params["forcedsplits_filename"] = str(tmp_path / "forced.json")
    bst = lgb.Booster(params=dict(params), train_set=lgb.Dataset(
        X, y, params=dict(params)))
    gbdt = bst._impl
    mark = newest()
    for _ in range(rounds):
        gbdt.train_many(1)
    trees = parse_trees(bst.model_to_string())   # the fetch joins the counts
    k = gbdt.num_tree_per_iteration
    blocks = [s["counts"] for s in spans_after(mark)
              if s["name"] == "train.block"]
    assert [b["start_iter"] for b in blocks] == list(range(rounds))
    assert gbdt.grow_params.row_chunk == CHUNK
    pool_slots = 2 if case.startswith("capped_pool") else None
    assert gbdt.grow_params.num_forced == forced
    rebuilt = 0
    assert gbdt.grow_params.pool_slots == (pool_slots or 0)
    sampled_blocks = 0
    for it, got in enumerate(blocks):
        mine = trees[it * k:(it + 1) * k]
        assert len(mine) == k and all(t["num_leaves"] > 1 for t in mine)
        sampled = got.get("goss_active") == 1
        sampled_blocks += sampled
        want = {}
        for t in mine:
            # the root's pass: every row, or the bag (the root's count)
            root_rows = int(t["internal_count"][0]) if sampled else N
            said = said_by(t, root_rows, pool_slots, forced)
            rebuilt += said["hist_rows"] - said_by(t, root_rows)["hist_rows"]
            for name, v in said.items():
                want[name] = want.get(name, 0) + v
        if kind == "trees":
            want = {c: want[c] for c in ("splits", "split_rows")}
        if sampled:
            assert want["hist_rows"] < N        # a 30% bag, 2.x bags a tree
        have = {c: got[c] for c in ("splits",) + grow.WORK_COUNTS
                if c in got}
        assert have == want, (case, it)
        assert "root_tiles" not in got and "routed_rows" not in got
    assert (rebuilt > 0) == (pool_slots is not None)
    assert sampled_blocks == (2 if case == "goss_bag" else 0)


def test_the_counts_carry_past_int32():
    """A lopsided tree splits (L - 1) x N rows: two limbs."""
    work = jnp.zeros((2, 3), jnp.int32)
    big = 2 ** 31 - 1
    for _ in range(5):
        work = grow._add_work(work, (big, 1, 26_562_500))
    limbs = np.asarray(work, np.int64)
    assert (limbs[0] < grow.WORK_LIMB).all()
    assert (limbs[1] * grow.WORK_LIMB + limbs[0]).tolist() == [
        5 * big, 5, 5 * 26_562_500]


def test_the_counts_leave_the_loop_as_one_small_vector():
    """No op inside a tile loop, nothing a row long: the split loop's state
    gains one int32 [2, W] array and the grower returns it by name."""
    import jax
    rng = np.random.default_rng(3)
    X, y = numeric(rng)
    bst = lgb.Booster(params=dict(BASE), train_set=lgb.Dataset(
        X, y, params=dict(BASE)))
    g = bst._impl
    out = jax.eval_shape(
        lambda xb, meta: grow.grow_tree(
            xb, jnp.zeros((N,)), jnp.ones((N,)), jnp.ones((N,)), meta,
            jnp.ones((6,), bool), g.grow_params), g.xb, g.feature_meta)
    assert isinstance(out, grow.Grown) and out.cegb is None
    assert out.work.shape == (2, len(grow.WORK_COUNTS))
    assert out.work.dtype == jnp.int32
    # the masked form (a mesh's fallback) walks no tile and counts nothing
    masked = jax.eval_shape(
        lambda xb, meta: grow.grow_tree(
            xb, jnp.zeros((N,)), jnp.ones((N,)), jnp.ones((N,)), meta,
            jnp.ones((6,), bool),
            g.grow_params._replace(use_partition=False)),
        g.xb, g.feature_meta)
    assert masked.work is None


def test_engine_train_is_a_span_over_its_children():
    X, y = numeric(np.random.default_rng(5))
    mark = newest()
    lgb.train(dict(BASE), lgb.Dataset(X, y, params=dict(BASE)),
              num_boost_round=2)
    spans = spans_after(mark)
    by_id = {s["id"]: s for s in spans}
    named = lambda n: [s for s in spans if s["name"] == n]
    engine, = named("train.engine")
    assert engine["counts"] == {} and engine["parent"] is None
    init, = named("train.booster_init")
    assert init["parent"] == engine["id"]
    assert by_id[named("train.setup")[0]["parent"]]["name"] == \
        "train.booster_init"
    for child in ("train.make_block_fn", "train.block"):
        assert all(s["parent"] == engine["id"] for s in named(child)), child
    inside = sum(s["end_ns"] - s["start_ns"] for s in spans
                 if s["parent"] == engine["id"])
    assert 0 < inside <= engine["end_ns"] - engine["start_ns"]


def test_set_up_is_under_spans_from_the_processs_start():
    """In a process of its own: ``import lightgbm_tpu`` alone, and the lazy
    names of the tooling (``callback``, ``checkpoint``), import neither jax
    nor the recorder; the first import of ``basic`` or ``engine`` records
    ``runtime.before_import`` once, ending before the package ran, and
    ``import.<module>`` for itself; a module already imported records
    nothing more."""
    code = """
import json, sys, time
import lightgbm_tpu as lgb
lgb.early_stopping; lgb.CheckpointManager
light = [m for m in ("jax", "lightgbm_tpu.obs.trace") if m in sys.modules]
time.sleep(0.2)
lgb.Dataset; lgb.train; lgb.Dataset; lgb.Booster; lgb.early_stopping
from lightgbm_tpu.obs import trace
print(json.dumps({"light": light, "spans": [
    [s["name"], s["start_ns"] / 1e9, s["end_ns"] / 1e9]
    for s in trace.recorded_spans()]}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["light"] == []
    names = [n for n, _, _ in got["spans"]]
    assert names == ["runtime.before_import", "import.basic",
                     "import.engine"]
    (_, b0, b1), (_, i0, i1), (_, e0, e1) = got["spans"]
    assert 0 < b1 - b0 < 60
    # the span ends where the package's import began: at least the sleep
    # before the first recorded import
    assert b1 <= i0 - 0.2
    assert i1 - i0 > e1 - e0 >= 0
