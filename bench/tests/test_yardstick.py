"""The yardstick's own arithmetic: the histogram work count against a
hand-counted tree, the trace reduction against a small recorded trace, the
reference's comparisons against a model written by hand.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider
"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import reference_gbdt as ref
from bench import trace_reduce, work
from bench.readers import hist_roofline, trace_ops

# a three-leaf tree over 1000 rows: the root sends 600 left and 400
# right, then the left child (node 1) is split 450 / 150
THREE_LEAVES = """tree
version=v2

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
split_gain=10 5
threshold=0.5 -0.25
decision_type=2 2
left_child=1 -1
right_child=-2 -3
leaf_value=0.1 -0.2 0.3
leaf_count=450 400 150
internal_value=0 0.15
internal_count=1000 600
shrinkage=0.1

end of trees
"""


def test_rows_visited_and_bytes_of_a_hand_counted_tree():
    tree = ref.parse_trees(THREE_LEAVES)[0]
    # the root's histogram reads all 1000 rows; the root's split prices
    # both children in one pass over its 1000 rows, the second split in
    # one pass over node 1's 600 rows
    assert work.hist_rows_visited(tree, 1000) == 1000 + 1000 + 600
    # 4 columns, 16 bins: each row read is 4 B of codes + 8 B of gradient
    # and hessian; written: 1 root histogram + 2 per split, each
    # 4 x 16 x 3 float32
    assert work.hist_bytes(2600, 2, 4, 16) == 2600 * 12 + 5 * 4 * 16 * 3 * 4


def load_sample():
    with open(os.path.join(os.path.dirname(__file__),
                           "trace_sample.json")) as f:
        s = json.load(f)
    return {"devices": [[tuple(e) for e in d] for d in s["devices"]],
            "host": [tuple(h) for h in s["host"]]}


def test_trace_reduction_on_the_recorded_trace():
    events = load_sample()
    leaves = trace_reduce.leaf_events(events["devices"][0])
    names = {e[0] for e in events["devices"][0]}
    # the two while loops span their bodies: control flow, not work
    assert {"while.272", "while.274"} <= names
    assert not {"while.272", "while.274"} & {e[0] for e in leaves}
    assert len(leaves) == 398
    tr = trace_reduce.reduce(events, {"wall_s": 7.0, "iters": 1,
                                      "first_iter": 2})
    # counted by hand from the recorded events: no two leaves overlap, so
    # busy is their sum; the root histogram kernel is the longest op
    assert abs(tr["busy_s"] - 0.055407069) < 1e-9
    assert tr["breakdown"]["device_ops"][0][0] == \
        "build_histogram_pallas_vals.12"
    assert abs(tr["breakdown"]["device_ops"][0][1] - 0.045309898) < 1e-9
    assert len(tr["breakdown"]["device_ops"]) == 10
    assert len(tr["breakdown"]["idle_gaps"]) == 10
    # the longest gaps fall while the host is still dispatching
    assert tr["breakdown"]["idle_gaps"][0][0].startswith("dispatch, after ")
    result = {"trace": tr}
    hist_ms = trace_ops.read({"what": "kernel_ms_per_iter",
                              "kernel": "histogram"}, result)
    other_ms = trace_ops.read({"what": "other_ms_per_iter",
                               "kernel": "histogram"}, result)
    assert abs(hist_ms - 45.517058) < 1e-6
    assert abs(hist_ms + other_ms - 55.407069) < 1e-6
    idle = trace_ops.read({"what": "idle_pct"}, result)
    assert abs(idle - 100 * (1 - 0.055407069 / 7.0)) < 1e-9


def test_readers_return_nothing_when_there_is_nothing_to_read():
    assert trace_ops.read({"what": "idle_pct"}, {"trace": None}) is None
    tr = trace_reduce.reduce(load_sample(), {"wall_s": 7.0, "iters": 1,
                                             "first_iter": 2})
    spec = {"what": "kernel_ms_per_iter", "kernel": "no_such_kernel"}
    assert trace_ops.read(spec, {"trace": tr}) is None
    assert hist_roofline.read({"kernel": "no_such_kernel",
                               "bound": "hbm_bytes_per_s"},
                              {"trace": tr}) is None
    assert trace_reduce.reduce({"devices": [], "host": []},
                               {"wall_s": 1.0, "iters": 1,
                                "first_iter": 2}) is None


def test_hist_roofline_is_bytes_over_the_peak_over_kernel_time():
    tr = {"op_s": {"build_histogram_x.1": 2e-3, "fusion.9": 1.0},
          "iters": 1, "first_iter": 0}
    result = {"trace": tr, "model_text": THREE_LEAVES,
              "config": {"data": {"rows": 1000, "cols": 4},
                         "params": {"max_bin": 16}},
              "peaks": {"hbm_bytes_per_s": 1e9}}
    got = hist_roofline.read({"kernel": "histogram",
                              "bound": "hbm_bytes_per_s"}, result)
    want = 100.0 * ((2600 * 12 + 5 * 4 * 16 * 3 * 4) / 1e9) / 2e-3
    assert abs(got - want) < 1e-9


def test_route_counts_values_and_gains_of_a_model_written_by_hand():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((1000, 4)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.standard_normal(1000) > 0).astype(np.float32)
    tree = ref.parse_trees(THREE_LEAVES)[0]
    leaf = ref.route(ref.Columns(X), tree, n=1000)
    by_hand = np.where(X[:, 0] <= 0.5, np.where(X[:, 1] <= -0.25, 0, 2), 1)
    assert (leaf == by_hand).all()
    followed = ref.follow(X, y, [tree], 0.1, 0.0, [[0, 1]], {
        "cells": 64, "min_data": 20, "search_rows": 1000})[0]
    p = float(np.mean(y, dtype=np.float64))
    g, h = p - y.astype(np.float64), p * (1 - p)
    for k in range(3):
        rows = by_hand == k
        assert followed["leaf_count"][k] == rows.sum()
        want = -g[rows].sum() / (h * rows.sum()) * 0.1 + np.log(p / (1 - p))
        assert abs(followed["leaf_value"][k] - want) < 1e-12
    left = X[:, 0] <= 0.5
    gain = (g[left].sum() ** 2 / (h * left.sum())
            + g[~left].sum() ** 2 / (h * (~left).sum())
            - g.sum() ** 2 / (h * 1000))
    assert abs(followed["split_gain"][0] - gain) < 1e-9
    # the hand-written model is nobody's best: the count of rows it got
    # wrong and its regret both show
    got = ref.readings([tree], [followed])
    assert got["count_mismatch"] > 0 and got["node_regret"] > 0.1


def test_node_regret_is_nought_for_the_best_split_and_one_for_noise():
    rng = np.random.default_rng(1)
    X = rng.standard_normal((20000, 5)).astype(np.float32)
    y = (X[:, 2] + 0.2 * rng.standard_normal(20000) > 0.3).astype(np.float32)
    p = float(np.mean(y, dtype=np.float64))
    g, h = p - y.astype(np.float64), np.full(20000, p * (1 - p))
    search = ref.GridSearch(ref.Columns(X), 5, 256, 20, 20000)
    best = search.best_gain(None, g, h, 0.0)
    # found on every fourth row and priced on all: not above the best,
    # and close under it
    coarse = ref.GridSearch(ref.Columns(X), 5, 256, 20, 5000).best_gain(
        None, g, h, 0.0)
    assert best * 0.99 < coarse <= best

    def gain(col, thr, rows=slice(None)):
        left = X[rows, col] <= thr
        gr, hr = g[rows], h[rows]
        return (ref.leaf_gain(gr[left].sum(), hr[left].sum(), 0.0)
                + ref.leaf_gain(gr[~left].sum(), hr[~left].sum(), 0.0)
                - ref.leaf_gain(gr.sum(), hr.sum(), 0.0))
    assert abs(best - gain(2, 0.3)) / best < 0.01     # the rule's own split
    assert (best - gain(4, 0.0)) / best > 0.99        # a noise column
    # on the rows of a node only: the left half by column 0
    idx = np.flatnonzero(X[:, 0] <= 0.0)
    below = search.best_gain(idx, g[idx], h[idx], 0.0)
    assert abs(below - gain(2, 0.3, idx)) / below < 0.01
    # a node too small to leave 20 rows on both sides has no candidate
    assert search.best_gain(idx[:30], g[idx[:30]], h[idx[:30]], 0.0) < 0


def test_order_gap_shows_a_leaf_split_before_a_better_one():
    # node 0 -> nodes 1 and 2; node 2 (gain 5) waited while node 1
    # (gain 3) was split: (5 - 3) / 3. Listed best first there is no gap.
    tree = {"num_leaves": 4, "left_child": np.array([1, -1, -3]),
            "right_child": np.array([2, -2, -4])}
    assert abs(ref.order_gap(tree, np.array([10.0, 3.0, 5.0])) - 2 / 3) < 1e-12
    assert ref.order_gap(tree, np.array([10.0, 5.0, 3.0])) == 0.0
    # a deeper tree listed level by level (the fault the number is for)
    from bench.tests.readings_on_chip import level_by_level
    deep = {"num_leaves": 5, "left_child": np.array([1, 2, 3, -1]),
            "right_child": np.array([-2, -3, -4, -5])}
    same, gain = level_by_level(deep, np.array([9.0, 8.0, 7.0, 6.0]))
    assert (same["left_child"] == deep["left_child"]).all()   # a chain
    bushy = {"num_leaves": 5, "left_child": np.array([1, 2, -1, -4]),
             "right_child": np.array([3, -2, -3, -5])}
    # split order 0, 1, 2 (under 1), 3 (under 0): level by level lists
    # node 3 before node 2
    relisted, gain = level_by_level(bushy, np.array([9.0, 8.0, 7.0, 1.0]))
    assert list(gain) == [9.0, 8.0, 1.0, 7.0]
    assert list(relisted["left_child"]) == [1, 3, -4, -1]
    assert abs(ref.order_gap(relisted, gain) - 6.0) < 1e-12


def test_draw_nodes_comes_from_the_seed_and_takes_one_root():
    trees = ref.parse_trees(THREE_LEAVES) * 2
    big = [dict(t, num_leaves=255) for t in trees]
    a, b = ref.draw_nodes(7, big, 6), ref.draw_nodes(7, big, 6)
    assert a == b and a != ref.draw_nodes(8, big, 6)
    assert 0 not in a[0] and a[1][0] == 0 and len(a[0]) == 6 == len(a[1]) - 1
    assert ref.draw_nodes(7, trees, 6) == [[1], [0, 1]]


def test_trace_reduction_averages_over_chips():
    one = load_sample()
    two = {"devices": one["devices"] * 2, "host": one["host"]}
    block = {"wall_s": 7.0, "iters": 1, "first_iter": 2}
    a, b = trace_reduce.reduce(one, block), trace_reduce.reduce(two, block)
    assert abs(a["busy_s"] - b["busy_s"]) < 1e-12
    assert a["breakdown"]["device_ops"][0][0] == \
        b["breakdown"]["device_ops"][0][0]
    assert all(abs(a["op_s"][k] - b["op_s"][k]) < 1e-12 for k in a["op_s"])


def test_bfloat16_round_keeps_eight_significand_bits():
    a = np.array([1.0, 1.0 + 2.0 ** -8, 1.0 + 2.0 ** -7, 0.3, -0.3])
    r = ref.bfloat16_round(a)
    assert r[0] == 1.0 and r[1] == 1.0 and r[2] == 1.0 + 2.0 ** -7
    assert abs(r[3] - 0.3) < 0.3 * 2.0 ** -8 and r[4] == -r[3]
