"""Readings behind the limits of a training cell's check, at the cell's
own size, several seeds in one process (run by hand through the chip tool;
the benchmark's own runs never run this):

    python bench/tests/readings_on_chip.py --workload <name> \
        --seeds 11,12,13 --seconds 25 [--rehearsal]

For every seed it drives a whole run (bench/run.py's path, short window)
and prints one JSON line. Every variant's numbers go through the job's
``hold`` against the cell's limits, as a run's do, and the line says which
of them each variant fails:
  program   the numbers compared, as the run read them (the lower readings)
  control   the reference put in the program's place with gradients and
            hessians rounded to bfloat16 before they are summed, the
            precision below the float32 the configuration states
  half      the reference put in the program's place, grown on the first
            half of the rows only (half of the batch left out, the mean
            taken over the rest)
  altered   the program's own model with one leaf value of the last judged
            tree moved by 1% where it is produced; (node_regret) the first
            tree's root moved to the last column at its median;
            (split_order_gap) the last judged tree's splits listed level
            by level
  unchanged the program's own scores with the last block's update undone
            (a step that returns its state unchanged), read by score_gap
It also keeps the model text under chiprun_out/models/, so that a number
added to the check later can be read from the same chip runs on the host.
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from bench import reference_gbdt as ref
from bench import run as bench_run
from bench.jobs import train_window


def in_programs_place(trees, followed):
    """Model trees whose numbers are what ``followed`` read."""
    out = []
    for tree, f in zip(trees, followed):
        t = dict(tree)
        t["leaf_value"], t["split_gain"] = f["leaf_value"], f["split_gain"]
        t["leaf_count"] = f["leaf_count"]
        t["internal_count"] = np.rint(f["internal_count"]).astype(np.int64)
        out.append(t)
    return out


def look(trees, followed, n=4):
    """The worst leaves of each judged tree: [gap, rows in the leaf, rows
    in its parent, reference value], to see whether the worst gap is a
    small leaf carved from a large parent."""
    out = []
    for tree, f in zip(trees, followed):
        g = ref.gaps(tree["leaf_value"], f["leaf_value"])
        parent = {}
        for k in range(tree["num_leaves"] - 1):
            for child in (tree["left_child"][k], tree["right_child"][k]):
                if child < 0:
                    parent[-int(child) - 1] = int(tree["internal_count"][k])
        out.append([[float(g[i]), int(tree["leaf_count"][i]), parent[int(i)],
                     float(f["leaf_value"][i])]
                    for i in np.argsort(-g)[:n]]
                   + [["median", float(np.median(g)),
                       float(np.median(np.abs(f["leaf_value"])))]])
    return out


def level_by_level(tree, gain):
    """The same tree with its splits listed breadth first, as a grower
    that does not pick the best waiting leaf would list them."""
    order, seen = [0], 0
    while seen < len(order):
        k = order[seen]
        seen += 1
        order += [int(c) for c in (tree["left_child"][k],
                                   tree["right_child"][k]) if c >= 0]
    new = {old: i for i, old in enumerate(order)}
    t = dict(tree)
    for side in ("left_child", "right_child"):
        t[side] = np.array([new[int(c)] if c >= 0 else int(c)
                            for c in tree[side][order]])
    return t, gain[order]


def extra_readings(ctx, X, y, model_text, scores):
    """{variant: numbers compared}; ``scores`` None (a saved model read on
    the host) leaves score_gap out."""
    check = ctx["workload"]["check"]
    params = ctx["config"]["params"]
    lr, l2 = params["learning_rate"], params.get("lambda_l2", 0.0)
    search = train_window.search_of(ctx)
    if scores is not None:
        os.makedirs("chiprun_out/models", exist_ok=True)
        with open("chiprun_out/models/%s_%d.txt" % (
                ctx["workload"]["name"], ctx["seed"]), "w") as f:
            f.write(model_text)
    trees = ref.parse_trees(model_text)
    judged = trees[:check["follow_trees"]]
    nodes = ref.draw_nodes(ctx["seed"], judged, check["regret_nodes"])
    rows = train_window.sample_rows(ctx["seed"], len(y), check["sample_rows"])

    def follow(X, y, **kw):
        return ref.follow(X, y, judged, lr, l2, nodes, search, **kw)

    exact = follow(X, y)
    out = {"look": look(judged, exact),
           "program": ref.readings(judged, exact)}
    low = follow(X, y, grad_cast=ref.bfloat16_round)
    out["control"] = ref.readings(in_programs_place(judged, low), exact)
    half = len(y) // 2
    sub = follow(X[:half], y[:half])
    out["half"] = ref.readings(in_programs_place(judged, sub), exact)
    moved = copy.deepcopy(trees)
    moved[len(judged) - 1]["leaf_value"][0] *= 1.01
    out["altered"] = ref.readings(moved[:len(judged)], exact)
    # the first tree's root moved to the last column at its median: that
    # split's gain on all rows against the best the grid finds there
    cols = ref.Columns(X)
    p = ref.sigmoid(ref.init_score(y))
    g, h = p - y.astype(np.float64), np.full(len(y), p * (1.0 - p))
    left = cols[X.shape[1] - 1] <= np.median(cols[X.shape[1] - 1])
    chosen = (ref.leaf_gain(g[left].sum(), h[left].sum(), l2)
              + ref.leaf_gain(g[~left].sum(), h[~left].sum(), l2)
              - ref.leaf_gain(g.sum(), h.sum(), l2))
    best = ref.GridSearch(cols, X.shape[1], **search).best_gain(None, g, h,
                                                              l2)
    out["altered"]["node_regret"] = float((best - chosen) / best)
    out["altered"]["split_order_gap"] = ref.order_gap(
        *level_by_level(judged[-1], exact[-1]["split_gain"]))
    if scores is not None:
        out["program"]["score_gap"] = ref.score_gap(X, trees, scores, rows)
        out["altered"]["score_gap"] = ref.score_gap(X, moved, scores, rows)
        # the reading ref.score_gap gives when the scores lack the last
        # tree is the same as when the model has one tree more than them
        out["unchanged"] = {"score_gap": ref.score_gap(X, trees[:-1], scores,
                                                       rows)}
    out["fails"] = {
        v: [k for k, c in train_window.hold(
            got, {k: lim for k, lim in check["limits"].items() if k in got}
        )[0].items() if not c["ok"]]
        for v, got in out.items() if v != "look"}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    seen = {}
    judge = train_window.judge

    def judge_and_read(ctx, X, y, model_text, scores):
        t = time.perf_counter()
        seen.update(extra_readings(ctx, X, y, model_text, scores))
        seen["extra_s"] = time.perf_counter() - t
        return judge(ctx, X, y, model_text, scores)

    train_window.judge = judge_and_read
    for seed in args.seeds.split(","):
        sys.argv = ["run.py", "--workload", args.workload, "--seed", seed,
                    "--seconds", str(args.seconds), "--trace", "0"]
        if args.rehearsal:
            sys.argv.append("--rehearsal")
        seen.clear()
        bench_run.T_START = time.time()
        line = bench_run.main()
        print("READINGS " + json.dumps({"seed": int(seed), **seen}),
              flush=True)
        assert seen["program"] == {k: v["value"]
                                   for k, v in line["compared"].items()}


if __name__ == "__main__":
    main()
