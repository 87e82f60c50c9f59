"""Readings behind the limits of the GOSS cell's check, at the cell's own
size (run by hand; the benchmark's own runs never run this). What
bench/tests/readings_clicklog.py is to the sibling, in one step, because a
run's bags (27 MB each) are too much to bring back from the chip's machine:

  on the chip   python bench/tests/readings_goss.py run --workload W \\
                    --seed N --seconds 30 --trace 0
                bench/run.py's run, the same line; then, on that machine's
                host, from the model, the scores and the bags the timed
                booster handed the check and the data the check had made
                again: the program's numbers once more and each planted
                fault beside the limits it fails, as one READINGS line.

The planted faults, and the number each is for:

  control          the reference in the program's place, gradients and
                   hessians rounded to bfloat16 before they are weighted
                   and summed (leaf_value_gap, split_gain_gap, the medians)
  no_multiplier    the others' weight 1, not (N - top_cnt) / other_cnt
                   (leaf_value_gap)
  counts_all_rows  the judged trees' counts taken over all rows, in the
                   bag or out of it (count_mismatch)
  bag_reused       the first judged bag used for every judged tree
                   (bag_uniformity: the others drawn again)
  others_in_order  the others taken as the first other_cnt of the rest by
                   row id (bag_uniformity: the 64 blocks of row position)
  top_swapped      1% of the top rows swapped for rows out of the bag
                   (bag_top_missed)
  bernoulli_rest   the rest drawn by a coin a row at other_cnt /
                   (N - top_cnt), the sampler the program had before
                   (bag_count_gap)
  oob_not_scored   the last judged tree's value left out of the scores of
                   the rows out of its bag (score_gap)
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from bench import reference_goss as ref
from bench import run as bench_run
from bench.jobs import goss_window
from bench.jobs.clicklog_window import search_of
from bench.jobs.train_window import hold, sample_rows

MODELS = os.path.join("chiprun_out", "models")


def in_programs_place(trees, followed):
    """Model trees whose numbers are what ``followed`` read."""
    out = list(trees)
    for i, f in followed.items():
        t = dict(trees[i])
        t["leaf_count"] = f["leaf_count"]
        t["internal_count"] = np.rint(f["internal_count"]).astype(np.int64)
        if "leaf_value" in f:
            t["leaf_value"], t["split_gain"] = f["leaf_value"], f["split_gain"]
        out[i] = t
    return out


def variants(ctx, X, y, model_text, scores, bags):
    """{variant: numbers compared} for the program's model and bags and
    for each planted fault, and which limits each fails."""
    check, params = ctx["workload"]["check"], ctx["config"]["params"]
    n = len(y)
    trees = ref.parse_trees(model_text)
    judged = [i for i in sorted(bags) if i < len(trees)][:check["follow_trees"]]
    trees = trees[:judged[-1] + 1]
    bags = {i: goss_window.rows_of(bags[i], n) for i in judged}
    rates = (params["top_rate"], params["other_rate"])
    top_cnt, other_cnt, _ = ref.bag_counts(n, *rates)
    leaves = ref.route_all(X, trees)
    rng = np.random.default_rng(np.random.SeedSequence([ctx["seed"], 0xFA17]))

    def follow(bags=bags, nodes=None, **kw):
        return ref.follow(X, y, trees, bags, rates, params["learning_rate"],
                          params.get("lambda_l2", 0.0), nodes or {},
                          search_of(ctx), leaves=leaves, **kw)

    exact = follow(nodes=ref.draw_nodes(ctx["seed"], trees, judged,
                                        check["regret_nodes"]))
    out = {"program": ref.readings(trees, exact)}
    out["control"] = ref.readings(in_programs_place(
        trees, follow(grad_cast=ref.bfloat16_round)), exact)
    out["no_multiplier"] = ref.readings(in_programs_place(
        trees, follow(weight_of=np.array([0.0, 1.0, 1.0]))), exact)
    all_rows = list(trees)
    for i in judged:
        cnt = np.bincount(leaves[i], minlength=trees[i]["num_leaves"])
        all_rows[i] = dict(
            trees[i], leaf_count=cnt, internal_count=np.rint(
                ref.node_sums(trees[i], cnt.astype(np.float64)))
            .astype(np.int64))
    out["counts_all_rows"] = ref.readings(all_rows, exact)
    out["bag_reused"] = ref.readings(
        trees, follow({i: bags[judged[0]] for i in judged}))

    def redrawn(draw):
        """Each judged bag with its codes outside the top drawn anew."""
        made = {}
        for i in judged:
            code = bags[i].copy()
            rest = np.flatnonzero(code != ref.BAG_TOP)
            code[rest] = ref.OUT_OF_BAG
            code[draw(rest)] = ref.BAG_OTHER
            made[i] = code
        return made
    out["others_in_order"] = ref.readings(
        trees, follow(redrawn(lambda rest: rest[:other_cnt])))
    out["bernoulli_rest"] = ref.readings(trees, follow(redrawn(
        lambda rest: rest[rng.random(len(rest)) < other_cnt / len(rest)])))
    swapped = {}
    for i in judged:
        code = bags[i].copy()
        k = max(1, top_cnt // 100)
        top = rng.choice(np.flatnonzero(code == ref.BAG_TOP), k, replace=False)
        oob = rng.choice(np.flatnonzero(code == ref.OUT_OF_BAG), k,
                         replace=False)
        code[top], code[oob] = ref.OUT_OF_BAG, ref.BAG_TOP
        swapped[i] = code
    out["top_swapped"] = ref.readings(trees, follow(swapped))

    rows = sample_rows(ctx["seed"], n, check["sample_rows"])
    all_trees = ref.parse_trees(model_text)
    out["program"]["score_gap"] = ref.score_gap(X, all_trees, scores, rows)
    last = judged[-1]
    unscored = np.asarray(scores, np.float64).copy()
    oob = bags[last] == ref.OUT_OF_BAG
    unscored[oob] -= trees[last]["leaf_value"][leaves[last][oob]]
    out["oob_not_scored"] = {"score_gap": ref.score_gap(X, all_trees,
                                                        unscored, rows)}
    limits = check["limits"]
    out["fails"] = {
        v: sorted(k for k, c in hold(got, {k: limits[k] for k in got})[0]
                  .items() if not c["ok"])
        for v, got in out.items()}
    first = trees[judged[0]]
    count = lambda c: first["leaf_count"][-c - 1] if c < 0 \
        else first["internal_count"][c]
    out["shape"] = {"judged": judged, "n": n, "top_cnt": top_cnt,
                    "other_cnt": other_cnt,
                    # what hist_rows_per_iter should read in a traced run:
                    # the bag, then each split's smaller child in the bag
                    "first_judged_hist_rows": int(
                        first["internal_count"][0] + sum(
                            min(count(int(l)), count(int(r))) for l, r in
                            zip(first["left_child"], first["right_child"]))),
                    "leaves": [int(trees[i]["num_leaves"]) for i in judged]}
    return out


def run(argv):
    """bench/run.py's run with what the check was handed kept, then the
    variants from it."""
    seen = {}
    real = goss_window.judge

    def keep(ctx, X, y, model_text, scores, bags):
        seen.update(ctx=ctx, X=X, y=y, model_text=model_text, scores=scores,
                    bags=bags)
        return real(ctx, X, y, model_text, scores, bags)
    goss_window.judge = keep
    sys.argv = ["run.py"] + argv
    line = bench_run.main()
    ctx = seen["ctx"]
    os.makedirs(MODELS, exist_ok=True)
    with open(os.path.join(MODELS, "%s_%d.txt" % (
            ctx["workload"]["name"], ctx["seed"])), "w") as f:
        f.write(seen["model_text"])
    got = variants(ctx, seen["X"], seen["y"], seen["model_text"],
                   seen["scores"], seen["bags"])
    print("READINGS " + json.dumps({"seed": ctx["seed"],
                                    "correct": line["correct"], **got}),
          flush=True)


if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] != "run":
        sys.exit(__doc__)
    run(sys.argv[2:])
