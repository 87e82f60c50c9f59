"""Readings behind the limits of the categorical cell's check, at the
cell's own size (run by hand; the benchmark's own runs never run this).
What bench/tests/readings_goss.py is to its cell:

  on the chip   python bench/tests/readings_categorical.py run \\
                    --workload W --seed N --seconds 30 --trace 0 \\
                    [--param max_cat_threshold=1]
                bench/run.py's run, the same line; then, on that machine's
                host, from the model and the scores the timed booster
                handed the check and the data the check had made again:
                the program's numbers once more and each planted fault
                beside the limits it fails, as one READINGS line.
                ``--param k=v`` trains the program with that parameter
                changed and judges it as the cell states it.

The planted faults, and the number each is for:

  control            the reference in the program's place, gradients and
                     hessians rounded to bfloat16 before they are summed
                     (leaf_value_gap, split_gain_gap, the medians)
  no_cat_l2          the reference in the program's place with cat_l2 left
                     out of the children's values and the split's gain
                     (leaf_value_gap)
  bitset_inverted    every categorical node's set inverted over its own
                     words (count_mismatch)
  unkept_left        at every categorical node the ids the bin mapper did
                     not keep sent left, as a catch-all bin on the left
                     would send them (count_mismatch)
  max_cat_threshold=1  (``--param``) the finder held to subsets of one
                     category, judged against upstream's 32 (node_regret)
"""
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from bench import reference_categorical as ref
from bench import run as bench_run
from bench.jobs import categorical_window as job
from bench.jobs.train_window import hold, sample_rows

MODELS = os.path.join("chiprun_out", "models")


def in_programs_place(trees, followed):
    """Model trees whose numbers are what ``followed`` read."""
    out = []
    for t, f in zip(trees, followed):
        out.append(dict(
            t, leaf_count=f["leaf_count"],
            internal_count=np.rint(f["internal_count"]).astype(np.int64),
            leaf_value=f["leaf_value"], split_gain=f["split_gain"]))
    return out


def with_sets(tree, new_words):
    """``tree`` with categorical node k's words replaced by
    ``new_words(k, words, column)``."""
    threshold = tree["threshold"].copy()
    words, bounds = [], [0]
    for k in np.flatnonzero(tree["decision_type"] & 1):
        w = new_words(int(k), ref.node_words(tree, k),
                      int(tree["split_feature"][k]))
        threshold[k] = len(bounds) - 1
        words.append(np.asarray(w, np.int64))
        bounds.append(bounds[-1] + len(w))
    return dict(tree, threshold=threshold,
                cat_threshold=(np.concatenate(words) if words
                               else np.zeros(0, np.int64)),
                cat_boundaries=np.asarray(bounds, np.int64))


def inverted(tree):
    return with_sets(tree, lambda k, w, j: ~w & 0xFFFFFFFF)


def unkept_left(tree, kept, largest_id):
    """Every id of the column up to the table's largest that is not a kept
    category joins the set."""
    def widen(k, w, j):
        out = np.full(largest_id[j] // 32 + 1, 0xFFFFFFFF, np.int64)
        ids = kept[j][kept[j] <= largest_id[j]]
        np.bitwise_and.at(out, ids >> 5, ~(np.int64(1) << (ids & 31)))
        out[:len(w)] |= w[:len(out)]
        return out
    return with_sets(tree, widen)


def variants(ctx, X, y, model_text, scores):
    """{variant: numbers compared} for the program's model and for each
    planted fault, and which limits each fails."""
    check, params = ctx["workload"]["check"], ctx["config"]["params"]
    all_trees = ref.parse_trees(model_text)
    trees = all_trees[:check["follow_trees"]]
    search = job.search_of(ctx, model_text)
    kept = search["cat"]["kept"]
    leaves = ref.route_all(X, trees)

    def follow(trees=trees, nodes=None, leaves=leaves, search=search, **kw):
        return ref.follow(X, y, trees, params["learning_rate"],
                          params.get("lambda_l2", 0.0),
                          nodes or [[] for _ in trees], search,
                          leaves=leaves, **kw)

    exact = follow(nodes=ref.draw_nodes(ctx["seed"], trees,
                                        check["regret_nodes"]))
    out = {"program": ref.readings(trees, exact)}
    out["program"]["score_gap"] = ref.score_gap(
        X, all_trees, scores, sample_rows(ctx["seed"], len(y),
                                          check["sample_rows"]))
    out["control"] = ref.readings(in_programs_place(
        trees, follow(grad_cast=ref.bfloat16_round)), exact)
    no_l2 = dict(search, cat=dict(search["cat"], cat_l2=0.0))
    out["no_cat_l2"] = ref.readings(in_programs_place(
        trees, follow(search=no_l2)), exact)
    out["bitset_inverted"] = ref.readings(
        trees, follow(trees=[inverted(t) for t in trees], leaves=None))
    largest = {j: int(np.nanmax(X[:, j])) for j in kept}
    out["unkept_left"] = ref.readings(
        trees, follow(trees=[unkept_left(t, kept, largest) for t in trees],
                      leaves=None))
    for v in ("control", "no_cat_l2", "bitset_inverted", "unkept_left"):
        # these read no regret and no order: their follow searched no node
        for name in ("node_regret", "split_order_gap"):
            out[v].pop(name)
    limits = check["limits"]
    out["fails"] = {
        v: sorted(k for k, c in hold(got, {k: limits[k] for k in got})[0]
                  .items() if not c["ok"])
        for v, got in out.items()}
    cat = [int((t["decision_type"] & 1).sum()) for t in all_trees]
    out["shape"] = {"cat_splits": cat,
                    "splits": [int(t["num_leaves"]) - 1 for t in all_trees],
                    "onehot_splits": [int(sum(
                        1 for k in np.flatnonzero(t["decision_type"] & 1)
                        if len(kept[int(t["split_feature"][k])]) + 1
                        <= params["max_cat_to_onehot"])) for t in trees],
                    "widest_set_words": int(max(
                        [len(ref.node_words(t, k)) for t in all_trees
                         for k in np.flatnonzero(t["decision_type"] & 1)]
                        or [0])),
                    "model_text_bytes": len(model_text)}
    return out


@contextlib.contextmanager
def kept(train_with):
    """The job trained with ``train_with`` changed in its parameters and
    judged as the cell states them; yields what the check was handed
    (ctx, X, y, model_text, scores), filled once the job has run."""
    seen = {}
    real_run, real_judge = job.run, job.judge

    def train_changed(ctx):
        seen["config"] = ctx["config"]
        ctx["config"] = dict(ctx["config"], params=dict(
            ctx["config"]["params"], **train_with))
        return real_run(ctx)

    def keep(ctx, X, y, model_text, scores):
        ctx = dict(ctx, config=seen["config"])
        seen.update(ctx=ctx, X=X, y=y, model_text=model_text, scores=scores)
        return real_judge(ctx, X, y, model_text, scores)
    job.run, job.judge = train_changed, keep
    try:
        yield seen
    finally:
        job.run, job.judge = real_run, real_judge


def run(argv):
    """bench/run.py's run with what the check was handed kept, then the
    variants from it."""
    overrides = {}
    while "--param" in argv:
        i = argv.index("--param")
        key, value = argv[i + 1].split("=", 1)
        overrides[key] = json.loads(value)
        del argv[i:i + 2]
    sys.argv = ["run.py"] + argv
    with kept(overrides) as seen:
        line = bench_run.main()
    ctx = seen["ctx"]
    os.makedirs(MODELS, exist_ok=True)
    with open(os.path.join(MODELS, "%s_%d.txt" % (
            ctx["workload"]["name"], ctx["seed"])), "w") as f:
        f.write(seen["model_text"])
    got = variants(ctx, seen["X"], seen["y"], seen["model_text"],
                   seen["scores"])
    print("READINGS " + json.dumps({"seed": ctx["seed"], "trained_with":
                                    overrides, "correct": line["correct"],
                                    **got}), flush=True)


if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] != "run":
        sys.exit(__doc__)
    run(sys.argv[2:])
