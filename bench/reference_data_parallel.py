"""Plain reference for the data-parallel job (``tree_learner=data`` over
``shards`` chips), and the numbers that decide ``correct``.

Upstream's data-parallel learner (data_parallel_tree_learner.cpp) is
exact: every worker histograms its own rows, the workers reduce the
histograms, and every worker then picks the split the serial learner would
pick over all rows. So the reference is the serial learner's semantics
over ALL rows: bench/reference_clicklog.py's judging of the trees (its
routing by raw value with the missing direction, its float64 sums, its
grid search, its readings), over the whole table. It imports nothing of
the program.

What is new is the share. A chip holds one contiguous block of the rows
(``shard_bounds``: the program shards rows in order over the mesh), and
``shares`` gives each shard's count and (gradient, hessian) sums at every
leaf, from the reference's own scores, which the tests add up against the
whole table's and against the model's leaves. ``count_mismatch`` over all
rows already holds the chips' int32 counts: routing is per row, so the
shards' counts always add up to the whole table's.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import reference_clicklog as ref

parse_trees = ref.parse_trees


def shard_bounds(n, shards):
    """[start, stop) of each chip's rows: equal contiguous blocks, the last
    one short where ``shards`` does not divide ``n`` (the program pads it)."""
    per = -(-n // shards)
    return [(min(i * per, n), min((i + 1) * per, n)) for i in range(shards)]


def shares(X, y, trees, learning_rate, l2, shards):
    """Follow ``trees`` from the seed's data as reference_clicklog.follow
    does (scores from the reference's own leaf values) and return per tree
    ``count`` [shards, leaves] int64, ``grad`` and ``hess`` [shards,
    leaves] float64: each shard's rows at each leaf, and their sums."""
    n = len(y)
    cols = ref.Columns(X)
    y64 = y.astype(np.float64)
    init = ref.init_score(y)
    score = np.full(n, init, np.float64)
    bounds = shard_bounds(n, shards)
    with ThreadPoolExecutor(max(len(trees), 1)) as pool:
        leaves = list(pool.map(lambda t: ref.route(cols, t, n=n), trees))
    out = []
    for tree, leaf in zip(trees, leaves):
        nl = tree["num_leaves"]
        p = ref.sigmoid(score)
        g, h = p - y64, p * (1.0 - p)
        per = {"count": [], "grad": [], "hess": []}
        for a, b in bounds:
            part = leaf[a:b]
            per["count"].append(np.bincount(part, minlength=nl))
            per["grad"].append(np.bincount(part, weights=g[a:b],
                                           minlength=nl))
            per["hess"].append(np.bincount(part, weights=h[a:b],
                                           minlength=nl))
        per = {k: np.stack(v) for k, v in per.items()}
        gl, hl = per["grad"].sum(axis=0), per["hess"].sum(axis=0)
        with np.errstate(divide="ignore", invalid="ignore"):
            value = -gl / (hl + l2) * learning_rate   # NaN: an empty leaf
        out.append(per)
        score += value[leaf]
    return out


def readings(X, y, trees, learning_rate, l2, nodes, search):
    """The numbers compared: reference_clicklog's over the whole table."""
    return ref.readings(trees, ref.follow(X, y, trees, learning_rate, l2,
                                          nodes, search))
