"""Seeded click-log-shaped data with the categorical fields fed as
categories: ``count_cols`` integer count columns, then one column of
category ids for each categorical field of the raw logs.

The integer columns are the sibling generator's count roles
(bench/generators/clicklog.py ``count_column``: NaN and zero shares, caps
and negatives as there). No column statistics are published, so what
follows is this benchmark's assumption (the configuration file lists it
under ``assumed``):

  categorical role q   ``cardinalities[q]`` categories; a value's rank is
                       ``floor((K + 1) ** u) - 1`` for u uniform, so rank
                       k has probability log((k + 2) / (k + 1)) /
                       log(K + 1) (Zipf of exponent 1); ids are a label
                       encoding in order of first appearance: the ranks
                       that occur at all, sorted by Exponential(1) / p_k,
                       take ids 0, 1, 2, ... so frequent categories hold
                       small ids in noisy order, contiguous from zero;
                       ``cat_nan_share[q]`` of the rows are NaN (an empty
                       field)
  label                Bernoulli(sigmoid(t + b)); t = an "is missing" and
                       an "is zero" term and four log-count terms over
                       integer roles, plus, for each role q in
                       ``rule.category``, an effect of the row's category
                       drawn N(0, sigma_q) by id (nought for an empty
                       field); b by bisection so that ``positive_share``
                       are positive

As in the sibling, the VALUES are one deployment's table: every role's
column, the id maps, the effects and the label's row blocks draw from
streams keyed by ``table`` and the role. ``--seed`` draws the LAYOUT:
which of the first ``count_cols`` columns plays which count role, which
of the others plays which categorical role (two permutations), and the
short count columns' caps. Values are float32 values (ids stay under
2**24) held as ``dtype`` in column-major order.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.generators.clicklog import (BLOCK, F32, THREADS, count_column,
                                       intercept)


def layout(seed, cols, count_cols, short_roles, short_distinct,
           negative_roles, count_sigma):
    """What the seed makes of each column: ``role_of[j]`` of count column
    j and ``column_of[r]`` its inverse, ``cat_role_of[p]`` of the p-th
    categorical column and ``cat_of[q]`` its inverse, the short roles'
    caps."""
    cats = cols - count_cols
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC11C]))
    column_of = rng.permutation(count_cols)
    cat_of = rng.permutation(cats)
    lo, hi = short_distinct
    cap = {int(r): int(rng.integers(lo, hi + 1)) - 1 for r in short_roles}
    return {"cats": cats, "column_of": column_of,
            "role_of": np.argsort(column_of), "cat_of": cat_of,
            "cat_role_of": np.argsort(cat_of), "cap": cap,
            "negative": {int(r) for r in negative_roles},
            "sigma": np.linspace(count_sigma[0], count_sigma[1], count_cols)}


def category_ids(rng, rows, k, nan_share, out):
    """One categorical field into ``out`` [rows]: Zipf ranks, label-encoded
    in order of first appearance, NaN where the field is empty. Worked in
    blocks of rows, so that a thread holds 9 bytes a row beside ``out``."""
    u = rng.random(rows, dtype=F32)
    gone = (rng.random(rows, dtype=F32) < F32(nan_share) if nan_share
            else np.zeros(rows, bool))
    rank = np.empty(rows, np.int32)
    seen = np.zeros(k, bool)
    for s in range(0, rows, BLOCK):
        sl = slice(s, s + BLOCK)
        r = np.exp(u[sl].astype(np.float64) * np.log(k + 1.0)).astype(np.int64)
        rank[sl] = np.minimum(r - 1, k - 1)
        seen[rank[sl][~gone[sl]]] = True
    del u
    ranks = np.arange(k, dtype=np.float64)
    first = rng.standard_exponential(k)
    first /= np.log((ranks + 2.0) / (ranks + 1.0)) / np.log(k + 1.0)
    del ranks
    first[~seen] = np.inf
    id_of = np.empty(k, np.int32)
    id_of[np.argsort(first, kind="stable")] = np.arange(k, dtype=np.int32)
    del first, seen
    for s in range(0, rows, BLOCK):
        sl = slice(s, s + BLOCK)
        v = id_of[rank[sl]].astype(F32)
        v[gone[sl]] = np.nan
        out[sl] = v


def generate(seed, rows, cols, table, count_cols, count_nan_share,
             count_zero_share, count_sigma, short_roles, short_distinct,
             negative_roles, negative_share, cardinalities, cat_nan_share,
             positive_share, rule, cardinality_cap=None, dtype=np.float64):
    """(X [rows, cols] ``dtype``, column-major; y [rows] float32 in {0, 1}).
    Columns ``count_cols`` .. ``cols`` - 1 hold category ids."""
    cats = cols - count_cols
    if not len(cardinalities) == len(cat_nan_share) == cats:
        raise ValueError("clicklog_categorical: a cardinality and a NaN "
                         "share for each of the %d categorical roles" % cats)
    if not len(count_nan_share) == len(count_zero_share) == count_cols:
        raise ValueError("clicklog_categorical: a NaN share and a zero share "
                         "for each of the %d count roles" % count_cols)
    lay = layout(seed, cols, count_cols, short_roles, short_distinct,
                 negative_roles, count_sigma)
    card = [min(int(k), int(cardinality_cap)) if cardinality_cap else int(k)
            for k in cardinalities]
    X = np.empty((rows, cols), dtype, order="F")

    def stream(*key):
        return np.random.default_rng(np.random.SeedSequence(
            [int(table)] + [int(k) for k in key]))

    def fill_count(j):
        r = int(lay["role_of"][j])
        X[:, j] = count_column(
            stream(r), rows, count_zero_share[r], count_nan_share[r],
            lay["sigma"][r], lay["cap"].get(r),
            negative_share if r in lay["negative"] else 0.0)

    def fill_cat(p):
        q = int(lay["cat_role_of"][p])
        category_ids(stream(count_cols + q), rows, card[q], cat_nan_share[q],
                     X[:, count_cols + p])

    blocks = [slice(s, min(s + BLOCK, rows)) for s in range(0, rows, BLOCK)]
    t = np.empty(rows, np.float64)
    # a category's effect on the label, by id, for the roles of the rule
    effects = {int(q): stream(1000 + int(q)).standard_normal(card[int(q)])
               * float(s) for q, s in rule["category"]}

    def fill_rule(sl):
        col = lambda r: X[sl, lay["column_of"][r]].astype(np.float64)   # noqa: E731
        tt = rule["is_missing"][1] * np.isnan(col(rule["is_missing"][0]))
        tt += rule["is_zero"][1] * (col(rule["is_zero"][0]) == 0.0)
        for r, w in rule["log_count"]:
            v = np.log1p(np.maximum(col(r), 0.0))
            tt += w * np.where(np.isnan(v), 0.0, v)
        for q, eff in effects.items():
            c = X[sl, count_cols + lay["cat_of"][q]].astype(np.float64)
            gone = np.isnan(c)
            tt += np.where(gone, 0.0,
                           eff[np.where(gone, 0.0, c).astype(np.int64)])
        t[sl] = tt

    y = np.empty(rows, np.float32)

    def fill_label(i):
        sl = blocks[i]
        u = stream(cols, i).random(sl.stop - sl.start)
        y[sl] = u < 1.0 / (1.0 + np.exp(-(t[sl] + b)))

    with ThreadPoolExecutor(THREADS) as pool:
        # the widest categorical fields first: they are the longer tasks
        order = sorted(range(cats),
                       key=lambda p: -card[int(lay["cat_role_of"][p])])
        tasks = [pool.submit(fill_cat, p) for p in order]
        tasks += [pool.submit(fill_count, j) for j in range(count_cols)]
        for task in tasks:
            task.result()
        list(pool.map(fill_rule, blocks))
        b = intercept(t[::max(1, rows // 500000)], positive_share)
        list(pool.map(fill_label, range(len(blocks))))
    return X, y
