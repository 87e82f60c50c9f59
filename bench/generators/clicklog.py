"""Seeded click-log-shaped tabular data for a binary GBDT job.

The table LightGBM's "Parallel Experiment" trains on: ``count_cols``
integer count columns, then (click-through rate, count) pairs, one pair
for each categorical field of the raw logs. No column statistics are
published, so every distribution here is this benchmark's assumption
(the configuration file lists them under ``assumed``):

  count column, role r   floor(exp(N(mu_r, sigma_r))), mu_r set so that
                         ``count_zero_share[r]`` of its values are exactly
                         0; ``count_nan_share[r]`` of its rows are NaN (an
                         empty field); a role in ``short_roles`` is capped
                         so that it holds 20 to 120 distinct values, a
                         number drawn from the seed; a role in
                         ``negative_roles`` holds -1, -2, -3 in
                         ``negative_share`` of its rows
  pair, role q           CTR = Beta(m s, (1 - m) s), m = ``pair_ctr_mean[q]``,
                         s = ``pair_ctr_strength``; count = floor(exp(N(
                         pair_count_mu, pair_count_sigma))); in
                         ``pair_unseen_share[q]`` of the rows (none at all
                         for some roles) BOTH are NaN: a category the
                         statistics days never saw
  label                  Bernoulli(sigmoid(t + b)), t a rule over twelve
                         columns (``rule``) with an "is missing" and an
                         "is zero" term, b set by bisection on every k-th
                         row so that ``positive_share`` are positive

The VALUES are one deployment's table: count role r, pair role q and the
label's row blocks draw from streams keyed by ``table`` and the role, not
by the seed. What the seed draws is the LAYOUT: which count column plays
which count role, which pair of columns plays which pair role (two
permutations), and the short columns' caps. So the zero bin, the missing
type and the bin count of a column differ from seed to seed, as two users'
tables differ, while the trees grown are the same trees on other columns:
with values drawn from the seed too, six runs spread by 0.5% to 3.4% in
seconds per iteration (leaf-wise growth on rare positives splits a few
million-row leaves more or fewer from one sample to the next), too wide
for a 1% bound to tell anything. So `correct` on N seeds judges one
table's trees N times; the limits of the check were read on other tables
as well (bench/tests/readings_clicklog.py ``--table``). The data depends
on the seed, ``table`` and the shape alone, not on the thread count. Values are float32 values
held as ``dtype`` in column-major order, the layout the program bins
without a copy.
"""
import os
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np

THREADS = min(16, os.cpu_count() or 8)
BLOCK = 1 << 20          # rows of the label drawn from one stream
F32 = np.float32


def layout(seed, cols, count_cols, count_nan_share, count_zero_share,
           count_sigma, short_roles, short_distinct, negative_roles,
           pair_ctr_mean, pair_unseen_share, **_):
    """What the seed makes of each column: ``role_of[j]`` of count column
    j and ``column_of[r]`` its inverse, ``pair_role_of[p]`` of the p-th
    pair of columns and ``pair_of[q]`` its inverse, the short roles' caps."""
    pairs = (cols - count_cols) // 2
    if count_cols + 2 * pairs != cols:
        raise ValueError("clicklog: %d columns are not %d counts and whole "
                         "pairs" % (cols, count_cols))
    if not len(count_nan_share) == len(count_zero_share) == count_cols:
        raise ValueError("clicklog: a NaN share and a zero share for each "
                         "of the %d count roles" % count_cols)
    if not len(pair_ctr_mean) == len(pair_unseen_share) == pairs:
        raise ValueError("clicklog: a CTR mean and an unseen share for each "
                         "of the %d pair roles" % pairs)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC11C]))
    column_of = rng.permutation(count_cols)
    pair_of = rng.permutation(pairs)
    lo, hi = short_distinct
    cap = {int(r): int(rng.integers(lo, hi + 1)) - 1 for r in short_roles}
    sigma = np.linspace(count_sigma[0], count_sigma[1], count_cols)
    return {"pairs": pairs, "column_of": column_of,
            "role_of": np.argsort(column_of), "pair_of": pair_of,
            "pair_role_of": np.argsort(pair_of), "cap": cap,
            "negative": {int(r) for r in negative_roles}, "sigma": sigma}


def count_column(rng, rows, zero_share, nan_share, sigma, cap, negative_share):
    """floor(exp(N(mu, sigma))) with ``zero_share`` exact zeros, capped,
    with a few small negatives and NaN rows; float32."""
    mu = -sigma * NormalDist().inv_cdf(zero_share)   # P(mu + sigma z < 0)
    v = rng.standard_normal(rows, dtype=F32)
    v *= F32(sigma)
    v += F32(mu)
    np.exp(v, out=v)
    np.floor(v, out=v)
    if cap is not None:
        np.minimum(v, F32(cap), out=v)
    u = rng.random(rows, dtype=F32)
    if negative_share:
        # -1 in seven of ten such rows, -2 in two, -3 in one
        neg = u < F32(negative_share)
        w = u[neg] / F32(negative_share)
        v[neg] = -1.0 - (w >= F32(0.7)) - (w >= F32(0.9))
    if nan_share:
        v[rng.random(rows, dtype=F32) < F32(nan_share)] = np.nan
    return v


def pair_columns(rng, rows, mean, strength, mu, sigma, unseen):
    """(CTR, count) of one categorical field; float32."""
    a = rng.standard_gamma(mean * strength, rows, dtype=F32)
    b = rng.standard_gamma((1.0 - mean) * strength, rows, dtype=F32)
    b += a
    a /= b                                   # Beta(mean s, (1 - mean) s)
    c = rng.standard_normal(rows, dtype=F32)
    c *= F32(sigma)
    c += F32(mu)
    np.exp(c, out=c)
    np.floor(c, out=c)
    if unseen:
        gone = rng.random(rows, dtype=F32) < F32(unseen)
        a[gone] = np.nan
        c[gone] = np.nan
    return a, c


def rule_terms(X, lay, count_cols, pair_ctr_mean, rule, sl):
    """The label's logit before its intercept, on the rows ``sl``; the
    rule names count roles and pair roles."""
    col = lambda r: X[sl, lay["column_of"][r]].astype(np.float64)   # noqa: E731
    ctr = lambda q: X[sl, count_cols + 2 * lay["pair_of"][q]].astype(   # noqa: E731
        np.float64)
    cnt = lambda q: X[sl, count_cols + 2 * lay["pair_of"][q] + 1].astype(   # noqa: E731
        np.float64)

    def seen(v, centre):
        """v - centre, and nought where the value is missing."""
        return np.where(np.isnan(v), 0.0, v - centre)

    t = rule["is_missing"][1] * np.isnan(col(rule["is_missing"][0]))
    t += rule["is_zero"][1] * (col(rule["is_zero"][0]) == 0.0)
    for r, w in rule["log_count"]:
        t += w * seen(np.log1p(np.maximum(col(r), 0.0)), 0.0)
    for q, w in rule["ctr"]:
        t += w * seen(ctr(q), pair_ctr_mean[q])
    q, w = rule["unseen"]
    t += w * np.isnan(ctr(q))
    q, w = rule["log_pair_count"]
    t += w * seen(np.log1p(cnt(q)), rule["log_pair_count_centre"])
    p, q, w = rule["ctr_product"]
    t += w * seen(ctr(p), pair_ctr_mean[p]) * seen(ctr(q), pair_ctr_mean[q])
    return t


def intercept(t, share):
    """b with mean(sigmoid(t + b)) = share, by bisection."""
    lo, hi = -40.0, 40.0
    for _ in range(60):
        b = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(t + b)))) < share:
            lo = b
        else:
            hi = b
    return 0.5 * (lo + hi)


def generate(seed, rows, cols, table, count_cols, count_nan_share,
             count_zero_share, count_sigma, short_roles, short_distinct,
             negative_roles,
             negative_share, pair_ctr_mean, pair_ctr_strength, pair_count_mu,
             pair_count_sigma, pair_unseen_share, positive_share, rule,
             dtype=np.float64):
    """(X [rows, cols] ``dtype``, column-major; y [rows] float32 in {0, 1}).
    Every value is a float32 value or NaN, so float32 holds the same data
    in half the room: the check asks for that, the program is fed
    float64."""
    lay = layout(seed, cols, count_cols, count_nan_share, count_zero_share,
                 count_sigma, short_roles, short_distinct, negative_roles,
                 pair_ctr_mean, pair_unseen_share)
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

    def fill_pair(p):
        j, q = count_cols + 2 * p, int(lay["pair_role_of"][p])
        X[:, j], X[:, j + 1] = pair_columns(
            stream(count_cols + q), rows, pair_ctr_mean[q], pair_ctr_strength,
            pair_count_mu, pair_count_sigma, pair_unseen_share[q])

    blocks = [slice(s, min(s + BLOCK, rows)) for s in range(0, rows, BLOCK)]
    t = np.empty(rows, np.float64)

    def fill_rule(sl):
        t[sl] = rule_terms(X, lay, count_cols, pair_ctr_mean, rule, sl)

    y = np.empty(rows, np.float32)

    def fill_label(i):
        sl = blocks[i]
        u = stream(cols, i).random(sl.stop - sl.start)
        y[sl] = u < 1.0 / (1.0 + np.exp(-(t[sl] + b)))

    with ThreadPoolExecutor(THREADS) as pool:
        # the pairs first: they are the longer tasks
        tasks = [pool.submit(fill_pair, p) for p in range(lay["pairs"])]
        tasks += [pool.submit(fill_count, j) for j in range(count_cols)]
        for task in tasks:
            task.result()
        list(pool.map(fill_rule, blocks))
        b = intercept(t[::max(1, rows // 500000)], positive_share)
        list(pool.map(fill_label, range(len(blocks))))
    return X, y
