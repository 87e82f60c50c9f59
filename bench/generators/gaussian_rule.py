"""Seeded dense tabular data for a binary GBDT job.

Column j of seed s always draws from ``SeedSequence([s, j])``, so the data
depends on the seed and the shape alone, not on the thread count. Columns
are ``offset`` + standard normal, rounded to float32 and held as float64 in
column-major order: the program converts whatever it is given to float64
and then bins column by column, so this is the layout it ingests without a
copy and without strided reads. The label follows chip_smoke.py's rule (a
linear term, a product, a sine, noise) on the centred columns, with the
linear term spread over the first ``linear_cols`` columns:

    y = [ sum_j<k z_j / sqrt(k) + z_k * z_k+1 + 0.5 sin(3 z_k+2)
          + noise * eps  >  0 ],   z = x - offset

A positive ``offset`` keeps every value above 0, as counts and rates are:
the bin that holds 0 is then the first bin of every column for every
seed. That matters to set-up: the program bakes each column's zero bin
into its compiled train block (boosting/gbdt.py closes over feature_meta),
so a zero bin that moves with the seed compiles the block anew in every
run (181 s at 26.6M rows; PERF.md, Open questions).
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = min(16, os.cpu_count() or 8)


def generate(seed, rows, cols, linear_cols, noise, offset, dtype=np.float64):
    """(X [rows, cols] ``dtype``, column-major; y [rows] float32 in {0, 1}).
    Every value is a float32 value, so float32 holds the same data in half
    the room: the check asks for that, the program is fed float64."""
    k = linear_cols
    if cols < k + 3:
        raise ValueError("gaussian_rule: %d columns cannot hold %d linear "
                         "columns and the rule's three" % (cols, k))
    X = np.empty((rows, cols), dtype, order="F")

    def fill(j):
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), j]))
        X[:, j] = rng.standard_normal(rows, dtype=np.float32) \
            + np.float32(offset)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(cols)))
    z = X[:, :k + 3].astype(np.float64) - np.float64(np.float32(offset))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), cols]))
    t = z[:, :k].sum(axis=1) / np.sqrt(k) + z[:, k] * z[:, k + 1] \
        + 0.5 * np.sin(3 * z[:, k + 2])
    t += noise * rng.standard_normal(rows, dtype=np.float32)
    return X, (t > 0).astype(np.float32)
