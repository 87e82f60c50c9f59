"""The click-log table of bench/generators/clicklog.py, made as ``shards``
contiguous blocks of rows, one a chip of a data-parallel mesh.

Block k is that generator's table at ``rows / shards`` rows under the
values' key ``table + 1000 k``: block 0 is the one-chip cells' table (the
same values, to the row, where they hold as many rows), and every block has
its roles' statistics, the seed's layout (which column plays which role,
the short columns' caps) and its own label intercept. Two blocks are made
at a time, each whole, and copied into the table: the host holds the table
and two blocks with their temporaries, not a table's worth of them. The
data depends on the seed, ``table`` and the shape alone.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.generators import clicklog

TABLE_STRIDE = 1000
AT_ONCE = 2              # blocks made at a time, each on clicklog's threads


def generate(seed, rows, table, shards, dtype=np.float64, **data):
    """(X [rows, cols] ``dtype``, column-major; y [rows] float32)."""
    if rows % shards:
        raise ValueError("clicklog_shards: %d rows are not %d equal blocks"
                         % (rows, shards))
    per = rows // shards
    X = np.empty((rows, data["cols"]), dtype, order="F")
    y = np.empty(rows, np.float32)

    def block(k):
        xk, yk = clicklog.generate(seed, per, table=table + TABLE_STRIDE * k,
                                   dtype=dtype, **data)
        X[k * per:(k + 1) * per] = xk
        y[k * per:(k + 1) * per] = yk

    with ThreadPoolExecutor(AT_ONCE) as pool:
        list(pool.map(block, range(shards)))
    return X, y
