"""The histogram work one boosting iteration needs, whatever implements it.

Rows visited: every row once for the root's histogram, then for each split
of the grown tree the rows of the leaf it split (the single-device exact
grower prices both children in one pass over the parent's rows; a
smaller-child pass plus a subtraction would visit fewer, and would then
read above this count's share, which is the point: the count is the job's,
not the kernel's). The counts are the model's ``internal_count``.

Bytes: rows visited x (stored columns x 1 B of bin code + 8 B of gradient
and hessian) read, plus the histograms written: one per pass at
columns x bins x 3 float32 (gradient, hessian, count), two children for a
split's pass. The bound is bytes over the HBM peak: a histogram adds and
needs no multiply, so the one-hot matmul's FLOPs are how this kernel does
it, not what the job needs.
"""


def hist_rows_visited(tree, num_data):
    """num_data for the root plus the rows of every split leaf."""
    return int(num_data) + int(sum(int(c) for c in tree["internal_count"]))


def hist_bytes(rows_visited, splits, cols, bins):
    read = rows_visited * (cols * 1 + 8)
    written = (1 + 2 * splits) * cols * bins * 3 * 4
    return read + written
