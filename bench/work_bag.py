"""The histogram work one boosting iteration needs when its tree is grown
on a bag (boosting=goss past the unsampled iterations): bench/work.py's
count with the bag's rows in the place of all rows.

Rows visited: the bag's rows once for the root's histogram, then for each
split of the grown tree the in-bag rows of the leaf it split. The model's
``internal_count`` are counts of in-bag rows, and the root's is the bag.
The rows out of the bag are routed and enter no histogram: they are not
the histogram's work. Bytes and bound: ``work.hist_bytes``.
"""


def hist_rows_visited(tree):
    """The bag for the root plus the in-bag rows of every split leaf."""
    counts = [int(c) for c in tree["internal_count"]]
    return (counts[0] if counts else 0) + sum(counts)
