"""Which of the exact grower's two carried roundings the check numbers need.

    python tools/compensation_lab.py <root> <tiles> <bench/run.py arguments>

Since PR 32 a leaf's sibling is parent - smaller, so a small leaf's bins
inherit the absolute float32 error of every larger ancestor's sums. Two sums
carry their rounding for that (histogram.compensated_add): the root's pass,
cut in blocks of ROW_BLOCK rows, and the tiles of a smaller child's range in
partition.hist_for_leaf. This runs one benchmark cell with either taken out:

    <root>   blocks    as the library has it
             one_call  the root's pass in one kernel call (the parent's)
    <tiles>  carried   as the library has it
             plain     acc + tile

and prints the cell's line, whose ``compared`` holds ``split_gain_gap``,
``leaf_value_gap`` and ``split_order_gap``. The readings are in PERF.md
section 6 (PR 32).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    root, tiles = sys.argv[1:3]
    assert root in ("blocks", "one_call") and tiles in ("carried", "plain")
    from lightgbm_tpu.core import histogram_pallas, partition
    if root == "one_call":
        histogram_pallas.ROW_BLOCK = 0
    if tiles == "plain":
        partition.compensated_add = \
            lambda total, lost, term: (total + term, lost)
    import bench.run
    sys.argv = ["bench/run.py"] + sys.argv[3:]
    bench.run.main()


if __name__ == "__main__":
    main()
