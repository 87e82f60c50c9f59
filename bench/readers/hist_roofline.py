"""The histogram kernels' share of their roofline in the traced iteration:
the least time the chip could take for the histogram work the grown tree
needed (bench/work.py: bytes over the HBM peak; a histogram needs no
multiply, so bytes bound it) over the kernels' device time."""
from bench import reference_gbdt, work
from bench.readers.trace_ops import kernel_seconds


def read(spec, result):
    tr = result.get("trace")
    if tr is None:
        return None
    kernel_s = kernel_seconds(tr, spec["kernel"])
    if not kernel_s:
        return None
    trees = reference_gbdt.parse_trees(result["model_text"])
    # the warm-up block grew the first tree(s); the traced block, the
    # window's first, grew the tree(s) from first_iter on
    first = tr["first_iter"]
    traced = trees[first:first + tr["iters"]]
    if len(traced) != tr["iters"]:
        return None
    data, params = result["config"]["data"], result["config"]["params"]
    nbytes = sum(work.hist_bytes(work.hist_rows_visited(t, data["rows"]),
                                 t["num_leaves"] - 1, data["cols"],
                                 params["max_bin"]) for t in traced)
    return 100.0 * (nbytes / result["peaks"][spec["bound"]]) / kernel_s
