"""bench/readers/hist_roofline.py for a tree grown on a bag: the histogram
kernels' share of their roofline in the traced iteration, the job's rows
counted by bench/work_bag.py (the bag, then the in-bag rows of every split
leaf) and not from the table's row count."""
from bench import reference_goss, work, work_bag
from bench.readers.trace_ops import kernel_seconds


def read(spec, result):
    tr = result.get("trace")
    if tr is None:
        return None
    kernel_s = kernel_seconds(tr, spec["kernel"])
    if not kernel_s:
        return None
    trees = reference_goss.parse_trees(result["model_text"])
    first = tr["first_iter"]
    traced = trees[first:first + tr["iters"]]
    if len(traced) != tr["iters"]:
        return None
    data, params = result["config"]["data"], result["config"]["params"]
    nbytes = sum(work.hist_bytes(work_bag.hist_rows_visited(t),
                                 t["num_leaves"] - 1, data["cols"],
                                 params["max_bin"]) for t in traced)
    return 100.0 * (nbytes / result["peaks"][spec["bound"]]) / kernel_s
