"""Readings behind the limits of the data-parallel cell's check (run by
hand; the benchmark's own runs never run this):

  on the chip   python bench/tests/readings_data4.py run [--flipped] \\
                    --workload criteo_clicklog_data4_train --seed N \\
                    --seconds 30 --trace 0|1
                bench/run.py's run, the same line; then, on that machine's
                host, from the trees and the table the check had, the
                variants below, as one READINGS line.
  on any host   JAX_PLATFORMS=cpu \\
                XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
                python bench/tests/readings_data4.py rows --seed N --rows R
                the cell's job at R rows (a multiple of 4) on the devices
                there are, with the cell's parameters and check but matmul
                histograms, three trees and no window; then the variants
                with ``--flipped``, as one READINGS line.

The variants, each beside the limits of the cell it fails:

  control   the bfloat16 control: the reference in the program's place,
            gradients and hessians rounded to bfloat16 before they are
            summed
  flipped   (``--flipped``) the model with ``default_left`` turned round at
            every node whose column has a NaN bin

The check's own follow of the trees is reused, so the control costs one
more pass of the reference over the table, and ``--flipped`` one more.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import reference_clicklog as ref
from bench import reference_data_parallel
from bench import run as bench_run
from bench.jobs.train_window import hold
from bench.tests.readings_clicklog import flip_default_left
from bench.tests.readings_on_chip import in_programs_place

CELL = "criteo_clicklog_data4_train"


def keep_the_follow():
    """Make the check keep what it followed; returns where it is kept."""
    seen = {}

    def keep(X, y, trees, learning_rate, l2, nodes, search):
        followed = ref.follow(X, y, trees, learning_rate, l2, nodes, search)
        seen.update(X=X, y=y, trees=trees, rest=(learning_rate, l2, nodes,
                                                 search), followed=followed)
        return ref.readings(trees, followed)
    reference_data_parallel.readings = keep
    return seen


def variants(seen, program, flipped):
    """{variant: numbers compared} and which limits of the cell each
    fails."""
    args, rest, trees = (seen["X"], seen["y"]), seen["rest"], seen["trees"]
    out = {"program": program}
    low = ref.follow(*args, trees, *rest, grad_cast=ref.bfloat16_round)
    out["control"] = ref.readings(in_programs_place(trees, low),
                                  seen["followed"])
    if flipped:
        turned, count = flip_default_left(trees)
        out["flipped"] = ref.readings(turned, ref.follow(*args, turned, *rest))
        out["nodes_flipped"] = count
    limits = bench_run.load_json(bench_run.HERE, "workloads",
                                 CELL + ".json")["check"]["limits"]
    out["fails"] = {
        v: sorted(k for k, c in hold(got, {k: limits[k] for k in got
                                           if k in limits})[0].items()
                  if not c["ok"])
        for v, got in out.items() if isinstance(got, dict)}
    return out


def run(argv):
    flipped = "--flipped" in argv
    argv = [a for a in argv if a != "--flipped"]
    seen = keep_the_follow()
    sys.argv = ["run.py"] + argv
    line = bench_run.main()
    got = variants(seen, {k: c["value"] for k, c in line["compared"].items()},
                   flipped)
    print("READINGS " + json.dumps({
        "seed": int(argv[argv.index("--seed") + 1]),
        "correct": line["correct"], **got}), flush=True)


def rows(argv):
    seed = int(argv[argv.index("--seed") + 1])
    n = int(argv[argv.index("--rows") + 1])
    seen = keep_the_follow()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    entry = bench_run.find(bench["configs"], bench_run.find(
        bench["workloads"], CELL, "workload")["config"], "configuration")
    cfg = bench_run.load_json(bench_run.ROOT, entry["file"])
    cfg = dict(cfg, data=dict(cfg["data"], rows=n),
               params=dict(cfg["params"], tpu_hist_impl="matmul"))
    wl = dict(bench_run.load_json(bench_run.HERE, "workloads",
                                  CELL + ".json"), block_iters=3)
    ctx = {"t_start": time.time(), "seed": seed, "seconds": 0.0,
           "trace": False, "rehearsal": True, "workload": wl, "config": cfg,
           "peaks": {},
           "generator": bench_run.load_module("generators", cfg["generator"])}
    result = bench_run.load_module("jobs", wl["job"]).run(ctx)
    got = variants(seen, {k: c["value"]
                          for k, c in result["compared"].items()}, True)
    print("READINGS " + json.dumps({"seed": seed, "rows": n, **got}),
          flush=True)


if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] not in ("run", "rows"):
        sys.exit(__doc__)
    {"run": run, "rows": rows}[sys.argv[1]](sys.argv[2:])
