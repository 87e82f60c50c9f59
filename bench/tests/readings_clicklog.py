"""Readings behind the limits of the click-log cell's check, at the cell's
own size (run by hand; the benchmark's own runs never run this). What
bench/tests/readings_on_chip.py is to the sibling, in three steps so that
the chip is held only for what needs it:

  on the chip   python bench/tests/readings_clicklog.py run [--table T] \
                    --workload W --seed N --seconds 30 --trace 0
                bench/run.py's run, the same line, with the model the
                timed booster wrote kept as chiprun_out/models/W_N.txt.
                ``--table T`` puts another table of VALUES in the place of
                the configuration's ``data.table`` (the cell's own runs
                all train on that one table, the seed drawing the layout
                alone): the limits' readings are taken on several
  on the chip   python bench/tests/readings_clicklog.py fault --workload W \
                    --seeds a,b [--rounds 3]
                the planted fault that needs the program: the same data
                binned and trained with use_missing=false (a NaN read as a
                zero, no missing direction priced), judged by the cell's
                check; one READINGS line a seed
  on any host   python bench/tests/readings_clicklog.py models --workload W \
                    --seeds a,b,c [--tables t,u,v] [--dir chiprun_out/models]
                from each kept model and the seed's data made again: the
                program's numbers, the bfloat16 control (the reference in
                the program's place, gradients and hessians rounded to
                bfloat16 before they are summed) and the model with
                ``default_left`` flipped at every node whose column has a
                NaN bin; one READINGS line a seed, each variant beside the
                limits it fails
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from bench import reference_clicklog as ref
from bench import run as bench_run
from bench.jobs import clicklog_window
from bench.tests.readings_on_chip import in_programs_place

MODELS = os.path.join("chiprun_out", "models")


def flip_default_left(trees):
    """The same trees with the missing direction turned round at every
    node whose column has a NaN bin; also how many nodes that was."""
    out, flipped = copy.deepcopy(trees), 0
    for t in out:
        nan = ((t["decision_type"] >> 2) & 3) == ref.MISSING_NAN
        t["decision_type"] = np.where(nan, t["decision_type"] ^ 2,
                                      t["decision_type"])
        flipped += int(nan.sum())
    return out, flipped


def variants(ctx, X, y, model_text):
    """{variant: numbers compared} for the program's model, the bfloat16
    control and the flipped model; score_gap is left to the run itself."""
    check, params = ctx["workload"]["check"], ctx["config"]["params"]
    lr, l2 = params["learning_rate"], params.get("lambda_l2", 0.0)
    judged = ref.parse_trees(model_text)[:check["follow_trees"]]
    nodes = ref.draw_nodes(ctx["seed"], judged, check["regret_nodes"])
    search = clicklog_window.search_of(ctx)
    exact = ref.follow(X, y, judged, lr, l2, nodes, search)
    low = ref.follow(X, y, judged, lr, l2, nodes, search,
                     grad_cast=ref.bfloat16_round)
    turned, count = flip_default_left(judged)
    out = {"program": ref.readings(judged, exact),
           "control": ref.readings(in_programs_place(judged, low), exact),
           "flipped": ref.readings(
               turned, ref.follow(X, y, turned, lr, l2, nodes, search)),
           "shape": {"nodes_flipped": count,
                     "leaves": [int(t["num_leaves"]) for t in judged],
                     "rows_visited": [int(len(y) + t["internal_count"].sum())
                                      for t in judged]}}
    out["fails"] = fails(ctx, out)
    return out


def fails(ctx, out):
    limits = ctx["workload"]["check"]["limits"]
    return {v: [k for k, c in clicklog_window.hold(
        got, {k: lim for k, lim in limits.items() if k in got})[0].items()
        if not c["ok"]]
        for v, got in out.items() if v != "shape"}


def on_table(cfg, table):
    """The configuration with another table of values, at both shapes."""
    if table is None:
        return cfg
    cfg = copy.deepcopy(cfg)
    cfg["data"]["table"] = cfg["rehearsal"]["data"]["table"] = int(table)
    return cfg


def context(workload, seed, rehearsal, table=None):
    """bench/run.py's ctx for the cell, without a device."""
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell = bench_run.find(bench["workloads"], workload, "workload")
    entry = bench_run.find(bench["configs"], cell["config"], "configuration")
    wl = bench_run.load_json(bench_run.HERE, "workloads", workload + ".json")
    cfg = on_table(bench_run.load_json(bench_run.ROOT, entry["file"]), table)
    if rehearsal:
        cfg = dict(cfg, **cfg["rehearsal"])
        wl = dict(wl, **wl.get("rehearsal", {}))
    return {"seed": seed, "workload": wl, "config": cfg,
            "generator": bench_run.load_module("generators",
                                               cfg["generator"])}


def run_and_keep(rest):
    judge = clicklog_window.judge
    if rest[:1] == ["--table"]:
        table, rest = rest[1], rest[2:]
        load_json = bench_run.load_json

        def on_that_table(*parts):   # the configuration is the one file
            loaded = load_json(*parts)   # that names a generator
            return on_table(loaded, table) if "generator" in loaded \
                else loaded
        bench_run.load_json = on_that_table

    def keep(ctx, X, y, model_text, scores):
        os.makedirs(MODELS, exist_ok=True)
        with open(os.path.join(MODELS, "%s_%d.txt" % (
                ctx["workload"]["name"], ctx["seed"])), "w") as f:
            f.write(model_text)
        return judge(ctx, X, y, model_text, scores)

    clicklog_window.judge = keep
    sys.argv = ["run.py"] + rest
    bench_run.main()


def seeds_and_tables(args):
    seeds = [int(s) for s in args.seeds.split(",")]
    tables = ([int(t) for t in args.tables.split(",")] if args.tables
              else [None] * len(seeds))
    if len(tables) != len(seeds):
        sys.exit("readings_clicklog: a table for each seed, or none")
    return list(zip(seeds, tables))


def fault(args):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.profiling import enable_compile_cache
    enable_compile_cache()
    for seed, table in seeds_and_tables(args):
        ctx = context(args.workload, seed, args.rehearsal, table)
        cfg = ctx["config"]
        t = time.perf_counter()
        X, y = ctx["generator"].generate(seed, **cfg["data"])
        params = dict(cfg["params"], use_missing=False)
        ds = lgb.Dataset(X, y, params=dict(params)).construct()
        ds.data = None
        del X
        bst = lgb.train(params, ds, num_boost_round=args.rounds)
        scores = np.asarray(jax.block_until_ready(bst._impl.scores))[:, 0]
        text = bst.model_to_string(num_iteration=-1)
        del bst, ds
        X, _ = ctx["generator"].generate(seed, dtype=np.float32,
                                         **cfg["data"])
        compared, _ = clicklog_window.judge(ctx, X, y, text, scores)
        got = {k: c["value"] for k, c in compared.items()}
        print("READINGS " + json.dumps({
            "seed": seed, "use_missing_false": got,
            "fails": [k for k, c in compared.items() if not c["ok"]],
            "seconds": time.perf_counter() - t}), flush=True)


def from_models(args):
    for seed, table in seeds_and_tables(args):
        ctx = context(args.workload, seed, args.rehearsal, table)
        with open(os.path.join(args.dir, "%s_%d.txt" % (
                args.workload, seed))) as f:
            text = f.read()
        t = time.perf_counter()
        X, y = ctx["generator"].generate(seed, dtype=np.float32,
                                         **ctx["config"]["data"])
        print("READINGS " + json.dumps({
            "seed": seed, "table": ctx["config"]["data"]["table"],
            **variants(ctx, X, y, text),
            "seconds": time.perf_counter() - t}), flush=True)


def main():
    if sys.argv[1:2] == ["run"]:
        return run_and_keep(sys.argv[2:])
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("fault", "models"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tables", default="")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--dir", default=MODELS)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    (fault if args.mode == "fault" else from_models)(args)


if __name__ == "__main__":
    main()
