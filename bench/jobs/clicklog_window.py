"""The training job on data whose columns differ from table to table:
what bench/jobs/train_window.py does (set-up, a timed window of boosting
blocks, the check; its docstring says how each is timed), with the plain
reference that knows a missing value (bench/reference_clicklog.py), and
four things more:

- Before anything else it asks the program whether its compiled train
  block takes the per-feature metadata (zero bins, missing types, bin
  counts) as an argument. A program that closes over it compiles the
  block anew for every new table (179 s at this cell's size, PERF.md),
  which no run of this cell outlasts: the job ends there, non-zero,
  within seconds, and makes no number. That is for the checkout of an
  older program with this benchmark laid over it, which finds the cell
  by name and would otherwise be killed at a run's time limit; the
  question reads names the program does not publish, so where it cannot
  be asked the job goes on and the time limit and ``setup_cache_misses``
  say the rest.
- The stored layout is part of the cell: EFB and the pair packing must
  leave the configuration's ``stored_columns``, or the job ends non-zero.
- ``setup_cache_misses``: the persistent compile cache's misses over
  set-up. 0 on a run whose seed was never run before is what says that
  the block's cache key no longer moves with the data. And three counts
  the program's spans carry of the table (``construct_nan_values``,
  ``construct_zero_values``, ``features_with_missing``): 0 is a reading
  of theirs, so the job hands them on as it hands on ``compile_s``.
"""
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import reference_clicklog, trace_reduce
from bench.jobs.train_window import hold, host_rss, sample_rows


def block_takes_metadata(lgb, params):
    """Whether the one-iteration device program has the feature metadata
    among its arguments: asked of a 64-row table, nothing is compiled.
    None where the program no longer keeps its block's arguments under
    these names."""
    from lightgbm_tpu.core.split import FeatureMeta
    x = np.arange(1.0, 129.0).reshape(64, 2)
    probe = lgb.Booster(params=dict(params),
                        train_set=lgb.Dataset(x, x[:, 0] % 2,
                                              params=dict(params)))
    try:
        probe._impl._make_train_iter_fn()
        captured = probe._impl._iter_capture
    except AttributeError:
        return None
    return any(isinstance(a, FeatureMeta) for a in captured)


def span_counts(name):
    """The counts of the newest span of that name the program recorded
    (the timed table's, not the 64-row one's), {} when there is none."""
    from lightgbm_tpu.obs import trace
    found = [s["counts"] for s in trace.recorded_spans()
             if s["name"] == name]
    return found[-1] if found else {}


def stored_columns():
    """What ``ingest.bundle`` said it left."""
    return span_counts("ingest.bundle").get("bundles")


def search_of(ctx):
    """GridSearch's arguments, from the cell's check and the job's
    parameters."""
    check, params = ctx["workload"]["check"], ctx["config"]["params"]
    return {"cells": check["grid_cells"], "search_rows": check["search_rows"],
            "min_data": params.get("min_data_in_leaf", 20),
            "zero_as_missing": bool(params.get("zero_as_missing", False))}


def read(ctx, X, y, trees, scores):
    """The numbers compared, read from the timed booster's trees and
    scores by the plain reference."""
    ref = reference_clicklog
    check = ctx["workload"]["check"]
    params = ctx["config"]["params"]
    judged = trees[:check["follow_trees"]]
    followed = ref.follow(
        X, y, judged, params["learning_rate"], params.get("lambda_l2", 0.0),
        ref.draw_nodes(ctx["seed"], judged, check["regret_nodes"]),
        search_of(ctx))
    got = ref.readings(judged, followed)
    got["score_gap"] = ref.score_gap(
        X, trees, scores, sample_rows(ctx["seed"], len(y), check["sample_rows"]))
    return got


def judge(ctx, X, y, model_text, scores):
    trees = reference_clicklog.parse_trees(model_text)
    return hold(read(ctx, X, y, trees, scores),
                ctx["workload"]["check"]["limits"])


def run(ctx):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        enable_compile_cache)

    cfg, wl = ctx["config"], ctx["workload"]
    block_iters = int(wl["block_iters"])
    params = dict(cfg["params"])
    clocks = {}
    enable_compile_cache()
    if block_takes_metadata(lgb, params) is False:
        sys.exit("clicklog_window: this program closes its train block over "
                 "the data's zero bins, missing types and bin counts "
                 "(boosting/gbdt.py _make_train_iter_fn): every new table "
                 "compiles the block anew, longer than a run of this cell "
                 "may take. No run, no number")
    c_start = compile_cache_stats()

    t = time.perf_counter()
    X, y = ctx["generator"].generate(ctx["seed"], **cfg["data"])
    clocks["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    clocks["binning_s"] = time.perf_counter() - t
    if stored_columns() != cfg["stored_columns"]:
        sys.exit("clicklog_window: ingest.bundle left %r stored columns, the "
                 "configuration states %d" % (stored_columns(),
                                              cfg["stored_columns"]))
    # the raw columns go once they are binned, as in train_window; the
    # check makes them again from the seed once the window has closed
    ds.data = None
    del X
    host_rss("after binning")
    t = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=block_iters)
    gbdt = bst._impl
    jax.block_until_ready(gbdt.scores)
    clocks["first_block_s"] = time.perf_counter() - t
    c_setup = compile_cache_stats()
    clocks["compile_s"] = (c_setup["backend_compile_seconds"]
                           - c_start["backend_compile_seconds"])
    clocks["setup_cache_misses"] = (c_setup["persistent_cache_misses"]
                                    - c_start["persistent_cache_misses"])
    # what the program counted of this table: NaN and zero values among
    # the rows it sampled for its bins, columns with a missing direction.
    # A program without such a count leaves the metric out
    for metric, (span, count) in {
            "construct_nan_values": ("ingest.find_bins", "nan_values"),
            "construct_zero_values": ("ingest.find_bins", "zero_values"),
            "features_with_missing": ("train.setup",
                                      "features_with_missing")}.items():
        counts = span_counts(span)
        if count in counts:
            clocks[metric] = counts[count]
    if not ctx["rehearsal"] and "hist_impl" in wl:
        got = gbdt.grow_params.hist_impl
        if got != wl["hist_impl"]:
            sys.exit("clicklog_window: tpu_hist_impl resolved to %r, the "
                     "cell states %r" % (got, wl["hist_impl"]))
    print("setup: %s" % {k: round(v, 3) for k, v in clocks.items()},
          "cache hits %d misses %d" % (
              c_setup["persistent_cache_hits"] - c_start["persistent_cache_hits"],
              clocks["setup_cache_misses"]), flush=True)

    # ------------------------------------------------------------ window
    trace_dir, traced_block = None, None
    attempted = failed = blocks = 0
    dispatch_s = 0.0
    w0 = time.perf_counter()
    setup_s = time.time() - ctx["t_start"]
    while time.perf_counter() - w0 < ctx["seconds"]:
        tracing = ctx["trace"] and blocks == 0
        if tracing:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        attempted += block_iters
        try:
            with jax.profiler.TraceAnnotation("bench_dispatch"):
                gbdt.train_many(block_iters)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_block_until_ready"):
                jax.block_until_ready(gbdt.scores)
        except Exception as e:   # a failed block is counted, not hidden
            print("block %d raised %r" % (blocks, e), flush=True)
            failed += block_iters
            t1 = time.perf_counter()
        t2 = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            traced_block = {"wall_s": t2 - t0, "iters": block_iters,
                            "first_iter": block_iters}
        dispatch_s += t1 - t0
        blocks += 1
    window_s = time.perf_counter() - w0
    c_end = compile_cache_stats()
    # ------------------------------------------------------------ after
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.devices())
    clocks["compiles_in_window"] = (c_end["backend_compiles"]
                                    - c_setup["backend_compiles"])
    clocks["dispatch_host_ms"] = 1e3 * dispatch_s / max(attempted, 1)
    done = attempted - failed
    scores = np.asarray(gbdt.scores)[:, 0]
    if not np.isfinite(scores).all():
        failed, done = attempted, 0
    model_text = bst.model_to_string(num_iteration=-1)
    print("window: %.3fs, %d iterations in %d blocks, %d failed, "
          "%d compiles in it; peak %.3f GB of %.3f GB" % (
              window_s, attempted, blocks, failed,
              clocks["compiles_in_window"], memory_peak / 1e9,
              stats.get("bytes_limit", 0) / 1e9), flush=True)
    del bst, gbdt, ds
    gc.collect()

    trace = None
    if trace_dir is not None:
        t = time.perf_counter()
        trace = trace_reduce.reduce(trace_reduce.load_events(trace_dir),
                                    traced_block)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print("trace read in %.1fs" % (time.perf_counter() - t), flush=True)

    host_rss("after the window")
    t = time.perf_counter()
    X, _ = ctx["generator"].generate(ctx["seed"], dtype=np.float32,
                                     **cfg["data"])
    compared, ok = judge(ctx, X, y, model_text, scores)
    print("check: %.1fs" % (time.perf_counter() - t), flush=True)
    host_rss("after the check")
    end_to_end = {"setup_s": setup_s}
    if done:
        end_to_end["train_s_per_iter"] = window_s / done
    return {"correct": bool(ok and failed == 0 and done > 0),
            "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "clocks": clocks, "trace": trace,
            "model_text": model_text, "config": cfg, "peaks": ctx["peaks"],
            "memory_peak_bytes": int(memory_peak), "compared": compared}
