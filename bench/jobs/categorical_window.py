"""The training job on a table with categorical columns: what
bench/jobs/clicklog_window.py does (set-up, a timed window of boosting
blocks, the check; train_window.py's docstring says how each is timed),
with the plain reference that knows a categorical node
(bench/reference_categorical.py). What differs:

- Before anything else it asks the program whether its exact grower tests
  a split's category set without a gather over the rows it routes (the
  count ``cat_route_gather_free`` on the ``train.setup`` span of a 64-row
  table with one categorical column; nothing is compiled). A program that
  looks the set's word up by ``cat_bitset[bin >> 5]`` pays a gather's 9 ns
  a row in the loop that is most of an iteration, and its host tree holds
  a node's set as wide as the table's largest id: the job ends there,
  non-zero, within seconds, before any data is made. That is for the
  checkout of an older program with this benchmark laid over it.
- The layout is part of the cell: ``stored_columns`` as in the sibling,
  and ``features_categorical`` on ``train.setup`` must be the number of
  columns the configuration's ``categorical_feature`` names.
- It hands on ``construct_bin_categorical_s`` (the program's span
  ``ingest.bin_categorical``: a metric on the ``program_spans`` reader has
  to read on every booster, bench/tests/test_program_spans.py) and
  ``cat_splits_per_iter`` (the count ``cat_splits`` on the window's first
  block's ``train.block`` span, the traced block of a traced run: a
  device value that joins its span when the host fetches the trees).
- A traced run reads the capture twice before dropping it, as
  goss_window does: bench/trace_reduce.py's reduction and the program's
  own join of scope to op (``capture_phases``), handed on as ``phases``
  (bench/readers/trace_phases.py reads ``lgbm.route_rows`` and
  ``lgbm.split_search_cat`` from it).
"""
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import reference_categorical, trace_reduce
from bench.jobs import clicklog_window
from bench.jobs.clicklog_window import span_counts, stored_columns
from bench.jobs.train_window import hold, host_rss, sample_rows
from bench.readers import program_spans


def categorical_columns(params):
    return [int(c) for c in str(params["categorical_feature"]).split(",")]


def routes_categories_without_a_gather(lgb, params):
    """Whether the booster of these parameters routes a categorical split
    without a gather over the rows: asked of a 64-row table whose second
    column is categorical, nothing is compiled."""
    x = np.arange(128.0).reshape(64, 2) % 5
    p = dict(params, categorical_feature="1")
    lgb.Booster(params=p, train_set=lgb.Dataset(x, x[:, 0] % 2, params=p))
    return span_counts("train.setup").get("cat_route_gather_free") == 1


def search_of(ctx, model_text):
    """GridSearch's arguments: the sibling's, and the categorical
    columns' kept ids (the model's feature_infos) and parameters."""
    params = ctx["config"]["params"]
    listed = reference_categorical.parse_kept_categories(model_text)
    cat = {"kept": {j: listed.get(j, np.zeros(0, np.int64))
                    for j in categorical_columns(params)}}
    for name in ("max_cat_threshold", "cat_l2", "cat_smooth",
                 "max_cat_to_onehot", "min_data_per_group"):
        cat[name] = params[name]
    return dict(clicklog_window.search_of(ctx), cat=cat)


def read(ctx, X, y, model_text, scores):
    """The numbers compared, read from the timed booster's trees and
    scores by the plain reference."""
    ref = reference_categorical
    check = ctx["workload"]["check"]
    params = ctx["config"]["params"]
    trees = ref.parse_trees(model_text)
    judged = trees[:check["follow_trees"]]
    followed = ref.follow(
        X, y, judged, params["learning_rate"], params.get("lambda_l2", 0.0),
        ref.draw_nodes(ctx["seed"], judged, check["regret_nodes"]),
        search_of(ctx, model_text))
    got = ref.readings(judged, followed)
    got["score_gap"] = ref.score_gap(
        X, trees, scores, sample_rows(ctx["seed"], len(y), check["sample_rows"]))
    return got


def judge(ctx, X, y, model_text, scores):
    return hold(read(ctx, X, y, model_text, scores),
                ctx["workload"]["check"]["limits"])


def block_spans():
    from lightgbm_tpu.obs import trace
    return [s for s in trace.recorded_spans() if s["name"] == "train.block"]


def run(ctx):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.trace import capture_phases
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        enable_compile_cache)

    cfg, wl = ctx["config"], ctx["workload"]
    block_iters = int(wl["block_iters"])
    params = dict(cfg["params"])
    clocks = {}
    enable_compile_cache()
    if not routes_categories_without_a_gather(lgb, params):
        sys.exit("categorical_window: this program looks a split's category "
                 "set up through a gather over the rows it routes "
                 "(core/grow.py _bin_go_left: cat_bitset[bin >> 5]) and "
                 "holds a node's raw-value set as wide as the table's "
                 "largest id: not the cell as it is meant. No run, no "
                 "number")
    c_start = compile_cache_stats()

    t = time.perf_counter()
    X, y = ctx["generator"].generate(ctx["seed"], **cfg["data"])
    clocks["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    clocks["binning_s"] = time.perf_counter() - t
    if stored_columns() != cfg["stored_columns"]:
        sys.exit("categorical_window: ingest.bundle left %r stored columns, the "
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
    wanted = len(categorical_columns(params))
    if span_counts("train.setup").get("features_categorical") != wanted:
        sys.exit("categorical_window: train.setup counts %r categorical "
                 "features, the configuration names %d" % (
                     span_counts("train.setup").get("features_categorical"),
                     wanted))
    # the program's own span over the categorical columns' id -> bin pass
    bin_cat_s = program_spans.read({"span": "ingest.bin_categorical",
                                    "which": "all", "what": "sum_s"}, {})
    if bin_cat_s is not None:
        clocks["construct_bin_categorical_s"] = bin_cat_s
    if not ctx["rehearsal"] and "hist_impl" in wl:
        got = gbdt.grow_params.hist_impl
        if got != wl["hist_impl"]:
            sys.exit("categorical_window: tpu_hist_impl resolved to %r, the "
                     "cell states %r" % (got, wl["hist_impl"]))
    print("setup: %s" % {k: round(v, 3) for k, v in clocks.items()},
          "cache hits %d misses %d" % (
              c_setup["persistent_cache_hits"] - c_start["persistent_cache_hits"],
              clocks["setup_cache_misses"]), flush=True)

    # ------------------------------------------------------------ window
    trace_dir, traced_block = None, None
    blocks_before = len(block_spans())
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
    # on its span once the trees have been fetched: the window's first
    # block's splits on a categorical column
    first = block_spans()[blocks_before:blocks_before + 1]
    if first and "cat_splits" in first[0]["counts"]:
        clocks["cat_splits_per_iter"] = (first[0]["counts"]["cat_splits"]
                                         / block_iters)
    print("window: %.3fs, %d iterations in %d blocks, %d failed, "
          "%d compiles in it; peak %.3f GB of %.3f GB" % (
              window_s, attempted, blocks, failed,
              clocks["compiles_in_window"], memory_peak / 1e9,
              stats.get("bytes_limit", 0) / 1e9), flush=True)
    del bst, gbdt, ds
    gc.collect()

    trace = phases = None
    if trace_dir is not None:
        t = time.perf_counter()
        trace = trace_reduce.reduce(trace_reduce.load_events(trace_dir),
                                    traced_block)
        phases = capture_phases(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print("trace read in %.1fs" % (time.perf_counter() - t), flush=True)
        if phases is not None:
            print("phases: %s" % {k: round(v, 4)
                                  for k, v in phases["by_scope"].items()},
                  "unscoped %.4f busy %.4f" % (phases["unscoped_s"],
                                               phases["busy_s"]), flush=True)

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
            "phases": phases, "model_text": model_text, "config": cfg, "peaks": ctx["peaks"],
            "memory_peak_bytes": int(memory_peak), "compared": compared}
