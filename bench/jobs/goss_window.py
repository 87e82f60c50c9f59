"""The GOSS training job: what bench/jobs/clicklog_window.py does (set-up,
a timed window of boosting blocks, the check; train_window.py's docstring
says how each is timed), on ``boosting=goss``, with the plain reference
that knows a bag (bench/reference_goss.py). What differs:

- Before anything else it asks the program whether its GOSS makes a bag
  the booster can hand over (``last_bag``: the rows a sampled tree was
  grown on, coded out / top / other, kept on the device). A program whose
  sampler is a multiplier on the gradients has no bag to judge and counts
  a tree's rows in float32, which holds no odd count past 2**24: the job
  ends there, non-zero, within seconds, and makes no number. That is for
  the checkout of an older program with this benchmark laid over it.
- Set-up holds the unsampled iterations: ``engine.train`` for the first
  block, which loads or compiles the plain block, then
  ``GBDT.train_many(block_iters)`` until ``int(1 / learning_rate)``
  iterations are done (upstream samples from there on), then
  ``GBDT.compile_block(block_iters)``, which loads or compiles the sampled
  iterations' program and runs nothing. They are what every GOSS job pays
  before its first sampled tree, so they are inside ``setup_s``; the
  program's span ``train.goss_warmup`` times them, and the job hands its
  seconds on as ``goss_warmup_s`` (a metric on the ``program_spans`` reader
  has to read on every booster: bench/tests/test_program_spans.py).
- The window is sampled iterations only. After each of its first
  ``follow_trees`` blocks the job keeps a reference to ``last_bag`` (a
  device array: nothing is copied to the host inside the window).
- A traced run reads the capture twice before dropping it: the reduction
  every cell makes (bench/trace_reduce.py), and the program's own join of
  scope to op (``lightgbm_tpu.obs.trace.capture_phases``), whose table by
  ``lgbm.*`` scope it hands on as ``phases`` (bench/readers/trace_phases.py).
- The check follows the unsampled trees and judges the first sampled
  ones: the sibling's eight numbers with in-bag counts and weighted sums,
  and three of the bag itself.
"""
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import reference_goss, trace_reduce
from bench.jobs.clicklog_window import search_of, stored_columns
from bench.jobs.train_window import hold, host_rss, sample_rows
from bench.readers import program_spans


def goss_hands_a_bag(lgb, params):
    """Whether the booster of these parameters grows a sampled tree on a
    bag it keeps for the asking: asked of a 64-row table, nothing is
    compiled."""
    x = np.arange(1.0, 129.0).reshape(64, 2)
    probe = lgb.Booster(params=dict(params),
                        train_set=lgb.Dataset(x, x[:, 0] % 2,
                                              params=dict(params)))
    return bool(getattr(probe._impl, "_goss_bag", False)) \
        and hasattr(probe._impl, "last_bag") \
        and hasattr(probe._impl, "compile_block")


def warmup_iterations(params):
    """goss.hpp Bagging: no sampling before iteration 1 / learning_rate."""
    return int(1.0 / params["learning_rate"])


def rows_of(codes, n):
    """A bag's codes over the ``n`` rows the reference holds: a row the
    booster never held is out of every bag."""
    out = np.zeros(n, np.uint8)
    out[:min(n, len(codes))] = codes[:n]
    return out


def read(ctx, X, y, trees, scores, bags):
    """The numbers compared, read from the timed booster's trees, scores
    and bags by the plain reference. ``bags``: {tree index: codes}."""
    ref = reference_goss
    check = ctx["workload"]["check"]
    params = ctx["config"]["params"]
    judged = [i for i in sorted(bags) if i < len(trees)][:check["follow_trees"]]
    got = {}
    if judged:
        followed = ref.follow(
            X, y, trees[:judged[-1] + 1],
            {i: rows_of(bags[i], len(y)) for i in judged},
            (params["top_rate"], params["other_rate"]),
            params["learning_rate"], params.get("lambda_l2", 0.0),
            ref.draw_nodes(ctx["seed"], trees, judged, check["regret_nodes"]),
            search_of(ctx))
        got = ref.readings(trees, followed)
    else:
        got = {name: float("inf") for name in check["limits"]}
    got["score_gap"] = ref.score_gap(
        X, trees, scores, sample_rows(ctx["seed"], len(y), check["sample_rows"]))
    return got


def judge(ctx, X, y, model_text, scores, bags):
    trees = reference_goss.parse_trees(model_text)
    return hold(read(ctx, X, y, trees, scores, bags),
                ctx["workload"]["check"]["limits"])


def traced_hist_rows(block_iters):
    """Rows whose bins entered a histogram kernel call, an iteration of
    the window's first block: the count the grower added on that block's
    ``train.block`` span. None where the program keeps no such count."""
    from lightgbm_tpu.obs import trace
    sampled = [s["counts"] for s in trace.recorded_spans()
               if s["name"] == "train.block"
               and s["counts"].get("goss_active") == 1]
    if not sampled or "hist_rows" not in sampled[0]:
        return None
    return sampled[0]["hist_rows"] / block_iters


def run(ctx):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.trace import capture_phases
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        enable_compile_cache)

    cfg, wl = ctx["config"], ctx["workload"]
    block_iters = int(wl["block_iters"])
    params = dict(cfg["params"])
    follow_trees = int(wl["check"]["follow_trees"])
    clocks = {}
    enable_compile_cache()
    if not goss_hands_a_bag(lgb, params):
        sys.exit("goss_window: this program's GOSS is a multiplier on the "
                 "gradients (boosting/gbdt.py run_iter): it has no bag to "
                 "hand to the check, every row still costs its histogram "
                 "passes, and a tree's counts are float32 sums, which hold "
                 "no odd count past 2**24. No run, no number")
    c_start = compile_cache_stats()

    t = time.perf_counter()
    X, y = ctx["generator"].generate(ctx["seed"], **cfg["data"])
    clocks["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    clocks["binning_s"] = time.perf_counter() - t
    if stored_columns() != cfg["stored_columns"]:
        sys.exit("goss_window: ingest.bundle left %r stored columns, the "
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
    t = time.perf_counter()
    while gbdt.iter_ < warmup_iterations(params):
        gbdt.train_many(block_iters)
        jax.block_until_ready(gbdt.scores)
    clocks["unsampled_rest_s"] = time.perf_counter() - t
    t = time.perf_counter()
    gbdt.compile_block(block_iters)
    clocks["sampled_block_ready_s"] = time.perf_counter() - t
    c_setup = compile_cache_stats()
    clocks["compile_s"] = (c_setup["backend_compile_seconds"]
                           - c_start["backend_compile_seconds"])
    clocks["setup_cache_misses"] = (c_setup["persistent_cache_misses"]
                                    - c_start["persistent_cache_misses"])
    # the program's own span over the unsampled iterations, closed when it
    # switched programs just now; a program without it leaves the metric out
    warmup_s = program_spans.read({"span": "train.goss_warmup",
                                   "which": "all", "what": "sum_s"}, {})
    if warmup_s is not None:
        clocks["goss_warmup_s"] = warmup_s
    if not ctx["rehearsal"] and "hist_impl" in wl:
        got = gbdt.grow_params.hist_impl
        if got != wl["hist_impl"]:
            sys.exit("goss_window: tpu_hist_impl resolved to %r, the cell "
                     "states %r" % (got, wl["hist_impl"]))
    print("setup: %s" % {k: round(v, 3) for k, v in clocks.items()},
          "cache hits %d misses %d" % (
              c_setup["persistent_cache_hits"] - c_start["persistent_cache_hits"],
              clocks["setup_cache_misses"]), flush=True)

    # ------------------------------------------------------------ window
    trace_dir, traced_block = None, None
    attempted = failed = blocks = 0
    dispatch_s = 0.0
    kept_bags = []
    first_sampled = gbdt.iter_
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
            if len(kept_bags) < follow_trees:
                kept_bags.append(gbdt.last_bag)    # (iteration, on device)
        except Exception as e:   # a failed block is counted, not hidden
            print("block %d raised %r" % (blocks, e), flush=True)
            failed += block_iters
            t1 = time.perf_counter()
        t2 = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            traced_block = {"wall_s": t2 - t0, "iters": block_iters,
                            "first_iter": first_sampled}
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
    bags = {int(it): np.asarray(code) for it, code in kept_bags}
    model_text = bst.model_to_string(num_iteration=-1)
    hist_rows = traced_hist_rows(block_iters)    # on its span once the
    if hist_rows is not None:                    # trees have been fetched
        clocks["hist_rows_per_iter"] = hist_rows
    print("window: %.3fs, %d iterations in %d blocks, %d failed, "
          "%d compiles in it; peak %.3f GB of %.3f GB" % (
              window_s, attempted, blocks, failed,
              clocks["compiles_in_window"], memory_peak / 1e9,
              stats.get("bytes_limit", 0) / 1e9), flush=True)
    del bst, gbdt, ds, kept_bags
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
    compared, ok = judge(ctx, X, y, model_text, scores, bags)
    print("check: %.1fs" % (time.perf_counter() - t), flush=True)
    host_rss("after the check")
    end_to_end = {"setup_s": setup_s}
    if done:
        end_to_end["train_s_per_iter"] = window_s / done
    return {"correct": bool(ok and failed == 0 and done > 0),
            "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "clocks": clocks, "trace": trace,
            "phases": phases, "bags": bags,
            "model_text": model_text, "config": cfg, "peaks": ctx["peaks"],
            "memory_peak_bytes": int(memory_peak), "compared": compared}
