"""The training job: set-up, a timed window of boosting blocks, the check.

Set-up (all of it inside ``setup_s``): data from the seed on the host,
``lgb.Dataset(...).construct()`` (binning), then
``lightgbm_tpu.engine.train(params, ds, num_boost_round=block_iters)``,
which compiles or loads the one train block and runs it once. The window
drives ``GBDT.train_many(block_iters)`` on the booster that call returned,
the call ``engine.train`` itself makes, with ``block_until_ready`` on the
scores after each block. A block starts while less than ``seconds`` have
passed; the window closes when the block then running ends. A traced run
wraps ONE block, the window's first, in ``jax.profiler`` (stopping the
profiler counts into that run's window, whose end-to-end numbers are not
reported) and reduces that trace.

The raw columns (14 GB on the host at the Criteo share's size) are dropped
once they are binned, as upstream's ``free_raw_data`` does. After the
window, outside every timing: the peak bytes are read, the model text and
the scores are taken to the host, the booster and the dataset are dropped,
the columns are made again from the seed, and the plain reference
(bench/reference_gbdt.py) judges what the timed booster grew.
"""
import gc
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import reference_gbdt, trace_reduce


def sample_rows(seed, n, k):
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC0FFEE]))
    return np.sort(rng.choice(n, size=min(k, n), replace=False)).astype(np.int64)


def host_rss(when):
    with open("/proc/self/statm") as f:
        now = int(f.read().split()[1]) * resource.getpagesize() / 1e9
    print("host RSS %s: %.2f GB now, %.2f GB peak" % (
        when, now, resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6),
        flush=True)


def search_of(ctx):
    """GridSearch's arguments, from the cell's check and the job's
    parameters."""
    check = ctx["workload"]["check"]
    return {"cells": check["grid_cells"], "search_rows": check["search_rows"],
            "min_data": ctx["config"]["params"].get("min_data_in_leaf", 20)}


def read(ctx, X, y, trees, scores):
    """The numbers compared, read from the timed booster's trees and
    scores by the plain reference."""
    ref = reference_gbdt
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


def hold(got, limits):
    """Each number beside its limit -> (compared, correct)."""
    compared = {}
    for name, limit in limits.items():
        v = got[name]
        compared[name] = {"value": v, "limit": limit,
                          "ok": bool(np.isfinite(v) and v <= limit)}
    return compared, all(c["ok"] for c in compared.values())


def judge(ctx, X, y, model_text, scores):
    trees = reference_gbdt.parse_trees(model_text)
    return hold(read(ctx, X, y, trees, scores),
                ctx["workload"]["check"]["limits"])


def run(ctx):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        enable_compile_cache)

    cfg, wl = ctx["config"], ctx["workload"]
    block_iters = int(wl["block_iters"])
    clocks = {}
    enable_compile_cache()
    c_start = compile_cache_stats()

    t = time.perf_counter()
    X, y = ctx["generator"].generate(ctx["seed"], **cfg["data"])
    clocks["data_s"] = time.perf_counter() - t
    params = dict(cfg["params"])
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    clocks["binning_s"] = time.perf_counter() - t
    # the host keeps 14 GB of raw columns at this size; the booster needs
    # only the binned matrix, and upstream's free_raw_data drops them here.
    # The check makes them again from the seed once the window has closed.
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
    if not ctx["rehearsal"] and "hist_impl" in wl:
        got = gbdt.grow_params.hist_impl
        if got != wl["hist_impl"]:
            sys.exit("train_window: tpu_hist_impl resolved to %r, the cell "
                     "states %r" % (got, wl["hist_impl"]))
    print("setup: %s" % {k: round(v, 3) for k, v in clocks.items()},
          "cache hits %d misses %d" % (
              c_setup["persistent_cache_hits"] - c_start["persistent_cache_hits"],
              c_setup["persistent_cache_misses"]
              - c_start["persistent_cache_misses"]), flush=True)

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
