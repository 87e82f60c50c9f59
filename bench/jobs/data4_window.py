"""The data-parallel training job: what bench/jobs/clicklog_window.py does
(set-up, a timed window of one-iteration blocks, the check; train_window's
docstring says how each is timed), with the booster's rows sharded over the
cell's chips (``tree_learner=data``, ``mesh_shape`` the configuration's),
and these differences:

- Before anything else it asks the program whether its exact grower's
  counts over a mesh are the row partitions' integers (``mesh_exact_counts``
  on the ``train.setup`` span of a 64-row booster on the cell's mesh). A
  program that does not say so (the checkout of an older program with this
  benchmark laid over it) counts every leaf in float32, which cannot hold
  this cell's counts: the job ends there, non-zero, within seconds, and
  makes no number. So does a chip run that finds fewer devices than the
  cell's chips, or a ``tpu_hist_impl`` that does not resolve to ``pallas``.
  A rehearsal runs on the devices there are, up to the cell's chips.
- The check is bench/reference_data_parallel.py's: the click-log
  reference over every row of the four chips. The raw table is kept
  through the window and judged as it is, not made again (the
  configuration's ``assumed`` says why), and a traced run reads its
  capture while the check runs.
- A traced run hands on, beside the reduced trace, each chip's busy time
  (``trace["busy_s_per_chip"]``), the program's table by scope averaged
  over the chips (``phases``, ``lightgbm_tpu.obs.trace.reduce_phases``) and
  the same table for each chip (``phases_per_chip``), and the device kind
  (``device_kind``) the exchange's peak is looked up by.
"""
import gc
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import reference_data_parallel, trace_reduce
from bench.jobs.clicklog_window import search_of, span_counts, stored_columns
from bench.jobs.train_window import hold, host_rss, sample_rows


def mesh_counts_exact(lgb, params):
    """``mesh_exact_counts`` of a 64-row booster on the cell's mesh: 1
    where the program counts over the mesh in integers, None where it does
    not say. Nothing is compiled."""
    x = np.arange(1.0, 129.0).reshape(64, 2)
    lgb.Booster(params=dict(params), train_set=lgb.Dataset(
        x, x[:, 0] % 2, params=dict(params)))
    return span_counts("train.setup").get("mesh_exact_counts")


def judge(ctx, X, y, model_text, scores):
    check = ctx["workload"]["check"]
    params = ctx["config"]["params"]
    trees = reference_data_parallel.parse_trees(model_text)
    judged = trees[:check["follow_trees"]]
    got = reference_data_parallel.readings(
        X, y, judged, params["learning_rate"], params.get("lambda_l2", 0.0),
        reference_data_parallel.ref.draw_nodes(ctx["seed"], judged,
                                               check["regret_nodes"]),
        search_of(ctx))
    got["score_gap"] = reference_data_parallel.ref.score_gap(
        X, trees, scores, sample_rows(ctx["seed"], len(y), check["sample_rows"]))
    return hold(got, check["limits"])


def read_trace(trace_dir, traced_block):
    """The reduced trace, with each chip's busy seconds; the program's
    table by scope, over the chips and for each."""
    from lightgbm_tpu.obs.trace import load_capture, reduce_phases
    # the two readings of the capture side by side: four chips' events
    with ThreadPoolExecutor(2) as pool:
        events, capture = pool.map(lambda load: load(trace_dir),
                                   (trace_reduce.load_events, load_capture))
    trace = trace_reduce.reduce(events, traced_block)
    if trace is not None:
        trace["busy_s_per_chip"] = [
            trace_reduce.busy_and_gaps(trace_reduce.leaf_events(dev))[0] / 1e9
            for dev in events["devices"]]
    phases = reduce_phases(capture)
    per_chip = [reduce_phases({"devices": [dev], "host": capture["host"]})
                for dev in capture["devices"]]
    return trace, phases, per_chip


def run(ctx):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        enable_compile_cache)

    cfg, wl = ctx["config"], ctx["workload"]
    block_iters = int(wl["block_iters"])
    params = dict(cfg["params"])
    chips = int(params["mesh_shape"][0])
    if ctx["rehearsal"]:
        chips = min(chips, len(jax.devices()))
    elif len(jax.devices()) < chips:
        sys.exit("data4_window: the mesh needs %d chips, jax found %d"
                 % (chips, len(jax.devices())))
    params["mesh_shape"] = [chips]
    ctx = dict(ctx, config=dict(cfg, params=params))
    clocks = {}
    enable_compile_cache()
    if mesh_counts_exact(lgb, params) != 1:
        sys.exit("data4_window: this program does not count the leaves of a "
                 "mesh in integers (no mesh_exact_counts = 1 on train.setup): "
                 "a float32 count cannot hold this cell's. No run, no number")
    c_start = compile_cache_stats()

    t = time.perf_counter()
    X, y = ctx["generator"].generate(ctx["seed"], **cfg["data"])
    clocks["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    clocks["binning_s"] = time.perf_counter() - t
    if stored_columns() != cfg["stored_columns"]:
        sys.exit("data4_window: ingest.bundle left %r stored columns, the "
                 "configuration states %d" % (stored_columns(),
                                              cfg["stored_columns"]))
    ds.data = None
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
            sys.exit("data4_window: tpu_hist_impl resolved to %r, the cell "
                     "states %r" % (got, wl["hist_impl"]))
    print("setup: %s" % {k: round(v, 3) for k, v in clocks.items()},
          "cache hits %d misses %d" % (
              c_setup["persistent_cache_hits"] - c_start["persistent_cache_hits"],
              c_setup["persistent_cache_misses"]
              - c_start["persistent_cache_misses"]),
          "mesh %d chips" % chips, flush=True)

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
    scores = np.asarray(gbdt.scores)[:len(y), 0]
    if not np.isfinite(scores).all():
        failed, done = attempted, 0
    model_text = bst.model_to_string(num_iteration=-1)
    print("window: %.3fs, %d iterations in %d blocks, %d failed, "
          "%d compiles in it; peak %.3f GB of %.3f GB (the fullest chip)" % (
              window_s, attempted, blocks, failed,
              clocks["compiles_in_window"], memory_peak / 1e9,
              stats.get("bytes_limit", 0) / 1e9), flush=True)
    del bst, gbdt, ds
    gc.collect()

    host_rss("after the window")
    t = time.perf_counter()
    with ThreadPoolExecutor(1) as pool:     # the capture beside the check
        read = (pool.submit(read_trace, trace_dir, traced_block)
                if trace_dir is not None else None)
        compared, ok = judge(ctx, X, y, model_text, scores)
        print("check: %.1fs" % (time.perf_counter() - t), flush=True)
        trace, phases, per_chip = read.result() if read else (None,) * 3
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        print("check and trace read in %.1fs" % (time.perf_counter() - t),
              flush=True)
        if trace is not None:
            print("busy by chip: %s of %.4f s" % (
                [round(b, 4) for b in trace["busy_s_per_chip"]],
                trace["window_s"]), flush=True)
        for i, p in enumerate(per_chip or []):
            if p is not None:
                print("phases chip %d: %s" % (i, {
                    k: round(v, 4) for k, v in p["by_scope"].items()}),
                    "unscoped %.4f busy %.4f" % (p["unscoped_s"],
                                                 p["busy_s"]), flush=True)
    host_rss("after the check")
    end_to_end = {"setup_s": setup_s}
    if done:
        end_to_end["train_s_per_iter"] = window_s / done
    return {"correct": bool(ok and failed == 0 and done > 0),
            "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "clocks": clocks, "trace": trace,
            "phases": phases, "phases_per_chip": per_chip,
            "device_kind": jax.devices()[0].device_kind,
            "model_text": model_text, "config": cfg, "peaks": ctx["peaks"],
            "memory_peak_bytes": int(memory_peak), "compared": compared}
