"""Serving load test: sustained concurrent traffic across a live hot-roll,
gating the SLO story end to end — the tier1 proof behind docs/Serving.md.

serve_smoke.py proves the single-threaded contract (zero recompiles, exact
parity). This driver proves the production one: N client threads push
randomized batches through a MicroBatchQueue while a CheckpointWatcher
(attached to the engine, so every roll prewarms off the request path)
hot-rolls a NEWER model snapshot into the registry mid-traffic. Asserts:

- zero predictor-cache misses and zero XLA backend compiles after warmup,
  ACROSS the roll — the staged bundle's compiles are credited to the
  warmup floor by ServingEngine.stage_and_prewarm, so any uncredited
  compile on the request path fails the gate;
- the roll actually happened (registry generation bumped) and post-roll
  outputs match the NEW Booster's predictions to 1e-6 (refs for both
  model generations are computed BEFORE warmup, so the reference path's
  own compilations never pollute the post-warmup count);
- client-observed p99 latency (queue wait + device call) stays under
  ``--p99-ms`` over the whole run, roll included.

Prints ONE JSON line with the verdict, per-bucket device-latency
quantiles, and the metrics snapshot. Exit 0 on pass, 1 on any violation.

Usage:
  python tools/load_test.py [--threads 4] [--requests 200] [--p99-ms 250]
CPU-friendly: JAX_PLATFORMS=cpu python tools/load_test.py --requests 50
"""
import argparse
import json
import os
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))   # repo root for lightgbm_tpu


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", type=int, default=4,
                    help="concurrent client threads")
    ap.add_argument("--requests", type=int, default=200,
                    help="requests per thread per phase (2 phases: "
                    "before and after the hot-roll)")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--min-bucket", type=int, default=16)
    ap.add_argument("--p99-ms", type=float, default=250.0,
                    help="client-observed p99 latency bound (ms)")
    ap.add_argument("--deadline-ms", type=float, default=2.0,
                    help="micro-batch coalescing deadline")
    ap.add_argument("--roll-timeout", type=float, default=60.0,
                    help="seconds to wait for the watcher to roll")
    ap.add_argument("--parity-sample", type=int, default=16,
                    help="per-phase requests checked against the Booster")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="enable request tracing at this tail-sampling "
                    "rate; every client mints a trace id and propagates "
                    "it (x-lgbm-trace style) into the queue")
    ap.add_argument("--trace-slow-ms", type=float, default=250.0,
                    help="always keep traces at least this slow")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback
    from lightgbm_tpu.checkpoint.manager import CheckpointManager
    from lightgbm_tpu.serving import (MicroBatchQueue, ServingEngine,
                                      install_compile_hook)

    install_compile_hook()   # before any compilation we intend to count
    rng = np.random.RandomState(args.seed)
    serve_dir = tempfile.mkdtemp(prefix="lgbm_load_test_")

    # ---- two model generations, checkpointed where the watcher looks.
    # Generation A trains with a checkpoint callback (snapshots 1..10 land
    # in serve_dir); generation B resumes to 15 rounds WITHOUT the
    # callback — its snapshot is published mid-traffic below, which is
    # the hot-roll under test.
    nf = 10
    Xtr = rng.rand(4000, nf).astype(np.float32)
    ytr = ((Xtr[:, 0] + Xtr[:, 1] * Xtr[:, 2]) > 0.6).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1}
    ds = lgb.Dataset(Xtr, label=ytr)
    bst_a = lgb.train(params, ds, num_boost_round=10,
                      callbacks=[callback.checkpoint(serve_dir, period=1)])
    bst_b = lgb.train(params, ds, num_boost_round=15, resume_from=serve_dir)

    # ---- query pool + parity refs for BOTH generations, pre-warmup
    pool = [rng.rand(int(s), nf).astype(np.float32)
            for s in rng.randint(1, args.max_batch + 1, size=64)]
    refs_a = [bst_a.predict(X) for X in pool]
    refs_b = [bst_b.predict(X) for X in pool]

    # ---- engine + watcher; first poll rolls generation A in, warmup
    # compiles every bucket and marks the floor
    engine = ServingEngine(max_batch=args.max_batch,
                           min_bucket=args.min_bucket)
    watcher = engine.registry.watch_dir("m", serve_dir, poll_interval=0.1,
                                        engine=engine)
    watcher.poll()
    gen0 = engine.registry.generation("m")
    t0 = time.time()
    warmed = engine.warmup()
    t_warm = time.time() - t0
    watcher.start()
    tracer = None
    if args.trace_sample > 0:
        from lightgbm_tpu.obs.reqtrace import RequestTracer, new_trace_id
        tracer = RequestTracer(slow_ms=args.trace_slow_ms,
                               sample=args.trace_sample, seed=args.seed)
    queue = MicroBatchQueue(engine, deadline_ms=args.deadline_ms,
                            tracer=tracer).start()

    latencies: list = []
    failures: list = []
    lat_lock = threading.Lock()

    def fire_phase(refs, tag):
        """args.threads clients x args.requests randomized requests,
        a sample of them parity-checked against ``refs``."""
        def client(tid):
            r = np.random.RandomState(args.seed + 1000 + tid)
            lats = []
            for i in range(args.requests):
                qi = int(r.randint(len(pool)))
                t1 = time.perf_counter()
                # client-minted context, exactly what an HTTP caller
                # sends in x-lgbm-trace: the kept trace's root carries
                # the id WE chose, proving propagation end to end
                ctx = new_trace_id() if tracer is not None else None
                out = queue.predict("m", pool[qi], trace=ctx)
                lats.append((time.perf_counter() - t1) * 1000.0)
                if i < args.parity_sample // max(args.threads, 1) + 1:
                    err = float(np.max(np.abs(out - refs[qi])))
                    if not err <= 1e-6:
                        with lat_lock:
                            failures.append(
                                "%s parity: thread %d query %d maxdiff %.3g"
                                % (tag, tid, qi, err))
            with lat_lock:
                latencies.extend(lats)
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(args.threads)]
        t1 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.time() - t1

    # ---- phase 1: traffic against generation A
    t_phase1 = fire_phase(refs_a, "pre-roll")

    # ---- hot-roll: publish generation B's snapshot, wait for the watcher
    # (traffic keeps flowing in phase 2 the moment the roll lands)
    CheckpointManager(serve_dir).save(bst_b)
    t1 = time.time()
    while engine.registry.generation("m") == gen0 \
            and time.time() - t1 < args.roll_timeout:
        time.sleep(0.05)
    t_roll = time.time() - t1
    rolled = engine.registry.generation("m") > gen0
    if not rolled:
        failures.append("hot-roll did not land within %.0fs"
                        % args.roll_timeout)

    # ---- phase 2: traffic against generation B
    t_phase2 = fire_phase(refs_b if rolled else refs_a, "post-roll")

    queue.stop()
    watcher.stop()

    misses = engine.metrics.cache_misses_after_warmup()
    recompiles = engine.metrics.recompiles_after_warmup()
    if misses != 0:
        failures.append("%d predictor-cache misses after warmup (across "
                        "the hot-roll)" % misses)
    if recompiles != 0:
        failures.append("%d XLA backend compiles after warmup (prewarm "
                        "credit did not cover the roll)" % recompiles)

    lat = np.asarray(latencies, np.float64)
    p50 = float(np.percentile(lat, 50)) if lat.size else 0.0
    p99 = float(np.percentile(lat, 99)) if lat.size else 0.0
    if p99 > args.p99_ms:
        failures.append("client p99 %.1fms exceeds bound %.1fms"
                        % (p99, args.p99_ms))

    snap = engine.metrics.snapshot()
    import jax
    print(json.dumps({
        "ok": not failures,
        # every latency below is this device's, whatever it is
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "failures": failures,
        "threads": args.threads,
        "requests": int(lat.size),
        "rolled": rolled,
        "generation": engine.registry.generation("m"),
        "buckets_warmed": warmed,
        "cache_misses_after_warmup": misses,
        "recompiles_after_warmup": recompiles,
        "warmup_seconds": round(t_warm, 3),
        "roll_seconds": round(t_roll, 3),
        "phase_seconds": [round(t_phase1, 3), round(t_phase2, 3)],
        "client_latency_ms": {"p50": round(p50, 3), "p99": round(p99, 3),
                              "bound_p99": args.p99_ms},
        "device_latency_by_bucket": engine.metrics.bucket_latency(),
        "traces_kept": (len(tracer.recent_traces())
                        if tracer is not None else None),
        "metrics": snap,
    }))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
