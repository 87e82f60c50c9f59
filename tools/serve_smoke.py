"""Serving smoke: warm every bucket, fire randomized traffic, assert ZERO
recompiles — the lightgbm_tpu.serving acceptance gate.

Boots a ServingEngine (plus, unless --no-http, the real HTTP server on an
OS-assigned port to prove the transport path), trains or loads a model,
warms every batch bucket, then fires N requests of uniform-random size in
[1, max_batch] and asserts:

- zero predictor-cache misses after warmup;
- zero XLA backend compilations after warmup, observed by the
  jax.monitoring compilation-count hook (serving/metrics.py) — this is
  the strict signal: it also catches retraces the cache key cannot see;
- every served output matches Booster.predict to 1e-6 (checked on a
  sample of requests; refs are computed BEFORE warmup so the reference
  path's own compilations do not pollute the post-warmup count).

Prints ONE JSON line with the verdict + the metrics snapshot. Exit 0 on
pass, 1 on any violated assertion.

Usage:
  python tools/serve_smoke.py [--requests 1000] [--max-batch 4096]
                              [--model path.txt] [--devices 1] [--no-http]
A CPU tool (JAX_PLATFORMS=cpu python tools/serve_smoke.py --requests 100):
its rows/s is a CPU-host figure, never a device number. The chip's serve
path is proven by chip_smoke.py's predict_and_serve phase.
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))   # repo root for lightgbm_tpu


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--max-batch", type=int, default=4096)
    ap.add_argument("--min-bucket", type=int, default=16)
    ap.add_argument("--model", default="", help="model-text file; default "
                    "trains a small binary model in-process")
    ap.add_argument("--devices", type=int, default=1,
                    help="serving devices (0 = all local)")
    ap.add_argument("--parity-sample", type=int, default=25,
                    help="requests checked against Booster.predict")
    ap.add_argument("--no-http", action="store_true",
                    help="skip the HTTP round-trip leg")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import (MicroBatchQueue, ServingEngine,
                                      ServingApp, bucket_sizes,
                                      install_compile_hook, make_server)

    install_compile_hook()   # before any compilation we intend to count
    rng = np.random.RandomState(args.seed)

    if args.model:
        bst = lgb.Booster(model_file=args.model)
    else:
        Xtr = rng.rand(4000, 10).astype(np.float32)
        ytr = ((Xtr[:, 0] + Xtr[:, 1] * Xtr[:, 2]) > 0.6).astype(np.float32)
        bst = lgb.train({"objective": "binary", "num_leaves": 31,
                         "verbosity": -1},
                        lgb.Dataset(Xtr, label=ytr), num_boost_round=20)
    nf = bst.num_feature()

    engine = ServingEngine(max_batch=args.max_batch,
                           min_bucket=args.min_bucket,
                           num_devices=args.devices)
    engine.registry.register(bst.as_serving_bundle("smoke"))

    # request sizes span the full ladder; refs BEFORE warmup (see module
    # docstring for why)
    sizes = rng.randint(1, engine.max_batch + 1,
                        size=args.requests).astype(int)
    parity_idx = set(
        rng.choice(args.requests, min(args.parity_sample, args.requests),
                   replace=False).tolist())
    parity_refs = {}
    parity_queries = {}
    for i in sorted(parity_idx):
        X = rng.rand(int(sizes[i]), nf).astype(np.float32)
        parity_queries[i] = X
        parity_refs[i] = bst.predict(X)

    t0 = time.time()
    warmed = engine.warmup()
    t_warm = time.time() - t0

    queue = MicroBatchQueue(engine, deadline_ms=1.0).start()
    app = ServingApp(engine, queue)
    server = httport = None
    if not args.no_http:
        server = make_server(app, "127.0.0.1", 0)
        httport = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()

    failures = []
    t0 = time.time()
    rows_total = 0
    for i, n in enumerate(sizes):
        n = int(n)
        if i in parity_idx:
            X = parity_queries[i]
        else:
            X = np.zeros((n, nf), np.float32)
            X[0] = rng.rand(nf)           # cheap per-request variety
        rows_total += n
        out = queue.predict("smoke", X)
        if i in parity_idx:
            err = float(np.max(np.abs(out - parity_refs[i])))
            if not err <= 1e-6:
                failures.append("parity: request %d (%d rows) maxdiff %.3g"
                                % (i, n, err))
    t_fire = time.time() - t0

    if server is not None:
        body = json.dumps({"data": parity_queries[min(parity_idx)].tolist(),
                           "model": "smoke"}).encode()
        rep = json.loads(urllib.request.urlopen(urllib.request.Request(
            "http://127.0.0.1:%d/predict" % httport, data=body)).read())
        err = float(np.max(np.abs(np.asarray(rep["predictions"])
                                  - parity_refs[min(parity_idx)])))
        if not err <= 1e-6:
            failures.append("http parity maxdiff %.3g" % err)
        server.shutdown()
        server.server_close()
    app.close()

    misses = engine.metrics.cache_misses_after_warmup()
    recompiles = engine.metrics.recompiles_after_warmup()
    if misses != 0:
        failures.append("%d predictor-cache misses after warmup" % misses)
    if recompiles != 0:
        failures.append("%d XLA backend compiles after warmup" % recompiles)

    snap = engine.metrics.snapshot()
    import jax
    print(json.dumps({
        "ok": not failures,
        # every rate below is this device's, whatever it is
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "failures": failures,
        "requests": args.requests,
        "rows": rows_total,
        "buckets_warmed": warmed,
        "bucket_ladder": bucket_sizes(engine.min_bucket, engine.max_batch),
        "cache_misses_after_warmup": misses,
        "recompiles_after_warmup": recompiles,
        "warmup_seconds": round(t_warm, 3),
        "fire_seconds": round(t_fire, 3),
        "predict_rows_per_sec": round(rows_total / max(t_fire, 1e-9), 1),
        "metrics": snap,
    }))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
