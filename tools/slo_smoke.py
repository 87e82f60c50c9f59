"""SLO burn-rate + request-tracing end-to-end smoke (tier1 CI).

Boots a 2-replica serving fleet (this script re-execed with
``--serve-replica``, same process pattern as fleet_smoke.py) with

- declarative SLOs armed: ``serve_slo_p99_ms`` (latency) and
  ``serve_slo_availability``, judged over deliberately short burn
  windows so CI sees a full fast-window cycle in seconds;
- request tracing on (``obs_trace``) with a per-replica event file; and
- an injected ``serve_delay`` fault that sleeps every dispatched
  predict past the latency threshold.

Then drives mixed traffic at both HTTP front-ends (some requests carry a
client-minted ``x-lgbm-trace`` header) and asserts the whole
observability story:

1. the latency SLO flips to *burning* on both replicas within ONE fast
   window of the first request — the multi-window clamp makes a
   sustained breach responsive even in a young process;
2. ``/slo`` agrees across replicas (same specs, same verdicts:
   ``serve_p99`` burning, ``serve_availability`` quiet) and the
   ``lgbm_slo_burning`` gauge rides the Prometheus exposition;
3. a kept slow trace's span tree names the stage that ate the latency:
   the batch's ``predict`` span holds the delay as SELF time (its
   ``device_*`` children stay fast), and the client-minted trace id
   survives the HTTP hop into the kept trace;
4. the span events landed in each replica's event file and
   ``tools/merge_events.py`` reconstructs parent/child trees from the
   merged streams;
5. tracing + SLO judging cost no correctness: zero recompiles after
   warmup, zero server-side errors, zero shed.

Exit 0 = every assertion holds. Summary JSON to ``--out`` + stdout.

A CPU tool: it starts child processes and pins each to the CPU backend
(``JAX_PLATFORMS=cpu``). A chip belongs to one process at a time, so this
launcher does not run on the chip and nothing it times is a device
number; the chip is reached with ``python chip_smoke.py`` through the
chip tool.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # repo root for lightgbm_tpu

DELAY_MS = 150.0          # injected per-dispatch sleep
P99_THRESHOLD_MS = 50.0   # latency SLO threshold (every request breaches)
FAST_WINDOW_S = 3.0
SLOW_WINDOW_S = 6.0
TICK_S = 0.25


def _get(base: str, path: str) -> bytes:
    with urllib.request.urlopen(base + path, timeout=10) as r:
        return r.read()


def _post(base: str, path: str, doc, headers=None) -> dict:
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        base + path, data=json.dumps(doc).encode(), headers=hdrs)
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _wait(pred, timeout_s=60.0, interval_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval_s)
    return pred()


def serve_replica(name: str, workdir: str) -> int:
    """One replica: build_app with SLOs + tracing + the delay fault,
    roll the initial snapshot, warm up, publish the base URL."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.fleet import FileKvClient
    from lightgbm_tpu.serving.server import build_app, make_server

    cfg = Config({
        "objective": "regression", "verbosity": -1,
        "checkpoint_dir": os.path.join(workdir, "ckpt"),
        "fleet_kv_dir": os.path.join(workdir, "kv"),
        "fleet_replica": name,
        "fleet_announce_period_s": 0.1,
        "serve_min_bucket": 16, "serve_max_batch": 128,
        # --- the fault under test: every dispatched predict sleeps
        "fault_inject": "serve_delay@request:*:%d" % int(DELAY_MS),
        # --- request tracing: the delay (>= slow_ms) keeps every trace
        "obs_trace": True,
        "obs_trace_slow_ms": 100.0,
        "obs_trace_sample": 0.05,
        "obs_event_file": os.path.join(workdir, "events.%s.jsonl" % name),
        # --- SLOs with CI-short windows
        "serve_slo_p99_ms": P99_THRESHOLD_MS,
        "serve_slo_target": 0.99,
        "serve_slo_availability": 0.999,
        "slo_fast_window_s": FAST_WINDOW_S,
        "slo_slow_window_s": SLOW_WINDOW_S,
        "slo_burn_warn": 2.0,
        "slo_tick_s": TICK_S,
    })
    app = build_app(cfg)
    if not _wait(lambda: app.watcher._last_id >= 0, timeout_s=60.0):
        print("replica %s: initial snapshot never rolled" % name,
              file=sys.stderr)
        return 1
    app.engine.warmup()            # marks the recompile floor
    server = make_server(app, port=0)
    base = "http://127.0.0.1:%d" % server.server_address[1]
    FileKvClient(cfg.fleet_kv_dir).key_value_set("http/" + name, base)
    signal.signal(signal.SIGTERM, lambda *_: threading.Thread(
        target=server.shutdown, daemon=True).start())
    try:
        server.serve_forever()
    finally:
        server.server_close()
        app.close()
    return 0


def _self_times(records):
    """``[(name, self_ms)]`` per span: duration minus direct children —
    the stage-attribution view of one trace's flat records."""
    child_sum = {}
    for r in records:
        p = r.get("parent")
        if p is not None:
            child_sum[p] = child_sum.get(p, 0.0) + float(r["dur_ms"])
    return [(r["name"],
             float(r["dur_ms"]) - child_sum.get(r["span_id"], 0.0))
            for r in records]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default="slo_smoke_out")
    ap.add_argument("--out", default="", help="write the summary JSON here")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--serve-replica", default="",
                    help=argparse.SUPPRESS)   # internal: replica mode
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)
    if args.serve_replica:
        return serve_replica(args.serve_replica, args.workdir)
    ckpt_dir = os.path.join(args.workdir, "ckpt")

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback, engine
    from lightgbm_tpu.fleet import FileKvClient

    failures = []

    def check(cond, msg):
        (failures.append(msg) if not cond else None)
        print("%s %s" % ("ok  " if cond else "FAIL", msg), flush=True)

    # ---- 1. train a small model the replicas will roll -----------------
    r = np.random.RandomState(0)
    n, f = 1500, 6
    X = r.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1]).astype(np.float32)
    engine.train({"objective": "regression", "num_leaves": 15,
                  "verbosity": -1}, lgb.Dataset(X, label=y),
                 num_boost_round=args.rounds,
                 callbacks=[callback.checkpoint(ckpt_dir, period=1)])

    # ---- 2. spawn the replicas -----------------------------------------
    kv = FileKvClient(os.path.join(args.workdir, "kv"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = {name: subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--serve-replica", name, "--workdir", args.workdir], env=env)
        for name in ("a", "b")}
    summary = {}
    stop_traffic = threading.Event()
    lock = threading.Lock()
    counts = {"sent": 0, "errors": 0, "overloaded": 0}
    minted = "c0ffee%010d"   # client trace ids (hex) by thread index

    def traffic(base, idx):
        rs = np.random.RandomState(100 + idx)
        first = True
        while not stop_traffic.is_set():
            rows = rs.randn(16, f)
            # the first request of each thread carries a client-minted
            # trace id; the rest let the server mint
            hdrs = {"x-lgbm-trace": minted % idx} if first else None
            first = False
            try:
                out = _post(base, "/predict",
                            {"model": "default", "data": rows.tolist()},
                            headers=hdrs)
                ok = len(out.get("predictions", [])) == 16
            except urllib.error.HTTPError as e:
                with lock:
                    counts["overloaded" if e.code == 503 else "errors"] += 1
                continue
            except Exception:
                with lock:
                    counts["errors"] += 1
                continue
            with lock:
                counts["sent"] += 1
                counts["errors"] += 0 if ok else 1

    threads = []
    try:
        check(_wait(lambda: all(kv.try_get("http/" + m) for m in procs),
                    timeout_s=180.0),
              "both replica processes came up warmed")
        replicas = sorted((m, kv.try_get("http/" + m)) for m in procs)

        def slo_doc(base):
            return json.loads(_get(base, "/slo"))

        for name, base in replicas:
            doc = slo_doc(base)
            check(sorted(doc.get("slos", {})) ==
                  ["serve_availability", "serve_p99"],
                  "replica %s declares both SLOs on /slo" % name)
            check(not doc["slos"]["serve_p99"]["burning"],
                  "replica %s: p99 SLO quiet before traffic" % name)

        # ---- 3. delayed traffic -> burn within one fast window ---------
        t_traffic = time.monotonic()
        threads = [threading.Thread(target=traffic, args=(b, i),
                                    daemon=True)
                   for i, (_, b) in enumerate(replicas)]
        for t in threads:
            t.start()

        flips = {}

        def burning(name, base):
            doc = slo_doc(base)["slos"]["serve_p99"]
            if doc["burning"] and name not in flips:
                flips[name] = time.monotonic() - t_traffic
            return doc["burning"]

        for name, base in replicas:
            ok = _wait(lambda: burning(name, base),
                       timeout_s=FAST_WINDOW_S + 5.0, interval_s=0.1)
            check(ok, "replica %s: p99 SLO flipped to burning" % name)
            if ok:
                check(flips[name] <= FAST_WINDOW_S,
                      "replica %s: flip in %.2fs <= one fast window "
                      "(%.0fs)" % (name, flips[name], FAST_WINDOW_S))

        # ---- 4. /slo agrees across replicas ----------------------------
        docs = {name: slo_doc(base) for name, base in replicas}
        for name in docs:
            p99 = docs[name]["slos"]["serve_p99"]
            avail = docs[name]["slos"]["serve_availability"]
            check(p99["burning"] and p99["fast_burn"] >= 2.0,
                  "replica %s: p99 burning (fast burn %.1fx)"
                  % (name, p99["fast_burn"]))
            check(not avail["burning"],
                  "replica %s: availability SLO stays quiet" % name)
        check(docs["a"]["slos"]["serve_p99"]["burning"] ==
              docs["b"]["slos"]["serve_p99"]["burning"],
              "/slo verdicts agree across replicas")
        for name, base in replicas:
            prom = _get(base, "/metrics/prometheus").decode()
            check('lgbm_slo_burning{slo="serve_p99"} 1' in prom,
                  "replica %s exports lgbm_slo_burning=1" % name)

        # a little steady-state so the verdicts rest on real volume (the
        # flip itself lands after a couple of 150ms requests)
        time.sleep(2.5)
        stop_traffic.set()
        for t in threads:
            t.join(timeout=10.0)

        # ---- 5. the kept slow trace names the guilty stage -------------
        slow_self_ms = {}
        for name, base in replicas:
            traces = json.loads(_get(base, "/traces"))["traces"]
            slow = [t for t in traces if t["reason"] == "slow"]
            check(len(slow) > 0,
                  "replica %s kept slow traces (%d)" % (name, len(slow)))
            if not slow:
                continue
            tr = slow[-1]
            names = {r["name"] for r in tr["records"]}
            check({"request", "queue_wait", "batch", "predict"} <= names,
                  "replica %s: slow trace has the full span tree (%s)"
                  % (name, sorted(names)))
            worst = max(_self_times(tr["records"]), key=lambda kv: kv[1])
            slow_self_ms[name] = {"stage": worst[0],
                                  "self_ms": round(worst[1], 1)}
            check(worst[0] == "predict" and worst[1] >= DELAY_MS * 0.8,
                  "replica %s: 'predict' ate the latency (%.0fms self "
                  "time)" % (name, worst[1]))
            check(any(t["trace"].startswith("c0ffee") for t in traces),
                  "replica %s kept a client-minted trace id" % name)

        # ---- 6. event files + merge reconstruct the trees --------------
        ev_files = [os.path.join(args.workdir, "events.%s.jsonl" % m)
                    for m in procs]
        check(all(os.path.exists(p) for p in ev_files),
              "both replicas wrote span event files")
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import merge_events
        merged = list(merge_events.merge([p for p in ev_files
                                          if os.path.exists(p)]))
        trees = merge_events.build_span_trees(merged)
        check(len(trees) > 0, "merged streams yield %d span tree(s)"
              % len(trees))
        rooted = [t for t in trees.values() if t["roots"]]
        check(len(rooted) > 0 and all(
            not t["orphans"] for t in rooted),
              "reconstructed trees are parent-linked (no orphans)")

        # ---- 7. tracing + SLOs cost nothing ----------------------------
        with lock:
            sent, errors = counts["sent"], counts["errors"]
            overloaded = counts["overloaded"]
        check(sent > 20, "drove %d live requests through the fleet" % sent)
        check(errors == 0, "zero client-observed errors (got %d)" % errors)
        check(overloaded == 0, "zero shed requests (got %d)" % overloaded)
        stats = {name: json.loads(_get(b, "/stats"))
                 for name, b in replicas}
        for name, _ in replicas:
            snap = stats[name]
            check(snap.get("recompiles_after_warmup", -1) == 0,
                  "replica %s: zero recompiles after warmup (got %s) "
                  "with tracing on" % (name,
                                       snap.get("recompiles_after_warmup")))
            check(snap.get("errors") == 0 and snap.get("shed") == 0,
                  "replica %s: no server-side errors or shed" % name)

        summary = {
            "requests": sent,
            "burn_flip_s": {k: round(v, 3) for k, v in flips.items()},
            "fast_window_s": FAST_WINDOW_S,
            "slow_trace_attribution": slow_self_ms,
            "span_trees_merged": len(trees),
            "p99_ms": {name: stats[name]["latency_ms"]["p99_ms"]
                       for name, _ in replicas},
        }
    finally:
        stop_traffic.set()
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()

    summary["failures"] = failures
    blob = json.dumps(summary, indent=2, sort_keys=True)
    print(blob)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(blob + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
