"""Continuous-training fleet end-to-end smoke (tier1 CI).

Runs the whole docs/Fleet.md loop the way an operator's fleet would:
TWO replica serving PROCESSES plus a refit worker, coordinating only
through a shared checkpoint directory and file-KV namespace:

1. train a small model with a checkpoint + training data profile; spawn
   replica processes "a" and "b" (this script re-execed with
   ``--serve-replica``), each booting ``build_app`` with
   ``fleet_kv_dir`` + ``checkpoint_dir`` — the rolling-deploy
   coordinators hot-roll the initial snapshot in sorted order, warm
   every bucket, and announce readiness over the KV namespace;
2. drive continuous DRIFTED traffic at both HTTP front-ends and assert
   both replicas reach ``drift: warn``;
3. the refit worker re-estimates leaf values on the drifted window
   (``Refitter``, structure preserved) and publishes the result with
   ``CheckpointManager.save_refit`` + the window's data profile;
4. the fleet rolls the refit snapshot one replica at a time UNDER the
   live traffic; afterwards assert:
   - zero dropped/errored requests and zero request shed,
   - zero recompiles after warmup in both replica processes (the
     hot-roll prewarmed the refit generation off the request path),
   - served p99 stays under the budget,
   - drift recovers to ``ok`` on the refit window's profile,
   - the served trees are structure-identical to the originals with
     different leaf values,
   - ``/stats/cluster`` + ``/metrics/cluster`` report a converged
     2-replica fleet on the refit snapshot.

Exit code 0 = every assertion holds. The summary JSON goes to ``--out``
(and stdout) for the CI artifact.

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


def _get(base: str, path: str) -> bytes:
    with urllib.request.urlopen(base + path, timeout=10) as r:
        return r.read()


def _post(base: str, path: str, doc) -> dict:
    req = urllib.request.Request(
        base + path, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
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
    """One replica process: build_app over the shared checkpoint + KV
    dirs, roll the initial snapshot, warm up, publish the HTTP base URL
    under ``http/<name>``, then serve until SIGTERM."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.fleet import FileKvClient
    from lightgbm_tpu.serving.server import build_app, make_server

    cfg = Config({"objective": "regression", "verbosity": -1,
                  "checkpoint_dir": os.path.join(workdir, "ckpt"),
                  "fleet_kv_dir": os.path.join(workdir, "kv"),
                  "fleet_replica": name,
                  "fleet_announce_period_s": 0.1,
                  "serve_min_bucket": 16, "serve_max_batch": 128,
                  "obs_drift_warn_psi": 0.25, "obs_drift_min_rows": 128})
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default="fleet_smoke_out",
                    help="checkpoints + KV namespace land here")
    ap.add_argument("--out", default="", help="write the summary JSON here")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--p99-budget-ms", type=float, default=750.0)
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
    from lightgbm_tpu.checkpoint.manager import CheckpointManager
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.fleet import FileKvClient, Refitter, ReplicaAnnouncer
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.obs.drift import DataProfile

    failures = []

    def check(cond, msg):
        (failures.append(msg) if not cond else None)
        print("%s %s" % ("ok  " if cond else "FAIL", msg), flush=True)

    # ---- 1. train with a checkpoint + data profile ---------------------
    r = np.random.RandomState(0)
    n, f = 2000, 6
    X = r.randn(n, f).astype(np.float32)

    def label_of(rows):
        return (rows[:, 0] + 0.5 * rows[:, 1]).astype(np.float32)

    y = label_of(X) + 0.2 * r.randn(n).astype(np.float32)
    params = {"objective": "regression", "num_leaves": 15,
              "verbosity": -1, "obs_modelstats": True}
    bst = engine.train(params, lgb.Dataset(X, label=y),
                       num_boost_round=args.rounds,
                       callbacks=[callback.checkpoint(ckpt_dir, period=1)])
    base_id = CheckpointManager(ckpt_dir).latest_model()[0]

    # ---- 2. spawn the replica processes --------------------------------
    kv = FileKvClient(os.path.join(args.workdir, "kv"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = {name: subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--serve-replica", name, "--workdir", args.workdir], env=env)
        for name in ("a", "b")}
    summary = {}
    drift_scale, drift_shift = 2.0, 3.0
    stop_traffic = threading.Event()
    lock = threading.Lock()
    counts = {"sent": 0, "errors": 0, "overloaded": 0}

    def traffic(base, seed):
        rs = np.random.RandomState(seed)
        while not stop_traffic.is_set():
            rows = rs.randn(32, f) * drift_scale + drift_shift
            try:
                out = _post(base, "/predict",
                            {"model": "default", "data": rows.tolist()})
                ok = len(out.get("predictions", [])) == 32
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
        # replicas announce their HTTP base once rolled + warmed
        check(_wait(lambda: all(kv.try_get("http/" + m) for m in procs),
                    timeout_s=180.0),
              "both replica processes came up warmed")
        bases = {m: kv.try_get("http/" + m) for m in procs}
        replicas = sorted(bases.items())

        def announced(field="snap_id"):
            fleet = ReplicaAnnouncer.read_fleet(kv)
            return {m: fleet.get(m, {}).get(field) for m in procs}

        check(all(v == base_id for v in announced().values()),
              "both replicas hot-rolled the initial snapshot %d" % base_id)

        def drift_of(base):
            return json.loads(_get(base, "/healthz")).get("drift")

        # ---- 3. drifted live traffic -> both replicas warn -------------
        threads = [threading.Thread(target=traffic, args=(b, i), daemon=True)
                   for i, (_, b) in enumerate(replicas)]
        for t in threads:
            t.start()
        for name, base in replicas:
            check(_wait(lambda: drift_of(base) == "warn"),
                  "replica %s reached drift: warn on shifted traffic" % name)

        # ---- 4. refit worker: re-estimate leaves on the fresh window ---
        t0 = time.perf_counter()
        rw = np.random.RandomState(7)
        Xw = (rw.randn(n, f) * drift_scale + drift_shift).astype(np.float32)
        yw = label_of(Xw) + 0.2 * rw.randn(n).astype(np.float32)
        refitted = Refitter(bst).refit(Xw, yw, decay_rate=0.0)
        window = BinnedDataset.from_matrix(Xw, Config(dict(params)), label=yw)
        entry = CheckpointManager(ckpt_dir).save_refit(
            refitted, data_profile=DataProfile.from_binned_dataset(window))
        refit_s = time.perf_counter() - t0
        refit_id = int(entry["id"])
        check(refit_id > base_id, "refit snapshot %d published" % refit_id)

        # ---- 5. rolling deploy under live traffic ----------------------
        check(_wait(lambda: all(v == refit_id
                                for v in announced().values()),
                    timeout_s=120.0),
              "both replicas rolled the refit snapshot under traffic")
        for name, base in replicas:
            check(_wait(lambda: drift_of(base) == "ok", timeout_s=30.0),
                  "replica %s drift recovered on the refit profile" % name)
        time.sleep(0.5)              # a little steady-state post-roll
        stop_traffic.set()
        for t in threads:
            t.join(timeout=10.0)

        # ---- 6. fleet invariants ---------------------------------------
        with lock:
            sent, errors = counts["sent"], counts["errors"]
            overloaded = counts["overloaded"]
        check(sent > 50, "drove %d live requests through the fleet" % sent)
        check(errors == 0, "zero dropped/errored requests (got %d)" % errors)
        check(overloaded == 0, "zero shed requests (got %d)" % overloaded)
        stats = {name: json.loads(_get(b, "/stats")) for name, b in replicas}
        for name, _ in replicas:
            snap = stats[name]
            check(snap.get("recompiles_after_warmup", -1) == 0,
                  "replica %s: zero recompiles after warmup (got %s)"
                  % (name, snap.get("recompiles_after_warmup")))
            check(snap.get("errors") == 0 and snap.get("shed") == 0,
                  "replica %s: no server-side errors or shed" % name)
            p99 = snap.get("latency_ms", {}).get("p99_ms", 1e9)
            check(p99 < args.p99_budget_ms,
                  "replica %s: p99 %.1f ms under %.0f ms budget"
                  % (name, p99, args.p99_budget_ms))
            check(snap.get("replica", {}).get("snap_id") == refit_id,
                  "replica %s /stats announces the refit snapshot" % name)

        served = lgb.Booster(
            model_file=CheckpointManager(ckpt_dir).latest_model()[1])
        same_structure = all(
            np.array_equal(s.split_feature, t.split_feature) and
            np.array_equal(s.threshold, t.threshold)
            for s, t in zip(served._impl.models, bst._impl.models))
        changed_leaves = sum(
            not np.array_equal(s.leaf_value, t.leaf_value)
            for s, t in zip(served._impl.models, bst._impl.models))
        check(same_structure, "served trees are structure-identical")
        check(changed_leaves == len(bst._impl.models),
              "every served leaf table was re-estimated (%d/%d)"
              % (changed_leaves, len(bst._impl.models)))

        cluster = json.loads(_get(replicas[0][1], "/stats/cluster"))
        check(cluster["fleet"]["live"] == 2,
              "/stats/cluster sees 2 live replicas")
        check(cluster["fleet"]["snap_id_min"] == refit_id
              and cluster["fleet"]["snap_id_max"] == refit_id
              and not cluster["fleet"]["rolling"],
              "/stats/cluster shows a converged fleet on snapshot %d"
              % refit_id)
        prom = _get(replicas[1][1], "/metrics/cluster").decode()
        check('lgbm_fleet_replica_up{replica="a"} 1' in prom
              and 'lgbm_fleet_replica_up{replica="b"} 1' in prom,
              "/metrics/cluster exports per-replica up gauges")
        check("lgbm_fleet_live_replicas 2" in prom,
              "/metrics/cluster exports the live-replica count")

        summary = {"rounds": args.rounds, "requests": sent,
                   "refit_snapshot": refit_id, "refit_s": round(refit_s, 3),
                   "p99_ms": {name: stats[name]["latency_ms"]["p99_ms"]
                              for name, _ in replicas},
                   "cluster": cluster["fleet"]}
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
