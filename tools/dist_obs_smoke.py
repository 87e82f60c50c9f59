"""Distributed-observability end-to-end smoke (tier1 CI).

A REAL 2-process run: two OS processes, one CPU device each, glued by
``jax.distributed`` through ``parallel/network.py`` — then the whole
distributed telemetry surface (obs/distributed.py) is exercised from the
outside, in three phases:

- **federation**: both ranks train the same small model with
  ``observability=basic``; rank 1's feature sampling is artificially
  delayed so it becomes a genuine straggler.  Each rank then asserts its
  OWN ``/stats/cluster`` + ``/metrics/cluster`` routes (served from the
  once-per-block allgather cache): both processes present, the skew gauge
  fired on the slow rank, the straggler report routed through the
  HealthMonitor, and the merged Prometheus text carries both
  ``process="0"`` and ``process="1"`` series.
- **crash**: a second 2-process run idles mid-training; the launcher
  SIGTERMs both ranks and asserts each one died BY the signal yet left a
  complete ``events.<rank>.jsonl.<rank>.crash.jsonl`` flight-recorder
  dump (header reason ``sigterm``, ring entries attached).
- **merge**: ``tools/merge_events.py`` zips the per-rank streams + crash
  dumps into one ``timeline.jsonl`` artifact and the launcher asserts the
  merge is complete and time-ordered.

Exit code 0 = every assertion holds.  Summary JSON goes to ``--out`` (and
stdout); per-rank event streams, crash dumps and the merged timeline land
under ``--workdir`` for CI artifact upload.

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
import socket
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WARN_SKEW = 1.2          # fed phase: assert skew >= this (config'd too)
SAMPLE_DELAY_S = 0.25    # rank 1's per-iteration feature-sampling delay
BLOCK = 4                # iterations per train_many call
BLOCKS = 3               # allgather rounds (>= 2: gauges lag one block)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _scrape(port: int, path: str) -> bytes:
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
        return r.read()


# --------------------------------------------------------------- worker
def _init_cluster(port: int):
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.parallel import network
    # rank 0's entry doubles as the jax.distributed coordinator address
    network.init(machines="127.0.0.1:%d,127.0.0.1:0" % port,
                 num_machines=2, time_out=60)
    assert jax.process_count() == 2, jax.process_count()


def _build_booster(rank: int, workdir: str, extra=None):
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.boosting import create_boosting

    r = np.random.RandomState(0)
    X = r.randn(800, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "observability": "basic", "health_monitor": "warn",
              "obs_event_file":
                  os.path.join(workdir, "events.%d.jsonl" % rank),
              "obs_straggler_warn_skew": WARN_SKEW}
    params.update(extra or {})
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    return create_boosting(cfg, ds, create_objective(cfg), [])


def _delay_sampling(delay_s: float) -> None:
    """Make THIS rank a straggler: feature-mask sampling happens inside
    the per-block host window (gbdt.py opens t0 before it), so a sleep
    here lands squarely in busy_s."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    orig = GBDT._sample_feature_mask

    def slow(self):
        time.sleep(delay_s)
        return orig(self)

    GBDT._sample_feature_mask = slow


def _worker_federation(rank: int, args) -> int:
    _init_cluster(args.port)
    if rank == 1:
        _delay_sampling(SAMPLE_DELAY_S)
    b = _build_booster(rank, args.workdir, extra={"obs_stats_port": 0})
    for _ in range(BLOCKS):
        b.train_many(BLOCK)

    obs = b.obs
    doc = obs.dist.cluster_stats()
    prom = obs.dist.cluster_prometheus()
    straggler_reports = [r for r in (obs.monitor.reports if obs.monitor
                                     else []) if r.kind == "straggler_wave"]
    res = {"rank": rank,
           "processes": sorted((doc.get("processes") or {}).keys()),
           "skew": (doc.get("straggler") or {}).get("skew"),
           "straggler_process":
               (doc.get("straggler") or {}).get("process"),
           "prom_has_p0": 'process="0"' in prom,
           "prom_has_p1": 'process="1"' in prom,
           "straggler_reports": len(straggler_reports)}
    # the HTTP routes must serve the same cache set_cluster wired up
    if obs.stats is not None:
        hdoc = json.loads(_scrape(obs.stats.port, "/stats/cluster"))
        res["http_processes"] = sorted((hdoc.get("processes") or {}).keys())
        hprom = _scrape(obs.stats.port, "/metrics/cluster").decode()
        res["http_prom_both"] = ('process="0"' in hprom
                                 and 'process="1"' in hprom)
    with open(os.path.join(args.workdir, "fed.rank%d.json" % rank),
              "w") as fh:
        json.dump(res, fh, sort_keys=True)
    # barrier before exit so neither rank tears the coordinator down
    # while the other is still mid-allgather
    from lightgbm_tpu.parallel.network import KvHostComm
    KvHostComm(namespace="lgbm_smoke_done").allgather({"rank": rank})
    return 0


def _worker_crash(rank: int, args) -> int:
    _init_cluster(args.port)
    b = _build_booster(rank, args.workdir, extra={"obs_stats_port": -1})
    b.train_many(BLOCK)     # populate the event stream + flight ring
    assert b.obs.flight is not None and len(b.obs.flight) > 0
    with open(os.path.join(args.workdir,
                           "ready.%d" % rank), "w") as fh:
        fh.write("ok\n")
    while True:             # idle until the launcher SIGTERMs us
        time.sleep(0.05)


# -------------------------------------------------------------- launcher
def _spawn(phase: str, port: int, workdir: str):
    procs = []
    for rank in range(2):
        env = {**os.environ,
               "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "",            # one device per process
               "LIGHTGBM_TPU_RANK": str(rank),
               "PYTHONPATH": REPO}
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", str(rank), "--phase", phase,
             "--port", str(port), "--workdir", workdir],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    return procs


def _drain(procs, timeout: float):
    outs = []
    for p in procs:
        try:
            so, se = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            so, se = p.communicate()
        outs.append((p.returncode, so, se))
    return outs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default="dist_obs_out")
    ap.add_argument("--out", default="", help="summary JSON path")
    ap.add_argument("--worker", type=int, default=-1,
                    help="(internal) run as rank N instead of launching")
    ap.add_argument("--phase", default="fed", choices=["fed", "crash"])
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)

    if args.worker >= 0:
        if args.phase == "fed":
            return _worker_federation(args.worker, args)
        return _worker_crash(args.worker, args)

    failures = []

    def check(cond, msg):
        (failures.append(msg) if not cond else None)
        print("%s %s" % ("ok  " if cond else "FAIL", msg))

    # ---- phase 1: federation + straggler detection ---------------------
    fed_dir = os.path.join(args.workdir, "fed")
    os.makedirs(fed_dir, exist_ok=True)
    outs = _drain(_spawn("fed", _free_port(), fed_dir), timeout=420)
    for rank, (rc, so, se) in enumerate(outs):
        check(rc == 0, "fed rank %d exited 0 (rc=%s)" % (rank, rc))
        if rc != 0:
            print("--- rank %d stdout ---\n%s\n--- rank %d stderr ---\n%s"
                  % (rank, so[-1500:], rank, se[-3000:]))
    results = {}
    for rank in range(2):
        path = os.path.join(fed_dir, "fed.rank%d.json" % rank)
        if os.path.exists(path):
            with open(path) as fh:
                results[rank] = json.load(fh)
    check(len(results) == 2, "both fed ranks reported")
    for rank, res in sorted(results.items()):
        check(res.get("processes") == ["0", "1"],
              "rank %d cluster doc has both processes (got %s)"
              % (rank, res.get("processes")))
        check((res.get("skew") or 0) >= WARN_SKEW,
              "rank %d skew %.3fx >= %.2fx"
              % (rank, res.get("skew") or 0, WARN_SKEW))
        check(res.get("straggler_process") == 1,
              "rank %d identifies rank 1 as the straggler (got %s)"
              % (rank, res.get("straggler_process")))
        check(res.get("prom_has_p0") and res.get("prom_has_p1"),
              "rank %d merged exposition carries both process series"
              % rank)
        check(res.get("straggler_reports", 0) >= 1,
              "rank %d routed >=1 straggler report through HealthMonitor"
              % rank)
        check(res.get("http_processes") == ["0", "1"],
              "rank %d /stats/cluster serves the federated cache" % rank)
        check(res.get("http_prom_both") is True,
              "rank %d /metrics/cluster carries both process series"
              % rank)

    # ---- phase 2: SIGTERM -> flight recorder crash dumps ---------------
    crash_dir = os.path.join(args.workdir, "crash")
    os.makedirs(crash_dir, exist_ok=True)
    procs = _spawn("crash", _free_port(), crash_dir)
    deadline = time.time() + 420
    ready = [os.path.join(crash_dir, "ready.%d" % r) for r in range(2)]
    while time.time() < deadline:
        if all(os.path.exists(p) for p in ready):
            break
        if any(p.poll() is not None for p in procs):
            break               # a worker died early; fall through
        time.sleep(0.2)
    ready_ok = all(os.path.exists(p) for p in ready)
    check(ready_ok, "both crash ranks reached the idle point")
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    outs = _drain(procs, timeout=60)
    for rank, (rc, so, se) in enumerate(outs):
        check(rc in (-signal.SIGTERM, 128 + signal.SIGTERM),
              "crash rank %d died by SIGTERM (rc=%s)" % (rank, rc))
        if rc not in (-signal.SIGTERM, 128 + signal.SIGTERM):
            print("--- rank %d stderr ---\n%s" % (rank, se[-3000:]))
        dump = os.path.join(crash_dir,
                            "events.%d.jsonl.%d.crash.jsonl"
                            % (rank, rank))
        exists = os.path.exists(dump)
        check(exists, "crash rank %d flight dump exists" % rank)
        if exists:
            with open(dump) as fh:
                lines = [json.loads(ln) for ln in fh if ln.strip()]
            hdr = lines[0] if lines else {}
            check(hdr.get("event") == "flight_recorder_dump"
                  and hdr.get("reason") == "sigterm"
                  and hdr.get("process") == rank,
                  "crash rank %d dump header (got %s)" % (rank, hdr))
            check(hdr.get("entries", 0) > 0 and len(lines) == 1
                  + hdr.get("entries", 0),
                  "crash rank %d dump carries its ring (%d entries)"
                  % (rank, hdr.get("entries", 0)))

    # ---- phase 3: merged timeline --------------------------------------
    streams = sorted(
        os.path.join(crash_dir, f) for f in os.listdir(crash_dir)
        if f.endswith(".jsonl"))
    timeline = os.path.join(args.workdir, "timeline.jsonl")
    merged, in_lines = [], 0
    if streams:
        rc = subprocess.call(
            [sys.executable, os.path.join(REPO, "tools",
                                          "merge_events.py")]
            + streams + ["--out", timeline], cwd=REPO)
        check(rc == 0, "merge_events exits 0 over %d streams"
              % len(streams))
        for p in streams:
            with open(p) as fh:
                in_lines += sum(1 for ln in fh if ln.strip())
        if os.path.exists(timeline):
            with open(timeline) as fh:
                merged = [json.loads(ln) for ln in fh if ln.strip()]
        check(len(merged) == in_lines,
              "timeline complete (%d/%d records)"
              % (len(merged), in_lines))
        # crash dumps are internally non-monotonic by design (the header
        # is stamped at dump time, the ring records keep their original
        # ts) and the merge keeps in-stream order authoritative, so the
        # cross-stream ts assertion covers the live streams only
        ts = [float(r.get("ts", 0)) for r in merged
              if not r["stream"].endswith(".crash.jsonl")]
        check(ts == sorted(ts), "timeline live streams are time-ordered")
        check(all("stream" in r for r in merged),
              "every timeline record attributes its stream")
        procs_seen = {r.get("process") for r in merged
                      if "process" in r}
        check({0, 1} <= procs_seen,
              "timeline carries events from both processes (got %s)"
              % sorted(procs_seen))
    else:
        check(False, "crash phase produced event streams to merge")

    summary = {"failures": failures,
               "federation": results,
               "timeline_records": len(merged),
               "streams_merged": len(streams)}
    blob = json.dumps(summary, indent=2, sort_keys=True)
    print(blob)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(blob + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
