"""Chaos end-to-end smoke (tier1 CI): fault-injected failure drills.

Every resilience contract in docs/Resilience.md, exercised from the
OUTSIDE with real processes and the shipped fault-injection plans:

- **kill**: a trainer child is SIGKILLed mid-run by its own armed
  ``kill@iter:3`` fault; the :class:`ProcessSupervisor` restarts it
  (``LGBM_SUPERVISOR_ATTEMPT`` gates the fault to attempt 0), the rerun
  auto-resumes from the checkpoint directory, and the final model's
  trees are byte-identical to an uninterrupted golden run.
- **exhaust**: an in-process supervised run whose ``crash@iter:*`` fault
  never stops firing burns its restart budget; the terminal error names
  the last flight-recorder dump and that dump exists on disk (CI
  artifact).
- **kv**: a REAL 2-process ``jax.distributed`` cluster. Round 0 proves
  retry: rank 0 arms ``kv_error@round:0`` and the allgather still
  completes through the transient. Round 1 proves surfacing: rank 1
  abstains, rank 0's bounded wait fails with namespace / round / rank /
  peer / key / elapsed-ms context.
- **overload**: a serving queue with ``serve_max_queue_rows`` bounded
  admission under a request burst (an injected ``serve_delay`` makes the
  engine slow): queued rows never exceed the bound, excess requests shed
  fast with OverloadedError + retry-after, admitted requests all answer,
  and drain-stop completes cleanly.
- **hotroll**: a staged all-NaN model is REFUSED by canary validation
  (``lgbm_serving_rollbacks_total`` ticks) while the prior generation
  keeps serving finite predictions.

Exit code 0 = every assertion holds. Summary JSON goes to ``--out`` (and
stdout); models, checkpoints, and flight dumps land under ``--workdir``
for CI artifact upload.

A CPU tool: it starts child processes and pins each to the CPU backend
(``JAX_PLATFORMS=cpu``). A chip belongs to one process at a time, so this
launcher does not run on the chip and nothing it times is a device
number; the chip is reached with ``python chip_smoke.py`` through the
chip tool.
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

KILL_AT = 3          # kill@iter:KILL_AT in the child trainer
ROUNDS = 8           # total boosting rounds per training scenario
QUEUE_ROWS = 8       # serve_max_queue_rows for the overload burst
BURST = 12           # concurrent 2-row requests thrown at the queue


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _train_data():
    import numpy as np
    r = np.random.RandomState(11)
    X = r.randn(240, 5)
    y = (X[:, 0] + 2.0 * X[:, 1] + 0.2 * r.randn(240) > 0)
    return X, y.astype(np.float64)


def _trees_only(model_text: str) -> str:
    """Model text minus the parameters echo (which legitimately differs:
    checkpoint paths, the fault plan itself)."""
    return model_text.split("\nparameters:", 1)[0]


# --------------------------------------------------------------- workers
def _worker_train(args) -> int:
    """One training attempt: checkpoint every iteration, arm the fault
    plan on supervisor attempt 0 only, save the final model."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine
    from lightgbm_tpu.resilience.supervisor import ATTEMPT_ENV

    attempt = int(os.environ.get(ATTEMPT_ENV, "0"))
    X, y = _train_data()
    params = dict(objective="binary", num_leaves=5, min_data_in_leaf=5,
                  verbosity=-1, checkpoint_dir=args.ckpt,
                  checkpoint_period=1)
    if args.fault and attempt == 0:
        params["fault_inject"] = args.fault
    ds = lgb.Dataset(X, label=y, params=dict(params))
    bst = engine.train(dict(params), ds, num_boost_round=ROUNDS,
                       verbose_eval=False)
    bst.save_model(args.model_out)
    return 0


def _init_cluster(port: int) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.parallel import network
    network.init(machines="127.0.0.1:%d,127.0.0.1:0" % port,
                 num_machines=2, time_out=60)
    assert jax.process_count() == 2, jax.process_count()


def _worker_kv(rank: int, args) -> int:
    """Round 0: allgather through an injected transient error (retry).
    Round 1: rank 1 abstains so rank 0's bounded wait surfaces a
    context-rich timeout error."""
    _init_cluster(args.port)
    from lightgbm_tpu.log import LightGBMError
    from lightgbm_tpu.parallel.network import KvHostComm
    from lightgbm_tpu.resilience import faults

    res = {"rank": rank}
    if rank == 0:
        faults.install_plan("kv_error@round:0")
    comm = KvHostComm(namespace="lgbm_chaos_kv",
                      timeout_ms=4000 if rank == 0 else 60000,
                      retries=2, retry_backoff_s=0.05)
    out = comm.allgather({"rank": rank})
    res["round0_peers"] = sorted(o["rank"] for o in out)
    if rank == 0:
        plan = faults.active_plan()
        res["fault_fired"] = bool(plan and plan.faults[0].fires == 1)
        err = ""
        try:
            comm.allgather({"rank": rank})    # peer 1 never publishes
        except LightGBMError as e:
            err = str(e)
        res["round1_error"] = err
    with open(os.path.join(args.workdir, "kv.rank%d.json" % rank),
              "w") as fh:
        json.dump(res, fh, sort_keys=True)
    if rank == 0:
        with open(os.path.join(args.workdir, "kv_done"), "w") as fh:
            fh.write("ok\n")
    else:
        # keep the cluster healthy while rank 0 waits out its timeout;
        # abstaining from the allgather is the failure being injected
        deadline = time.time() + 120
        done = os.path.join(args.workdir, "kv_done")
        while time.time() < deadline and not os.path.exists(done):
            time.sleep(0.2)
    return 0


# -------------------------------------------------------- scenario: kill
def _scenario_kill(args, check) -> dict:
    from lightgbm_tpu.resilience.supervisor import ProcessSupervisor

    def spawn_args(ckpt, model_out, fault):
        return [sys.executable, os.path.abspath(__file__),
                "--worker", "train", "--workdir", args.workdir,
                "--ckpt", ckpt, "--model-out", model_out,
                "--fault", fault]

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    golden_model = os.path.join(args.workdir, "golden.txt")
    rc = subprocess.call(
        spawn_args(os.path.join(args.workdir, "ckpt_g"), golden_model, ""),
        env=env, cwd=REPO)
    check(rc == 0, "kill: golden trainer exited 0 (rc=%s)" % rc)

    victim_model = os.path.join(args.workdir, "victim.txt")
    sup = ProcessSupervisor(
        spawn_args(os.path.join(args.workdir, "ckpt_v"), victim_model,
                   "kill@iter:%d" % KILL_AT),
        max_restarts=2, backoff_s=0.2, backoff_max_s=1.0, env=env, cwd=REPO)
    rc = sup.run()
    check(rc == 0, "kill: supervised trainer converged (rc=%s)" % rc)
    check(sup.restarts >= 1 and sup.attempts[0] != 0,
          "kill: attempt 0 died by the armed fault (attempts=%s)"
          % sup.attempts)
    identical = False
    if os.path.exists(golden_model) and os.path.exists(victim_model):
        identical = (_trees_only(open(golden_model).read())
                     == _trees_only(open(victim_model).read()))
    check(identical, "kill: resumed model trees byte-identical to golden")
    return {"attempts": sup.attempts, "restarts": sup.restarts,
            "identical": identical}


# ----------------------------------------------------- scenario: exhaust
def _scenario_exhaust(args, check) -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine
    from lightgbm_tpu.log import LightGBMError
    from lightgbm_tpu.resilience import faults

    X, y = _train_data()
    params = dict(objective="binary", num_leaves=5, min_data_in_leaf=5,
                  verbosity=-1,
                  checkpoint_dir=os.path.join(args.workdir, "ckpt_x"),
                  checkpoint_period=1, fault_inject="crash@iter:*",
                  supervise=True, supervise_max_restarts=1,
                  supervise_backoff_s=0.05, supervise_backoff_max_s=0.1,
                  observability="basic",
                  obs_event_file=os.path.join(args.workdir,
                                              "train_events.jsonl"))
    ds = lgb.Dataset(X, label=y, params=dict(params))
    msg, dump = "", ""
    try:
        engine.train(dict(params), ds, num_boost_round=4,
                     verbose_eval=False)
    except LightGBMError as e:
        msg = str(e)
    finally:
        faults.clear_plan()
    check("after 1 restart" in msg,
          "exhaust: budget exhaustion surfaced (got %r)" % msg[:120])
    check("last flight dump:" in msg,
          "exhaust: terminal error names the flight dump")
    if "last flight dump:" in msg:
        dump = msg.rsplit("last flight dump:", 1)[1].strip().rstrip(")")
        check(os.path.exists(dump),
              "exhaust: flight dump exists at %s" % dump)
    return {"error": msg[:300], "flight_dump": dump}


# ---------------------------------------------------------- scenario: kv
def _scenario_kv(args, check) -> dict:
    port = _free_port()
    procs = []
    for rank in range(2):
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
               "LIGHTGBM_TPU_RANK": str(rank), "PYTHONPATH": REPO}
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", "kv", "--rank", str(rank),
             "--port", str(port), "--workdir", args.workdir],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    for rank, p in enumerate(procs):
        try:
            so, se = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            so, se = p.communicate()
        check(p.returncode == 0,
              "kv: rank %d exited 0 (rc=%s)" % (rank, p.returncode))
        if p.returncode != 0:
            print("--- kv rank %d stderr ---\n%s" % (rank, se[-3000:]))
    results = {}
    for rank in range(2):
        path = os.path.join(args.workdir, "kv.rank%d.json" % rank)
        if os.path.exists(path):
            with open(path) as fh:
                results[rank] = json.load(fh)
    check(all(r.get("round0_peers") == [0, 1] for r in results.values())
          and len(results) == 2,
          "kv: round-0 allgather completed on both ranks")
    r0 = results.get(0, {})
    check(r0.get("fault_fired") is True,
          "kv: the injected transient error fired (and was retried)")
    err = r0.get("round1_error", "")
    for needle in ("lgbm_chaos_kv", "rank=0", "peer=1", "key=",
                   "elapsed=", "attempts="):
        check(needle in err,
              "kv: timeout error carries %r (got %r)" % (needle, err[:160]))
    return {"round1_error": err[:300]}


# ---------------------------------------------------- scenario: overload
def _scenario_overload(args, check) -> dict:
    import numpy as np
    from lightgbm_tpu.log import OverloadedError
    from lightgbm_tpu.resilience import faults
    from lightgbm_tpu.serving import MicroBatchQueue, ServingEngine
    from lightgbm_tpu.serving.registry import ModelBundle

    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine as train_engine

    X, y = _train_data()
    params = dict(objective="binary", num_leaves=5, min_data_in_leaf=5,
                  verbosity=-1)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    bst = train_engine.train(dict(params), ds, num_boost_round=3,
                             verbose_eval=False)
    eng = ServingEngine(max_batch=16, min_bucket=16)
    eng.registry.register(ModelBundle.from_booster("m", bst))
    eng.warmup()

    # a slow engine is what makes the queue fill: 60 ms per dispatch
    faults.install_plan("serve_delay@req:*:60")
    q = MicroBatchQueue(eng, max_rows=2, deadline_ms=5.0,
                        max_queue_rows=QUEUE_ROWS).start()
    outcomes, rows_seen = [], []
    lock = threading.Lock()

    def one(i):
        try:
            fut = q.submit("m", np.zeros((2, 5), np.float32))
            with lock:
                rows_seen.append(eng.metrics.queue_rows)
            outcomes.append(("ok", fut.result(timeout=30)))
        except OverloadedError as e:
            outcomes.append(("shed", e))
        time.sleep(0.001 * i)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(BURST)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    q.stop()                       # graceful drain
    faults.clear_plan()

    served = [o for o in outcomes if o[0] == "ok"]
    sheds = [o for o in outcomes if o[0] == "shed"]
    check(len(served) + len(sheds) == BURST,
          "overload: every request resolved (%d ok + %d shed)"
          % (len(served), len(sheds)))
    check(len(sheds) >= 1, "overload: bounded admission shed load")
    check(all(o[1].shape == (2,) for o in served),
          "overload: admitted requests all answered")
    check(all(getattr(o[1], "retry_after_s", 0) > 0 for o in sheds),
          "overload: shed errors carry a retry-after hint")
    check(max(rows_seen or [0]) <= QUEUE_ROWS,
          "overload: queued rows stayed <= serve_max_queue_rows=%d "
          "(max seen %d)" % (QUEUE_ROWS, max(rows_seen or [0])))
    check(eng.metrics.shed == len(sheds),
          "overload: lgbm_serving_shed_total == observed sheds")
    return {"served": len(served), "shed": len(sheds),
            "max_queue_rows_seen": max(rows_seen or [0])}


# ----------------------------------------------------- scenario: hotroll
def _scenario_hotroll(args, check) -> dict:
    import re
    import numpy as np
    from lightgbm_tpu.log import LightGBMError
    from lightgbm_tpu.serving import ServingEngine

    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine as train_engine

    X, y = _train_data()
    params = dict(objective="binary", num_leaves=5, min_data_in_leaf=5,
                  verbosity=-1)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    bst = train_engine.train(dict(params), ds, num_boost_round=3,
                             verbose_eval=False)
    good = os.path.join(args.workdir, "roll_good.txt")
    bad = os.path.join(args.workdir, "roll_bad.txt")
    bst.save_model(good)
    text = open(good).read()
    poisoned = re.sub(
        r"leaf_value=([^\n]+)",
        lambda m: "leaf_value=" + " ".join(
            ["nan"] * len(m.group(1).split())), text)
    open(bad, "w").write(poisoned)

    eng = ServingEngine(max_batch=16, min_bucket=16)
    eng.registry.register(eng.stage_and_prewarm("m", good), replace=True)
    ref = eng.predict("m", X[:4])
    refused = ""
    try:
        eng.stage_and_prewarm("m", bad)
    except LightGBMError as e:
        refused = str(e)
    check("canary" in refused,
          "hotroll: NaN model refused by canary validation (got %r)"
          % refused[:120])
    check(eng.metrics.rollbacks == 1,
          "hotroll: lgbm_serving_rollbacks_total ticked")
    out = eng.predict("m", X[:4])
    check(np.isfinite(out).all() and np.array_equal(out, ref),
          "hotroll: prior generation still serves identical finite output")
    return {"refused": refused[:200], "rollbacks": eng.metrics.rollbacks}


# -------------------------------------------------------------- launcher
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default="chaos_out")
    ap.add_argument("--out", default="", help="summary JSON path")
    ap.add_argument("--worker", default="",
                    help="(internal) run as a worker: train | kv")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--model-out", dest="model_out", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)

    if args.worker == "train":
        return _worker_train(args)
    if args.worker == "kv":
        return _worker_kv(args.rank, args)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    failures = []

    def check(cond, msg):
        (failures.append(msg) if not cond else None)
        print("%s %s" % ("ok  " if cond else "FAIL", msg))

    summary = {"failures": failures}
    scenarios = [("kill", _scenario_kill), ("exhaust", _scenario_exhaust),
                 ("kv", _scenario_kv), ("overload", _scenario_overload),
                 ("hotroll", _scenario_hotroll)]
    for name, fn in scenarios:
        print("=== scenario: %s ===" % name)
        try:
            summary[name] = fn(args, check)
        except Exception as e:  # noqa: BLE001 - verdict, not traceback
            check(False, "%s: scenario crashed: %s: %s"
                  % (name, type(e).__name__, e))

    blob = json.dumps(summary, indent=2, sort_keys=True)
    print(blob)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(blob + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
