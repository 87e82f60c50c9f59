"""Distributed-training end-to-end smoke (tier1 CI).

A REAL 2-process training run: two OS processes, one CPU device each,
glued by ``jax.distributed`` through ``parallel/network.py`` (which
selects gloo so compiled collectives actually cross process boundaries).
The mesh spans both processes, so every per-wave collective in
``parallel/learners.py`` — the reduce-scatter + best-record election of
``tree_learner=data`` and the PV-Tree vote of ``tree_learner=voting`` —
runs over a genuine multi-controller topology, not the single-process
virtual-device mesh the unit tests use.

Asserted end to end:

- **model agreement**: after training, each rank digests its committed
  trees (structure + leaf values) AND its predictions; digests must be
  identical across ranks for BOTH learner schedules
  (``network.check_model_agreement`` raises on divergence).  Data-parallel
  training is replicated-by-construction, so any mismatch is a real bug.
- **weak scaling**: a 1-process baseline trains half the rows (constant
  rows/device); efficiency = t_base / t_dist is recorded for BENCH and
  sanity-gated only against pathology (collectives serializing the run).
- **straggler skew**: max/min per-rank steady-state seconds, recorded.

Exit code 0 = every assertion holds.  Summary JSON goes to ``--out`` (and
stdout); per-rank results land under ``--workdir`` for artifact upload.

A CPU tool: it starts child processes and pins each to the CPU backend
(``JAX_PLATFORMS=cpu``). A chip belongs to one process at a time, so this
launcher does not run on the chip and nothing it times is a device
number; the chip is reached with ``python chip_smoke.py`` through the
chip tool.
"""
import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOTAL_ROWS = 12000       # distributed run: 6000 rows/device on 2 devices
NUM_FEATURES = 12
WARMUP_ITERS = 1         # compile happens here; excluded from timing
TIMED_ITERS = 2          # enough for a scaling row without bloating CI
TOP_K = 3                # voting run: well under F, so the vote matters

# chunks x chips (stream phase): each process streams ONLY its row shard
# in fixed-size chunks — 2400 rows/shard at chunk_rows=1200 means no
# process ever holds more than half its shard on device, i.e. the global
# dataset exceeds any single process's chunk budget by construction
STREAM_ROWS = 4800       # 2400 rows/shard on 2 processes
STREAM_SRC_CHUNK = 640   # raw source granularity (!= device chunk_rows)
STREAM_CHUNK2 = 1200     # 2 device chunks per shard
STREAM_CHUNK4 = 600      # 4 device chunks per shard (same padded length)
STREAM_TOP_K = 4         # voting leg nomination width


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _make_data(rows: int):
    import numpy as np
    r = np.random.RandomState(7)
    X = r.randn(rows, NUM_FEATURES).astype(np.float32)
    logit = (1.4 * X[:, 0] - 1.1 * X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
             + 0.5 * X[:, 4])
    y = (logit + 0.25 * r.randn(rows) > 0).astype(np.float32)
    return X, y


def _train_timed(X, y, extra):
    """Train WARMUP+TIMED iters; returns (booster, steady-state seconds)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.boosting import create_boosting
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "tree_growth": "frontier"}
    params.update(extra)
    import jax
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    b = create_boosting(cfg, ds, create_objective(cfg), [])
    for _ in range(WARMUP_ITERS):
        b.train_one_iter()
    jax.block_until_ready(b.scores)     # don't time the warmup's tail
    t0 = time.monotonic()
    for _ in range(TIMED_ITERS):
        b.train_one_iter()
    jax.block_until_ready(b.scores)     # dispatch is async; time the work
    return b, time.monotonic() - t0


def _digest(booster, X) -> str:
    """Model digest: committed structure + leaf stats + predictions.
    Replicated training must make this bit-identical on every rank."""
    import numpy as np
    h = hashlib.sha256()
    for t in booster.models:
        nn = t.num_leaves - 1
        h.update(np.asarray(t.split_feature[:nn], np.int32).tobytes())
        h.update(np.asarray(t.threshold_bin[:nn], np.int32).tobytes())
        h.update(np.asarray(t.leaf_value[:t.num_leaves],
                            np.float64).tobytes())
        h.update(np.asarray(t.leaf_count[:t.num_leaves],
                            np.float64).tobytes())
    h.update(np.asarray(booster.predict(X[:512], raw_score=True),
                        np.float64).tobytes())
    return h.hexdigest()


# --------------------------------------------------------------- workers
def _worker_train(rank: int, args) -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.parallel import network
    # rank 0's entry doubles as the jax.distributed coordinator address;
    # network.init also flips the CPU backend to gloo collectives
    network.init(machines="127.0.0.1:%d,127.0.0.1:0" % args.port,
                 num_machines=2, time_out=60)
    assert jax.process_count() == 2, jax.process_count()

    X, y = _make_data(TOTAL_ROWS)
    res = {"rank": rank}
    for mode, extra in (
            ("data", {"tree_learner": "data", "num_machines": 2,
                      "mesh_shape": [2]}),
            ("voting", {"tree_learner": "voting", "num_machines": 2,
                        "mesh_shape": [2], "top_k": TOP_K})):
        b, secs = _train_timed(X, y, extra)
        d = _digest(b, X)
        # raises LightGBMError on divergence — the worker exits nonzero
        # and the launcher surfaces its stderr
        network.check_model_agreement(
            d, namespace="lgbm_train_smoke_%s" % mode)
        res["digest_%s" % mode] = d
        res["seconds_%s" % mode] = secs
        res["trees_%s" % mode] = len(b.models)
    with open(os.path.join(args.workdir, "train.rank%d.json" % rank),
              "w") as fh:
        json.dump(res, fh, sort_keys=True)
    # barrier before exit so neither rank tears the coordinator down
    # while the other is still mid-allgather
    from lightgbm_tpu.parallel.network import KvHostComm
    KvHostComm(namespace="lgbm_train_smoke_done").allgather({"rank": rank})
    return 0


def _worker_base(args) -> int:
    """1-process weak-scaling baseline: half the rows on one device —
    rows/device match the distributed run, so t_base/t_dist is the
    weak-scaling efficiency (1.0 = collectives cost nothing)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    X, y = _make_data(TOTAL_ROWS // 2)
    _, secs = _train_timed(X, y, {})
    with open(os.path.join(args.workdir, "base.json"), "w") as fh:
        json.dump({"seconds": secs, "rows": TOTAL_ROWS // 2}, fh)
    return 0


def _structure_digest(models) -> str:
    """Tree STRUCTURE only (splits + routing + row counts, no leaf
    values): the cross-topology identity contract — chunked == single-
    shot and sharded == serial hold structurally, while f32 leaf-value
    accumulation order may differ across chunk boundaries."""
    import numpy as np
    h = hashlib.sha256()
    for t in models:
        nn = t.num_leaves - 1
        h.update(np.asarray(t.split_feature[:nn], np.int32).tobytes())
        h.update(np.asarray(t.threshold_bin[:nn], np.int32).tobytes())
        h.update(np.asarray(t.left_child[:nn], np.int32).tobytes())
        h.update(np.asarray(t.right_child[:nn], np.int32).tobytes())
        h.update(np.asarray(t.leaf_count[:t.num_leaves],
                            np.float64).tobytes())
    return h.hexdigest()


def _stream_base() -> dict:
    return {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "tree_growth": "frontier", "deterministic": True,
            "min_data_in_leaf": 5,
            # exact-parity hook: sample == full data, so the allgathered
            # reservoir reproduces serial bin boundaries bit-for-bit
            "bin_construct_sample_cnt": 2 * STREAM_ROWS}


def _worker_stream(rank: int, args) -> int:
    """Rank body of the chunks-x-chips smoke: sharded ingest + streamed
    training over the 2-process mesh, for both learner schedules, at 2
    and 4 chunks per shard, plus kill-and-resume byte-identity."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.parallel import network
    network.init(machines="127.0.0.1:%d,127.0.0.1:0" % args.port,
                 num_machines=2, time_out=60)
    assert jax.process_count() == 2, jax.process_count()

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback, engine
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.profiling import (backend_compile_count,
                                        install_compile_hook)
    from lightgbm_tpu.stream.sampler import ingest
    from lightgbm_tpu.stream.source import ArraySource, ShardedSource

    install_compile_hook()
    X, y = _make_data(STREAM_ROWS)
    res = {"rank": rank}

    def sharded_ds(cfg):
        # each rank streams ONLY its contiguous row block; ingest merges
        # the reservoir samples + labels over one host allgather
        return ingest(ShardedSource(
            ArraySource(X, label=y, chunk_rows=STREAM_SRC_CHUNK),
            rank, 2), cfg)

    def fit(extra, sd=None, iters=WARMUP_ITERS + TIMED_ITERS,
            timed=False):
        p = dict(_stream_base(), num_machines=2, mesh_shape=[2],
                 tree_learner="data")
        p.update(extra)
        cfg = Config(p)
        if sd is None:
            sd = sharded_ds(cfg)
        c0 = backend_compile_count()
        b = create_boosting(cfg, sd, create_objective(cfg), [])
        secs = 0.0
        if timed:
            for _ in range(WARMUP_ITERS):
                b.train_one_iter()
            jax.block_until_ready(b.scores)
            t0 = time.monotonic()
            for _ in range(iters - WARMUP_ITERS):
                b.train_one_iter()
            jax.block_until_ready(b.scores)
            secs = time.monotonic() - t0
        else:
            for _ in range(iters):
                b.train_one_iter()
            jax.block_until_ready(b.scores)
        return b, sd, secs, float(backend_compile_count() - c0)

    # throwaway single-chunk run absorbs every once-per-process compile
    # (shared jitted helpers), so the measured runs see only their own
    # program sets — same discipline as the perf gate's stream counters
    fit({"data_stream_chunk_rows": 2400}, iters=1)

    # ---- data learner, 2 chunks/shard (the timed leg) ----------------
    b2, sd2, secs, c2 = fit({"data_stream_chunk_rows": STREAM_CHUNK2},
                            timed=True)
    d2 = _digest(b2, X)
    network.check_model_agreement(d2, namespace="lgbm_stream_smoke_data2")
    res.update(digest_data2=d2, seconds_data2=secs,
               trees_data2=len(b2.models),
               structure_data2=_structure_digest(b2.models),
               compiles_data2=c2,
               chunks2=int(b2._stream.num_chunks),
               rows_per_shard=int(b2._stream.rows_per_sweep))

    # warm booster trains more: ZERO new programs
    c0 = backend_compile_count()
    b2.train_one_iter()
    res["compiles_after_warmup"] = float(backend_compile_count() - c0)

    # ---- data learner, 4 chunks/shard: structure-identical, and the
    # fresh-booster program set is the same SIZE (chunk-count invariance
    # under the mesh — chunk count only changes how often each fixed-
    # shape kernel runs)
    b4, _, _, c4 = fit({"data_stream_chunk_rows": STREAM_CHUNK4}, sd=sd2)
    d4 = _digest(b4, X)
    network.check_model_agreement(d4, namespace="lgbm_stream_smoke_data4")
    res.update(digest_data4=d4, trees_data4=len(b4.models),
               structure_data4=_structure_digest(b4.models),
               compile_chunk_invariance=float(c4 - c2),
               chunks4=int(b4._stream.num_chunks))

    # ---- voting learner over the same sharded stream -----------------
    bv, _, _, _ = fit({"tree_learner": "voting", "top_k": STREAM_TOP_K,
                       "data_stream_chunk_rows": STREAM_CHUNK2}, sd=sd2)
    dv = _digest(bv, X)
    network.check_model_agreement(dv, namespace="lgbm_stream_smoke_vote")
    res.update(digest_voting=dv, trees_voting=len(bv.models),
               structure_voting=_structure_digest(bv.models))

    # ---- single-process streamed baseline (no mesh, full data, run
    # identically on both ranks): the sharded run must reproduce its
    # tree structure exactly
    ps = dict(_stream_base(), data_stream_chunk_rows=STREAM_CHUNK4)
    cfgs = Config(ps)
    sds = ingest(ArraySource(X, label=y, chunk_rows=STREAM_SRC_CHUNK),
                 cfgs)
    bs = create_boosting(cfgs, sds, create_objective(cfgs), [])
    for _ in range(WARMUP_ITERS + TIMED_ITERS):
        bs.train_one_iter()
    res["structure_serial"] = _structure_digest(bs.models)

    # ---- kill-and-resume byte-identity under the 2-process mesh ------
    pr = dict(_stream_base(), num_machines=2, mesh_shape=[2],
              tree_learner="data", data_stream_chunk_rows=STREAM_CHUNK2)

    def run_ck(ckpt, rounds, resume=False):
        d = lgb.Dataset(np.zeros((2, NUM_FEATURES)))
        d._binned = sharded_ds(Config(pr))
        return engine.train(
            dict(pr), d, num_boost_round=rounds,
            callbacks=[callback.checkpoint(ckpt, period=1)],
            resume_from=(ckpt if resume else None), verbose_eval=False)

    gdir = os.path.join(args.workdir, "ck_golden_r%d" % rank)
    idir = os.path.join(args.workdir, "ck_interrupt_r%d" % rank)
    golden = run_ck(gdir, 4)
    run_ck(idir, 2)                       # "killed" after 2 rounds
    resumed = run_ck(idir, 4, resume=True)
    gtxt, rtxt = golden.model_to_string(), resumed.model_to_string()
    res["resume_byte_identical"] = bool(gtxt == rtxt)
    dr = hashlib.sha256(rtxt.encode()).hexdigest()
    network.check_model_agreement(dr, namespace="lgbm_stream_smoke_ck")
    res["digest_resumed"] = dr

    with open(os.path.join(args.workdir, "stream.rank%d.json" % rank),
              "w") as fh:
        json.dump(res, fh, sort_keys=True)
    from lightgbm_tpu.parallel.network import KvHostComm
    KvHostComm(namespace="lgbm_stream_smoke_done").allgather(
        {"rank": rank})
    return 0


def _worker_stream_base(args) -> int:
    """1-process weak-scaling baseline for the stream phase: half the
    rows, same chunks/shard (constant rows/device AND chunks/device)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import time as _time
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.stream.sampler import ingest
    from lightgbm_tpu.stream.source import ArraySource

    X, y = _make_data(STREAM_ROWS // 2)
    cfg = Config(dict(_stream_base(),
                      data_stream_chunk_rows=STREAM_CHUNK2))
    sd = ingest(ArraySource(X, label=y, chunk_rows=STREAM_SRC_CHUNK), cfg)
    b = create_boosting(cfg, sd, create_objective(cfg), [])
    for _ in range(WARMUP_ITERS):
        b.train_one_iter()
    jax.block_until_ready(b.scores)
    t0 = _time.monotonic()
    for _ in range(TIMED_ITERS):
        b.train_one_iter()
    jax.block_until_ready(b.scores)
    secs = _time.monotonic() - t0
    with open(os.path.join(args.workdir, "stream_base.json"), "w") as fh:
        json.dump({"seconds": secs, "rows": STREAM_ROWS // 2}, fh)
    return 0


# -------------------------------------------------------------- launcher
def _spawn_pair(port: int, workdir: str, phase: str = "train"):
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
    ap.add_argument("--workdir", default="dist_train_out")
    ap.add_argument("--out", default="", help="summary JSON path")
    ap.add_argument("--worker", type=int, default=-1,
                    help="(internal) run as rank N instead of launching")
    ap.add_argument("--phase", default="train",
                    choices=["train", "base", "stream", "stream_base"])
    ap.add_argument("--only", default="all",
                    choices=["all", "train", "stream"],
                    help="which phases the launcher runs")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)

    if args.worker >= 0:
        if args.phase == "base":
            return _worker_base(args)
        if args.phase == "stream":
            return _worker_stream(args.worker, args)
        if args.phase == "stream_base":
            return _worker_stream_base(args)
        return _worker_train(args.worker, args)

    failures = []

    def check(cond, msg):
        (failures.append(msg) if not cond else None)
        print("%s %s" % ("ok  " if cond else "FAIL", msg))

    summary = {"failures": failures}
    if args.only in ("all", "train"):
        summary.update(_run_train_phase(args, check))
    if args.only in ("all", "stream"):
        summary["stream"] = _run_stream_phase(args, check)

    blob = json.dumps(summary, indent=2, sort_keys=True)
    print(blob)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(blob + "\n")
    return 1 if failures else 0


def _run_train_phase(args, check) -> dict:
    # ---- 2-process distributed training --------------------------------
    outs = _drain(_spawn_pair(_free_port(), args.workdir), timeout=420)
    for rank, (rc, so, se) in enumerate(outs):
        check(rc == 0, "train rank %d exited 0 (rc=%s)" % (rank, rc))
        if rc != 0:
            print("--- rank %d stdout ---\n%s\n--- rank %d stderr ---\n%s"
                  % (rank, so[-1500:], rank, se[-3000:]))
    results = {}
    for rank in range(2):
        path = os.path.join(args.workdir, "train.rank%d.json" % rank)
        if os.path.exists(path):
            with open(path) as fh:
                results[rank] = json.load(fh)
    check(len(results) == 2, "both train ranks reported")

    # ---- cross-process model agreement (launcher-side re-check) --------
    agreement = {}
    for mode in ("data", "voting"):
        ds = [results[r].get("digest_%s" % mode) for r in sorted(results)]
        ok = len(ds) == 2 and ds[0] is not None and ds[0] == ds[1]
        check(ok, "%s-parallel model identical across processes" % mode)
        agreement[mode] = ds[0] if ok else ds
        trees = {results[r].get("trees_%s" % mode) for r in results}
        check(trees == {WARMUP_ITERS + TIMED_ITERS},
              "%s-parallel committed %d trees on every rank (got %s)"
              % (mode, WARMUP_ITERS + TIMED_ITERS, sorted(trees)))

    # ---- weak-scaling baseline (1 process, rows/device held constant) --
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO}
    rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--worker", "0",
         "--phase", "base", "--workdir", args.workdir],
        env=env, cwd=REPO, timeout=420)
    check(rc == 0, "weak-scaling baseline exited 0 (rc=%s)" % rc)
    base = {}
    base_path = os.path.join(args.workdir, "base.json")
    if os.path.exists(base_path):
        with open(base_path) as fh:
            base = json.load(fh)

    weak = {}
    skew = None
    if len(results) == 2 and base.get("seconds"):
        t_ranks = [results[r].get("seconds_data", 0.0)
                   for r in sorted(results)]
        t_dist = max(t_ranks)          # the run is as slow as its slowest
        t_base = float(base["seconds"])
        eff = t_base / t_dist if t_dist > 0 else 0.0
        skew = (max(t_ranks) / min(t_ranks)) if min(t_ranks) > 0 else None
        weak = {"rows_per_device": TOTAL_ROWS // 2,
                "timed_iters": TIMED_ITERS,
                "t_base_1p_s": round(t_base, 3),
                "t_dist_2p_s": round(t_dist, 3),
                "efficiency": round(eff, 3),
                "straggler_skew": round(skew, 3) if skew else None}
        # sanity floor only — the measured number is the BENCH artifact,
        # the gate just catches a wedged/livelocked collective, and only
        # on machines that can genuinely host both ranks: with <4 cores
        # the two processes time-slice the same cores and gloo's
        # rendezvous spin makes the ratio meaningless (a 1-core box
        # measures 0.003 with a perfectly healthy schedule)
        cores = os.cpu_count() or 1
        weak["cores"] = cores
        if cores >= 4:
            check(eff > 0.005, "weak-scaling efficiency %.3f above "
                               "pathology floor 0.005" % eff)
        else:
            print("note weak-scaling efficiency %.3f recorded only "
                  "(%d cores cannot host 2 ranks fairly)" % (eff, cores))
        check(skew is not None and skew < 10.0,
              "straggler skew %.2fx within 10x sanity bound"
              % (skew or float("inf")))

    return {"agreement": agreement, "ranks": results,
            "weak_scaling": weak}


def _run_stream_phase(args, check) -> dict:
    """Chunks x chips: 2-process sharded-stream training + its
    1-process weak-scaling baseline, assembled into one row."""
    outs = _drain(_spawn_pair(_free_port(), args.workdir, phase="stream"),
                  timeout=480)
    for rank, (rc, so, se) in enumerate(outs):
        check(rc == 0, "stream rank %d exited 0 (rc=%s)" % (rank, rc))
        if rc != 0:
            print("--- rank %d stdout ---\n%s\n--- rank %d stderr ---\n%s"
                  % (rank, so[-1500:], rank, se[-3000:]))
    results = {}
    for rank in range(2):
        path = os.path.join(args.workdir, "stream.rank%d.json" % rank)
        if os.path.exists(path):
            with open(path) as fh:
                results[rank] = json.load(fh)
    check(len(results) == 2, "both stream ranks reported")
    if len(results) != 2:
        return {"ranks": results}
    r0, r1 = results[0], results[1]

    # cross-process digest agreement (launcher-side re-check; the
    # workers already ran check_model_agreement per leg)
    for leg in ("data2", "data4", "voting", "resumed"):
        check(r0.get("digest_" + leg) == r1.get("digest_" + leg)
              and r0.get("digest_" + leg) is not None,
              "stream %s model identical across processes" % leg)

    # structure identity: sharded == serial streamed, and chunk-count
    # invariant (2 vs 4 chunks per shard)
    check(r0.get("structure_data2") == r0.get("structure_serial"),
          "sharded streamed trees structure-identical to 1-process "
          "streamed")
    check(r0.get("structure_data4") == r0.get("structure_data2"),
          "streamed-sharded structure invariant in chunk count (2 vs 4)")

    # compiled-program contracts, per rank
    for rank, r in sorted(results.items()):
        check(r.get("compile_chunk_invariance") == 0.0,
              "rank %d: fresh-booster program count invariant 2->4 "
              "chunks (diff=%s)"
              % (rank, r.get("compile_chunk_invariance")))
        check(r.get("compiles_after_warmup") == 0.0,
              "rank %d: zero compiles after warmup (got %s)"
              % (rank, r.get("compiles_after_warmup")))
        check(bool(r.get("resume_byte_identical")),
              "rank %d: kill-and-resume byte-identical model" % rank)
    trees = {r.get("trees_data2") for r in results.values()}
    check(trees == {WARMUP_ITERS + TIMED_ITERS},
          "stream data leg committed %d trees on every rank (got %s)"
          % (WARMUP_ITERS + TIMED_ITERS, sorted(trees)))
    check(int(r0.get("chunks2", 0)) == 2 and int(r0.get("chunks4", 0)) == 4,
          "chunk schedule as declared (2 and 4 chunks/shard, got %s/%s)"
          % (r0.get("chunks2"), r0.get("chunks4")))

    # ---- 1-process weak-scaling baseline (constant rows/device) --------
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO}
    rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--worker", "0",
         "--phase", "stream_base", "--workdir", args.workdir],
        env=env, cwd=REPO, timeout=420)
    check(rc == 0, "stream weak-scaling baseline exited 0 (rc=%s)" % rc)
    base = {}
    base_path = os.path.join(args.workdir, "stream_base.json")
    if os.path.exists(base_path):
        with open(base_path) as fh:
            base = json.load(fh)

    weak = {}
    if base.get("seconds"):
        t_ranks = [results[r].get("seconds_data2", 0.0)
                   for r in sorted(results)]
        t_dist = max(t_ranks)
        t_base = float(base["seconds"])
        rows_base = float(base["rows"]) * TIMED_ITERS
        rows_dist = float(STREAM_ROWS) * TIMED_ITERS
        weak = {"rows_per_shard": STREAM_ROWS // 2,
                "chunks_per_shard": 2,
                "chunk_rows": STREAM_CHUNK2,
                "timed_iters": TIMED_ITERS,
                "t_base_1p_s": round(t_base, 3),
                "t_dist_2p_s": round(t_dist, 3),
                "rows_per_sec_1p": round(rows_base / t_base, 1)
                if t_base > 0 else None,
                "rows_per_sec_2p": round(rows_dist / t_dist, 1)
                if t_dist > 0 else None,
                "efficiency": round(t_base / t_dist, 3)
                if t_dist > 0 else None,
                "platform": "cpu", "cores": os.cpu_count() or 1}
        if weak["cores"] >= 4:
            check((weak["efficiency"] or 0) > 0.005,
                  "stream weak-scaling efficiency %s above pathology "
                  "floor 0.005" % weak["efficiency"])
        else:
            print("note stream weak-scaling efficiency %s recorded only "
                  "(%d cores cannot host 2 ranks fairly)"
                  % (weak["efficiency"], weak["cores"]))

    return {"ranks": results, "weak_scaling": weak,
            "agreement": {leg: r0.get("digest_" + leg)
                          for leg in ("data2", "data4", "voting",
                                      "resumed")}}


if __name__ == "__main__":
    sys.exit(main())
