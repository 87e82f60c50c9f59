"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the public entry points, at
the widths of the model BASELINE.json names (HIGGS-shaped binary GBDT: 28
columns, num_leaves=255, max_bin=255) on seeded synthetic rows:

  train_exact        lgb.train with the config defaults (tree_growth=exact,
                     tpu_hist_impl=auto, which must resolve to pallas)
  kernel_parity      Pallas histograms against the f32 scatter histogram
  train_frontier     the same call with tree_growth=frontier
  train_goss         the same call with boosting=goss at learning_rate 0.5,
                     so that three of the five trees are sampled: they are
                     grown on a bag of 30% of the rows, counted in its
                     integers, and every row still takes their score
  predict_and_serve  bst.predict, save_model, then the task=serve path over
                     HTTP in this process, zero compiles after warm-up
  train_categorical  the same call on 39 columns, 8 of them categorical (3
                     to 100,000 categories, with NaN and negative ids): the
                     share of categorical splits, the kernel against the
                     scatter histogram at 39 columns, held scores equal to
                     the trees' leaves by RAW category, and the model served
                     over HTTP on ids it kept, never saw, NaN and negatives
  mesh4              tree_learner=data over four devices (only when the
                     machine shows four or more; then it must pass)

It never sets JAX_PLATFORMS and never falls back: no TPU means a non-zero
exit before anything is trained, and a phase that fails ends the run with
its traceback. ``--rehearsal`` is the one way to run it elsewhere: a loudly
labelled CPU dry run at toy size with interpreted kernels, which proves the
control flow and nothing about the chip.

The seconds printed are smoke timings — how long this script took — not
benchmark numbers. The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.

    python chip_smoke.py [--rows N]
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal
"""
import argparse
import importlib.metadata
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu import native
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.histogram import (build_histogram,
                                         build_histogram_frontier,
                                         hist_tile_vals)
from lightgbm_tpu.obs.costmodel import detect_peaks
from lightgbm_tpu.profiling import compile_cache_stats, enable_compile_cache
from lightgbm_tpu.serving import make_server
from lightgbm_tpu.serving.server import build_app

FEATURES = 28
# widths of the smoked model; the rehearsal cuts them, a chip run never does
CHIP_SHAPE = {"rows": 1_000_000, "num_leaves": 255, "max_bin": 255}
REHEARSAL_SHAPE = {"rows": 4000, "num_leaves": 15, "max_bin": 63}
ROUNDS = 5
# stated bars (the synthetic is learnable; 255-leaf trees clear these in 5
# rounds with wide margin, a miscompiled kernel does not)
MIN_TRAIN_AUC = 0.85
FRONTIER_AUC_BAND = 0.02
# Pallas two-term bf16 split vs f32 scatter at 65536 rows: round 4 saw
# 1.8e-4 max abs difference (docs/Performance.md "Round 4")
PARITY_ROWS = 65536
PARITY_TOL = 1e-3
SERVE_TOL = 1e-6          # tools/serve_smoke.py's tolerance
# GOSS samples from iteration 1 / learning_rate on: 2 of the 5 rounds stay
# unsampled. Held scores are a float32 running sum of five leaf values
GOSS_RATE = 0.5
GOSS_SCORE_TOL = 1e-5
# four-device model against the single-device one: tests/test_parallel.py
# holds predictions to 1e-3; at 255 leaves a near-tied split may flip under
# f32 summation order and move one leaf's rows, so a small share may exceed it
MESH_PRED_TOL = 1e-3
MESH_MOVED_FRAC = 0.01
MESH_AUC_GAP = 1e-3
# the categorical phase: 31 numerical columns and one categorical column
# of each of these cardinalities (the rehearsal caps them at its rows / 8)
CAT_FEATURES = 39
CAT_CARDINALITIES = (3, 10, 27, 300, 2000, 5000, 20000, 100000)
CAT_SCORE_TOL = 1e-5      # a float32 running sum of five leaf values
# the kernel against the scatter histogram on the TRAINED table's bins: a
# categorical column's largest bin holds half the rows, so one sum is tens of
# thousands of values and an absolute bar means nothing (0.059 of 26,000 on
# the chip, PR 35); the two-term bfloat16 split lands within ~3e-6 of float32
CAT_PARITY_REL_TOL = 2e-5
SERVE_REQUEST_ROWS = (1, 3, 16, 17, 100, 255, 256, 1000, 2048, 4096)
SERVE_REQUESTS = 40


def synth(rows, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(rows, FEATURES).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2] + 0.5 * np.sin(X[:, 3] * 3)
          + 0.3 * r.randn(rows)) > 0).astype(np.float32)
    return X, y


def synth_categorical(rows, seed=1):
    """39 columns: synth's rule over the first four, then one categorical
    column a cardinality (Zipf ranks as ids, 2% NaN, 1% negative), five of
    which move the label by an effect a category."""
    r = np.random.RandomState(seed)
    X = r.randn(rows, CAT_FEATURES).astype(np.float32)
    t = (X[:, 0] + X[:, 1] * X[:, 2] + 0.5 * np.sin(X[:, 3] * 3)
         + 0.3 * r.randn(rows))
    first = CAT_FEATURES - len(CAT_CARDINALITIES)
    for i, k in enumerate(CAT_CARDINALITIES):
        k = max(3, min(k, rows // 8))
        ids = np.minimum((k + 1.0) ** r.rand(rows) - 1, k - 1).astype(np.int64)
        if i % 2 == 0:
            t += 0.8 * r.randn(k)[ids]
        col = ids.astype(np.float32)
        u = r.rand(rows)
        col[u < 0.02] = np.nan
        col[u > 0.99] = -1.0
        X[:, first + i] = col
    return X, (t > 0).astype(np.float32), list(range(first, CAT_FEATURES))


def auc(y, score):
    order = np.argsort(score)
    ranks = np.empty(len(score))
    ranks[order] = np.arange(1, len(score) + 1)
    npos = float(y.sum())
    return float((ranks[y > 0].sum() - npos * (npos + 1) / 2)
                 / (npos * (len(y) - npos)))


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def trees_text(bst):
    """Model text without the trailing parameters block (which records
    tree_learner / mesh_shape and so differs by construction)."""
    return bst.model_to_string().split("\nparameters:", 1)[0]


def structure_lines(text):
    """The lines of a model text that say which splits were chosen and
    how many rows went where, without the ones that print f32 sums."""
    values = ("tree_sizes", "leaf_value", "internal_value", "split_gain",
              "leaf_weight", "internal_weight")
    return [ln for ln in text.splitlines()
            if ln.split("=", 1)[0] not in values]


class Smoke:
    def __init__(self, args):
        self.rehearsal = args.rehearsal
        shape = dict(REHEARSAL_SHAPE if args.rehearsal else CHIP_SHAPE)
        if args.rows:
            shape["rows"] = args.rows
        self.rows = shape["rows"]
        self.params = {"objective": "binary", "verbosity": -1,
                       "num_leaves": shape["num_leaves"],
                       "max_bin": shape["max_bin"]}
        # the rehearsal has no Mosaic: it asks for the interpreted kernel by
        # name; a chip run leaves tpu_hist_impl at auto and checks what it
        # resolved to
        self.want_impl = "pallas_interpret" if args.rehearsal else "pallas"
        if args.rehearsal:
            self.params["tpu_hist_impl"] = "pallas_interpret"
        self.eval_rows = min(self.rows, 200_000)
        self.phase_rows = []

    def phase(self, name, fn):
        """Run one phase. No handler: a failure ends the run non-zero."""
        s0, t0 = compile_cache_stats(), time.perf_counter()
        info = fn() or {}
        wall = time.perf_counter() - t0
        d = {k: v - s0[k] for k, v in compile_cache_stats().items()}
        row = {"phase": name, "wall_s": round(wall, 2),
               "compile_s": round(d["backend_compile_seconds"], 2),
               "backend_compiles": d["backend_compiles"],
               "cache_hits": d["persistent_cache_hits"],
               "cache_misses": d["persistent_cache_misses"], **info}
        self.phase_rows.append(row)
        print("[%s] PASSED (smoke timings, not benchmark numbers) %s"
              % (name, json.dumps(row)), flush=True)

    # ------------------------------------------------------------ phases
    def make_data(self):
        self.X, self.y = synth(self.rows)
        t0 = time.perf_counter()
        self.train_set = lgb.Dataset(self.X, self.y,
                                     params=dict(self.params)).construct()
        # host code: say which binner ran, so a slow ingest is not read as
        # the system's
        print("binning: %.1fs for %d x %d (host code: %s)"
              % (time.perf_counter() - t0, self.rows, FEATURES,
                 native.origin()), flush=True)

    def train(self, **extra):
        params = dict(self.params, **extra)
        bst = lgb.train(params, self.train_set, num_boost_round=ROUNDS)
        jax.block_until_ready(bst._impl.scores)
        impl = bst._impl.grow_params.hist_impl
        check(impl == self.want_impl, "hist_impl resolved to %r, expected %r"
              % (impl, self.want_impl))
        check(bst.num_trees() == ROUNDS, "trained %d trees, expected %d"
              % (bst.num_trees(), ROUNDS))
        return bst

    def train_auc(self, bst):
        pred = bst.predict(self.X[:self.eval_rows])
        check(pred.shape == (self.eval_rows,) and np.isfinite(pred).all(),
              "predict returned shape %s / non-finite values" % (pred.shape,))
        return auc(self.y[:self.eval_rows], pred)

    def train_exact(self):
        self.bst_exact = self.train()
        self.auc_exact = self.train_auc(self.bst_exact)
        check(self.auc_exact > MIN_TRAIN_AUC, "train AUC %.4f <= %.2f"
              % (self.auc_exact, MIN_TRAIN_AUC))
        gp = self.bst_exact._impl.grow_params
        return {"hist_impl": gp.hist_impl, "tree_growth": "exact",
                "train_auc": round(self.auc_exact, 4)}

    def train_goss(self):
        bst = self.train(boosting="goss", learning_rate=GOSS_RATE)
        gbdt = bst._impl
        check(gbdt._goss_bag, "boosting=goss on the serial exact grower did "
              "not start its row partition from the bag")
        bag = int(self.rows * 0.2) + int(self.rows * 0.1)
        roots = [int(t.internal_count[0]) for t in gbdt.models]
        unsampled = int(1 / GOSS_RATE)
        check(roots == [self.rows] * unsampled + [bag] * (ROUNDS - unsampled),
              "root counts %s: expected %d unsampled trees on %d rows, then "
              "bags of %d" % (roots, unsampled, self.rows, bag))
        # rows out of the last bags were routed all the same: what the
        # booster holds for a row is what its trees give that row
        held = np.asarray(gbdt.scores)[:self.eval_rows, 0]
        raw = bst.predict(self.X[:self.eval_rows], raw_score=True)
        worst = float(np.abs(raw - held).max())
        check(worst <= GOSS_SCORE_TOL, "held scores vs the trees' leaves "
              "maxdiff %.3g > %.0g" % (worst, GOSS_SCORE_TOL))
        a = self.train_auc(bst)
        check(a > MIN_TRAIN_AUC, "GOSS train AUC %.4f <= %.2f"
              % (a, MIN_TRAIN_AUC))
        return {"boosting": "goss", "bag_rows": bag, "train_auc": round(a, 4),
                "held_vs_leaves_maxdiff": worst}

    def kernel_parity(self):
        r = np.random.RandomState(7)
        n = 4096 if self.rehearsal else PARITY_ROWS
        b = self.params["max_bin"]
        xb_host = r.randint(0, b, (n, FEATURES)).astype(np.uint8)
        xb = jnp.asarray(xb_host)
        g = jnp.asarray(r.randn(n).astype(np.float32))
        h = jnp.asarray(np.abs(r.randn(n)).astype(np.float32))
        m = jnp.asarray((r.rand(n) > 0.3).astype(np.float32))
        impl = self.want_impl
        out = {}

        def compare(name, kernel, reference):
            """max |kernel - reference|, and what each side took to compile
            (the XLA scatter reference is the slow one on the chip)."""
            c0 = compile_cache_stats()["backend_compile_seconds"]
            got = np.asarray(kernel())
            c1 = compile_cache_stats()["backend_compile_seconds"]
            ref = np.asarray(reference())
            c2 = compile_cache_stats()["backend_compile_seconds"]
            out[name + "_maxdiff"] = float(np.abs(got - ref).max())
            out[name + "_compile_s"] = [round(c1 - c0, 2), round(c2 - c1, 2)]

        # K=3: one leaf's (grad, hess, count) histogram over every row
        compare("k3",
                lambda: build_histogram(xb, g, h, m, num_bins=b, impl=impl),
                lambda: build_histogram(xb, g, h, m, num_bins=b,
                                        impl="scatter"))
        # one 4,096-row tile of the exact grower's smaller-child pass
        v3 = jnp.asarray(r.randn(4096, 3).astype(np.float32))
        compare("tile", lambda: hist_tile_vals(xb[:4096], v3, b, impl),
                lambda: hist_tile_vals(xb[:4096], v3, b, "scatter"))
        # the frontier wave kernel at its widest ladder width, against a
        # host scatter in f64 (the XLA scatter at 254 slots compiles for
        # minutes on the chip)
        s = self.params["num_leaves"] - 1
        slot = r.randint(-1, s, n).astype(np.int32)
        live = slot >= 0
        flat = ((slot[live, None] * FEATURES + np.arange(FEATURES)) * b
                + xb_host[live])
        gm, hm, mm = (np.asarray(a, np.float64) for a in (g * m, h * m, m))
        ref = np.zeros((s * FEATURES * b, 3))
        np.add.at(ref, flat.reshape(-1),
                  np.repeat(np.stack([gm, hm, mm], -1)[live], FEATURES, 0))
        compare("slots%d" % s,
                lambda: build_histogram_frontier(
                    xb, jnp.asarray(slot), g, h, m, num_bins=b, num_slots=s,
                    impl=impl),
                lambda: ref.reshape(s, FEATURES, b, 3))
        for k, v in out.items():
            check(k.endswith("_compile_s") or v <= PARITY_TOL,
                  "%s = %s > tolerance %.1g" % (k, v, PARITY_TOL))
        return {"hist_impl": impl, **out}

    def train_frontier(self):
        self.bst_frontier = self.train(tree_growth="frontier")
        a = self.train_auc(self.bst_frontier)
        check(abs(a - self.auc_exact) <= FRONTIER_AUC_BAND,
              "frontier train AUC %.4f not within %.2f of exact %.4f"
              % (a, FRONTIER_AUC_BAND, self.auc_exact))
        gp = self.bst_frontier._impl.grow_params
        return {"hist_impl": gp.hist_impl, "tree_growth": "frontier",
                "word_packed_cols": gp.word_packed_cols,
                "train_auc": round(a, 4)}

    def predict_and_serve(self):
        r = np.random.RandomState(3)
        sizes = [SERVE_REQUEST_ROWS[i % len(SERVE_REQUEST_ROWS)]
                 for i in range(SERVE_REQUESTS)]
        return self.serve(self.bst_exact,
                          [r.randn(n, FEATURES).astype(np.float32)
                           for n in sizes])

    def serve(self, bst, queries):
        """The model saved, loaded by the task=serve path and asked over
        HTTP: every reply against ``bst.predict``, no compile after
        warm-up."""
        # references BEFORE warm-up, so that the reference path's own
        # compilations do not count against the served path
        refs = [bst.predict(q) for q in queries]
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            path = os.path.join(tmp, "model.txt")
            bst.save_model(path)
            # what cli.py task=serve does (serving/server.py run_server),
            # with the listener on a thread instead of the main loop
            app = build_app(Config({"task": "serve", "input_model": path,
                                    "verbosity": -1}))
            warmed = app.engine.warmup()
            cache_dir = jax.config.jax_compilation_cache_dir
            server = make_server(app, "127.0.0.1", 0)
            port = server.server_address[1]
            thread = threading.Thread(target=server.serve_forever,
                                      daemon=True)
            thread.start()
            worst = 0.0
            try:
                for q, ref in zip(queries, refs):
                    # a NaN travels as null
                    rows = [[None if v != v else v for v in row]
                            for row in q.tolist()]
                    body = json.dumps({"data": rows}).encode()
                    rep = json.loads(urllib.request.urlopen(
                        urllib.request.Request(
                            "http://127.0.0.1:%d/predict" % port, data=body),
                        timeout=120).read())
                    got = np.asarray(rep["predictions"])
                    check(got.shape == ref.shape and np.isfinite(got).all(),
                          "served shape %s vs %s" % (got.shape, ref.shape))
                    worst = max(worst, float(np.abs(got - ref).max()))
            finally:
                server.shutdown()
                server.server_close()
                thread.join(timeout=30)
                app.close()
        check(not thread.is_alive(), "HTTP server thread did not stop")
        check(worst <= SERVE_TOL, "served vs bst.predict maxdiff %.3g > %.0g"
              % (worst, SERVE_TOL))
        recompiles = app.engine.metrics.recompiles_after_warmup()
        check(recompiles == 0,
              "%d backend compiles after serve warm-up" % recompiles)
        return {"requests": len(queries), "buckets_warmed": warmed,
                "served_maxdiff": worst, "recompiles_after_warmup": 0,
                "cache_dir_after_warmup": cache_dir}

    def train_categorical(self):
        X, y, cats = synth_categorical(self.rows)
        params = dict(self.params,
                      categorical_feature=",".join(str(c) for c in cats))
        ds = lgb.Dataset(X, y, params=dict(params)).construct()
        bst = lgb.train(params, ds, num_boost_round=ROUNDS)
        gbdt = bst._impl
        jax.block_until_ready(gbdt.scores)
        gp = gbdt.grow_params
        check(gp.hist_impl == self.want_impl and gp.with_categorical
              == len(cats), "hist_impl %r, %d categorical features"
              % (gp.hist_impl, gp.with_categorical))
        nodes = sum(t.num_leaves_actual - 1 for t in gbdt.models)
        cat_nodes = sum(int(t.is_categorical[:t.num_leaves_actual - 1].sum())
                        for t in gbdt.models)
        check(cat_nodes > 0, "no split on a categorical column")
        # bins routed the rows while training; raw ids route them here
        held = np.asarray(gbdt.scores)[:self.eval_rows, 0]
        raw = bst.predict(X[:self.eval_rows], raw_score=True)
        worst = float(np.abs(raw - held).max())
        check(worst <= CAT_SCORE_TOL, "held scores vs the trees' leaves by "
              "raw category maxdiff %.3g > %.0g" % (worst, CAT_SCORE_TOL))
        a = auc(y[:self.eval_rows], bst.predict(X[:self.eval_rows]))
        check(a > MIN_TRAIN_AUC, "categorical train AUC %.4f <= %.2f"
              % (a, MIN_TRAIN_AUC))
        # the kernel at 39 columns, a shape no other phase gives it
        n = min(self.rows, 4096 if self.rehearsal else PARITY_ROWS)
        r = np.random.RandomState(11)
        b = self.params["max_bin"]
        xb = gbdt.xb[:n]
        g = jnp.asarray(r.randn(n).astype(np.float32))
        h = jnp.asarray(np.abs(r.randn(n)).astype(np.float32))
        m = jnp.asarray((r.rand(n) > 0.3).astype(np.float32))
        ref = np.asarray(build_histogram(xb, g, h, m, num_bins=b,
                                         impl="scatter"))
        k3 = float(np.abs(np.asarray(build_histogram(
            xb, g, h, m, num_bins=b, impl=self.want_impl)) - ref).max()
            / np.abs(ref).max())
        check(k3 <= CAT_PARITY_REL_TOL, "k3 at %d columns maxdiff %.3g of "
              "the largest sum > %.0g" % (xb.shape[1], k3, CAT_PARITY_REL_TOL))
        # served on ids the model kept, ids it never saw, NaN and negatives
        queries = []
        for size in (1, 17, 256, 1000):
            q = X[r.randint(0, self.rows, size)].copy()
            q[:, cats[-1]] = r.randint(0, 10 ** 7, size)    # mostly unseen
            queries.append(q)
        served = self.serve(bst, queries)
        return {"hist_impl": gp.hist_impl, "columns": int(xb.shape[1]),
                "cat_splits": cat_nodes, "splits": nodes,
                "train_auc": round(a, 4), "held_vs_leaves_maxdiff": worst,
                "k3_maxdiff_rel": k3,
                "served_maxdiff": served["served_maxdiff"]}

    def mesh4(self):
        out = {}
        for growth, serial in (("exact", self.bst_exact),
                               ("frontier", self.bst_frontier)):
            bst = self.train(tree_growth=growth, tree_learner="data",
                             mesh_shape=[4])
            impl = bst._impl
            for name, arr in (("xb", impl.xb), ("scores", impl.scores)):
                shards = arr.addressable_shards
                check(len({s.device for s in shards}) == 4
                      and all(s.data.shape[0] * 4 == arr.shape[0]
                              for s in shards),
                      "%s of tree_growth=%s is not row-sharded over four "
                      "devices: %r, shard shapes %s"
                      % (name, growth, arr.sharding,
                         [s.data.shape for s in shards]))
            # docs/Distributed.md: the data-parallel election reproduces
            # the serial tie-break, so the trees have the serial structure;
            # the summed histograms differ in f32 summation order, so the
            # values printed into the model text agree only to ~1e-6 (the
            # same on the CPU mesh). Text and structure identity are
            # reported; the stated bars are on what the models predict.
            a, b = trees_text(bst), trees_text(serial)
            p_mesh = bst.predict(self.X[:self.eval_rows])
            p_one = serial.predict(self.X[:self.eval_rows])
            delta = np.abs(p_mesh - p_one)
            moved = float((delta > MESH_PRED_TOL).mean())
            auc_gap = abs(auc(self.y[:self.eval_rows], p_mesh)
                          - auc(self.y[:self.eval_rows], p_one))
            out[growth] = {
                "model_text_identical": a == b,
                "structure_identical":
                    structure_lines(a) == structure_lines(b),
                "pred_maxdiff": float(delta.max()),
                "rows_moved_frac": moved, "auc_gap": auc_gap,
                "xb_sharding": str(impl.xb.sharding.spec),
                "scores_sharding": str(impl.scores.sharding.spec),
                "rs_learner": bool(impl.grow_params.frontier_rs)}
            check(moved <= MESH_MOVED_FRAC and auc_gap <= MESH_AUC_GAP,
                  "tree_learner=data disagrees with the single-chip %s "
                  "model: %s" % (growth, json.dumps(out[growth])))
        return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=0,
                    help="training rows (default %d; 10500000 is the real "
                         "HIGGS shape)" % CHIP_SHAPE["rows"])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run at toy size with interpreted kernels; "
                         "proves control flow only, never a chip result")
    args = ap.parse_args()

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print("device: platform=%s device_kind=%r count=%d"
          % (dev["platform"], dev["kind"], dev["count"]), flush=True)
    if args.rehearsal:
        print("=" * 72 + "\nREHEARSAL: toy size, interpreted kernels, on %s. "
              "This is NOT a chip run\nand nothing printed below is a device "
              "measurement.\n" % dev["platform"] + "=" * 72, flush=True)
    elif dev["platform"] != "tpu":
        print("chip_smoke: jax.devices()[0].platform is %r, not 'tpu' — "
              "refusing to run (no fallback; --rehearsal is the labelled "
              "CPU dry run)" % dev["platform"], file=sys.stderr)
        return 1

    print("versions: python=%s jax=%s jaxlib=%s libtpu=%s"
          % (sys.version.split()[0], jax.__version__,
             importlib.metadata.version("jaxlib"),
             importlib.metadata.version("libtpu")))
    print("compile cache: %s (JAX_COMPILATION_CACHE_DIR %s)"
          % (enable_compile_cache(),
             "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
             else "not set"))
    # raises on a TPU generation the peaks table does not know
    print("chip peaks for %r: %s" % (dev["kind"], detect_peaks()))

    smoke = Smoke(args)
    print("shape: %d x %d, num_leaves=%d, max_bin=%d, %d rounds"
          % (smoke.rows, FEATURES, smoke.params["num_leaves"],
             smoke.params["max_bin"], ROUNDS), flush=True)
    smoke.make_data()
    smoke.phase("train_exact", smoke.train_exact)
    smoke.phase("kernel_parity", smoke.kernel_parity)
    smoke.phase("train_frontier", smoke.train_frontier)
    smoke.phase("train_goss", smoke.train_goss)
    smoke.phase("predict_and_serve", smoke.predict_and_serve)
    smoke.phase("train_categorical", smoke.train_categorical)
    if len(devices) >= 4:
        smoke.phase("mesh4", smoke.mesh4)
    else:
        print("mesh4: not run (%d devices)" % len(devices), flush=True)
    total = {k: round(sum(r[k] for r in smoke.phase_rows), 2)
             for k in ("wall_s", "compile_s", "cache_hits", "cache_misses")}
    print("all phases passed; totals (smoke timings, not benchmark "
          "numbers): %s" % json.dumps(total))
    print(json.dumps({"ok": True, **({"rehearsal": True}
                                     if args.rehearsal else {}),
                      "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
