"""Scale-shaped EFB ingest + training stress (docs/Performance.md).

Synthesizes Expo-shaped (one-hot blocks + dense, ~95% sparse) and
Allstate-shaped (4228-column one-hot heavy) matrices — the structured
sparsity of the reference's large benchmarks (Experiments.rst:110-147) —
then ingests through EFB/nbit packing and times a few training
iterations. It times the chip, so it runs only where jax finds a TPU
(through the chip tool) and exits non-zero anywhere else: no fallback.

    python tools/stress_shapes.py [--rows-expo N] [--rows-allstate N]
"""
import argparse
import os
import resource
import sys
import time

import numpy as np
from scipy import sparse

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402


def onehot_blocks(n, groups, card, seed, extra_dense):
    r = np.random.RandomState(seed)
    parts = []
    for _ in range(groups):
        choice = r.randint(0, card, n)
        parts.append(sparse.csr_matrix(
            (np.ones(n, np.float32), (np.arange(n), choice)),
            shape=(n, card)))
    parts.append(sparse.csr_matrix(r.randn(n, extra_dense)
                                   .astype(np.float32)))
    return sparse.hstack(parts, format="csr")


def run_shape(name, n, groups, card, extra_dense, iters, leaves):
    if n <= 0:
        print("%s: skipped (rows=0)" % name)
        return
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.boosting import create_boosting

    S = onehot_blocks(n, groups, card, 0, extra_dense)
    sig = np.asarray(S[:, -2].todense()).ravel()
    y = (sig + 0.3 * np.random.RandomState(1).randn(n) > 0) \
        .astype(np.float32)
    print("%s: %d x %d, %.2f%% nnz" % (
        name, S.shape[0], S.shape[1], 100 * S.nnz / (S.shape[0] * S.shape[1])))
    # STRESS_GROWTH overrides. Default batched: at these WIDE shapes the
    # round-4 on-chip comparison favors batched (Expo 0.47 vs exact 0.55
    # s/iter; Allstate 1.52 vs 1.93) — many stored columns make the
    # per-split fused pass expensive, and batching amortizes it; the
    # narrow HIGGS shape favors exact (docs/Performance.md).
    growth = os.environ.get("STRESS_GROWTH", "batched")
    cfg = Config({"objective": "binary", "verbosity": 1,
                  "num_leaves": leaves, "tree_growth": growth,
                  "tree_batch_splits": 16})
    t0 = time.time()
    ds = BinnedDataset.from_matrix(S, cfg, label=y)
    print("%s ingest: %.0fs, %d features -> %d stored cols, "
          "binned %.2f GB, rss %.2f GB" % (
              name, time.time() - t0, S.shape[1], ds.num_columns,
              ds.X_binned.nbytes / 1e9,
              resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6))
    b = create_boosting(cfg, ds, create_objective(cfg), [])
    t0 = time.time()
    b.train_many(iters)
    jax.block_until_ready(b.scores)
    compile_s = time.time() - t0
    t0 = time.time()
    b.train_many(iters)
    jax.block_until_ready(b.scores)
    dt = (time.time() - t0) / iters
    print("%s train (%s, %s L=%d): %.2f s/iter "
          "(compile+%d iters: %.0fs)" % (
              name, jax.devices()[0].device_kind, growth, leaves, dt,
              iters, compile_s))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-expo", type=int, default=1_100_000)
    ap.add_argument("--rows-allstate", type=int, default=400_000)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--leaves", type=int, default=63)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("stress_shapes: jax.devices()[0].platform is %r, not 'tpu'; "
              "this script times the chip and has no fallback"
              % dev.platform, file=sys.stderr)
        return 1
    print("device: platform=%s device_kind=%r count=%d"
          % (dev.platform, dev.device_kind, len(jax.devices())), flush=True)
    run_shape("EXPO-shaped", args.rows_expo, 20, 34, 20, args.iters,
              args.leaves)
    run_shape("ALLSTATE-shaped", args.rows_allstate, 120, 35, 28,
              args.iters, args.leaves)
    return 0


if __name__ == "__main__":
    sys.exit(main())
