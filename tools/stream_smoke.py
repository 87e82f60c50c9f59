"""Out-of-core streaming end-to-end smoke (tier1 CI).

Builds a dataset 4x larger than the configured chunk cap, writes it to a
``.npy`` file, and trains it through the full out-of-core path — mmap
chunk source, two-round sample binning, double-buffered host->device
pipeline, cross-chunk frontier growth — then verifies from the outside:

- the streamed model is STRUCTURE-IDENTICAL to a single-shot in-memory
  run on the same rows (same splits/thresholds/children/counts; value
  lines are allowed last-ulp float drift from chunked f32 summation);
- predictions agree with the single-shot run to fp32 tolerance;
- the dataset really was chunked (>= 4 chunks) and the bin matrix was
  never materialized whole (``X_binned is None``);
- the pipeline's overlap accounting is sane and reported: sweeps,
  rows transferred, overlap_efficiency in [0, 1], ingest rows/sec;
- host chunks are word-packed exactly when ``--bin-packing`` says so
  (auto resolves to byte for streaming), and every wave runs in
  chunks+1 dispatches (the last chunk's sweep fused with the commit).

Exit code 0 = every assertion holds. The summary JSON goes to ``--out``
(and stdout) so CI uploads it as an artifact.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # repo root for lightgbm_tpu

# model-text lines that define tree STRUCTURE (value lines carry
# float-accumulation noise between chunked and single-shot runs)
_STRUCT_KEYS = ("split_feature=", "threshold=", "left_child=",
                "right_child=", "leaf_count=", "internal_count=",
                "num_leaves=", "decision_type=", "cat_boundaries=",
                "cat_threshold=", "num_cat=")


def _struct(model_str):
    return [l for l in model_str.splitlines() if l.startswith(_STRUCT_KEYS)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default="stream_smoke_out",
                    help="the .npy dataset and model dumps land here")
    ap.add_argument("--out", default="", help="write the summary JSON here")
    ap.add_argument("--rows", type=int, default=8000)
    ap.add_argument("--chunk-rows", type=int, default=2000,
                    help="rows per chunk (dataset is rows/chunk-rows chunks)")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--bin-packing", default="auto",
                    choices=("auto", "none", "nibble", "byte"),
                    help="tpu_bin_packing for the STREAMED run (auto "
                    "resolves to byte: word-packed host chunks)")
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)

    import jax
    import numpy as np
    import lightgbm_tpu as lgb

    r = np.random.RandomState(0)
    n, f = args.rows, 10
    X = r.randn(n, f)
    X[:, 3] = r.randint(0, 8, n)          # a low-cardinality column
    y = (2 * X[:, 0] + np.sin(X[:, 1]) + 0.7 * X[:, 2]
         + 0.3 * r.randn(n) > 0).astype(np.float64)
    npy = os.path.join(args.workdir, "train.npy")
    np.save(npy, X)

    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "tree_growth": "frontier", "deterministic": True,
              "min_data_in_leaf": 20,
              # sample >= n so streamed and in-memory binning see the
              # same boundaries and structure parity is exact
              "bin_construct_sample_cnt": max(200000, n)}

    failures = []

    def check(cond, msg):
        (failures.append(msg) if not cond else None)
        print("%s %s" % ("ok  " if cond else "FAIL", msg))

    # ---- single-shot baseline (in-memory) ------------------------------
    base = lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=args.iters)

    # ---- streamed run from the .npy mmap source ------------------------
    sp = dict(params, data_stream_chunk_rows=args.chunk_rows,
              data_stream_prefetch=2, tpu_bin_packing=args.bin_packing)
    ds = lgb.Dataset(npy, label=y, params=sp)
    bst = lgb.train(dict(sp), ds, num_boost_round=args.iters)

    binned = ds.construct()._binned
    check(getattr(binned, "is_streamed", False),
          "dataset took the streamed path")
    check(binned.X_binned is None, "bin matrix never materialized whole")
    nchunks = len(binned.chunks)
    check(nchunks >= 4, ">= 4 host chunks (got %d)" % nchunks)

    # ---- structure parity ----------------------------------------------
    s_base = _struct(base.model_to_string())
    s_stream = _struct(bst.model_to_string())
    check(s_base == s_stream,
          "streamed model structure identical to single-shot "
          "(%d structural lines)" % len(s_base))
    pred_b = base.predict(X[:512])
    pred_s = bst.predict(X[:512])
    max_dp = float(np.max(np.abs(pred_b - pred_s)))
    check(max_dp < 1e-4, "predictions match single-shot "
          "(max |dp| = %.3g)" % max_dp)
    with open(os.path.join(args.workdir, "model_streamed.txt"), "w") as fh:
        fh.write(bst.model_to_string())

    # ---- pipeline accounting -------------------------------------------
    pipe = bst._impl._stream
    check(pipe is not None, "trainer holds a ChunkPipeline")
    stats = pipe.stats() if pipe is not None else {}
    packed = bool(pipe is not None and pipe.packed)
    if pipe is not None:
        want_packed = args.bin_packing != "none"
        check(packed == want_packed,
              "host chunks %s word-packed (tpu_bin_packing=%s)"
              % ("are" if want_packed else "are NOT", args.bin_packing))
        grower = bst._impl._stream_grower
        if grower is not None and grower.waves:
            per_wave = grower.wave_dispatches / grower.waves
            check(per_wave == pipe.num_chunks + 1,
                  "chunks+1 dispatches per wave — last chunk's sweep "
                  "fused with the commit (%.2f vs %d chunks)"
                  % (per_wave, pipe.num_chunks))
        check(stats["num_chunks"] == nchunks,
              "pipeline sweeps all %d chunks" % nchunks)
        check(stats["sweeps"] >= args.iters,
              "at least one sweep per iteration (%d sweeps / %d iters)"
              % (stats["sweeps"], args.iters))
        check(stats["rows_transferred"] == stats["sweeps"] * n,
              "every sweep transfers all %d rows" % n)
        eff = stats["overlap_efficiency"]
        check(0.0 <= eff <= 1.0,
              "overlap_efficiency in [0, 1] (got %.3f)" % eff)
        print("overlap_efficiency: %.3f" % eff)
        print("ingest_rows_per_sec: %.0f" % (stats["ingest_rows_per_sec"]
                                             or 0.0))

    summary = {"rows": n, "chunk_rows": args.chunk_rows,
               "num_chunks": nchunks, "iterations": args.iters,
               "structure_identical": s_base == s_stream,
               "max_pred_delta": max_dp, "bin_packing": args.bin_packing,
               "chunks_word_packed": packed,
               "device": {"platform": jax.devices()[0].platform,
                          "kind": jax.devices()[0].device_kind,
                          "count": len(jax.devices())},
               "pipeline": stats, "failures": failures}
    blob = json.dumps(summary, indent=2, sort_keys=True)
    print(blob)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(blob + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
