"""The lab behind PR 34: what routing ALL rows of a split in row space costs.

  python tools/rowspace_route_lab.py [N] [--reps R]     on the chip

A tree grown on a GOSS bag has to send every row out of the bag down every
split, and nothing else is ever asked of those rows. In row space that is
``leaf_id = where((leaf_id == leaf) & ~go_left(col), right_leaf, leaf_id)``
with ``col`` the split column of all N rows. With N x 67 uint8 bins resident,
one recorded click-log tree's 254 splits are replayed (split_leaf from
tools/schedule_lab_trees.json; a split routes on the column of its leaf's
depth at the threshold that sends the recorded share left, through the
library's ``_bin_go_left`` with a NaN bin, under a ``lax.cond`` as the grower
has it), the column taken

  (c) from a copy shaped to whole tiles, [C, ceil(N / W), W] for W in 1024
      (partition.bins_by_column's shape) and 128, the leaf ids viewed alike;
  (b) from a feature-major copy [C, N];
  (a) from the row-major table, ``xb[:, c]``;

each with the leaf ids int32 and uint8. Also: the copy made on the device
(the transpose, once a set-up) and the ids widened back to int32 [N] (once
a tree). One JSON line a measurement, with the seconds since the start,
appended to chiprun_out/rowspace_route_lab.jsonl; the readings are in
PERF.md section 6 (PR 34). That run made its copies by ``pad(xb.T).reshape``
and had W = 4096 after 128: the call's limit ended it there, 20 minutes in,
with every line above printed and all the minutes in the transposes'
compiles (the GOSS cell's set-up then read 1,000 s for the one). The copies
here are made a column a step, as the library makes its own, and every line
carries its time.
"""
import json
import os
import sys
import time

import numpy as np

N = int(sys.argv[1]) if len(sys.argv) > 1 and not sys.argv[1].startswith("-") else 26_562_500
REPS = int(sys.argv[sys.argv.index("--reps") + 1]) if "--reps" in sys.argv else 3
C = 67
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from lightgbm_tpu.core.grow import MISSING_NAN, _bin_go_left

OUT = os.path.join(ROOT, "chiprun_out")


T0 = time.perf_counter()


def say(**kw):
    kw["device"] = jax.devices()[0].device_kind
    kw["at_s"] = round(time.perf_counter() - T0, 1)
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "rowspace_route_lab.jsonl"), "a") as f:
        f.write(line + "\n")


def med(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts)), out


def by_column(xb, width):
    """[N, C] -> [C, ceil(N / width), width], the tail padded
    (partition.bins_by_column at another width; width 0: [C, N]). A column
    a step: ``xb.T`` of this shape takes the v5e's compiler over a quarter
    of an hour, which is where the first run of this lab went."""
    n, c = xb.shape
    m = -(-n // width) if width else 0

    def column(j):
        col = lax.dynamic_index_in_dim(xb, j, 1, keepdims=False)
        return jnp.pad(col, (0, m * width - n)).reshape(m, width) \
            if width else col
    return lax.map(column, jnp.arange(c, dtype=jnp.int32))


def timed_copy(xb, width):
    """(compile seconds, median ms, the copy)."""
    t0 = time.perf_counter()
    fn = jax.jit(lambda x: by_column(x, width)).lower(xb).compile()
    compile_s = time.perf_counter() - t0
    ms, out = med(fn, xb)
    return compile_s, ms, out


def replay(take_col, id_dtype, shape):
    """254 splits over leaf ids of ``shape``, the bins' row shape;
    ``take_col(bins, c)`` is the split column in that shape."""
    def run(bins, split_leaf, col, thr):
        def step(t, lid):
            def route(lid):
                go_left = _bin_go_left(
                    take_col(bins, col[t]), thr[t], jnp.asarray(True),
                    jnp.int32(MISSING_NAN), jnp.int32(256), jnp.int32(0),
                    None, None)
                return jnp.where((lid == split_leaf[t].astype(id_dtype))
                                 & ~go_left, (t + 1).astype(id_dtype), lid)
            with jax.named_scope("lgbm.route_only"):
                return lax.cond(thr[t] >= 0, route, lambda lid: lid, lid)
        return lax.fori_loop(0, split_leaf.shape[0], step,
                             jnp.zeros(shape, id_dtype))
    return jax.jit(run)


def main():
    say(what="shape", n=N, c=C, reps=REPS, backend=jax.default_backend())
    tree = json.load(open(os.path.join(HERE, "schedule_lab_trees.json")))[
        "criteo-1of64-clicklog"][0]
    split_leaf = jnp.asarray(tree["split_leaf"], jnp.int32)
    depth = np.asarray(tree["depth"], np.int32)
    share_left = np.asarray(tree["left_count"], np.float64) \
        / np.asarray(tree["internal_count"], np.float64)
    col = jnp.asarray(depth % C, jnp.int32)
    thr = jnp.asarray(np.clip(np.round(share_left * 256) - 1, 0, 254),
                      jnp.int32)
    splits = int(split_leaf.shape[0])
    # made on the host and put on the device as the program puts its bins
    xb = jnp.asarray(np.random.default_rng(34).integers(
        0, 256, (N, C), dtype=np.uint8))
    dev = jax.devices()[0]

    def peak():
        stats = dev.memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    counts = {}

    def one(name, bins, take_col, shape):
        for dt in (jnp.int32, jnp.uint8):
            ms, lid = med(replay(take_col, dt, shape), bins, split_leaf, col,
                          thr)
            leaves = np.bincount(np.asarray(lid).reshape(-1)[:N],
                                 minlength=splits + 1)
            counts[(name, jnp.dtype(dt).name)] = leaves
            say(what="replay", how=name, ids=jnp.dtype(dt).name,
                splits=splits, ms_a_tree=ms, ms_a_split=ms / splits,
                largest_leaf=int(leaves.max()), leaves=int((leaves > 0).sum()))

    def take0(b, c):
        return lax.dynamic_index_in_dim(b, c, 0, keepdims=False)

    before = peak()
    for width in (1024, 128):
        compile_s, t_ms, cols = timed_copy(xb, width)
        say(what="copy", how="c_whole_tiles", width=width, ms=t_ms,
            compile_s=compile_s, bytes=int(cols.nbytes), peak_before=before,
            peak_after=peak())
        one("c_whole_tiles_%d" % width, cols, take0, cols.shape[1:])
        if width == 1024:
            ids = jnp.zeros(cols.shape[1:], jnp.uint8)
            t_ms, _ = med(jax.jit(
                lambda l: l.reshape(-1)[:N].astype(jnp.int32)), ids)
            say(what="ids_back_to_int32_rows", ms=t_ms)
        del cols
    compile_s, t_ms, xbt = timed_copy(xb, 0)
    say(what="copy", how="b_feature_major", ms=t_ms, compile_s=compile_s,
        bytes=int(xbt.nbytes), peak_after=peak())
    one("b_feature_major", xbt, take0, (N,))
    del xbt
    one("a_row_major", xb,
        lambda b, c: lax.dynamic_index_in_dim(b, c, 1, keepdims=False), (N,))
    ref = counts[("a_row_major", "int32")]
    say(what="agree", variants=len(counts),
        all_equal=bool(all(np.array_equal(ref, v) for v in counts.values())))


if __name__ == "__main__":
    main()
