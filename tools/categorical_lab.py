"""The lab behind PR 35: what a table WITH categorical columns costs.

  python tools/categorical_lab.py train [--rows N] [--seed S]     on the chip
  python tools/categorical_lab.py route [--rows N]
  python tools/categorical_lab.py finder
  python tools/categorical_lab.py gather [--rows N]

It imports ``lightgbm_tpu`` and ``bench`` from the checkout it stands in, so
a copy laid into an export of another commit (with the new configuration and
generator beside it) reads THAT program: the baseline of PERF.md section 5's
categorical column is ``train`` run so on the parent of PR 35.

``train``  the configuration criteo-1of48-categorical as its cell runs it:
           data from the seed, ``Dataset.construct()``, ``engine.train`` of
           one iteration (the cold compile), one more, then one under
           ``jax.profiler`` reduced by ``capture_phases``; the program's
           ingest spans, the share of categorical splits, the size of the
           model text and the seconds to write and parse it.
``route``  the membership test of one split's 256-bit category set, standalone:
           (a) the word looked up by ``bitset[bin >> 5]`` (a gather),
           (b) eight selects on ``bin >> 5``, (c) a compare against the (at
           most 32) member bins, (d) a one-hot product; each over tiles of
           4,096 bins (2,048 tiles with a set of their own, in one loop) and
           over one pass of ``rows`` uint8 bins.
``finder`` ``per_feature_split_categorical`` over a recorded-shape histogram,
           508 calls a tree in one scan: all 39 columns, and the 26
           categorical ones alone.

``gather`` the tile's row gather alone, 4,096 rows of W bytes out of N, by
           W (51 = 39 columns + 12 value bytes, 52, 64, 79, 128) and by N
           (35.4M, 26.6M), and from a table folded in two (two rows abreast,
           half the length): what found that a packed row under ~57 bytes is
           gathered four times as slowly (core/partition.py MIN_PACKED_WIDTH).

One JSON line a measurement, appended to chiprun_out/categorical_lab.jsonl;
the readings are in PERF.md section 6 (PR 35).
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")
T0 = time.perf_counter()


def say(**kw):
    import jax
    kw["device"] = jax.devices()[0].device_kind
    kw["at_s"] = round(time.perf_counter() - T0, 1)
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "categorical_lab.jsonl"), "a") as f:
        f.write(line + "\n")


def median_s(fn, reps=3):
    import jax
    jax.block_until_ready(fn())
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t)
    return float(np.median(out))


# ------------------------------------------------------------------ train
def train(args):
    import jax
    import lightgbm_tpu as lgb
    from bench.generators import clicklog_categorical
    from lightgbm_tpu.io.model_text import parse_model_string
    from lightgbm_tpu.obs import trace
    from lightgbm_tpu.profiling import (compile_cache_stats,
                                        enable_compile_cache)

    with open(os.path.join(ROOT, "bench", "configs",
                           "criteo-1of48-categorical.json")) as f:
        cfg = json.load(f)
    data = dict(cfg["data"])
    if args.rows:
        data["rows"] = args.rows
    params = dict(cfg["params"])
    enable_compile_cache()
    c0 = compile_cache_stats()
    t = time.perf_counter()
    X, y = clicklog_categorical.generate(args.seed, **data)
    say(phase="train", what="data_s", s=time.perf_counter() - t,
        rows=len(y), cols=X.shape[1], program=ROOT)
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(params)).construct()
    say(phase="train", what="binning_s", s=time.perf_counter() - t)
    for s in trace.recorded_spans():
        if s["name"].startswith("ingest."):
            say(phase="train", what="span", name=s["name"],
                s=(s["end_ns"] - s["start_ns"]) / 1e9, counts=s["counts"])
    ds.data = None
    del X
    t = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=1)
    gbdt = bst._impl
    jax.block_until_ready(gbdt.scores)
    c1 = compile_cache_stats()
    say(phase="train", what="first_block_s", s=time.perf_counter() - t,
        compile_s=c1["backend_compile_seconds"] - c0["backend_compile_seconds"],
        cache_misses=c1["persistent_cache_misses"]
        - c0["persistent_cache_misses"],
        with_categorical=bool(gbdt.grow_params.with_categorical),
        hist_impl=gbdt.grow_params.hist_impl)
    for s in trace.recorded_spans():
        if s["name"] in ("train.setup", "train.device_put_bins"):
            say(phase="train", what="span", name=s["name"],
                s=(s["end_ns"] - s["start_ns"]) / 1e9, counts=s["counts"])
    t = time.perf_counter()
    gbdt.train_many(1)
    jax.block_until_ready(gbdt.scores)
    say(phase="train", what="second_block_s", s=time.perf_counter() - t)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(trace_dir)
    t = time.perf_counter()
    gbdt.train_many(1)
    jax.block_until_ready(gbdt.scores)
    wall = time.perf_counter() - t
    jax.profiler.stop_trace()
    phases = trace.capture_phases(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    say(phase="train", what="traced_block", wall_s=wall, phases=phases)
    for _ in range(args.more):
        t = time.perf_counter()
        gbdt.train_many(1)
        jax.block_until_ready(gbdt.scores)
        say(phase="train", what="block_s", s=time.perf_counter() - t)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    t = time.perf_counter()
    text = bst.model_to_string(num_iteration=-1)
    t_write = time.perf_counter() - t
    t = time.perf_counter()
    parse_model_string(text)
    t_parse = time.perf_counter() - t
    cat, total = [], []
    for block in text.split("\nTree=")[1:]:
        kv = dict(ln.split("=", 1) for ln in block.splitlines() if "=" in ln)
        dt = np.array(kv.get("decision_type", "").split(), np.int64)
        cat.append(int((dt & 1).sum()))
        total.append(len(dt))
    say(phase="train", what="model", memory_peak_bytes=int(peak),
        text_bytes=len(text), write_s=t_write, parse_s=t_parse,
        cat_splits=cat, splits=total)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "categorical_lab_model_%d.txt" % args.seed),
              "w") as f:
        f.write(text)


# ------------------------------------------------------------------ route
def member_gather(coli, bitset):
    word = bitset[coli >> 5]
    return ((word >> (coli & 31).astype(np.uint32)) & 1) == 1


def member_selects(coli, bitset):
    import jax.numpy as jnp
    w = coli >> 5
    word = bitset[7]
    for i in range(6, -1, -1):
        word = jnp.where(w == i, bitset[i], word)
    return ((word >> (coli & 31).astype(jnp.uint32)) & 1) == 1


def member_compare(coli, members):
    """``members`` [32] int32 bins going left, -1 where unused."""
    return (coli[:, None] == members[None, :]).any(axis=1)


def member_onehot(coli, table):
    """``table`` [256] float32 0/1 membership by bin."""
    import jax
    import jax.numpy as jnp
    oh = jax.nn.one_hot(coli, 256, dtype=jnp.bfloat16)
    return (oh @ table.astype(jnp.bfloat16)) > 0.5


def route(args):
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(35)
    tiles, tile = 2048, 4096
    n = args.rows or 35_416_666
    n = -(-n // tile) * tile
    col8 = jnp.asarray(rng.integers(0, 255, size=n, dtype=np.uint8))
    # a sorted-subset split's set: up to 32 of 254 bins
    members = np.full((tiles, 32), -1, np.int32)
    bitsets = np.zeros((tiles, 8), np.uint32)
    tables = np.zeros((tiles, 256), np.float32)
    for i in range(tiles):
        m = rng.choice(np.arange(1, 255), size=rng.integers(1, 33),
                       replace=False)
        members[i, :len(m)] = m
        tables[i, m] = 1.0
        for b in m:
            bitsets[i, b >> 5] |= np.uint32(1 << (b & 31))
    members, bitsets, tables = map(jnp.asarray, (members, bitsets, tables))
    forms = {"gather": (member_gather, bitsets),
             "selects": (member_selects, bitsets),
             "compare32": (member_compare, members),
             "onehot": (member_onehot, tables)}
    want = None
    for name, (fn, sets) in forms.items():
        @jax.jit
        def tile_loop(col8, sets, fn=fn):
            def body(i, acc):
                c = lax.dynamic_slice(col8, (i * tile,), (tile,))
                left = fn(c.astype(jnp.int32), sets[i])
                return acc + jnp.sum(left.astype(jnp.int32))
            return lax.fori_loop(0, tiles, body, jnp.int32(0))

        @jax.jit
        def full_pass(col8, sets, fn=fn):
            left = fn(col8.astype(jnp.int32), sets[0])
            return jnp.where(left, jnp.uint8(1), jnp.uint8(2))

        got = int(tile_loop(col8, sets))
        want = got if want is None else want
        t_tile = median_s(lambda: tile_loop(col8, sets)) / tiles
        t_full = median_s(lambda: full_pass(col8, sets))
        say(phase="route", form=name, rows_left=got, agrees=got == want,
            us_a_tile=1e6 * t_tile, ms_a_pass=1e3 * t_full, rows=n)

    # the floor of both: the same loops with a plain threshold compare
    @jax.jit
    def tile_floor(col8):
        def body(i, acc):
            c = lax.dynamic_slice(col8, (i * tile,), (tile,))
            return acc + jnp.sum((c.astype(jnp.int32) <= i % 255)
                                 .astype(jnp.int32))
        return lax.fori_loop(0, tiles, body, jnp.int32(0))

    @jax.jit
    def full_floor(col8):
        return jnp.where(col8.astype(jnp.int32) <= 100, jnp.uint8(1),
                         jnp.uint8(2))

    say(phase="route", form="numerical_compare",
        us_a_tile=1e6 * median_s(lambda: tile_floor(col8)) / tiles,
        ms_a_pass=1e3 * median_s(lambda: full_floor(col8)), rows=n)


# ----------------------------------------------------------------- gather
def gather(args):
    """The tile's row gather alone: 4,096 rows of W bytes out of N, by
    width and by the table's length (the exact grower packs a row's bins
    and its three float32 values into W = columns + 12 bytes)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    tiles, tile = 1024, 4096
    rng = np.random.default_rng(37)
    shapes = [(args.rows or 35_416_666, (51, 52, 64, 79, 128))]
    if not args.rows:
        shapes.append((26_562_500, (51, 79)))    # the siblings' length
    for n, widths in shapes:
        idx = jnp.asarray(rng.integers(0, n, size=(tiles, tile),
                                       dtype=np.int32))
        for w in widths:
            table = jax.jit(lambda k, w=w, n=n: jax.random.randint(
                k, (n, w), 0, 255, dtype=jnp.uint8))(jax.random.PRNGKey(w))

            @jax.jit
            def loop(table, idx):
                def body(i, acc):
                    rows = table[idx[i]]
                    return acc + jnp.sum(rows[:, :4].astype(jnp.int32))
                return lax.fori_loop(0, tiles, body, jnp.int32(0))

            say(phase="gather", rows=n, width=w,
                us_a_tile=1e6 * median_s(lambda: loop(table, idx)) / tiles)
            if n > 1 << 25 and w in (51, 79):
                # two rows side by side in a table of half the length:
                # row i is half i & 1 of super-row i >> 1
                half = (n + 1) // 2
                folded = jax.jit(lambda t: jnp.concatenate(
                    [t[0::2], jnp.pad(t[1::2], ((0, half - n // 2), (0, 0)))],
                    axis=1))(table)

                @jax.jit
                def loop2(folded, idx):
                    def body(i, acc):
                        q = folded[idx[i] >> 1]
                        rows = jnp.where((idx[i] & 1)[:, None] == 1,
                                         q[:, w:], q[:, :w])
                        return acc + jnp.sum(rows[:, :4].astype(jnp.int32))
                    return lax.fori_loop(0, tiles, body, jnp.int32(0))

                t = time.perf_counter()
                same = int(loop2(folded, idx)) == int(loop(table, idx))
                say(phase="gather", rows=n, width=w, form="folded_in_two",
                    agrees=same, first_call_s=time.perf_counter() - t,
                    us_a_tile=1e6 * median_s(lambda: loop2(folded, idx))
                    / tiles)
                del folded
            del table


# ----------------------------------------------------------------- finder
def finder(args):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from lightgbm_tpu.core import split as split_mod

    rng = np.random.default_rng(36)
    f, b, calls = 39, 256, 508
    num_bin = np.array([255] * 13 + [255] * 16 + [4, 5, 11, 16, 19, 25, 28,
                                                   106, 255, 255], np.int32)
    is_cat = np.arange(f) >= 13
    cnt = rng.integers(0, 5000, size=(f, b)).astype(np.float32)
    cnt *= (np.arange(b)[None, :] < num_bin[:, None])
    g = (rng.standard_normal((f, b)) * np.sqrt(cnt) * 0.2).astype(np.float32)
    hist = jnp.asarray(np.stack([g, cnt * 0.03, cnt], axis=-1))
    meta = split_mod.FeatureMeta(
        num_bin=jnp.asarray(num_bin),
        missing_type=jnp.zeros(f, jnp.int32),
        default_bin=jnp.zeros(f, jnp.int32),
        is_categorical=jnp.asarray(is_cat),
        penalty=jnp.ones(f, jnp.float32), monotone=jnp.zeros(f, jnp.int32))
    sp = split_mod.SplitParams(
        lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0, min_data_in_leaf=20,
        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
        max_cat_threshold=32, cat_smooth=10.0, cat_l2=10.0,
        max_cat_to_onehot=4, min_data_per_group=100)
    mask = jnp.ones(f, bool)

    def best(h, scale):
        h = h * scale
        tot = jnp.sum(h[0], axis=0)
        return split_mod.find_best_split(h, meta, sp, tot[0], tot[1], tot[2],
                                         mask, with_categorical=True)

    @jax.jit
    def tree_of_calls(hist):
        def body(acc, i):
            r = best(hist, 1.0 + 0.001 * i.astype(jnp.float32))
            return acc + r.gain + r.cat_bitset[0].astype(jnp.float32), None
        return lax.scan(body, jnp.float32(0),
                        jnp.arange(calls, dtype=jnp.int32))[0]

    t = time.perf_counter()
    jax.block_until_ready(tree_of_calls(hist))
    say(phase="finder", what="compile_and_first_s", s=time.perf_counter() - t,
        program=ROOT)
    say(phase="finder", what="find_best_split_x508_ms",
        ms=1e3 * median_s(lambda: tree_of_calls(hist)), program=ROOT)

    @jax.jit
    def numeric_only(hist):
        def body(acc, i):
            h = hist * (1.0 + 0.001 * i.astype(jnp.float32))
            tot = jnp.sum(h[0], axis=0)
            r = split_mod.find_best_split(h, meta, sp, tot[0], tot[1], tot[2],
                                          mask, with_categorical=False)
            return acc + r.gain, None
        return lax.scan(body, jnp.float32(0),
                        jnp.arange(calls, dtype=jnp.int32))[0]

    say(phase="finder", what="numerical_only_x508_ms",
        ms=1e3 * median_s(lambda: numeric_only(hist)), program=ROOT)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("phase", choices=("train", "route", "finder", "gather"))
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--seed", type=int, default=3500003001)
    ap.add_argument("--more", type=int, default=2)
    a = ap.parse_args()
    {"train": train, "route": route, "finder": finder,
     "gather": gather}[a.phase](a)
