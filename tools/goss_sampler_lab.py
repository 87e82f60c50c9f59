"""The lab behind PR 33's sampler: what finding a GOSS bag costs standalone.

  python tools/goss_sampler_lab.py [N] [--reps R] [--skip-topk]   on the chip

At N rows (26,562,500: the benchmark's share) with |g*h| as a binary
objective leaves it after ten trees of 255 leaves (a few thousand distinct
values, so thousands of exact ties at any threshold), k = N/5:

(1) the threshold of the k largest, four ways: the 32 counting passes the
    library keeps (boosting/goss.py ``top_k_mask``, exact, ties by row id),
    a full ``sort``, a 64k-bin histogram of the float's high 16 bits and
    then of the boundary bin's low 16 (two scatter-adds), and the parent's
    ``lax.top_k`` (last: it is the one that may not come back);
(2) the whole sampler (two selections and the random keys);
(3) moving the bag to the front of ``order``, three ways: the library's
    stable two-key ``sort`` (partition.bag_partition), one prefix sum and a
    full-size scatter, partition_rows' tile loop over the identity order.
One JSON line a measurement, also appended to chiprun_out/goss_sampler_lab.jsonl;
the readings are in PERF.md section 6 (PR 33).
"""
import json
import os
import sys
import time

import numpy as np

N = int(sys.argv[1]) if len(sys.argv) > 1 and not sys.argv[1].startswith("-") else 26_562_500
REPS = int(sys.argv[sys.argv.index("--reps") + 1]) if "--reps" in sys.argv else 3
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from lightgbm_tpu.boosting.goss import bag_counts, sample_bag, top_k_mask
from lightgbm_tpu.core.partition import (bag_partition, init_partition,
                                         partition_rows)

CHUNK = 4096
OUT = os.path.join(ROOT, "chiprun_out")


def say(**kw):
    kw["device"] = jax.devices()[0].device_kind
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "goss_sampler_lab.jsonl"), "a") as f:
        f.write(line + "\n")


def med(fn, *args):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts))


def make_gh(n):
    """|g*h| of a logistic loss at 3.4% positives where the score takes
    4,000 distinct values: p(1-p) * |y - p|."""
    rng = np.random.default_rng(33)
    score = rng.normal(-3.3, 1.2, 4000).astype(np.float32)[
        rng.integers(0, 4000, n)]
    y = rng.random(n) < 1 / (1 + np.exp(-score.astype(np.float64)))
    p = 1 / (1 + np.exp(-score))
    return jnp.asarray((np.abs(y - p) * p * (1 - p)).astype(np.float32))


def thr_sort(gh, k):
    return jnp.sort(gh)[gh.shape[0] - k]


def thr_topk(gh, k):
    return lax.top_k(gh, k)[0][-1]


def thr_hist16(gh, k):
    """Two scatter-adds of 65,536 bins: the high 16 bits, then the low 16
    inside the boundary bin."""
    bits = lax.bitcast_convert_type(gh, jnp.uint32)
    hi = (bits >> 16).astype(jnp.int32)
    h1 = jnp.zeros((65536,), jnp.int32).at[hi].add(1)
    above = jnp.cumsum(h1[::-1])[::-1]            # rows with hi >= b
    b = jnp.sum((above >= k).astype(jnp.int32)) - 1
    need = k - (above[b] - h1[b])
    lo = (bits & 0xFFFF).astype(jnp.int32)
    h2 = jnp.zeros((65536,), jnp.int32).at[jnp.where(hi == b, lo, 65536)] \
        .add(1, mode="drop")
    above2 = jnp.cumsum(h2[::-1])[::-1]
    b2 = jnp.sum((above2 >= need).astype(jnp.int32)) - 1
    return lax.bitcast_convert_type(
        (b.astype(jnp.uint32) << 16) | b2.astype(jnp.uint32), jnp.float32)


def thr_passes(gh, k):
    m = top_k_mask(lax.bitcast_convert_type(gh, jnp.uint32), k)
    return jnp.min(jnp.where(m, gh, jnp.inf)), jnp.sum(m)


def compact_scatter(in_bag):
    n = in_bag.shape[0]
    c = jnp.cumsum(in_bag.astype(jnp.int32))
    pos = jnp.where(in_bag, c - 1,
                    c[-1] + jnp.arange(n, dtype=jnp.int32) - c)
    return jnp.zeros((n,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="promise_in_bounds")


def compact_tiles(in_bag):
    """partition_rows over the identity order with ``in_bag`` as the split
    decision: a tile's flags come by a contiguous slice."""
    n = in_bag.shape[0]
    flags = jnp.concatenate([in_bag, jnp.zeros((CHUNK,), bool)])
    part, _ = partition_rows(
        init_partition(n, 2, CHUNK), jnp.zeros((n,), jnp.int32),
        jnp.int32(0), jnp.int32(1), lambda f: f, jnp.asarray(True), CHUNK,
        lambda idx: (lax.dynamic_slice(flags, (idx[0],), (CHUNK,)), None),
        windows=jax.default_backend() == "tpu")
    return part.order


def main():
    top_cnt, other_cnt, _ = bag_counts(N, 0.2, 0.1)
    gh = make_gh(N)
    key = jax.random.PRNGKey(7)
    say(what="shape", n=N, top_cnt=top_cnt, other_cnt=other_cnt,
        distinct=int(len(np.unique(np.asarray(gh[:2_000_000])))))

    thr, cnt = jax.jit(thr_passes, static_argnums=1)(gh, top_cnt)
    say(what="threshold", how="passes", thr=float(thr), kept=int(cnt),
        at_or_above=int(jnp.sum(gh >= thr)),
        ms=med(jax.jit(thr_passes, static_argnums=1), gh, top_cnt))
    for how, fn in (("sort", thr_sort), ("hist16", thr_hist16)):
        f = jax.jit(fn, static_argnums=1)
        say(what="threshold", how=how, thr=float(f(gh, top_cnt)),
            ms=med(f, gh, top_cnt))

    sampler = jax.jit(lambda g, k: sample_bag(g, k, top_cnt, other_cnt))
    w = sampler(gh, key)
    say(what="sampler", tops=int(jnp.sum(w == 1)),
        others=int(jnp.sum(w == 2)), ms=med(sampler, gh, key))
    bits = jax.jit(lambda k: jax.random.bits(k, (N,), jnp.uint32))
    say(what="random_bits", ms=med(bits, key))

    in_bag = w > 0
    kept = jax.jit(lambda m: bag_partition(m, CHUNK))
    part = kept(in_bag)
    ref = np.asarray(compact_scatter(in_bag))
    n_bag = int(part.leaf_count[0])
    say(what="compact", how="sort (kept)", bag=n_bag,
        front_equal=bool((np.asarray(part.order[:n_bag]) == ref[:n_bag]).all()),
        back_is_rest=bool((np.sort(np.asarray(part.order[n_bag:N]))
                           == ref[n_bag:]).all()),
        ms=med(kept, in_bag))
    say(what="compact", how="scatter", ms=med(jax.jit(compact_scatter), in_bag))
    say(what="compact", how="tiles", ms=med(jax.jit(compact_tiles), in_bag))

    if "--skip-topk" not in sys.argv:
        f = jax.jit(thr_topk, static_argnums=1)
        t0 = time.perf_counter()
        v = float(f(gh, top_cnt))
        say(what="threshold", how="lax.top_k", thr=v,
            first_call_s=time.perf_counter() - t0, ms=med(f, gh, top_cnt))


if __name__ == "__main__":
    main()
