"""The lab that decided PR 32: the two schedules of ISSUE 32 standalone.

  python tools/schedule_lab.py [N] [--reps R]        on the chip, ~14 minutes
  python tools/schedule_lab.py [N] --hlo now,s2,s1   compile for a v5e, no run

(1) a 4,096-row tile's kernel call at K = 3 and K = 6 value channels, and its
row gather through a random and through an ascending `order`; (2) one recorded
tree's 254 splits replayed on N x 67 resident rows under three schedules:
  now  one fused pass a split, six channels (the parent's partition_and_hist)
  s1   one fused pass a split, three channels weighted by is_small, + pool
  s2   the library's partition_rows, then hist_for_leaf over the smaller child, + pool
A replayed split routes on the column of its leaf's depth (all ancestors used
other columns, so the column is uniform within the leaf) at the threshold that
sends the recorded share left. One JSON line per measurement; the readings are
in PERF.md section 6 (PR 32). ``fused_pass`` is the parent's tile loop, which
the library no longer has. The trees (tools/schedule_lab_trees.json:
split_leaf, the split leaf's depth, internal_count and left count of each
split) were grown on the CPU at 400,000 rows by each cell's generator, seed
2200003001, three trees a configuration, with the configuration's parameters.
"""
import json
import os
import sys
import time

import numpy as np

N = int(sys.argv[1]) if len(sys.argv) > 1 and not sys.argv[1].startswith("-") else 26_562_500
REPS = int(sys.argv[sys.argv.index("--reps") + 1]) if "--reps" in sys.argv else 3
HLO = "--hlo" in sys.argv      # compile the replays for a described v5e, no run
if HLO:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
C, B, L, CHUNK = 67, 256, 255, 4096
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax
import jax.numpy as jnp
from jax import lax

from lightgbm_tpu.core.histogram import hist_tile_vals
from lightgbm_tpu.core.partition import (RowPartition, _write_window,
                                         hist_for_leaf, init_partition,
                                         make_row_gather, partition_rows,
                                         stack_vals)

IMPL = "pallas" if (jax.default_backend() == "tpu" or HLO) \
    else "pallas_interpret"


def say(**kw):
    print(json.dumps(kw), flush=True)


def med(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), out


def fused_pass(part, leaf, right_leaf, go_left_from_rows, chunk, gather_rows,
               weights):
    """The parent's partition_and_hist (windows placement), with the value
    channels given by ``weights(is_l, is_r) -> [chunk, K / 3]`` masks."""
    n_rows = part.order.shape[0] - chunk
    beg = part.leaf_begin[leaf]
    cnt = part.leaf_count[leaf]
    k = 3 * len(weights(jnp.zeros((chunk,), bool), jnp.zeros((chunk,), bool)))

    def cond(c):
        return c[0] * chunk < cnt

    def body(c):
        i, nl, nr, order_new, acc = c
        j = jnp.arange(chunk, dtype=jnp.int32)
        in_range = (i * chunk + j) < cnt
        idx = lax.dynamic_slice(part.order, (beg + i * chunk,), (chunk,))
        idx_safe = jnp.minimum(idx, n_rows - 1)
        rows, v = gather_rows(idx_safe)
        go_left = go_left_from_rows(rows)
        is_l = go_left & in_range
        is_r = (~go_left) & in_range
        vk = jnp.concatenate([v * w[:, None].astype(v.dtype)
                              for w in weights(is_l, is_r)], axis=1)
        acc = acc + hist_tile_vals(rows, vk, B, IMPL)
        kl = jnp.sum(is_l.astype(jnp.int32), dtype=jnp.int32)
        kr = jnp.sum(is_r.astype(jnp.int32), dtype=jnp.int32)
        key = jnp.where(is_l, j, jnp.where(is_r, 3 * chunk - j, chunk + j))
        _, packed = lax.sort((key, idx), num_keys=1, is_stable=False)
        order_new = _write_window(order_new, packed, kl, beg + nl)
        order_new = _write_window(order_new, jnp.roll(packed, kr), kr,
                                  beg + cnt - nr - kr)
        return (i + 1, nl + kl, nr + kr, order_new, acc)

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), part.order,
            jnp.zeros((C, B, k), jnp.float32))
    _, n_left, n_right, order_new, acc = lax.while_loop(cond, body, init)
    leaf_begin = part.leaf_begin.at[right_leaf].set(beg + n_left)
    leaf_count = part.leaf_count.at[leaf].set(n_left) \
        .at[right_leaf].set(n_right)
    return RowPartition(order_new, leaf_begin, leaf_count), acc


def replay(schedule, n):
    def run(xb, g, h, m, part, split_leaf, col, thr, left_small):
        # as grow_tree builds it: the stack fuses into the packing pass
        gather_rows = make_row_gather(xb, stack_vals(g, h, m))
        pool = jnp.zeros((L, C, B, 3), jnp.float32)

        def step(t, s):
            part, pool, chk, rows_p, rows_s = s
            leaf, right_leaf = split_leaf[t], t + 1
            onehot = (jnp.arange(C, dtype=jnp.int32) == col[t]) \
                .astype(jnp.float32)

            def go_left(rows):
                colv = jnp.einsum("rc,c->r", rows.astype(jnp.float32),
                                  onehot).astype(jnp.int32)
                return colv <= thr[t]

            ls = left_small[t]
            small = jnp.where(ls, leaf, right_leaf)
            large = jnp.where(ls, right_leaf, leaf)
            rows_p = rows_p + part.leaf_count[leaf]
            if schedule == "now":
                part, acc = fused_pass(part, leaf, right_leaf, go_left, CHUNK,
                                       gather_rows, lambda l, r: (l, r))
                chk = chk + acc[0, 0, 0] + acc[1, 1, 3]
            else:
                if schedule == "s1":
                    part, h_small = fused_pass(
                        part, leaf, right_leaf, go_left, CHUNK, gather_rows,
                        lambda l, r: (jnp.where(ls, l, r),))
                else:
                    part, _ = partition_rows(
                        part, jnp.zeros((n,), jnp.int32), leaf, right_leaf,
                        go_left, jnp.asarray(True), CHUNK, gather_rows,
                        windows=True)
                    h_small = hist_for_leaf(part, small, gather_rows, n, C, B,
                                            CHUNK, impl=IMPL)
                h_large = pool[leaf] - h_small
                pool = pool.at[small].set(h_small).at[large].set(h_large)
                chk = chk + h_small[0, 0, 0] + h_large[1, 1, 0]
            rows_s = rows_s + part.leaf_count[small]
            return part, pool, chk, rows_p, rows_s

        s = lax.fori_loop(0, split_leaf.shape[0], step,
                          (part, pool, jnp.float32(0), jnp.int32(0),
                           jnp.int32(0)))
        return s[2], s[3], s[4], s[0].leaf_count
    return jax.jit(run)


def compile_only():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    part = RowPartition(sds((N + CHUNK,), jnp.int32), sds((L,), jnp.int32),
                        sds((L,), jnp.int32))
    args = (sds((N, C), jnp.uint8), sds((N,), jnp.float32),
            sds((N,), jnp.float32), sds((N,), jnp.float32), part,
            sds((254,), jnp.int32), sds((254,), jnp.int32),
            sds((254,), jnp.int32), sds((254,), jnp.bool_))
    for schedule in sys.argv[sys.argv.index("--hlo") + 1].split(","):
        t0 = time.perf_counter()
        compiled = replay(schedule, N).lower(*args).compile()
        say(what="compiled", schedule=schedule,
            seconds=time.perf_counter() - t0,
            memory=str(compiled.memory_analysis())[:400])


def main():
    if HLO:
        return compile_only()
    say(device=jax.devices()[0].device_kind, backend=jax.default_backend(),
        n=N, impl=IMPL, reps=REPS)
    # made on the host and put on the device as the program puts its bins
    # (jax.random.bits of N x 67 uint8 wants 7 GB of 32-bit temporaries)
    rng = np.random.default_rng(32)
    xb = jnp.asarray(rng.integers(0, 256, (N, C), dtype=np.uint8))
    g = jnp.asarray(rng.standard_normal(N, dtype=np.float32))
    h = jnp.asarray(rng.random(N, dtype=np.float32))
    m = jnp.ones((N,), jnp.float32)
    k3 = jax.random.PRNGKey(32)
    per_call(xb, g, h, m, k3)
    jax.clear_caches()          # and the packed rows its loops closed over
    schedules(xb, g, h, m)


def per_call(xb, g, h, m, k3):
    tiles = min(64, N // CHUNK)
    calls = 2000 if jax.default_backend() == "tpu" else 2 * tiles
    rows_buf = xb[:tiles * CHUNK].reshape(tiles, CHUNK, C)
    for k in (3, 6):
        v_buf = jax.random.normal(k3, (tiles, CHUNK, k), jnp.float32)

        @jax.jit
        def kernel_loop(rows_buf, v_buf):
            def body(i, acc):
                return acc + hist_tile_vals(rows_buf[i % tiles],
                                            v_buf[i % tiles], B, IMPL)
            return lax.fori_loop(0, calls, body,
                                 jnp.zeros((C, B, k), jnp.float32))
        t, _ = med(kernel_loop, rows_buf, v_buf)
        say(what="kernel_call", channels=k, rows=CHUNK,
            us_a_call=t / calls * 1e6, ns_a_row=t / calls / CHUNK * 1e9)
    for name, order in (
            ("random", jnp.asarray(np.random.default_rng(3).permutation(N)
                                   .astype(np.int32))),
            ("ascending_stride_13", (jnp.arange(N, dtype=jnp.int32) * 13) % N)):
        @jax.jit
        def gather_loop(order, xb, g, h, m):
            # the packed rows are made inside, as in the train block (a
            # closure over them would lower as a 2.1 GB constant)
            gather_rows = make_row_gather(xb, stack_vals(g, h, m))

            def body(i, acc):
                idx = lax.dynamic_slice(order, (i * CHUNK,), (CHUNK,))
                rows, v = gather_rows(idx)
                return acc + jnp.sum(rows.astype(jnp.int32), axis=0)[:3] \
                    + jnp.sum(v, axis=0).astype(jnp.int32)
            return lax.fori_loop(0, calls, body, jnp.zeros((3,), jnp.int32))
        t, _ = med(gather_loop, order, xb, g, h, m)
        say(what="row_gather", order=name, us_a_call=t / calls * 1e6)
        del order


def schedules(xb, g, h, m):
    fns = {}
    trees = json.load(open(os.path.join(HERE, "schedule_lab_trees.json")))
    for cfg, which in (("criteo-1of64", 0), ("criteo-1of64-clicklog", 1)):
        tr = trees[cfg][which]
        ic = np.asarray(tr["internal_count"], np.float64)
        lc = np.asarray(tr["left_count"], np.float64)
        frac = lc / ic
        args = (jnp.asarray(tr["split_leaf"], jnp.int32),
                jnp.asarray(np.minimum(tr["depth"], C - 1), jnp.int32),
                jnp.asarray(np.round(frac * 256) - 1, jnp.int32),
                jnp.asarray(frac <= 0.5))
        say(what="recorded", tree=cfg, splits=len(ic),
            sum_parent_over_n=float(ic.sum() / 400_000),
            alpha=float(np.minimum(lc, ic - lc).sum() / ic.sum()))
        for schedule in ("now", "s2", "s1"):
            t0 = time.perf_counter()
            fn = fns.setdefault(schedule, replay(schedule, N))
            t, (chk, rows_p, rows_s, counts) = med(
                fn, xb, g, h, m, init_partition(N, L, CHUNK), *args)
            say(what="replay", tree=cfg, schedule=schedule, seconds=t,
                first_call_s=time.perf_counter() - t0 - REPS * t,
                parent_rows_over_n=int(rows_p) / N,
                small_rows_over_n=int(rows_s) / N,
                parent_tiles=int(rows_p) / CHUNK, small_tiles=int(rows_s) / CHUNK,
                leaves_live=int((np.asarray(counts) > 0).sum()),
                checksum=float(chk))


if __name__ == "__main__":
    main()
