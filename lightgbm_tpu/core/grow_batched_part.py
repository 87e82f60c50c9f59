"""Partitioned batched-frontier growth: K splits per step over rows kept
physically grouped by leaf.

Why this exists (round-4 on-chip measurements, docs/Performance.md): the
original batched mode (core/grow_batched.py) pays a FULL pass over all N
rows per sequential step, and its joint slot kernel contracts every row
against an S = 2K-wide slot one-hot — S x redundant MXU work, since each
row lands in exactly one slot. Measured on a v5e chip it LOSES to exact
growth (0.74 vs 1.79 iters/s at 1M x 28), inverting the CPU datapoint
that motivated it. Exact growth wins because its row partition
(core/partition.py) makes per-split cost track rows-in-leaf — but it
still pays the ~ms-scale sequential-step floor per SPLIT.

Measured outcome (v5e, 1M x 28, K = 16): the per-step ROW PERMUTATION —
one XLA gather over the [C, Np] bins + [3, Np] values, ~2.3 GB/s
effective, ~30 ms — and the per-tile output DMA latency of the
scalar-prefetch kernel cost more than the slot-redundancy they remove,
so this mode currently LOSES to both exact growth and the joint slot
kernel (0.25 vs 1.79 / 0.74 iters/s) and stays opt-in
(tpu_batched_part=true). It is kept because the design is the only one
whose per-step cost is asymptotically right (tracks splitting leaves'
rows, no S-factor); if the permutation moves into a device kernel or
XLA's gather improves, revisit docs/Performance.md's round-4 table.

This module combines two structural advantages:

- rows live physically grouped by leaf (the DataPartition invariant,
  data_partition.hpp:20-37) in row_tile-ALIGNED segments of a
  feature-major [C, Np] buffer, so each kernel row-tile belongs to at
  most one frontier leaf;
- each sequential step takes the top-K frontier leaves and routes,
  histograms, and splits them all at once — per-step cost tracks the
  SPLITTING leaves' rows (tiles outside them skip their compute body via
  a scalar-prefetched tile->slot map, histogram_pallas.py
  build_histogram_part_tiles), with zero slot-one-hot redundancy;
- both children of every splitting leaf are priced in ONE pass over the
  parent's rows: the per-row go-left bit routes (g, h, m) into left/right
  channel triples, which also doubles MXU row utilization (M = 96 vs 48);
- the layout is maintained by ONE dense permutation per step (a
  tile-aligned segmented cumsum computes every row's new position; XLA
  gathers move the [C, Np] bins, [3, Np] values and row metadata), the
  functional analog of DataPartition::Split.

Semantics are identical to grow_batched (approximate best-first, K = 1 ==
exact; node numbering tree.cpp:49-67); only row visit ORDER inside
histogram sums differs (f32 summation-order noise). Forced splits and
CEGB keep the exact path, same as grow_batched.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import build_histogram
from .grow import (GrowParams, TreeArrays, _empty_best, count_i32,
                   empty_tree, expand_hist)
from .grow_batched import (_combined_hist, _drop_set, apply_split_wave,
                           interleave_lr, route_split_rows,
                           scatter_child_best)
from .split import (BestSplit, FeatureMeta, K_MIN_SCORE,
                    calculate_leaf_output, find_best_split)

PART_TILE = 2048   # kernel row tile AND segment alignment quantum


def _local_slot_mask(slot_vals: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """[n_slots] bool: which slots appear in ``slot_vals`` (-1 = none).

    The pallas part-tiles kernel only WRITES the output block of a slot
    that owns at least one local row tile — a slot with no local tiles
    leaves its block uninitialized (histogram_pallas.py documents this).
    Under a data-parallel shard_map a globally-valid leaf can easily have
    zero rows on one shard, so masking by global validity alone would
    feed that shard's garbage block into the psum. Negative entries are
    routed to index ``n_slots`` and dropped (never wrapped to the last
    slot)."""
    idx = jnp.where(slot_vals >= 0, slot_vals, n_slots)
    return jnp.zeros((n_slots,), bool).at[idx].set(True, mode="drop")


def _part_capacity(n: int, num_leaves: int, tile: int) -> int:
    """Static padded row capacity: every leaf segment rounded up to a
    tile boundary fits, and the last row is guaranteed padding (the
    drop-target of the permutation scatter)."""
    return -(-n // tile) * tile + (num_leaves + 1) * tile


class _PartState(NamedTuple):
    xb_fm: jnp.ndarray        # [C, Np] uint8, feature-major, leaf-grouped
    vals3: jnp.ndarray        # [3, Np] f32 (g*m, h*m, m), same layout
    row_leaf: jnp.ndarray     # [Np] int32 leaf id (-1 = padding)
    orig: jnp.ndarray         # [Np] int32 original row id (-1 = padding)
    leaf_begin: jnp.ndarray   # [L] int32 (tile-aligned)
    leaf_count: jnp.ndarray   # [L] int32
    best: BestSplit           # per-leaf best split, fields [L]
    tree: TreeArrays
    leaf_min: jnp.ndarray     # [L] f32 monotone lower bound
    leaf_max: jnp.ndarray     # [L] f32 monotone upper bound


def grow_tree_batched_part(xb: jnp.ndarray, grad: jnp.ndarray,
                           hess: jnp.ndarray, sample_mask: jnp.ndarray,
                           meta: FeatureMeta, feature_mask: jnp.ndarray,
                           params: GrowParams,
                           axis_name: Optional[str] = None,
                           ) -> Tuple[TreeArrays, jnp.ndarray, None]:
    """Same contract as grow_batched.grow_tree_batched (returns
    (tree, per-row leaf_id in ORIGINAL row order, None))."""
    n, ncols = xb.shape
    l = params.num_leaves
    b = params.num_bins
    sp = params.split
    kb = max(1, min(params.batch_splits, l - 1))
    with_efb = params.with_efb
    tile = PART_TILE
    np_cap = _part_capacity(n, l, tile)
    n_tiles = np_cap // tile
    impl = params.hist_impl
    use_kernel = impl.startswith("pallas")

    def psum(x):
        return lax.psum(x, axis_name) if axis_name is not None else x

    def child_best(hist_col, sum_g, sum_h, cnt, min_c, max_c):
        return find_best_split(
            expand_hist(hist_col, sum_g, sum_h, cnt, meta, params, ncols),
            meta, sp, sum_g, sum_h, cnt, feature_mask,
            min_constraint=min_c, max_constraint=max_c,
            with_categorical=params.with_categorical)

    # ---- root (identical to grow_batched) -------------------------------
    sample_mask = sample_mask.astype(jnp.float32)
    root_g = psum(jnp.sum(grad * sample_mask))
    root_h = psum(jnp.sum(hess * sample_mask))
    root_c = psum(jnp.sum(sample_mask))
    hist_root = psum(build_histogram(xb, grad, hess, sample_mask, num_bins=b,
                                     row_chunk=params.row_chunk,
                                     impl=params.hist_impl))
    tree = empty_tree(l)
    tree = tree._replace(
        leaf_value=tree.leaf_value.at[0].set(
            calculate_leaf_output(root_g, root_h, sp.lambda_l1, sp.lambda_l2,
                                  sp.max_delta_step)),
        leaf_weight=tree.leaf_weight.at[0].set(root_h),
        leaf_count=tree.leaf_count.at[0].set(count_i32(root_c)))
    best0 = child_best(hist_root, root_g, root_h, root_c, -jnp.inf, jnp.inf)
    best = jax.tree.map(lambda a, v: a.at[0].set(v), _empty_best(l), best0)

    # ---- initial partitioned layout: leaf 0 owns [0, n) -----------------
    pad = np_cap - n
    ar = jnp.arange(np_cap, dtype=jnp.int32)
    xb_fm = jnp.pad(xb.T, ((0, 0), (0, pad))).astype(jnp.uint8)
    m = sample_mask
    vals3 = jnp.pad(jnp.stack([grad * m, hess * m, m], axis=0),
                    ((0, 0), (0, pad)))
    row_leaf = jnp.where(ar < n, 0, -1).astype(jnp.int32)
    orig = jnp.where(ar < n, ar, -1)
    if axis_name is not None:
        row_leaf = lax.pcast(row_leaf, (axis_name,), to="varying")
        orig = lax.pcast(orig, (axis_name,), to="varying")
    leaf_begin = jnp.zeros((l,), jnp.int32)
    leaf_count = jnp.zeros((l,), jnp.int32).at[0].set(jnp.int32(n))

    state = _PartState(
        xb_fm=xb_fm, vals3=vals3, row_leaf=row_leaf, orig=orig,
        leaf_begin=leaf_begin, leaf_count=leaf_count, best=best, tree=tree,
        leaf_min=jnp.full((l,), -jnp.inf, jnp.float32),
        leaf_max=jnp.full((l,), jnp.inf, jnp.float32))

    def cond_fn(s: _PartState) -> jnp.ndarray:
        return (s.tree.num_leaves < l) & jnp.any(s.best.gain > 0.0)

    def step(s: _PartState) -> _PartState:
        tree = s.tree
        nl = tree.num_leaves
        rank = jnp.arange(kb, dtype=jnp.int32)
        gval, gleaf = lax.top_k(s.best.gain, kb)
        valid = (gval > 0.0) & (rank < (l - nl))
        nvalid = jnp.sum(valid.astype(jnp.int32))
        node = (nl - 1) + rank
        right_leaf = nl + rank
        cur = jax.tree.map(lambda a: a[gleaf], s.best)     # fields [kb]

        # ---- per-row slot + go-left over the K split columns ------------
        rank_of_leaf = jnp.full((l,), -1, jnp.int32)
        rank_of_leaf = _drop_set(rank_of_leaf, gleaf, rank, valid)
        safe_rl = jnp.clip(s.row_leaf, 0, l - 1)
        slot_r = jnp.where(s.row_leaf >= 0, rank_of_leaf[safe_rl], -1)
        active = slot_r >= 0
        rs = jnp.maximum(slot_r, 0)

        onek = rank[:, None] == rs[None, :]                 # [kb, Np]
        go_left = route_split_rows(s.xb_fm, rank, rs, onek, cur, meta,
                                   with_efb, params.with_categorical)

        # ---- segmented left-counts via one cumsum -----------------------
        actL = active & go_left
        gl_cum = jnp.cumsum(actL.astype(jnp.int32))         # inclusive
        beg = s.leaf_begin[gleaf]                           # [kb]
        cnt = jnp.where(valid, s.leaf_count[gleaf], 0)
        base_l = jnp.where(beg > 0, gl_cum[jnp.maximum(beg - 1, 0)], 0)
        end_i = jnp.clip(beg + cnt - 1, 0, np_cap - 1)
        n_left = jnp.where(cnt > 0, gl_cum[end_i] - base_l, 0)
        n_right = cnt - n_left

        # ---- new tile-aligned layout ------------------------------------
        counts_new = _drop_set(s.leaf_count, gleaf, n_left, valid)
        counts_new = _drop_set(counts_new, right_leaf, n_right, valid)
        seg_tiles = -(-counts_new // tile)                  # ceil [L]
        begin_new = (jnp.cumsum(seg_tiles) - seg_tiles) * tile

        base_l_r = base_l[rs]
        lrank = gl_cum - 1 - base_l_r
        rrank = (ar - beg[rs]) - (gl_cum - base_l_r)
        pos_split = jnp.where(go_left,
                              begin_new[safe_rl] + lrank,
                              begin_new[jnp.minimum(right_leaf[rs], l - 1)]
                              + rrank)
        pos_unsplit = begin_new[safe_rl] + (ar - s.leaf_begin[safe_rl])
        pos = jnp.where(active, pos_split, pos_unsplit)
        pos = jnp.where(s.row_leaf >= 0, pos, np_cap)       # pads drop

        row_leaf_new = jnp.where(active & ~go_left,
                                 right_leaf[rs], s.row_leaf)

        # ---- all 2K children's histograms over the OLD layout -----------
        if use_kernel:
            from .histogram_pallas import build_histogram_part_tiles
            tstart = jnp.arange(n_tiles, dtype=jnp.int32) * tile
            slot_at = slot_r[tstart]                        # [T]
            prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32),
                                    slot_at[:-1]])
            first = ((slot_at >= 0) & (slot_at != prev)).astype(jnp.int32)
            hist6 = build_histogram_part_tiles(
                s.xb_fm, go_left.astype(jnp.float32), s.vals3,
                slot_at, first, num_bins=b, n_slots=kb, row_tile=tile,
                interpret=impl.endswith("interpret"),
                highest="highest" in impl)                  # [kb, C, B, 6]
            # the kernel leaves blocks of slots with NO local tiles
            # uninitialized; those slots can still be globally valid under
            # shard_map, so they must be zeroed here, per shard, before
            # the psum — validity alone is not enough
            has_tile = _local_slot_mask(slot_at, kb)        # [kb]
            ch_hist = jnp.stack([hist6[..., :3], hist6[..., 3:]],
                                axis=1).reshape(2 * kb, ncols, b, 3)
        else:
            # reference fallback (tests, CPU): combined-index build over
            # per-row child slots on the row-major view
            child_slot = jnp.where(active,
                                   rs * 2 + (~go_left).astype(jnp.int32), 0)
            ch_hist = _combined_hist(
                s.xb_fm.T, child_slot, active, s.vals3[0], s.vals3[1],
                s.vals3[2] * active.astype(jnp.float32), b, kb, impl,
                params.row_chunk, False)                    # [2K, C, B, 3]
            # scatter-built histograms are zero-initialized, so this mask
            # is a semantic no-op here — applying it anyway keeps the CPU
            # shard_map tests exercising the same masking the kernel needs
            has_tile = _local_slot_mask(jnp.where(active, slot_r, -1), kb)
        keep2 = jnp.repeat(valid & has_tile, 2)
        ch_hist = jnp.where(keep2[:, None, None, None], ch_hist, 0.0)
        ch_hist = psum(ch_hist)

        # ---- apply the permutation (DataPartition::Split analog) --------
        perm = jnp.full((np_cap,), np_cap - 1, jnp.int32)
        perm = perm.at[pos].set(ar, mode="drop")
        xb_fm2 = jnp.take(s.xb_fm, perm, axis=1)
        vals3_2 = jnp.take(s.vals3, perm, axis=1)
        row_leaf2 = row_leaf_new[perm]
        orig2 = s.orig[perm]

        # ---- tree bookkeeping for up to K splits (same as grow_batched) -
        (tree, leaf_min, leaf_max, safe_leaf,
         ch_min, ch_max, ch_ok) = apply_split_wave(
            tree, s.leaf_min, s.leaf_max, cur, gleaf, node, right_leaf,
            valid, nvalid, meta, sp, params.max_depth)

        # ---- best splits for all 2K children, one vmapped search --------
        ch_sg = interleave_lr(cur.left_sum_grad, cur.right_sum_grad)
        ch_sh = interleave_lr(cur.left_sum_hess, cur.right_sum_hess)
        ch_cnt = interleave_lr(cur.left_count, cur.right_count)
        b2k = jax.vmap(child_best)(ch_hist, ch_sg, ch_sh, ch_cnt,
                                   ch_min, ch_max)
        b2k = b2k._replace(gain=jnp.where(ch_ok, b2k.gain, K_MIN_SCORE))
        best = scatter_child_best(s.best, b2k, safe_leaf, right_leaf, valid)

        return _PartState(
            xb_fm=xb_fm2, vals3=vals3_2, row_leaf=row_leaf2, orig=orig2,
            leaf_begin=begin_new, leaf_count=counts_new, best=best,
            tree=tree, leaf_min=leaf_min, leaf_max=leaf_max)

    state = lax.while_loop(cond_fn, step, state)

    # ---- final per-row leaf ids in ORIGINAL row order -------------------
    safe_orig = jnp.where(state.orig >= 0, state.orig, n)
    leaf_id = jnp.zeros((n,), jnp.int32).at[safe_orig].set(
        jnp.maximum(state.row_leaf, 0), mode="drop")
    return state.tree, leaf_id, None
