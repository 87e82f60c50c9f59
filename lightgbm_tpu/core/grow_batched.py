"""Batched-frontier tree growth: split many leaves per sequential step.

Why this exists (docs/Performance.md "Known limits"): on TPU, per-split
latency inside a sequential growth loop has a ~1-1.5 ms floor set by the
dependency chain partition -> child split scans -> next leaf choice —
nearly independent of how fast the histogram kernel is. Exact leaf-wise
(best-first) growth (serial_tree_learner.cpp:169-233) therefore costs
~(num_leaves - 1) x floor per tree no matter what. This module amortizes
the floor: each sequential step takes the TOP-K leaves of the frontier by
best gain and splits them all at once — one fused routing pass, one
multi-leaf histogram build, one vmapped split search, one set of scatters
per STEP instead of per SPLIT. A 255-leaf tree takes ~20 steps at K=16
instead of 254.

Semantics: this is *approximate* best-first. Exact leaf-wise would re-rank
after every single split (a child can out-gain the current second-best
leaf); top-K batching commits to K splits per re-rank. K=1 reproduces the
exact algorithm (and is tested to). The accuracy contract follows the
reference's own precedent for its GPU learner — small, documented
deviations from the CPU algorithm in exchange for device throughput
(GPU-Performance.rst:132-139) — opt-in via ``tree_growth=batched``.
Forced splits and CEGB keep the exact path (their per-split accounting is
order-dependent).

Design notes (same profiling facts as core/partition.py):
- rows are routed by ONE dense table-gather pass per step: each row reads
  its leaf's split-rank (-1 = leaf not splitting), gathers its split's
  feature column byte via one take_along_axis, and computes go-left for
  all K splits simultaneously;
- child histograms for all 2K children come from ONE histogram build over
  the rows — the multi-leaf analog of the fused partition+histogram pass:
  on the Pallas spellings the parent-slot x 6-channel kernel
  (histogram_pallas.build_histogram_slots6), on matmul / scatter the
  leaf-indexed builder over child slots (build_histogram_frontier);
- tree/leaf bookkeeping writes use scatter-with-drop (invalid lanes route
  to an out-of-bounds index) so masked lanes cannot race resident writes.

Node numbering: step-local rank i (gain-descending) gets node
(num_leaves - 1 + i) and right-child leaf (num_leaves + i) — identical to
the reference's numbering (tree.cpp:49-67) when K=1, and still
deterministic (gain-ranked) for K>1.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import build_histogram, build_histogram_frontier
from .grow import (GrowParams, TreeArrays, _bin_go_left, _empty_best,
                   count_i32, decode_bundle_value, empty_tree, expand_hist,
                   propagate_monotone_bounds)
from .split import (BestSplit, FeatureMeta, K_MIN_SCORE,
                    calculate_leaf_output, find_best_split)


def _drop_set(arr: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
              cond: jnp.ndarray) -> jnp.ndarray:
    """Scatter val into arr[idx] where cond; lanes with cond False write
    nowhere (out-of-bounds index + mode='drop'). Unlike a write-back of
    arr[idx], this cannot race another lane targeting the same index."""
    n = arr.shape[0]
    safe = jnp.where(cond, idx, n)
    return arr.at[safe].set(val, mode="drop")


def interleave_lr(a: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """[K] left + [K] right per-split values -> [2K] interleaved
    L,R,L,R,... — the child lane order of the wave-wide vmapped split
    search (left child of rank i at lane 2i, right at 2i+1)."""
    return jnp.stack([a, c], axis=1).reshape(-1)


def apply_split_wave(tree: TreeArrays, leaf_min: jnp.ndarray,
                     leaf_max: jnp.ndarray, cur, gleaf: jnp.ndarray,
                     node: jnp.ndarray, right_leaf: jnp.ndarray,
                     valid: jnp.ndarray, nvalid: jnp.ndarray,
                     meta: FeatureMeta, sp, max_depth: int):
    """Commit one wave of up to K frontier splits to the tree arrays
    (Tree::Split x K, tree.cpp:49-67) plus monotone-bound propagation.

    Every write is a scatter-with-drop, so invalid lanes touch nothing.
    Shared by the plain batched, partitioned-batched and frontier-wave
    growers so the wave-commit semantics cannot drift between them.
    Returns (tree, leaf_min, leaf_max, safe_leaf, ch_min, ch_max, ch_ok)
    with the ch_* arrays in the interleaved [2K] child lane order."""
    l = tree.leaf_value.shape[0]
    nl = tree.num_leaves
    safe_leaf = jnp.where(valid, gleaf, l - 1)
    parent_node = tree.leaf_parent[safe_leaf]                 # [K]
    p_exists = valid & (parent_node >= 0)
    safe_p = jnp.maximum(parent_node, 0)
    was_left = tree.left_child[safe_p] == ~safe_leaf
    left_child = _drop_set(tree.left_child, safe_p, node,
                           p_exists & was_left)
    right_child = _drop_set(tree.right_child, safe_p, node,
                            p_exists & ~was_left)
    left_child = _drop_set(left_child, node, ~safe_leaf, valid)
    right_child = _drop_set(right_child, node, ~right_leaf, valid)

    depth = tree.leaf_depth[safe_leaf] + 1                    # [K]
    parent_value = calculate_leaf_output(
        cur.left_sum_grad + cur.right_sum_grad,
        cur.left_sum_hess + cur.right_sum_hess,
        sp.lambda_l1, sp.lambda_l2, sp.max_delta_step)

    def set_node(arr, val):
        return _drop_set(arr, node, val, valid)

    def set_leaves(arr, lval, rval):
        return _drop_set(_drop_set(arr, safe_leaf, lval, valid),
                         right_leaf, rval, valid)

    tree = tree._replace(
        split_feature=set_node(tree.split_feature, cur.feature),
        threshold_bin=set_node(tree.threshold_bin, cur.threshold),
        default_left=set_node(tree.default_left, cur.default_left),
        missing_type=set_node(tree.missing_type,
                              meta.missing_type[cur.feature]),
        is_categorical=set_node(tree.is_categorical, cur.is_categorical),
        cat_bitset=_drop_set(tree.cat_bitset, node, cur.cat_bitset,
                             valid),
        left_child=left_child, right_child=right_child,
        split_gain=set_node(tree.split_gain, cur.gain),
        internal_value=set_node(tree.internal_value, parent_value),
        internal_weight=set_node(tree.internal_weight,
                                 cur.left_sum_hess + cur.right_sum_hess),
        internal_count=set_node(tree.internal_count,
                                count_i32(cur.left_count + cur.right_count)),
        split_leaf=set_node(tree.split_leaf, safe_leaf),
        leaf_value=set_leaves(tree.leaf_value, cur.left_output,
                              cur.right_output),
        leaf_weight=set_leaves(tree.leaf_weight, cur.left_sum_hess,
                               cur.right_sum_hess),
        leaf_count=set_leaves(tree.leaf_count, count_i32(cur.left_count),
                              count_i32(cur.right_count)),
        leaf_parent=set_leaves(tree.leaf_parent, node, node),
        leaf_depth=set_leaves(tree.leaf_depth, depth, depth),
        num_leaves=nl + nvalid)

    mono = meta.monotone[cur.feature]
    p_min, p_max = leaf_min[safe_leaf], leaf_max[safe_leaf]
    l_min, l_max, r_min, r_max = propagate_monotone_bounds(
        mono, cur.left_output, cur.right_output, p_min, p_max)
    leaf_min = set_leaves(leaf_min, l_min, r_min)
    leaf_max = set_leaves(leaf_max, l_max, r_max)

    depth_ok = (max_depth <= 0) | (depth < max_depth)
    return (tree, leaf_min, leaf_max, safe_leaf,
            interleave_lr(l_min, r_min), interleave_lr(l_max, r_max),
            interleave_lr(depth_ok, depth_ok))


def scatter_child_best(best, b2k, safe_leaf: jnp.ndarray,
                       right_leaf: jnp.ndarray, valid: jnp.ndarray):
    """De-interleave the [2K]-lane child split search back onto the
    per-leaf best table (left child keeps the parent's leaf index, right
    child takes its new leaf) — drop-scattered so invalid lanes write
    nothing. Shared by every wave-batched grower."""
    bl = jax.tree.map(lambda a: a[0::2], b2k)
    br = jax.tree.map(lambda a: a[1::2], b2k)
    return jax.tree.map(
        lambda arr, vl, vr: _drop_set(_drop_set(arr, safe_leaf, vl, valid),
                                      right_leaf, vr, valid),
        best, bl, br)


def route_split_rows(xb_fm, rank, rs, onek, cur, meta, with_efb,
                     with_categorical):
    """Per-row go-left decisions for the K frontier splits, built
    ENTIRELY from dense one-hot selects over the K split descriptors.

    Per-row gathers (take_along_axis on the bins, [rs]-indexed parameter
    lookups) are latency-bound on TPU (~0.3-0.5 ms EACH; the round-3
    routing cost ~18 ms/step at 1M rows, round-4 kernel lab) — one
    [kb, N] one-hot serves every lookup instead. Shared by the plain and
    partitioned batched growers so the routing semantics cannot drift.

    xb_fm: [C, N] feature-major bins; rank: [kb] iota; rs: [N] clamped
    per-row split rank; onek: [kb, N] (rank == rs) one-hot.
    Returns go_left [N] bool.
    """
    def sel_k(table_k):
        """[kb] per-split values -> [N] per-row via the one-hot."""
        t = table_k[:, None]
        if t.dtype == jnp.bool_:
            return jnp.any(onek & t, axis=0)
        return jnp.sum(jnp.where(onek, t, jnp.zeros_like(t)), axis=0)

    stored_col = (meta.col[cur.feature] if with_efb
                  else cur.feature).astype(jnp.int32)        # [kb]
    cols = xb_fm[stored_col, :].astype(jnp.int32)            # [kb, N]
    colv = jnp.sum(jnp.where(onek, cols, 0), axis=0)         # [N]
    num_bin_r = sel_k(meta.num_bin[cur.feature])
    default_bin_r = sel_k(meta.default_bin[cur.feature])
    if with_efb:
        fbin = decode_bundle_value(
            colv, sel_k(meta.offset[cur.feature]),
            num_bin_r, default_bin_r,
            pack_div=(sel_k(meta.pack_div[cur.feature])
                      if meta.pack_div is not None else None),
            pack_mod=(sel_k(meta.pack_mod[cur.feature])
                      if meta.pack_mod is not None else None))
    else:
        fbin = colv
    return _bin_go_left(
        fbin, sel_k(cur.threshold), sel_k(cur.default_left),
        sel_k(meta.missing_type[cur.feature]),
        num_bin_r, default_bin_r,
        (cur.is_categorical[rs] if with_categorical else None),
        (cur.cat_bitset[rs] if with_categorical else None))


class _BatchState(NamedTuple):
    leaf_id: jnp.ndarray      # [N] int32
    best: BestSplit           # per-leaf best split, fields [L]
    tree: TreeArrays
    leaf_min: jnp.ndarray     # [L] f32 monotone lower bound
    leaf_max: jnp.ndarray     # [L] f32 monotone upper bound


def grow_tree_batched(xb: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                      sample_mask: jnp.ndarray, meta: FeatureMeta,
                      feature_mask: jnp.ndarray, params: GrowParams,
                      axis_name: Optional[str] = None,
                      ) -> Tuple[TreeArrays, jnp.ndarray, None]:
    """Grow one tree, splitting up to ``params.batch_splits`` frontier
    leaves per sequential step. Same contract as grow.grow_tree (minus
    forced/CEGB, which require exact ordering); returns
    (tree, final per-row leaf_id, None)."""
    n, ncols = xb.shape
    f = meta.num_bin.shape[0]
    l = params.num_leaves
    b = params.num_bins
    sp = params.split
    kb = max(1, min(params.batch_splits, l - 1))
    with_efb = params.with_efb

    def psum(x):
        return lax.psum(x, axis_name) if axis_name is not None else x

    def child_best(hist_col, sum_g, sum_h, cnt, min_c, max_c):
        return find_best_split(
            expand_hist(hist_col, sum_g, sum_h, cnt, meta, params, ncols),
            meta, sp, sum_g, sum_h, cnt, feature_mask,
            min_constraint=min_c, max_constraint=max_c,
            with_categorical=params.with_categorical)

    # ---- root (identical to exact mode) ---------------------------------
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

    # feature-major view for split-column routing: loop-invariant, so the
    # transpose happens once per tree, not per step (measured ~4 ms per
    # occurrence on a v5e chip at 1M rows — the routing gather it
    # replaces measured ~18 ms per step)
    xb_fm = xb.T

    leaf_id0 = jnp.zeros((n,), jnp.int32)
    if axis_name is not None:
        leaf_id0 = lax.pcast(leaf_id0, (axis_name,), to="varying")
    state = _BatchState(
        leaf_id=leaf_id0, best=best, tree=tree,
        leaf_min=jnp.full((l,), -jnp.inf, jnp.float32),
        leaf_max=jnp.full((l,), jnp.inf, jnp.float32))

    def cond_fn(s: _BatchState) -> jnp.ndarray:
        return (s.tree.num_leaves < l) & jnp.any(s.best.gain > 0.0)

    def step(s: _BatchState) -> _BatchState:
        tree = s.tree
        nl = tree.num_leaves                      # dynamic scalar
        rank = jnp.arange(kb, dtype=jnp.int32)
        gval, gleaf = lax.top_k(s.best.gain, kb)  # distinct leaves, desc
        # both conditions are prefix masks of the gain-sorted ranks
        valid = (gval > 0.0) & (rank < (l - nl))
        nvalid = jnp.sum(valid.astype(jnp.int32))
        node = (nl - 1) + rank                    # [kb]
        right_leaf = nl + rank                    # [kb]
        cur = jax.tree.map(lambda a: a[gleaf], s.best)   # fields [kb]

        # ---- route every row through its leaf's split (one dense pass) --
        rank_of_leaf = jnp.full((l,), -1, jnp.int32)
        rank_of_leaf = _drop_set(rank_of_leaf, gleaf, rank, valid)
        r_r = rank_of_leaf[s.leaf_id]             # [N], -1 = not splitting
        active = r_r >= 0
        rs = jnp.maximum(r_r, 0)
        onek = rank[:, None] == rs[None, :]                  # [kb, N]
        go_left = route_split_rows(xb_fm, rank, rs, onek, cur, meta,
                                   with_efb, params.with_categorical)
        leaf_id = jnp.where(active & ~go_left, right_leaf[rs], s.leaf_id)

        # ---- all 2K children's histograms in one combined build ---------
        hmask = sample_mask * active.astype(jnp.float32)
        if params.hist_impl.startswith("pallas"):
            # parent-slot x 6-channel joint kernel: half the slot one-hot
            # width, double the MXU row utilization (round-4 on-chip fix)
            from .histogram_pallas import build_histogram_slots6
            vals3 = jnp.stack([grad * hmask, hess * hmask, hmask], axis=0)
            h6 = psum(build_histogram_slots6(
                xb, jnp.where(active, rs, -1), go_left.astype(jnp.float32),
                vals3, num_bins=b, n_slots=kb,
                interpret=params.hist_impl.endswith("interpret")))
            # h6: [K, C, B, 6]
            ch_hist = jnp.stack([h6[..., :3], h6[..., 3:]],
                                axis=1).reshape(2 * kb, ncols, b, 3)
        else:
            # matmul / scatter: the leaf-indexed frontier builder (slot
            # one-hot x bin one-hot); child slot = 2*rank + side, rows
            # outside a splitting leaf marked slot -1
            slot = jnp.where(active,
                             rs * 2 + (~go_left).astype(jnp.int32), -1)
            ch_hist = psum(build_histogram_frontier(
                xb, slot, grad, hess, hmask,
                num_bins=b, num_slots=2 * kb, row_chunk=params.row_chunk,
                impl=params.hist_impl))                   # [2K, C, B, 3]

        # ---- tree bookkeeping for up to K splits (Tree::Split, x K) -----
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

        return _BatchState(leaf_id=leaf_id, best=best, tree=tree,
                           leaf_min=leaf_min, leaf_max=leaf_max)

    state = lax.while_loop(cond_fn, step, state)
    return state.tree, state.leaf_id, None
