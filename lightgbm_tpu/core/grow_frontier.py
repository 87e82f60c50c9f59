"""Frontier-wave tree growth: O(depth) dataset sweeps per tree.

grow_tree (exact) rebuilds ONE leaf's histogram per loop iteration, so a
255-leaf tree pays ~254 serial sweeps over (half of) the dataset — the
dominant cost in the round-5 bench (partition_hist_fused ~86 ms +
hist_leaf_half ~17 ms per split step on CPU). Both GPU GBDT papers in
PAPERS.md (arXiv:1706.08359, arXiv:1806.11248) fix this the same way:
build the histograms of EVERY active node of a level in a single
node-indexed pass over the data. This module is that schedule:

- split selection stays leaf-wise / best-first WITHIN each wave: every
  frontier leaf whose best split has positive gain is committed, ranked
  by gain (rank i -> node nl-1+i, right leaf nl+i — the same numbering
  as grow_batched, and tree.cpp:49-67 when one leaf splits);
- histogram construction is batched per wave: ONE leaf-indexed pass
  (histogram.build_histogram_frontier) produces the [K, F, B, 3] tensor
  for every split's SMALLER child at once, and the larger sibling is
  derived by the subtraction trick from a per-leaf histogram pool that
  survives across waves — so a tree costs O(max leaf depth) ~ 8-12
  dataset sweeps instead of O(num_leaves) ~ 254;
- the sharded path psums the batched [K, F, B, 3] tensor ONCE per wave
  instead of once per leaf.

Routing differs from grow_batched.route_split_rows on purpose: that
helper materializes a [K, N] one-hot so per-STEP routing costs no
per-row gathers — the right trade at K<=32 where the one-hot is cheap
and steps are many. Here K can be num_leaves - 1 (every leaf can
split), so a [K, N] one-hot would be O(L*N) per wave; instead each row
gathers its own split's parameters (~6 per-row gathers per WAVE), which
runs O(depth) times per tree, not O(num_leaves) times.

Wave-width bucketing (GrowParams.frontier_bucketing): wave ``w`` has at
most ``min(2^w, leaf budget)`` positive-gain leaves, but a fixed-width
wave builds the full ``[kb, C, B, 3]`` histogram tensor regardless —
~``depth * kb`` slot-sweeps per tree where ~``num_leaves`` are live.
Both GPU GBDT papers size the node dimension to the actual frontier;
here that is done with compile-time specialization, reusing serving's
pow-2 bucket ladder (lightgbm_tpu.bucketing): the while_loop body counts
the live frontier and ``lax.switch``es into a wave step specialized at
the smallest ladder width covering it, so hist FLOPs and the per-wave
psum payload track ``2^w`` on early waves. Occupancy-weighted
slot-sweeps become ``sum_w bucket(live_w) <= 2 * (num_leaves - 1)``.
Every branch runs the same gain-ranked top_k prefix (stable ties, and
the live set always fits the chosen width), so committed splits, node
numbering, and the hist pool are bit-identical to the fixed-width path.
The branch index derives from psum-replicated gains, so all devices of
a shard_map mesh take the same branch and the per-branch psum is a
uniform collective. The ladder is also clamped by max_depth — a
depth-``d`` tree's frontier never exceeds ``2^(d-1)`` leaves (depth-
capped children are never granted positive gain).

Semantics: splitting every positive-gain frontier leaf is exactly the
set of splits exact best-first performs when the num_leaves cap never
binds (each leaf's best split depends only on its own rows and its
ancestors' monotone bounds), so the grown PARTITION is identical there —
tested in tests/test_grow_frontier.py. Near the cap the wave commits
gain-ranked until the cap, which can differ from fully-serial re-ranking
(same documented approximation as grow_batched at K>1). Forced splits
and CEGB keep the exact path (order-dependent accounting), same as
grow_batched.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..bucketing import frontier_max_width, wave_width_ladder
from ..obs.modelstats import init_mstats, update_mstats
from ..parallel.learners import make_frontier_learner
from .binpack import CODES_PER_WORD, words_per_row
from .histogram import build_histogram, build_histogram_frontier
from .grow import (GrowParams, TreeArrays, _bin_go_left, _empty_best,
                   count_i32, decode_bundle_value, empty_tree, expand_hist)
from .grow_batched import (_drop_set, apply_split_wave, interleave_lr,
                           scatter_child_best)
from .split import (FeatureMeta, K_MIN_SCORE, calculate_leaf_output,
                    find_best_split)


def _xb_sds(n: int, xb_cols: int, xb_dtype, params: GrowParams):
    """ShapeDtypeStruct mirror of the grower's bin-matrix operand:
    int32 packed words when the params say the device matrix is
    word-packed (core/binpack.py), the plain [N, C] matrix otherwise."""
    if params.word_packed_cols:
        return jax.ShapeDtypeStruct(
            (n, words_per_row(params.word_packed_cols)), jnp.int32)
    return jax.ShapeDtypeStruct((n, xb_cols), jnp.dtype(xb_dtype))


def wave_hist_entry(n: int, xb_cols: int, xb_dtype, params: GrowParams,
                    kw: int):
    """The wave's one-dataset-sweep kernel — ``wave_step(kw)``'s
    ``build_histogram_frontier`` call — as a standalone AOT-lowerable
    entry point: returns ``(fn, args, kwargs)`` such that
    ``fn.lower(*args, **kwargs)`` lowers exactly the program a width-
    ``kw`` wave dispatches for its dataset sweep.  Args are
    ``jax.ShapeDtypeStruct`` mirrors (no real arrays are built), so the
    obs cost model and the perf gate price wave buckets through this one
    definition and can never drift from the grower's actual kernel."""
    sds = jax.ShapeDtypeStruct
    args = (_xb_sds(n, xb_cols, xb_dtype, params),
            sds((n,), jnp.int32),          # slot: wave rank or -1
            sds((n,), jnp.float32),        # grad
            sds((n,), jnp.float32),        # hess
            sds((n,), jnp.float32))        # sample mask
    kwargs = dict(num_bins=params.num_bins, num_slots=int(kw),
                  row_chunk=params.row_chunk, impl=params.hist_impl,
                  packed_cols=params.word_packed_cols)
    return build_histogram_frontier, args, kwargs


def derive_child_hists(parent_hist, hist_small, left_small, kw: int):
    """Sibling-subtraction step shared by the wave commit and the fused
    pricing entry: [kw, C, B, 3] smaller-child sweep + pooled parents ->
    the interleaved [2*kw, C, B, 3] (left, right) child tensor."""
    hist_large = parent_hist - hist_small
    ls = left_small[:, None, None, None]
    hist_left = jnp.where(ls, hist_small, hist_large)
    hist_right = jnp.where(ls, hist_large, hist_small)
    ch_hist = jnp.stack([hist_left, hist_right],
                        axis=1).reshape((2 * kw,) + hist_left.shape[1:])
    return hist_left, hist_right, ch_hist


def wave_fused_entry(n: int, xb_cols: int, xb_dtype, meta: FeatureMeta,
                     feature_mask, params: GrowParams, kw: int):
    """The ENTIRE fused wave region — histogram sweep -> sibling
    subtraction -> expand/fix -> 2K-child bin-scan best split — as one
    AOT-lowerable entry: ``(fn, args, kwargs)`` with ShapeDtypeStruct
    args, same contract as :func:`wave_hist_entry`.

    This is the pricing seam of the fused pipeline (serial schedule): it
    composes the same building blocks the wave step runs
    (``build_histogram_frontier``, :func:`derive_child_hists`,
    ``expand_hist`` + ``find_best_split``), so the [kw, C, B, 3] wave
    histogram is an internal value of ONE compiled region — never a
    separate dispatch output — and the per-bucket cost entries
    (``frontier_wave_w*``) price work that genuinely scales with the
    wave width (the bin scan and subtraction are O(kw * C * B), unlike
    the scatter sweep whose update traffic is width-invariant)."""
    ncols = params.word_packed_cols or xb_cols
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    hshape = (kw, ncols, params.num_bins, 3)
    args = (_xb_sds(n, xb_cols, xb_dtype, params),
            sds((n,), jnp.int32),           # slot
            sds((n,), f32), sds((n,), f32), sds((n,), f32),
            sds(hshape, f32),               # pooled parent histograms
            sds((kw,), jnp.bool_),          # left_small
            sds((2 * kw,), f32), sds((2 * kw,), f32),   # child g/h sums
            sds((2 * kw,), f32),            # child counts
            sds((2 * kw,), f32), sds((2 * kw,), f32))   # monotone bounds

    def fused(xb, slot, grad, hess, mask, parent_hist, left_small,
              ch_sg, ch_sh, ch_cnt, ch_min, ch_max):
        hist_small = build_histogram_frontier(
            xb, slot, grad, hess, mask, num_bins=params.num_bins,
            num_slots=kw, row_chunk=params.row_chunk,
            impl=params.hist_impl, packed_cols=params.word_packed_cols)
        _, _, ch_hist = derive_child_hists(parent_hist, hist_small,
                                           left_small, kw)

        def one(hc, sg, sh, cnt, mn, mx):
            return find_best_split(
                expand_hist(hc, sg, sh, cnt, meta, params, ncols),
                meta, params.split, sg, sh, cnt, feature_mask,
                min_constraint=mn, max_constraint=mx,
                with_categorical=params.with_categorical)

        return jax.vmap(one)(ch_hist, ch_sg, ch_sh, ch_cnt, ch_min,
                             ch_max)

    return jax.jit(fused), args, {}


class _FrontierState(NamedTuple):
    leaf_id: jnp.ndarray      # [N] int32
    hist_pool: jnp.ndarray    # [L, C, B, 3] per-leaf histograms
    best: jnp.ndarray         # per-leaf best split, fields [L] (BestSplit)
    tree: TreeArrays
    leaf_min: jnp.ndarray     # [L] f32 monotone lower bound
    leaf_max: jnp.ndarray     # [L] f32 monotone upper bound
    # [2] f32 (waves executed, nonfinite committed gain) when
    # params.obs_health, else None (empty pytree leaf — the carry and the
    # compiled program are unchanged when monitoring is off)
    health: Optional[jnp.ndarray] = None
    # [F, MS_WIDTH] f32 per-feature (split count, gain sum, gain max)
    # when params.obs_modelstats, else None (same empty-leaf contract)
    mstats: Optional[jnp.ndarray] = None


def _gain_anomaly(gain: jnp.ndarray) -> jnp.ndarray:
    """Elementwise "this gain is corrupt": NaN or +inf. -inf is the
    K_MIN_SCORE no-valid-split sentinel and therefore healthy."""
    return jnp.isnan(gain) | (gain == jnp.inf)


def _route_rows_gather(xb, rs, cur, meta, with_efb, with_categorical,
                       packed_cols: int = 0):
    """Per-row go-left decisions for the wave's splits via per-row
    gathers of each row's split descriptor (see module docstring for why
    this is gather-based where route_split_rows is one-hot-based).

    xb: [N, C] row-major bins (int32 packed words when ``packed_cols``);
    rs: [N] clamped per-row split rank; cur: BestSplit fields [K].
    Returns go_left [N] bool (garbage on rows whose leaf is not
    splitting — callers mask with ``active``)."""
    fk = cur.feature[rs]                                     # [N]
    stored_col = (meta.col[fk] if with_efb else fk).astype(jnp.int32)
    if packed_cols:
        # gather the routed column's code straight from the packed words
        # (one per-row word gather + shift/mask — the full unpacked
        # matrix never materializes on the routing path either)
        word = jnp.take_along_axis(
            xb, (stored_col // CODES_PER_WORD)[:, None], axis=1)[:, 0]
        colv = (word >> ((stored_col % CODES_PER_WORD) * 8)) & 0xFF
    else:
        colv = jnp.take_along_axis(
            xb, stored_col[:, None], axis=1)[:, 0].astype(jnp.int32)
    num_bin_r = meta.num_bin[fk]
    default_bin_r = meta.default_bin[fk]
    if with_efb:
        fbin = decode_bundle_value(
            colv, meta.offset[fk], num_bin_r, default_bin_r,
            pack_div=(meta.pack_div[fk]
                      if meta.pack_div is not None else None),
            pack_mod=(meta.pack_mod[fk]
                      if meta.pack_mod is not None else None))
    else:
        fbin = colv
    return _bin_go_left(
        fbin, cur.threshold[rs], cur.default_left[rs],
        meta.missing_type[fk], num_bin_r, default_bin_r,
        (cur.is_categorical[rs] if with_categorical else None),
        (cur.cat_bitset[rs] if with_categorical else None))


def wave_plan(best, nl, kw: int, l: int):
    """Wave bookkeeping that depends only on per-leaf state (no dataset
    access): the gain-ranked top-k frontier, its commit mask, node/leaf
    numbering, the gathered split records, and the leaf->rank map.
    Shared verbatim by the in-memory wave (``wave_step``) and the
    streamed grower (stream/grow_stream.py), which runs it once per wave
    BEFORE touching any chunk."""
    rank = jnp.arange(kw, dtype=jnp.int32)
    gval, gleaf = lax.top_k(best.gain, kw)    # distinct leaves, desc
    # the whole positive-gain frontier splits, gain-ranked; both
    # conditions are prefix masks of the sorted ranks
    valid = (gval > 0.0) & (rank < (l - nl))
    nvalid = jnp.sum(valid.astype(jnp.int32))
    node = (nl - 1) + rank                    # [kw]
    right_leaf = nl + rank                    # [kw]
    cur = jax.tree.map(lambda a: a[gleaf], best)     # fields [kw]
    rank_of_leaf = jnp.full((l,), -1, jnp.int32)
    rank_of_leaf = _drop_set(rank_of_leaf, gleaf, rank, valid)
    return gval, gleaf, valid, nvalid, node, right_leaf, cur, rank_of_leaf


def wave_route(xb, leaf_id, cur, rank_of_leaf, right_leaf, meta,
               with_efb: bool, with_categorical: bool,
               packed_cols: int = 0):
    """Route a batch of rows through their leaf's committed split.
    Works on any row slice whose ``leaf_id`` it is given — the full
    dataset in-memory, one resident chunk when streaming."""
    r_r = rank_of_leaf[leaf_id]               # [N], -1 = not splitting
    active = r_r >= 0
    rs = jnp.maximum(r_r, 0)
    go_left = _route_rows_gather(xb, rs, cur, meta, with_efb,
                                 with_categorical, packed_cols)
    new_leaf_id = jnp.where(active & ~go_left, right_leaf[rs], leaf_id)
    return new_leaf_id, active, rs, go_left


def wave_slots(cur, active, go_left, rs):
    """Histogram slot of every row: its split's rank iff it lands in
    the SMALLER child, else -1 (the larger sibling comes from the pool
    by subtraction, so the sweep touches each splitting row at most
    once)."""
    left_small = cur.left_count <= cur.right_count       # [kw]
    in_small = active & (go_left == left_small[rs])
    slot = jnp.where(in_small, rs, -1)
    return left_small, slot


def wave_commit(s: "_FrontierState", kw: int, l: int, gval, gleaf, valid,
                nvalid, node, right_leaf, cur, left_small, hist_small,
                meta: FeatureMeta, sp, max_depth: int, lrn):
    """Everything after the wave's dataset sweep: sibling derivation from
    the pool, pool update, tree bookkeeping, the 2K-children best-split
    search, and the health/mstats accumulators. ``hist_small`` is the
    learner-reduced [kw, C, B, 3] smaller-child tensor — one sweep
    in-memory, a sum of per-chunk sweeps when streaming (histograms are
    additive, so the commit is identical either way)."""
    parent_hist = s.hist_pool[jnp.where(valid, gleaf, 0)]
    hist_left, hist_right, ch_hist = derive_child_hists(
        parent_hist, hist_small, left_small, kw)

    # pool update: left child reuses the parent's leaf index, right
    # child takes its new leaf; invalid lanes drop
    pool = s.hist_pool
    pool = pool.at[jnp.where(valid, gleaf, l)].set(
        hist_left, mode="drop")
    pool = pool.at[jnp.where(valid, right_leaf, l)].set(
        hist_right, mode="drop")

    # ---- tree bookkeeping for the wave (shared with grow_batched) ---
    (tree, leaf_min, leaf_max, safe_leaf,
     ch_min, ch_max, ch_ok) = apply_split_wave(
        s.tree, s.leaf_min, s.leaf_max, cur, gleaf, node, right_leaf,
        valid, nvalid, meta, sp, max_depth)

    # ---- best splits for all 2K children, one vmapped search --------
    ch_sg = interleave_lr(cur.left_sum_grad, cur.right_sum_grad)
    ch_sh = interleave_lr(cur.left_sum_hess, cur.right_sum_hess)
    ch_cnt = interleave_lr(cur.left_count, cur.right_count)
    b2k = lrn.best_children(ch_hist, ch_sg, ch_sh, ch_cnt,
                            ch_min, ch_max)
    b2k = b2k._replace(gain=jnp.where(ch_ok, b2k.gain, K_MIN_SCORE))
    best = scatter_child_best(s.best, b2k, safe_leaf, right_leaf, valid)

    health = s.health
    if health is not None:
        # committed lanes must be finite (NaN/-inf never pass
        # gval > 0, +inf does); child searches may only return real
        # gains or the -inf sentinel
        bad_gain = jnp.any(~jnp.isfinite(gval) & valid) | \
            jnp.any(_gain_anomaly(b2k.gain))
        health = jnp.stack([health[0] + 1.0,
                            jnp.maximum(health[1],
                                        bad_gain.astype(jnp.float32))])

    mstats = s.mstats
    if mstats is not None:
        # committed lanes' inner feature + ranked gain, values the
        # wave computed anyway — two scatter-adds + a scatter-max,
        # zero new collectives
        mstats = update_mstats(mstats, cur.feature, gval, valid)

    return pool, tree, leaf_min, leaf_max, best, health, mstats


def root_state(hist_root, root_g, root_h, root_c, n: int, l: int, sp,
               lrn, params: GrowParams, feature_mask,
               axis_name: Optional[str]) -> "_FrontierState":
    """Seed the frontier state from the root's (already learner-reduced)
    histogram and psum'd gradient sums — tree arrays, per-leaf best
    records, the histogram pool, and the obs accumulators. Shared by the
    in-memory grower and the streamed one (which sums the root histogram
    over chunks first)."""
    tree = empty_tree(l)
    tree = tree._replace(
        leaf_value=tree.leaf_value.at[0].set(
            calculate_leaf_output(root_g, root_h, sp.lambda_l1, sp.lambda_l2,
                                  sp.max_delta_step)),
        leaf_weight=tree.leaf_weight.at[0].set(root_h),
        leaf_count=tree.leaf_count.at[0].set(count_i32(root_c)))
    best0 = lrn.best_root(hist_root, root_g, root_h, root_c)
    best = jax.tree.map(lambda a, v: a.at[0].set(v), _empty_best(l), best0)

    # per-leaf histogram pool: a frontier leaf's histogram survives from
    # the wave that created it, so the subtraction trick works wave-wide
    # (parent - smaller child = larger child; histogram.cpp:xx Subtract).
    # Shape follows the learner's reduced histogram: full [C, B, 3] on the
    # serial/voting schedules, the device's feature shard under data_rs
    hist_pool = jnp.zeros((l,) + hist_root.shape, jnp.float32)
    if lrn.varying_pool:
        # the pool holds device-varying content (local histograms under
        # voting, per-device feature shards under data_rs)
        hist_pool = lax.pcast(hist_pool, (axis_name,), to="varying")
    hist_pool = hist_pool.at[0].set(hist_root)

    leaf_id0 = jnp.zeros((n,), jnp.int32)
    if axis_name is not None:
        leaf_id0 = lax.pcast(leaf_id0, (axis_name,), to="varying")
    # health accumulator (obs): waves executed + anomalous gain, seeded
    # with the root search's gain — everything below reads values the
    # wave already computed, so no new sweeps or collectives. Anomalous
    # means NaN or +inf: K_MIN_SCORE (-inf) is the legitimate "no valid
    # split" sentinel and must not flag.
    health0 = None
    if params.obs_health:
        health0 = jnp.stack([
            jnp.float32(0.0),
            jnp.any(_gain_anomaly(best0.gain)).astype(jnp.float32)])
    # model-statistics accumulator (obs.modelstats): zeros are correct —
    # EVERY committed split, the root's included, flows through a
    # wave_step commit and scatters there
    mstats0 = (init_mstats(feature_mask.shape[0])
               if params.obs_modelstats else None)
    return _FrontierState(
        leaf_id=leaf_id0, hist_pool=hist_pool, best=best, tree=tree,
        leaf_min=jnp.full((l,), -jnp.inf, jnp.float32),
        leaf_max=jnp.full((l,), jnp.inf, jnp.float32),
        health=health0, mstats=mstats0)


def _frontier_driver(xb: jnp.ndarray, sample_mask: jnp.ndarray,
                     meta: FeatureMeta, feature_mask: jnp.ndarray,
                     params: GrowParams, axis_name: Optional[str]):
    """Shared machinery of the single-class and class-batched frontier
    growers: returns ``(seed, wave_step, ladder, kb)`` where
    ``seed(grad, hess)`` builds the root _FrontierState and
    ``wave_step(s, grad, hess, kw)`` runs one width-``kw`` wave. Both
    take gradients explicitly (not by closure) so the class-batched
    driver can jax.vmap them over the class axis while the ladder
    selection stays OUTSIDE the vmap."""
    n = xb.shape[0]
    ncols = params.word_packed_cols or xb.shape[1]
    l = params.num_leaves
    b = params.num_bins
    sp = params.split
    # max wave width: any frontier leaf can split, but max_depth bounds
    # the frontier at 2^(d-1) leaves — without the clamp a shallow-tree
    # config pays full num_leaves-1 slot-sweeps per wave
    kb = frontier_max_width(l, params.max_depth)
    with_efb = params.with_efb
    packed = params.word_packed_cols
    sample_mask = sample_mask.astype(jnp.float32)

    def psum(x):
        return lax.psum(x, axis_name) if axis_name is not None else x

    def child_best(hist_col, sum_g, sum_h, cnt, min_c, max_c):
        return find_best_split(
            expand_hist(hist_col, sum_g, sum_h, cnt, meta, params, ncols),
            meta, sp, sum_g, sum_h, cnt, feature_mask,
            min_constraint=min_c, max_constraint=max_c,
            with_categorical=params.with_categorical)

    # wave-collective schedule (parallel/learners.py): serial emits the
    # psum/child_best closures verbatim; data_rs reduce-scatters histograms
    # over the feature axis and elects packed best records; voting keeps
    # histograms local and exchanges only vote-elected columns
    lrn = make_frontier_learner(params, axis_name, meta, feature_mask,
                                psum, child_best)

    def seed(grad: jnp.ndarray, hess: jnp.ndarray) -> _FrontierState:
        # ---- root (identical to exact mode) -----------------------------
        root_g = psum(jnp.sum(grad * sample_mask))
        root_h = psum(jnp.sum(hess * sample_mask))
        root_c = psum(jnp.sum(sample_mask))
        hist_root = lrn.reduce(build_histogram(
            xb, grad, hess, sample_mask, num_bins=b,
            row_chunk=params.row_chunk, impl=params.hist_impl,
            packed_cols=packed))
        return root_state(hist_root, root_g, root_h, root_c, n, l, sp,
                          lrn, params, feature_mask, axis_name)

    def wave_step(s: _FrontierState, grad, hess, kw: int) -> _FrontierState:
        """One frontier wave at static width ``kw`` (1 <= kw <= kb). The
        caller guarantees the live positive-gain frontier fits in ``kw``
        lanes, so the top_k prefix it commits — and therefore the grown
        structure and numbering — is identical for every width. A wave
        with NO positive-gain leaf is a perfect no-op (every commit
        scatter drops), which is what lets the class-batched driver run
        finished classes through further waves harmlessly."""
        nl = s.tree.num_leaves                    # dynamic scalar
        (gval, gleaf, valid, nvalid, node, right_leaf, cur,
         rank_of_leaf) = wave_plan(s.best, nl, kw, l)

        # ---- route every row through its leaf's split -------------------
        leaf_id, active, rs, go_left = wave_route(
            xb, s.leaf_id, cur, rank_of_leaf, right_leaf, meta, with_efb,
            params.with_categorical, packed)

        # ---- ONE dataset sweep: smaller child of every split ------------
        # slot = split rank iff the row lands in the SMALLER child of its
        # leaf's split, else -1 (inactive); the larger sibling is derived
        # from the pool by subtraction, so the sweep touches each
        # splitting row at most once and the wave costs one pass total.
        # The sweep, subtraction, expand/fix, and the bin-scan best-split
        # below compile into ONE wave region (wave_fused_entry is the
        # AOT pricing mirror) — the [kw, C, B, 3] tensor is an internal
        # value, never a separate dispatch output.
        left_small, slot = wave_slots(cur, active, go_left, rs)
        hist_small = lrn.reduce(build_histogram_frontier(
            xb, slot, grad, hess, sample_mask, num_bins=b, num_slots=kw,
            row_chunk=params.row_chunk,
            impl=params.hist_impl,
            packed_cols=packed))                   # [kw, C, B, 3]

        (pool, tree, leaf_min, leaf_max, best, health,
         mstats) = wave_commit(
            s, kw, l, gval, gleaf, valid, nvalid, node, right_leaf, cur,
            left_small, hist_small, meta, sp, params.max_depth, lrn)

        return _FrontierState(leaf_id=leaf_id, hist_pool=pool, best=best,
                              tree=tree, leaf_min=leaf_min,
                              leaf_max=leaf_max, health=health,
                              mstats=mstats)

    ladder = wave_width_ladder(l, params.max_depth)  # pow-2 widths, <= kb
    return seed, wave_step, ladder, kb


def grow_tree_frontier(xb: jnp.ndarray, grad: jnp.ndarray,
                       hess: jnp.ndarray, sample_mask: jnp.ndarray,
                       meta: FeatureMeta, feature_mask: jnp.ndarray,
                       params: GrowParams,
                       axis_name: Optional[str] = None,
                       ) -> Tuple[TreeArrays, jnp.ndarray,
                                  Optional[jnp.ndarray]]:
    """Grow one tree in frontier waves: every positive-gain frontier
    leaf splits per sequential step, with ONE batched histogram pass per
    wave. Same contract as grow.grow_tree (minus forced/CEGB); returns
    (tree, final per-row leaf_id, aux). The aux slot is the [2] f32
    health accumulator (waves executed, nonfinite committed gain) when
    ``params.obs_health`` and None otherwise — unless
    ``params.obs_modelstats``, in which case aux is the 2-tuple
    ``(health_or_None, mstats)`` with ``mstats`` the f32[F, MS_WIDTH]
    per-feature (split count, gain sum, gain max) accumulator."""
    l = params.num_leaves
    seed, wave_step, ladder, kb = _frontier_driver(
        xb, sample_mask, meta, feature_mask, params, axis_name)
    state = seed(grad, hess)

    def cond_fn(s: _FrontierState) -> jnp.ndarray:
        return (s.tree.num_leaves < l) & jnp.any(s.best.gain > 0.0)

    if params.frontier_bucketing and len(ladder) > 1:
        # adaptive width: count the live frontier and dispatch the wave
        # step specialized at the smallest covering ladder width. ``live``
        # is replicated across a shard_map mesh (gains derive from psum'd
        # histograms), so every device takes the same branch and the
        # branch-local psum stays a uniform collective. cond_fn guarantees
        # live >= 1; live <= kb always (the frontier is one depth level,
        # bounded by 2^(max_depth-1) and by the nl < l leaf budget), so
        # the chosen width never truncates the live set.
        widths = jnp.asarray(ladder, jnp.int32)
        branches = [lambda s, w=w: wave_step(s, grad, hess, w)
                    for w in ladder]

        def step(s: _FrontierState) -> _FrontierState:
            live = jnp.sum(s.best.gain > 0.0)
            return lax.switch(jnp.sum(live > widths), branches, s)
    else:
        # fixed width (frontier_bucketing=false, or a degenerate ladder):
        # every wave runs at the clamped maximum
        def step(s: _FrontierState) -> _FrontierState:
            return wave_step(s, grad, hess, kb)

    state = lax.while_loop(cond_fn, step, state)
    if params.obs_modelstats:
        return state.tree, state.leaf_id, (state.health, state.mstats)
    return state.tree, state.leaf_id, state.health


def grow_tree_frontier_classes(xb: jnp.ndarray, grad: jnp.ndarray,
                               hess: jnp.ndarray,
                               sample_mask: jnp.ndarray,
                               meta: FeatureMeta,
                               feature_mask: jnp.ndarray,
                               params: GrowParams,
                               ) -> Tuple[TreeArrays, jnp.ndarray,
                                          Optional[jnp.ndarray]]:
    """Class-batched frontier growth with the wave ladder OUTSIDE the
    vmap: grad/hess are [K, N] (one row per class) and all K trees grow
    together, one class-vmapped wave per step.

    The naive ``jax.vmap(grow_tree_frontier)`` forces bucketing off
    because vmapping a ``lax.switch`` on a batched index lowers to
    execute-ALL-branches — every wave would pay the whole ladder. Here
    the while_loop and the switch live at the top level: the branch
    index is the MAX live frontier over classes (an unbatched scalar, so
    the switch stays a real single-branch dispatch) and the chosen
    branch vmaps ``wave_step`` over classes. A class whose frontier is
    exhausted (or whose leaf budget is spent) runs through later waves
    as a structural no-op — wave_plan grants it zero valid lanes and
    every commit write is a drop-mode scatter — so the grown structure
    of every class is identical to its solo unbucketed run; only the
    health wave COUNTER sees the shared schedule (it counts global
    waves, max over classes instead of per-class).

    Serial learner only (the vmapped-multiclass gate never arises on
    sharded schedules — the GBDT driver keeps mesh multiclass on the
    pooled path)."""
    l = params.num_leaves
    seed, wave_step, ladder, kb = _frontier_driver(
        xb, sample_mask, meta, feature_mask, params, axis_name=None)
    states = jax.vmap(seed)(grad, hess)

    def cond_fn(ss: _FrontierState) -> jnp.ndarray:
        return jnp.any((ss.tree.num_leaves < l)
                       & jnp.any(ss.best.gain > 0.0, axis=-1))

    if params.frontier_bucketing and len(ladder) > 1:
        widths = jnp.asarray(ladder, jnp.int32)
        branches = [
            lambda ss, w=w: jax.vmap(
                lambda s, g, h: wave_step(s, g, h, w))(ss, grad, hess)
            for w in ladder]

        def step(ss: _FrontierState) -> _FrontierState:
            # widest live frontier over classes still in budget — an
            # UNBATCHED scalar, so lax.switch dispatches one real branch
            live_c = jnp.sum(ss.best.gain > 0.0, axis=-1)       # [K]
            can = ss.tree.num_leaves < l                        # [K]
            live = jnp.max(jnp.where(can, live_c, 0))
            return lax.switch(jnp.sum(live > widths), branches, ss)
    else:
        def step(ss: _FrontierState) -> _FrontierState:
            return jax.vmap(
                lambda s, g, h: wave_step(s, g, h, kb))(ss, grad, hess)

    states = lax.while_loop(cond_fn, step, states)
    if params.obs_modelstats:
        return states.tree, states.leaf_id, (states.health, states.mstats)
    return states.tree, states.leaf_id, states.health
