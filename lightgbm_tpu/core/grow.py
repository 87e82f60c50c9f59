"""Leaf-wise (best-first) tree growth as a single jit-compiled loop.

TPU-native re-design of SerialTreeLearner::Train
(src/treelearner/serial_tree_learner.cpp:169-233) and Tree::Split
(include/LightGBM/tree.h:393, src/io/tree.cpp:49-67). Differences by design:

- The reference breaks out of the split loop when the best gain <= 0
  (serial_tree_learner.cpp:217-219); under jit the loop runs a fixed
  ``num_leaves - 1`` iterations with *masked no-op* splits instead.
- Every path keeps the histogram-subtraction trick: only the smaller
  child's histogram is built (serial_tree_learner.cpp:383-397, 547-548);
  the sibling is parent - child, the parent read from a per-leaf pool
  whose slot 0 holds the root's. Dead iterations skip the work.
- Single-device growth and the shard_map data-parallel learner keep rows
  grouped by leaf (core/partition.py): one pass over the split leaf's rows
  partitions its range (DataPartition::Split), a second pass over the
  smaller child's new range builds its histogram through the kernel. The
  final ``leaf_id`` (reconstructed from the ranges) doubles as the
  score-update fast path (score_updater.hpp:53-117).
- The other mesh paths use masked full-data passes with a per-row
  ``leaf_id`` vector.
- Node numbering matches the reference exactly: splitting leaf ``l`` at step
  ``t`` creates internal node ``t``; the left child keeps leaf index ``l``,
  the right child becomes leaf ``t + 1`` (tree.cpp:49-67). Child pointers use
  the ``~leaf`` encoding (negative = leaf).
- Data-parallel training (data_parallel_tree_learner.cpp:146-245) falls out
  of the same code: when ``axis_name`` is set, histograms and root sums are
  psum-reduced over the mesh axis — the ReduceScatter+best-split-sync dance
  collapses into XLA collectives.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import build_histogram
from .partition import (RowPartition, hist_for_leaf, init_partition,
                        leaf_id_from_partition, make_row_gather,
                        partition_rows, route_in_row_space,
                        row_space_leaf_ids, row_space_leaf_ids0, stack_vals,
                        window_placement)
from .split import (BestSplit, FeatureMeta, SplitParams, K_EPSILON,
                    K_MIN_SCORE, MISSING_NAN, MISSING_NONE, MISSING_ZERO,
                    calculate_leaf_output, find_best_split, leaf_split_gain,
                    per_feature_split_merged)


class GrowParams(NamedTuple):
    """Static growth hyper-parameters (hashable; part of the jit key)."""
    num_leaves: int
    num_bins: int           # padded bin axis size B
    max_depth: int
    split: SplitParams
    row_chunk: int = 16384
    hist_impl: str = "matmul"
    # histogram accumulation dtype: "f32" (default) or "f64" (gpu_use_dp,
    # config.h:784 — the reference's double-precision histograms; needs
    # jax_enable_x64, enforced by the GBDT driver)
    hist_dtype: str = "f32"
    # PV-Tree voting-parallel (voting_parallel_tree_learner.cpp): each device
    # votes its local top_k features; only the elected <=2*top_k candidates'
    # histograms are globally reduced. 0 = disabled (full reduction).
    voting_top_k: int = 0
    # how many categorical features the dataset has (0: none): the
    # categorical split finder then runs over those columns alongside the
    # numerical one (FindBestThreshold dispatch) and routing tests the
    # split's category set. WHICH columns they are is the metadata's to
    # say, an argument of the compiled block
    with_categorical: int = 0
    # the row partition holds exactly the counted rows: no bagging, no
    # padded rows, and GOSS only where the partition starts from its bag
    # (grow_tree's ``bag``), so the partition's integer counts ARE the
    # leaves' counts. The exact grower then records those; a float32
    # histogram count cannot hold an odd number past 2**24 rows
    all_rows_in_bag: bool = False
    # row-partition mode (DataPartition analog, core/partition.py): keep rows
    # grouped by leaf and build each histogram only over the leaf's rows —
    # O(N x depth) row visits per tree instead of O(N x num_leaves).
    use_partition: bool = False
    # allow the partition path under an explicit shard_map data-parallel
    # learner: every device partitions its LOCAL row shard (trip counts
    # diverge freely — no collective sits inside the chunk loops) and only
    # the [F, B, 3] histogram of the GLOBALLY smaller child is
    # psum-combined, the ReduceScatter moment of
    # data_parallel_tree_learner.cpp:146-161.
    # GSPMD paths must keep this off (a gather through a sharded order
    # array would shuffle rows across devices).
    partition_on_mesh: bool = False
    # EFB (io/bundle.py): histograms are built over stored bundle columns
    # ([C, num_bins]) and expanded to per-feature views ([F, num_feat_bins])
    # before split search; split decisions decode column values through
    # meta.col/offset. num_feat_bins = 0 means "same as num_bins".
    with_efb: bool = False
    num_feat_bins: int = 0
    # joint-coded pair packing: max marginalization width (the largest
    # pack_partner; 1 = no packed columns, expand() stays a pure gather)
    # and the static tuple of packed inner-feature indices
    pack_j: int = 1
    packed_features: tuple = ()
    # word-packed device bin matrix (tpu_bin_packing, core/binpack.py):
    # the REAL stored-column count C when xb arrives as int32 words
    # holding 4 eight-bit codes each ([N, ceil(C/4)]); 0 = xb is the
    # plain [N, C] uint8 matrix. Unpack happens inside each histogram
    # impl and routing gathers codes straight from the words — the
    # unpacked matrix never exists on device. Frontier growth only.
    word_packed_cols: int = 0
    # forced splits (serial_tree_learner.cpp ForceSplits :593-751): the
    # first `num_forced` loop steps split a BFS-predetermined (leaf,
    # feature, threshold) instead of the best-gain candidate
    num_forced: int = 0
    # CEGB (serial_tree_learner.cpp :533-539): per-candidate gain penalties.
    # cegb_split_penalty is tradeoff * cegb_penalty_split (scaled by leaf
    # count at evaluation time); coupled/lazy switches enable the
    # feature-acquisition terms carried in CegbState.
    cegb_split_penalty: float = 0.0
    with_cegb_coupled: bool = False
    with_cegb_lazy: bool = False
    # grow_tree is class-batched under jax.vmap (multiclass, uncapped
    # pool): a batched window start would be a scatter again, so the
    # partition keeps the element scatter (partition.window_placement)
    vmapped_classes: bool = False
    # histogram pool cap (HistogramPool, feature_histogram.hpp:646-820):
    # 0 = one slot per leaf (unlimited); otherwise S < num_leaves slots with
    # LRU eviction, rebuilding an evicted parent histogram from its rows
    # (its range of the row partition, where there is one) when that leaf
    # is finally chosen for splitting (the Move/Get dance)
    pool_slots: int = 0
    # batched-frontier growth (core/grow_batched.py): split up to this many
    # of the highest-gain frontier leaves per sequential step instead of
    # exactly one. 0 = exact leaf-wise (the reference's semantics)
    batch_splits: int = 0
    # frontier-wave growth (core/grow_frontier.py): split EVERY
    # positive-gain frontier leaf per sequential step, with histogram
    # construction batched into one leaf-indexed dataset pass per wave
    # (histogram.build_histogram_frontier) — O(depth) sweeps per tree
    # instead of O(num_leaves). Split selection stays leaf-wise/best-first
    # within each wave (gain-ranked node numbering, like batched growth)
    frontier_mode: bool = False
    # wave-width bucketing (tpu_frontier_bucketing): the frontier
    # while_loop body lax.switches into a wave step specialized at the
    # smallest pow-2 ladder width covering the live positive-gain
    # frontier, so early waves pay 2^w slot-sweeps instead of
    # num_leaves - 1 (lightgbm_tpu.bucketing.wave_width_ladder). Committed
    # splits and numbering are identical to the fixed-width wave. Must
    # stay off under vmapped_classes — vmap lowers switch to
    # execute-all-branches, which would cost MORE than fixed width.
    frontier_bucketing: bool = False
    # frontier data-parallel reduce-scatter schedule (parallel/learners.py
    # DataRSLearner, data_parallel_tree_learner.cpp:146-161): the per-wave
    # histogram psum becomes a tiled psum_scatter over the feature axis,
    # each device scans only its contiguous feature block, and one small
    # all_gather of packed best-split records elects the global winners.
    # Requires stored columns divisible by the mesh axis size (the GBDT
    # driver pads) and no EFB. False = the PR 2 full-psum schedule.
    frontier_rs: bool = False
    # observability health piggy-back (lightgbm_tpu.obs): the frontier
    # wave loop threads a 2-scalar (waves executed, nonfinite committed
    # gain) accumulator through its carry and returns it in the aux slot.
    # The accumulator derives from the gains the wave already computed
    # from its psum'd histograms, so the per-wave collective count is
    # unchanged (pinned by tests/test_obs.py). Off: aux slot stays None
    # and the compiled program is identical to an uninstrumented build.
    obs_health: bool = False
    # model-statistics piggy-back (lightgbm_tpu.obs.modelstats): the
    # frontier wave loop additionally threads an f32[F, 3] per-feature
    # (split count, gain sum, gain max) accumulator through its carry and
    # returns it alongside health in the aux slot. Like obs_health it is
    # scatter-updated from the committed lanes the wave already ranked
    # (zero new sweeps or collectives; psums/wave pinned by
    # tests/test_modelstats.py). Off: the carry leaf stays None and the
    # compiled program is byte-identical to an uninstrumented build.
    obs_modelstats: bool = False


class TreeArrays(NamedTuple):
    """Fixed-capacity SoA tree, mirroring Tree's layout (tree.h:404-517).

    Internal-node arrays have length ``num_leaves - 1``; leaf arrays
    ``num_leaves``. ``split_leaf[t]`` records which leaf node ``t`` split —
    that is what makes sequential partition replay (and thus vectorized
    prediction) possible without pointer chasing.
    """
    split_feature: jnp.ndarray    # [L-1] int32 (inner feature index)
    threshold_bin: jnp.ndarray    # [L-1] int32
    default_left: jnp.ndarray     # [L-1] bool
    missing_type: jnp.ndarray     # [L-1] int32
    is_categorical: jnp.ndarray   # [L-1] bool
    cat_bitset: jnp.ndarray       # [L-1, 8] uint32 (bins going left)
    left_child: jnp.ndarray       # [L-1] int32 (~leaf encoding for leaves)
    right_child: jnp.ndarray      # [L-1] int32
    split_gain: jnp.ndarray       # [L-1] f32
    internal_value: jnp.ndarray   # [L-1] f32 (node output)
    internal_weight: jnp.ndarray  # [L-1] f32 (sum_hess)
    internal_count: jnp.ndarray   # [L-1] int32 (rows; past 2**24 f32 drops odd counts)
    split_leaf: jnp.ndarray       # [L-1] int32
    leaf_value: jnp.ndarray       # [L] f32
    leaf_weight: jnp.ndarray      # [L] f32 (sum_hess)
    leaf_count: jnp.ndarray       # [L] int32
    leaf_parent: jnp.ndarray      # [L] int32 (node index, -1 = root)
    leaf_depth: jnp.ndarray       # [L] int32
    num_leaves: jnp.ndarray       # scalar int32

    @property
    def max_leaves(self) -> int:
        return self.leaf_value.shape[0]


def empty_tree(num_leaves: int, dtype=jnp.float32) -> TreeArrays:
    l = num_leaves
    return TreeArrays(
        split_feature=jnp.zeros((l - 1,), jnp.int32),
        threshold_bin=jnp.zeros((l - 1,), jnp.int32),
        default_left=jnp.zeros((l - 1,), bool),
        missing_type=jnp.zeros((l - 1,), jnp.int32),
        is_categorical=jnp.zeros((l - 1,), bool),
        cat_bitset=jnp.zeros((l - 1, 8), jnp.uint32),
        left_child=jnp.full((l - 1,), -1, jnp.int32),
        right_child=jnp.full((l - 1,), -1, jnp.int32),
        split_gain=jnp.zeros((l - 1,), dtype),
        internal_value=jnp.zeros((l - 1,), dtype),
        internal_weight=jnp.zeros((l - 1,), dtype),
        internal_count=jnp.zeros((l - 1,), jnp.int32),
        split_leaf=jnp.full((l - 1,), -1, jnp.int32),
        leaf_value=jnp.zeros((l,), dtype),
        leaf_weight=jnp.zeros((l,), dtype),
        leaf_count=jnp.zeros((l,), jnp.int32),
        leaf_parent=jnp.full((l,), -1, jnp.int32),
        leaf_depth=jnp.zeros((l,), jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32),
    )


class ForcedSplits(NamedTuple):
    """BFS-linearized forcedsplits_filename JSON (ForceSplits,
    serial_tree_learner.cpp:593-751). Step ``t < num_forced`` splits
    ``leaf[t]`` on ``feature[t]`` at feature-space bin ``threshold[t]``
    (rows with bin <= threshold go left). The leaf indices are computable
    at setup time because the node numbering is deterministic: step t's
    right child is always leaf t + 1."""
    leaf: jnp.ndarray       # [Q] int32
    feature: jnp.ndarray    # [Q] int32 (inner feature index)
    threshold: jnp.ndarray  # [Q] int32 (feature-space bin)


class CegbState(NamedTuple):
    """Cost-Effective Gradient Boosting acquisition state. Persists across
    trees (a SerialTreeLearner member in the reference, reset only with the
    training data): once a feature is bought, later splits on it are free."""
    coupled_penalty: jnp.ndarray  # [F] f32, tradeoff * penalty_feature_coupled
    lazy_penalty: jnp.ndarray     # [F] f32, tradeoff * penalty_feature_lazy
    feature_used: jnp.ndarray     # [F] bool — any split on f so far
    row_used: jnp.ndarray         # [F, N] uint8 — row paid for f (lazy);
    #                               [F, 0] when lazy penalties are off


class PoolMap(NamedTuple):
    """Slot bookkeeping for the capped histogram pool."""
    slot_of_leaf: jnp.ndarray  # [L] int32, -1 = evicted / never built
    leaf_of_slot: jnp.ndarray  # [S] int32, -1 = free
    last_used: jnp.ndarray     # [S] int32 LRU stamp, -1 = free


class _GrowState(NamedTuple):
    leaf_id: jnp.ndarray      # [N] int32; grown on a bag, the row-space
    #                           ids in bins_by_col's row shape
    hist_pool: jnp.ndarray    # [S, F, B, 3] f32 histogram slots (S = L
    #                           uncapped, or pool_slots under the LRU cap)
    best: BestSplit           # per-leaf best split, fields [L]
    tree: TreeArrays
    leaf_min: jnp.ndarray     # [L] f32 monotone lower output bound
    leaf_max: jnp.ndarray     # [L] f32 monotone upper output bound
    part: Optional[RowPartition]  # row partition (use_partition mode only)
    cegb: Optional[CegbState]     # CEGB acquisition state (None = off)
    force_aborted: jnp.ndarray    # scalar bool — a forced split failed;
    #                               remaining forced steps fall back to
    #                               best-first (aborted_last_force_split)
    pool_map: Optional[PoolMap]   # LRU slot map (None = uncapped)
    work: Optional[jnp.ndarray] = None  # [2, W] int32 work counts (Grown.work)


class Grown(NamedTuple):
    """What grow_tree returns. ``work`` is the tree's work counts where it
    grew over the single-device row partition, else None: an int32
    [2, len(WORK_COUNTS)] array, a count being
    ``work[1] * WORK_LIMB + work[0]`` (two limbs: a lopsided tree splits
    (L - 1) x N rows, 9e9 at 35.4M rows and 255 leaves, and int32 is what
    the device has)."""
    tree: TreeArrays
    leaf_id: jnp.ndarray
    cegb: Optional[CegbState]
    work: Optional[jnp.ndarray]


_LIMB_BITS = 20
WORK_LIMB = 1 << _LIMB_BITS

# The work a tree's growth over the row partition adds up, once a split and
# from counts the loop holds anyway (no op inside a tile loop):
# ``split_rows``, the rows of the leaves split (the partition pass's rows)
# in ``partition_tiles`` tiles of ``row_chunk``; ``hist_rows``, the rows
# whose bins entered a histogram kernel call: the root's pass (every row, or
# the bag), then in ``hist_tiles`` tiles each smaller child's and, under a
# capped pool, each leaf's whose histogram a miss built again. A count a
# grower has no meaning for is absent, never 0.
WORK_COUNTS = ("split_rows", "partition_tiles", "hist_rows", "hist_tiles")


def _tiles(rows, chunk: int):
    return (rows + (chunk - 1)) // chunk


def _add_work(work: jnp.ndarray, counts) -> jnp.ndarray:
    """``work`` + a vector of non-negative int32 ``counts``, limb by limb."""
    add = jnp.stack([jnp.asarray(c, jnp.int32) for c in counts])
    low = work[0] + (add & (WORK_LIMB - 1))
    return jnp.stack([low & (WORK_LIMB - 1),
                      work[1] + (add >> _LIMB_BITS) + (low >> _LIMB_BITS)])


def _empty_best(num_leaves: int, dtype=jnp.float32) -> BestSplit:
    l = num_leaves
    f32 = lambda: jnp.zeros((l,), dtype)
    return BestSplit(
        gain=jnp.full((l,), K_MIN_SCORE, dtype),
        feature=jnp.zeros((l,), jnp.int32),
        threshold=jnp.zeros((l,), jnp.int32),
        default_left=jnp.zeros((l,), bool),
        left_sum_grad=f32(), left_sum_hess=f32(), left_count=f32(),
        right_sum_grad=f32(), right_sum_hess=f32(), right_count=f32(),
        left_output=f32(), right_output=f32(),
        is_categorical=jnp.zeros((l,), bool),
        cat_bitset=jnp.zeros((l, 8), jnp.uint32),
    )


def _masked_set(arr: jnp.ndarray, idx: jnp.ndarray, val, valid) -> jnp.ndarray:
    return arr.at[idx].set(jnp.where(valid, val, arr[idx]))


def count_i32(count: jnp.ndarray) -> jnp.ndarray:
    """A histogram's count (a float sum of 0/1 sample weights) as the
    integer a tree records."""
    return jnp.round(count).astype(jnp.int32)


def expand_hist(hist, sum_g, sum_h, cnt, meta: FeatureMeta,
                params: "GrowParams", ncols: int) -> jnp.ndarray:
    """[C, B, 3] column histograms -> [F, Bf, 3] per-feature views.

    EFB: each feature's bins are a contiguous slice of its column
    (feature_group.h bin_offsets_). A bundled feature's default bin is
    shared with its bundle-mates, so its entry is rebuilt from leaf
    totals — the Dataset::FixHistogram idea (dataset.h:411-412).
    Joint-coded pair columns: a feature's bin-b entry is the MARGINAL
    over the pair-mate's digit — sum of `pack_partner` joint bins at
    stride pack_div (for the high digit) or pack_mod (low digit).
    """
    b = params.num_bins
    bf = params.num_feat_bins or b
    if not params.with_efb:
        return hist
    flat = hist.reshape(ncols * b, 3)
    bidx = jnp.arange(bf, dtype=jnp.int32)[None, :]          # [1, Bf]
    in_feat = bidx < meta.num_bin[:, None]                   # [F, Bf]
    idx = meta.col[:, None] * b + meta.offset[:, None] + bidx
    out = jnp.take(flat, jnp.clip(idx, 0, ncols * b - 1), axis=0) \
        * in_feat[..., None]
    if params.packed_features:
        # joint-coded pairs: overwrite just the packed features' rows
        # with marginals of their column's joint histogram — a [P, Bf,
        # J] gather-sum over the (static) packed subset, so unpacked
        # features never pay for the marginalization width
        pf = jnp.asarray(params.packed_features, jnp.int32)  # [P]
        jstride = jnp.where(meta.pack_div[pf] > 1, 1,
                            jnp.maximum(meta.pack_mod[pf], 1))
        jj = jnp.arange(params.pack_j, dtype=jnp.int32)[None, None, :]
        bidx_p = jnp.arange(bf, dtype=jnp.int32)[None, :, None]
        idx_p = (meta.col[pf][:, None, None] * b
                 + bidx_p * meta.pack_div[pf][:, None, None]
                 + jj * jstride[:, None, None])              # [P, Bf, J]
        ok = (jj < meta.pack_partner[pf][:, None, None]) \
            & (bidx_p < meta.num_bin[pf][:, None, None])
        out_p = jnp.sum(
            jnp.take(flat, jnp.clip(idx_p, 0, ncols * b - 1), axis=0)
            * ok[..., None], axis=2)                         # [P, Bf, 3]
        out = out.at[pf].set(out_p)
    totals = jnp.stack([sum_g, sum_h, cnt])                  # [3]
    is_def = bidx == meta.default_bin[:, None]               # [F, Bf]
    sum_wo_def = jnp.sum(jnp.where(is_def[..., None], 0.0, out), axis=1)
    rebuilt = totals[None, :] - sum_wo_def                   # [F, 3]
    return jnp.where((is_def & meta.bundled[:, None])[..., None],
                     rebuilt[:, None, :], out)


def decode_bundle_value(v: jnp.ndarray, offset: jnp.ndarray,
                        num_bin: jnp.ndarray,
                        default_bin: jnp.ndarray,
                        pack_div=None, pack_mod=None) -> jnp.ndarray:
    """Stored column value -> the feature's own bin index.

    EFB bundles: a value inside [offset, offset + num_bin) belongs to this
    feature; anything else means some bundle-mate (or the shared zero slot)
    is active, i.e. this feature sits at its default bin (io/bundle.py
    encoding). Joint-coded pair columns (io/dataset.py _pack_small_pairs):
    the feature's bin is a base-`pack_div` digit of the stored value.
    Identity for singleton columns (offset 0, values always in range).
    """
    vv = v.astype(jnp.int32)
    if pack_div is not None:
        packed = pack_mod > 0
        vv = jnp.where(packed,
                       (vv // jnp.maximum(pack_div, 1))
                       % jnp.maximum(pack_mod, 1), vv)
    vv = vv - offset
    return jnp.where((vv >= 0) & (vv < num_bin), vv, default_bin)


def _bin_go_left(col: jnp.ndarray, threshold: jnp.ndarray,
                 default_left: jnp.ndarray, missing_type: jnp.ndarray,
                 num_bin: jnp.ndarray, default_bin: jnp.ndarray,
                 is_cat: jnp.ndarray, cat_bitset: jnp.ndarray) -> jnp.ndarray:
    """Decision in bin space (Tree::NumericalDecisionInner /
    CategoricalDecisionInner, tree.h:212-260).

    One split (cat_bitset [8], scalar split params) or per-row splits
    (cat_bitset [N, 8], every param [N] — batched-frontier routing); the
    missing-value and categorical semantics must stay in exactly one
    place so exact growth, batched growth, and predict cannot diverge.
    One split's set is tested without a gather (``_select_word``); the
    per-row form keeps its ``take_along_axis``.
    ``is_cat=None`` skips the categorical branch entirely (datasets with
    no categorical features — avoids materializing [N, 8] bitset gathers
    in the batched routing pass).
    """
    coli = col.astype(jnp.int32)
    is_missing = jnp.where(
        missing_type == MISSING_NAN, coli == num_bin - 1,
        jnp.where(missing_type == MISSING_ZERO, coli == default_bin, False))
    numerical = jnp.where(is_missing, default_left, coli <= threshold)
    if is_cat is None:
        return numerical
    if cat_bitset.ndim == 1:
        word = _select_word(cat_bitset, coli >> 5)
    else:
        word = jnp.take_along_axis(cat_bitset, (coli >> 5)[:, None],
                                   axis=1)[:, 0]
    categorical = ((word >> (coli & 31).astype(jnp.uint32)) & 1) == 1
    return jnp.where(is_cat, categorical, numerical)


def _select_word(cat_bitset: jnp.ndarray, word_index: jnp.ndarray
                 ) -> jnp.ndarray:
    """``cat_bitset[word_index]`` of ONE split's eight words without a
    gather: seven selects, elementwise over the rows, which fuse into the
    routing pass. A gather costs per index whatever it reads (9 ns a row
    on a v5e: 37 us a 4,096-row tile, 258 ms a pass over 26.6M rows), and
    the exact grower routes every tile of every split through here."""
    last = cat_bitset.shape[0] - 1
    word = cat_bitset[last]
    for i in range(last - 1, -1, -1):
        word = jnp.where(word_index == i, cat_bitset[i], word)
    return word


class FeatureParallelCtx(NamedTuple):
    """Device-varying context for the EXPLICIT feature-parallel learner
    (feature_parallel_tree_learner.cpp:30-60): every device holds the full
    rows, histogram/search work is divided by a bin-balanced column
    assignment, and only best-split STRUCTS cross the mesh.

    xb_local: [N, Cd] this device's stored-column slice (hist build input);
    meta_local: FeatureMeta over the device's features, with ``col``
    pointing into xb_local; global_of_local: [Fd] int32 map back to global
    feature indices (-1 padding carries feature_mask False).
    """
    xb_local: jnp.ndarray
    meta_local: FeatureMeta
    global_of_local: jnp.ndarray


def _psum(x, axis_name):
    """Every collective of the exact grower carries one scope, so a trace
    of a sharded run finds the exchange named."""
    with jax.named_scope("lgbm.exchange"):
        return lax.psum(x, axis_name)


def _all_gather(x, axis_name):
    with jax.named_scope("lgbm.exchange"):
        return lax.all_gather(x, axis_name)


def sync_best_split(bs: BestSplit, axis_name: str) -> BestSplit:
    """SyncUpGlobalBestSplit (parallel_tree_learner.h:186-230) as one
    argmax-allreduce: every rank contributes its local best-split struct,
    the max-gain rank's struct is broadcast to all. Comm volume is
    O(struct fields), never O(F*B)."""
    gains = _all_gather(bs.gain, axis_name)             # [D]
    winner = jnp.argmax(gains).astype(jnp.int32)
    mine = lax.axis_index(axis_name) == winner

    def bcast(v):
        if v.dtype == jnp.bool_:
            z = jnp.where(mine, v.astype(jnp.int32), 0)
            return _psum(z, axis_name) > 0
        if v.dtype == jnp.uint32:
            # lossless: bitcast to i32 (sum of winner's word + zeros is
            # exact), never a value-cast that truncates the high bit
            z = jnp.where(mine, lax.bitcast_convert_type(v, jnp.int32), 0)
            return lax.bitcast_convert_type(_psum(z, axis_name),
                                            jnp.uint32)
        return _psum(jnp.where(mine, v, jnp.zeros_like(v)), axis_name)

    return jax.tree.map(bcast, bs)


def propagate_monotone_bounds(mono, left_output, right_output, p_min, p_max):
    """Monotone constraint propagation (serial_tree_learner.cpp:790-847):
    children inherit the parent's output bounds; a monotone split feature
    additionally pins the shared boundary at the midpoint of the two child
    outputs. Returns (l_min, l_max, r_min, r_max). Shared by exact and
    batched growth — the K=1 bit-for-bit parity contract depends on it."""
    mid = (left_output + right_output) * 0.5
    l_min = jnp.where(mono < 0, jnp.maximum(p_min, mid), p_min)
    l_max = jnp.where(mono > 0, jnp.minimum(p_max, mid), p_max)
    r_min = jnp.where(mono > 0, jnp.maximum(p_min, mid), p_min)
    r_max = jnp.where(mono < 0, jnp.minimum(p_max, mid), p_max)
    return l_min, l_max, r_min, r_max


def grow_tree(xb: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              sample_mask: jnp.ndarray, meta: FeatureMeta,
              feature_mask: jnp.ndarray, params: GrowParams,
              axis_name: Optional[str] = None,
              forced: Optional[ForcedSplits] = None,
              cegb: Optional[CegbState] = None,
              fp: Optional[FeatureParallelCtx] = None,
              bag: Optional[RowPartition] = None,
              bins_by_col: Optional[jnp.ndarray] = None,
              ) -> Grown:
    """Grow one leaf-wise tree; returns (tree, final per-row leaf_id,
    updated CEGB state or None, the tree's work counts or None).

    xb [N, F] uint8 binned features; grad/hess [N] f32 (objective-weighted);
    sample_mask [N] f32 bagging inclusion. With ``axis_name`` set, rows are
    assumed sharded over that mesh axis and histograms/root sums are
    psum-reduced (the data-parallel learner's ReduceScatter analog).

    With ``fp`` set (explicit feature-parallel,
    feature_parallel_tree_learner.cpp:30-60): rows are REPLICATED, each
    device builds histograms and searches splits only over its assigned
    columns (fp.xb_local / fp.meta_local), and the per-leaf best split is
    argmax-allreduced as a struct (sync_best_split) — row partitioning is
    then computed locally and identically on every device from the
    replicated xb.

    With ``bag`` set (partition.bag_partition; the single-device partition
    path only): the tree is grown on the bag's rows. ``sample_mask`` is 1
    on them and 0 elsewhere; the row partition holds the bag alone, so the
    root's pass, every smaller child's pass and the tree's counts see its
    rows alone. The rows out of it have no place in ``order``: every split
    routes ALL rows in row space, one streaming pass over the split column
    of ``bins_by_col`` (partition.bins_by_column(xb), made once by the
    caller), so the returned leaf_id covers all rows and
    leaf_id_from_partition is not run.
    """
    n, ncols = xb.shape                 # stored columns (== F without EFB)
    f = meta.num_bin.shape[0]           # logical features
    l = params.num_leaves
    b = params.num_bins                 # column-histogram bin axis
    bf = params.num_feat_bins or b      # per-feature bin axis (split search)
    sp = params.split
    # histogram accumulation dtype (f64 = reference gpu_use_dp semantics)
    # lgbm-lint: disable=LGL105 gated gpu_use_dp fallback, f32 default
    hdt = jnp.float64 if params.hist_dtype == "f64" else jnp.float32

    fp_mode = fp is not None and axis_name is not None
    # self-enforcing invariant (not just the GBDT gate): fp mode has no
    # expand/global-histogram machinery for forced splits, CEGB penalties,
    # or voting — silently dropping them would build wrong trees
    assert not fp_mode or params.hist_dtype == "f32", \
        "f64 histograms are not supported on the explicit feature-parallel " \
        "learner (sync_best_split bitcasts f32; use the GSPMD fallback)"
    assert not fp_mode or (forced is None and cegb is None
                           and params.num_forced == 0
                           and params.voting_top_k == 0), \
        "feature-parallel fp mode is incompatible with forced splits / " \
        "CEGB / voting (route through the GSPMD fallback instead)"
    voting = params.voting_top_k > 0 and axis_name is not None and not fp_mode
    use_partition = params.use_partition and not fp_mode and (
        axis_name is None or (params.partition_on_mesh and not voting))
    # histogram source: the device's column slice in fp mode
    xb_hist = fp.xb_local if fp_mode else xb
    ncols_h = xb_hist.shape[1]
    if fp_mode:
        gofl = fp.global_of_local
        fmask_local = jnp.where(
            gofl >= 0, feature_mask[jnp.maximum(gofl, 0)], False)

    def psum(x):
        # fp mode: histograms are per-device partial WORK, not partial
        # sums — nothing to reduce (rows are replicated)
        if fp_mode or axis_name is None:
            return x
        return _psum(x, axis_name)

    # CEGB's lazy acquisition accounting reads leaf_id during growth; only
    # then is the per-split leaf_id scatter worth its cost — otherwise the
    # assignment is reconstructed from the final ranges in one dense pass
    maintain_lid = (cegb is not None and params.with_cegb_lazy)

    def hist_for_mask(mask_f32, compensated=False):
        h = build_histogram(xb_hist, grad, hess, mask_f32, num_bins=b,
                            row_chunk=params.row_chunk, impl=params.hist_impl,
                            compensated=compensated)
        # voting mode keeps histograms LOCAL (the pool then supports local
        # subtraction); only elected candidates are reduced, in voting_best
        return h if voting else psum(h)

    def expand(hist, sum_g, sum_h, cnt):
        return expand_hist(hist, sum_g, sum_h, cnt, meta, params, ncols)

    def cegb_gain_penalty(cegb_state, cnt, leaf_mask):
        """[F] CEGB penalty for one candidate leaf
        (serial_tree_learner.cpp:533-539): split cost scales with leaf
        size; coupled cost applies to never-bought features; lazy cost
        counts the leaf's rows that haven't paid for the feature yet
        (CalculateOndemandCosts, :484-504)."""
        if cegb_state is None:
            return None
        pen = jnp.full((f,), params.cegb_split_penalty * cnt, jnp.float32)
        if params.with_cegb_coupled:
            pen = pen + jnp.where(cegb_state.feature_used, 0.0,
                                  cegb_state.coupled_penalty)
        if params.with_cegb_lazy:
            unpaid = psum(jnp.sum(
                leaf_mask[None, :] * (1.0 - cegb_state.row_used
                                      .astype(jnp.float32)), axis=1))  # [F]
            pen = pen + cegb_state.lazy_penalty * unpaid
        return pen

    def full_best(hist, sum_g, sum_h, cnt, depth_ok, min_c=-jnp.inf,
                  max_c=jnp.inf, gain_penalty=None):
        if fp_mode:
            # local search over this device's columns, then one struct
            # allreduce (SyncUpGlobalBestSplit) — comm O(fields), not O(F*B)
            assert gain_penalty is None, \
                "CEGB gain penalties cannot ride the fp-mode local search"
            bs = find_best_split(hist, fp.meta_local, sp, sum_g, sum_h, cnt,
                                 fmask_local, min_constraint=min_c,
                                 max_constraint=max_c,
                                 with_categorical=bool(
                                     params.with_categorical))
            bs = bs._replace(
                feature=jnp.maximum(gofl[bs.feature], 0),
                gain=jnp.where(depth_ok, bs.gain, K_MIN_SCORE))
            return sync_best_split(bs, axis_name)
        bs = find_best_split(expand(hist, sum_g, sum_h, cnt), meta, sp,
                             sum_g, sum_h, cnt,
                             feature_mask, min_constraint=min_c,
                             max_constraint=max_c,
                             with_categorical=params.with_categorical,
                             gain_penalty=gain_penalty)
        return bs._replace(gain=jnp.where(depth_ok, bs.gain, K_MIN_SCORE))

    def voting_best(hist_local, sum_g, sum_h, cnt, depth_ok, min_c=-jnp.inf,
                    max_c=jnp.inf, gain_penalty=None):
        """PV-Tree candidate election (voting_parallel_tree_learner.cpp:
        166-360): rank-local top-k proposals from local-histogram gains, a
        global vote elects <=2*top_k features, and only those features'
        histograms are summed across the mesh (comm O(2k*B) vs O(F*B))."""
        assert gain_penalty is None, \
            "CEGB is not supported with the voting-parallel learner"
        k = min(params.voting_top_k, f)
        k2 = min(2 * params.voting_top_k, f)
        # local leaf totals from the local histogram itself: every local row
        # lands in exactly one bin of feature 0
        lsg = jnp.sum(hist_local[0, :, 0])
        lsh = jnp.sum(hist_local[0, :, 1])
        lsc = jnp.sum(hist_local[0, :, 2])
        pf, _ = per_feature_split_merged(
            hist_local, meta, sp, lsg, lsh, lsc, feature_mask,
            with_categorical=params.with_categorical)
        top_gain, top_idx = lax.top_k(pf.gain, k)
        w = jnp.isfinite(top_gain).astype(jnp.int32)   # only real proposals
        all_idx = _all_gather(top_idx, axis_name).reshape(-1)
        all_w = _all_gather(w, axis_name).reshape(-1)
        votes = jnp.zeros((f,), jnp.int32).at[all_idx].add(all_w)
        elected = lax.top_k(votes, k2)[1]
        cand = _psum(jnp.take(hist_local, elected, axis=0), axis_name)
        gh = jnp.zeros_like(hist_local).at[elected].set(cand)
        cand_mask = jnp.zeros((f,), bool).at[elected].set(True)
        bs = find_best_split(gh, meta, sp, sum_g, sum_h, cnt,
                             feature_mask & cand_mask,
                             min_constraint=min_c, max_constraint=max_c,
                             with_categorical=params.with_categorical)
        return bs._replace(gain=jnp.where(depth_ok, bs.gain, K_MIN_SCORE))

    def best_for(*args, **kwargs):
        with jax.named_scope("lgbm.split_search"):
            return (voting_best if voting else full_best)(*args, **kwargs)

    # ---- root ------------------------------------------------------------
    sample_mask = sample_mask.astype(hdt)
    grad = grad.astype(hdt)
    hess = hess.astype(hdt)
    # bins + value channels behind one gather closure: packed single-gather
    # rows on the normal path; two gathers under vmapped class batching,
    # where packing would copy the shared bin matrix per class
    # (make_row_gather docstring)
    # XLA fuses the objective's gradient math into this pass over the rows
    # (the fused op takes its root's name), so it carries the same scope
    with jax.named_scope("lgbm.gradients"):
        gather_rows = (make_row_gather(xb,
                                       stack_vals(grad, hess, sample_mask),
                                       packed=not params.vmapped_classes)
                       if use_partition else None)
    # the tree's counts come from the partition's integers where those
    # count what the histogram's count channel counts
    assert bag is None or (use_partition and axis_name is None
                           and not maintain_lid
                           and bins_by_col is not None), \
        "a bag is the single-device row partition's, routed in row space"
    exact_counts = ((params.all_rows_in_bag or bag is not None)
                    and use_partition and axis_name is None)
    part0 = None
    if bag is not None:
        zeros_l = jnp.zeros((l,), jnp.int32)
        part0 = RowPartition(bag.order, zeros_l,
                             zeros_l.at[0].set(bag.leaf_count[0]))
    elif use_partition:
        part0 = init_partition(n, l, params.row_chunk)

    def hist_of_range(part, leaf_idx, valid=True, scope=None):
        """[C, B, 3] over ``leaf_idx``'s range of the row partition (this
        device's rows of it)."""
        return hist_for_leaf(part, leaf_idx, gather_rows, n, ncols, b,
                             params.row_chunk, valid=valid,
                             impl=params.hist_impl, val_dtype=hdt,
                             scope=scope)

    with jax.named_scope("lgbm.root_hist"):
        root_g = psum(jnp.sum(grad * sample_mask))
        root_h = psum(jnp.sum(hess * sample_mask))
        # over the row partition every other leaf's histogram comes from
        # this one by subtracting sums that carry their rounding
        # (hist_for_leaf), so this one carries its own too
        if bag is None:
            root_c = psum(jnp.sum(sample_mask))
            hist_root = hist_for_mask(sample_mask, compensated=use_partition)
        else:
            root_c = bag.leaf_count[0].astype(hdt)
            hist_root = hist_of_range(part0, 0, scope="lgbm.root_hist")

    tree = empty_tree(l, hdt)
    tree = tree._replace(
        leaf_value=tree.leaf_value.at[0].set(
            calculate_leaf_output(root_g, root_h, sp.lambda_l1, sp.lambda_l2,
                                  sp.max_delta_step)),
        leaf_weight=tree.leaf_weight.at[0].set(root_h),
        leaf_count=tree.leaf_count.at[0].set(
            (jnp.int32(n) if bag is None else bag.leaf_count[0])
            if exact_counts else count_i32(root_c)))

    root_pen = cegb_gain_penalty(cegb, root_c, sample_mask)
    best0 = best_for(hist_root, root_g, root_h, root_c, True,
                     gain_penalty=root_pen)  # root: depth 0
    best = jax.tree.map(lambda a, v: a.at[0].set(v), _empty_best(l, hdt),
                        best0)

    capped = 0 < params.pool_slots < l
    assert not (capped and axis_name is not None), \
        "histogram_pool_size cap is not supported on sharded learners " \
        "(rebuild-on-miss cannot psum under lax.cond)"
    assert not capped or params.pool_slots >= 2, \
        "a capped histogram pool needs at least 2 slots (both children " \
        "of a split are resident)"
    num_slots = params.pool_slots if capped else l
    hist_pool = jnp.zeros((num_slots, ncols_h, b, 3), hdt)
    if voting:
        # the pool holds LOCAL histograms in voting mode -> device-varying
        hist_pool = lax.pcast(hist_pool, (axis_name,), to="varying")
    hist_pool = hist_pool.at[0].set(hist_root)
    pool_map0 = None
    if capped:
        pool_map0 = PoolMap(
            slot_of_leaf=jnp.full((l,), -1, jnp.int32).at[0].set(0),
            leaf_of_slot=jnp.full((num_slots,), -1, jnp.int32).at[0].set(0),
            last_used=jnp.full((num_slots,), -1, jnp.int32).at[0].set(0))

    def leaf_hist(s: _GrowState, leaf_idx, live=True):
        """A leaf's [C, B, 3] histogram: the pool slot when resident, else
        rebuilt from the leaf's rows (HistogramPool::Get miss path). Must
        run BEFORE the step's partition update — the rebuild walks the
        pre-split row partition / leaf_id."""
        if not capped:
            return s.hist_pool[leaf_idx]
        sl = s.pool_map.slot_of_leaf[leaf_idx]

        def read(_):
            return s.hist_pool[jnp.maximum(sl, 0)]

        def rebuild(_):
            if use_partition:
                return hist_of_range(s.part, leaf_idx)
            m = (s.leaf_id == leaf_idx).astype(hdt) * sample_mask
            return hist_for_mask(m)

        # dead iterations (live=False) never pay for a rebuild
        return lax.cond((sl < 0) & live, rebuild, read, operand=None)

    def rebuilt_rows(s: _GrowState, leaf_idx, live):
        """The rows leaf_hist walks again for ``leaf_idx``: its range's on a
        pool miss, 0 on a hit."""
        missed = (s.pool_map.slot_of_leaf[leaf_idx] < 0) & live
        return jnp.where(missed, s.part.leaf_count[leaf_idx], 0)

    # the work counts (WORK_COUNTS) start from the root's pass: every row,
    # or the bag
    work0 = None
    if use_partition and axis_name is None:
        root_rows = n if bag is None else bag.leaf_count[0]
        work0 = _add_work(jnp.zeros((2, len(WORK_COUNTS)), jnp.int32),
                          (0, 0, root_rows, 0))

    leaf_id0 = jnp.zeros((n,), jnp.int32)
    if bag is not None:
        leaf_id0 = row_space_leaf_ids0(bins_by_col, l)
    if axis_name is not None:
        # under shard_map the carry must be marked device-varying up front:
        # it starts as a constant but becomes a function of the sharded rows
        leaf_id0 = lax.pcast(leaf_id0, (axis_name,), to="varying")
    if part0 is not None and axis_name is not None:
        # same pcast story as leaf_id0: starts constant, becomes a function
        # of the device-local rows
        part0 = jax.tree.map(
            lambda a: lax.pcast(a, (axis_name,), to="varying"), part0)
    state = _GrowState(leaf_id=leaf_id0, hist_pool=hist_pool,
                       best=best, tree=tree,
                       leaf_min=jnp.full((l,), -jnp.inf, hdt),
                       leaf_max=jnp.full((l,), jnp.inf, hdt),
                       part=part0, cegb=cegb,
                       force_aborted=jnp.asarray(False),
                       pool_map=pool_map0, work=work0)

    def forced_split_info(s: _GrowState, t: jnp.ndarray, in_phase):
        """Evaluate the step-t forced (leaf, feature, threshold) from the
        leaf's pooled histogram — GatherInfoForThresholdNumerical
        (feature_histogram.hpp:284-357). Returns (leaf, BestSplit, ok)."""
        tq = jnp.minimum(t, params.num_forced - 1)
        fleaf = forced.leaf[tq]
        ff = forced.feature[tq]
        fthr = forced.threshold[tq]
        # steps past the forced phase discard this whole evaluation;
        # live=False keeps them from paying a pool-miss rebuild
        ph_col = leaf_hist(s, fleaf, live=in_phase)       # [C, B, 3]
        # exact-enough leaf totals: every row lands in one bin of column 0
        sum_g = jnp.sum(ph_col[0, :, 0])
        sum_h = jnp.sum(ph_col[0, :, 1])
        cnt = jnp.sum(ph_col[0, :, 2])
        row = expand(ph_col, sum_g, sum_h, cnt)[ff]       # [Bf, 3]
        nb = meta.num_bin[ff]
        db = meta.default_bin[ff]
        mt = meta.missing_type[ff]
        bidx = jnp.arange(row.shape[0], dtype=jnp.int32)
        # right side accumulates bins > threshold; the default bin (Zero
        # missing) and the NaN bin fall left by subtraction, exactly like
        # the reference's skip_default_bin / use_na_as_missing loop
        in_right = (bidx > fthr) & (bidx < nb) \
            & ~((mt == MISSING_ZERO) & (bidx == db)) \
            & ~((mt == MISSING_NAN) & (bidx == nb - 1))
        r = jnp.sum(row * in_right[:, None].astype(row.dtype), axis=0)
        rg, rh, rc = r[0], r[1] + K_EPSILON, r[2]
        lg, lh, lc = sum_g - rg, sum_h - rh, cnt - rc
        shift = leaf_split_gain(sum_g, sum_h, sp.lambda_l1, sp.lambda_l2,
                                sp.max_delta_step) + sp.min_gain_to_split
        gain = leaf_split_gain(lg, lh, sp.lambda_l1, sp.lambda_l2,
                               sp.max_delta_step) \
            + leaf_split_gain(rg, rh, sp.lambda_l1, sp.lambda_l2,
                              sp.max_delta_step) - shift
        ok = (gain > 0.0) & (lc > 0) & (rc > 0)
        bs = BestSplit(
            gain=jnp.maximum(gain, 1e-30), feature=ff, threshold=fthr,
            default_left=jnp.asarray(True),
            left_sum_grad=lg, left_sum_hess=lh, left_count=lc,
            right_sum_grad=rg, right_sum_hess=rh, right_count=rc,
            left_output=calculate_leaf_output(
                lg, lh, sp.lambda_l1, sp.lambda_l2, sp.max_delta_step),
            right_output=calculate_leaf_output(
                rg, rh, sp.lambda_l1, sp.lambda_l2, sp.max_delta_step),
            is_categorical=jnp.asarray(False),
            cat_bitset=jnp.zeros((8,), jnp.uint32))
        return fleaf, bs, ok

    def step(t: jnp.ndarray, s: _GrowState,
             with_forced: bool = False) -> _GrowState:
        tree = s.tree
        leaf = jnp.argmax(s.best.gain).astype(jnp.int32)
        cur = jax.tree.map(lambda a: a[leaf], s.best)
        force_aborted = s.force_aborted
        if with_forced:
            # only traced into the first num_forced loop steps (the loop is
            # split at the static phase boundary below), so steps past the
            # forced phase never pay the evaluation or its sharded-rebuild
            # psum; the dynamic mask only covers mid-phase aborts
            in_phase = ~s.force_aborted
            fleaf, fcur, fok = forced_split_info(s, t, in_phase)
            use_forced = in_phase & fok
            force_aborted = s.force_aborted | (in_phase & ~fok)
            leaf = jnp.where(use_forced, fleaf, leaf)
            cur = jax.tree.map(
                lambda fv, bv: jnp.where(use_forced, fv, bv), fcur,
                jax.tree.map(lambda a: a[leaf], s.best))
        valid = cur.gain > 0.0  # reference breaks on gain <= 0 (:217-219)

        # ---- partition rows of `leaf` (DataPartition::Split analog) ------
        right_leaf = t + 1
        # the split column's metadata, looked up once a split: the tile
        # loop below routes every tile by the same few scalars
        split_missing = meta.missing_type[cur.feature]
        split_num_bin = meta.num_bin[cur.feature]
        split_default_bin = meta.default_bin[cur.feature]
        if params.with_efb:
            stored_col = meta.col[cur.feature]
            split_offset = meta.offset[cur.feature]
            split_div = (meta.pack_div[cur.feature]
                         if meta.pack_div is not None else None)
            split_mod = (meta.pack_mod[cur.feature]
                         if meta.pack_mod is not None else None)

            def to_feat_bin(v):
                return decode_bundle_value(
                    v, split_offset, split_num_bin, split_default_bin,
                    pack_div=split_div, pack_mod=split_mod)
        else:
            stored_col = cur.feature

            def to_feat_bin(v):
                return v

        # a dataset without categorical columns routes without the
        # category bitset (as the wave growers do)
        split_is_cat = cur.is_categorical if params.with_categorical else None

        def go_left_bins(colv):
            """The split's decision from its stored column's values."""
            return _bin_go_left(
                to_feat_bin(colv), cur.threshold, cur.default_left,
                split_missing, split_num_bin, split_default_bin,
                split_is_cat, cur.cat_bitset)

        if use_partition:
            def go_left_rows(rows):
                # dynamic-column extract as a one-hot matvec — bin bytes
                # are exact in f32, and a dense [chunk, C] @ [C] product
                # avoids another indexed gather
                onehot_col = (jnp.arange(ncols, dtype=jnp.int32)
                              == stored_col).astype(jnp.float32)
                return go_left_bins(
                    jnp.einsum("rc,c->r", rows.astype(jnp.float32),
                               onehot_col).astype(jnp.int32))

            part, leaf_id = partition_rows(
                s.part, s.leaf_id, leaf, right_leaf, go_left_rows, valid,
                params.row_chunk, gather_rows,
                maintain_leaf_id=maintain_lid,
                windows=window_placement(params.hist_impl,
                                         params.vmapped_classes))
            if bag is not None:
                # every row, in the bag or out, takes the split's side in
                # row space; ``order`` lists the bag's rows alone
                leaf_id = route_in_row_space(
                    leaf_id, bins_by_col, stored_col, go_left_bins, leaf,
                    right_leaf, valid)
        else:
            part = s.part
            go_left = go_left_bins(jnp.take(xb, stored_col, axis=1))
            in_leaf = s.leaf_id == leaf
            leaf_id = jnp.where(valid & in_leaf & ~go_left, right_leaf,
                                s.leaf_id)

        # ---- tree bookkeeping (Tree::Split, tree.cpp:49-67) --------------
        node = t
        parent_node = tree.leaf_parent[leaf]
        safe_p = jnp.maximum(parent_node, 0)
        p_exists = valid & (parent_node >= 0)
        was_left = tree.left_child[safe_p] == ~leaf
        left_child = _masked_set(tree.left_child, safe_p, node,
                                 p_exists & was_left)
        right_child = _masked_set(tree.right_child, safe_p, node,
                                  p_exists & ~was_left)
        left_child = _masked_set(left_child, node, ~leaf, valid)
        right_child = _masked_set(right_child, node, ~right_leaf, valid)

        if exact_counts:
            left_count = part.leaf_count[leaf]
            right_count = part.leaf_count[right_leaf]
        else:
            left_count = count_i32(cur.left_count)
            right_count = count_i32(cur.right_count)
        depth = tree.leaf_depth[leaf] + 1
        parent_value = calculate_leaf_output(
            cur.left_sum_grad + cur.right_sum_grad,
            cur.left_sum_hess + cur.right_sum_hess,
            sp.lambda_l1, sp.lambda_l2, sp.max_delta_step)

        tree = tree._replace(
            split_feature=_masked_set(tree.split_feature, node, cur.feature, valid),
            threshold_bin=_masked_set(tree.threshold_bin, node, cur.threshold, valid),
            default_left=_masked_set(tree.default_left, node, cur.default_left, valid),
            missing_type=_masked_set(tree.missing_type, node,
                                     split_missing, valid),
            is_categorical=_masked_set(tree.is_categorical, node,
                                       cur.is_categorical, valid),
            cat_bitset=tree.cat_bitset.at[node].set(
                jnp.where(valid, cur.cat_bitset, tree.cat_bitset[node])),
            left_child=left_child, right_child=right_child,
            split_gain=_masked_set(tree.split_gain, node, cur.gain, valid),
            internal_value=_masked_set(tree.internal_value, node, parent_value, valid),
            internal_weight=_masked_set(tree.internal_weight, node,
                                        cur.left_sum_hess + cur.right_sum_hess, valid),
            internal_count=_masked_set(tree.internal_count, node,
                                       left_count + right_count, valid),
            split_leaf=_masked_set(tree.split_leaf, node, leaf, valid),
            leaf_value=_masked_set(
                _masked_set(tree.leaf_value, leaf, cur.left_output, valid),
                right_leaf, cur.right_output, valid),
            leaf_weight=_masked_set(
                _masked_set(tree.leaf_weight, leaf, cur.left_sum_hess, valid),
                right_leaf, cur.right_sum_hess, valid),
            leaf_count=_masked_set(
                _masked_set(tree.leaf_count, leaf, left_count, valid),
                right_leaf, right_count, valid),
            leaf_parent=_masked_set(
                _masked_set(tree.leaf_parent, leaf, node, valid),
                right_leaf, node, valid),
            leaf_depth=_masked_set(
                _masked_set(tree.leaf_depth, leaf, depth, valid),
                right_leaf, depth, valid),
            num_leaves=tree.num_leaves + valid.astype(jnp.int32))

        # ---- histograms: build smaller child, subtract for sibling -------
        # smaller by the split's own counts, which on a mesh are the global
        # ones: every device builds the same child, whichever is the
        # smaller among its local rows
        left_smaller = cur.left_count <= cur.right_count
        small_leaf = jnp.where(left_smaller, leaf, right_leaf)
        large_leaf = jnp.where(left_smaller, right_leaf, leaf)

        if use_partition:
            # a second pass, over the smaller child's new range only; a dead
            # iteration walks no tile (and psums zeros on a mesh: one
            # collective a split, outside every loop)
            hist_small = psum(hist_of_range(part, small_leaf, valid))
        elif axis_name is None:
            def live_hist(_):
                m = (leaf_id == small_leaf).astype(hdt) * sample_mask
                return hist_for_mask(m)

            # skip dead iterations entirely (tree stopped growing early)
            hist_small = lax.cond(valid, live_hist,
                                  lambda _: jnp.zeros((ncols_h, b, 3),
                                                      hdt),
                                  operand=None)
        else:
            # collectives can't sit under a cond branch in SPMD code; a dead
            # iteration just psums zeros
            hist_small = hist_for_mask(
                (leaf_id == small_leaf).astype(hdt) * sample_mask
                * valid.astype(hdt))
        work = s.work
        if work is not None:
            # what this split's two tile loops walked, from the ranges' own
            # counts; under a capped pool also what a miss walks again: the
            # parent's range below, the forced leaf's in forced_split_info
            split_rows = jnp.where(valid, s.part.leaf_count[leaf], 0)
            walked = [jnp.where(valid, part.leaf_count[small_leaf], 0)]
            if capped:
                walked.append(rebuilt_rows(s, leaf, valid))
                if with_forced:
                    walked.append(rebuilt_rows(s, fleaf, in_phase))
            work = _add_work(work, (
                split_rows, _tiles(split_rows, params.row_chunk),
                sum(walked),
                sum(_tiles(r, params.row_chunk) for r in walked)))
        with jax.named_scope("lgbm.hist_subtract"):
            hist_parent = leaf_hist(s, leaf, live=valid)
            hist_large = hist_parent - hist_small
        if not capped:
            # one slot a leaf
            pool_map = s.pool_map
            target_large, target_small = large_leaf, small_leaf
        else:
            # LRU slot allocation (HistogramPool::Move/Get): the larger
            # child reuses the parent's slot when resident; the smaller
            # child takes the least-recently-used other slot. Evicted
            # occupants rebuild from rows if ever chosen for splitting.
            pm = s.pool_map
            big = jnp.int32(2 ** 30)
            sl_parent = pm.slot_of_leaf[leaf]
            lru1 = jnp.argmin(pm.last_used).astype(jnp.int32)
            target_large = jnp.where(sl_parent >= 0, sl_parent, lru1)
            target_small = jnp.argmin(
                pm.last_used.at[target_large].set(big)).astype(jnp.int32)
            sol = pm.slot_of_leaf
            for prev in (pm.leaf_of_slot[target_large],
                         pm.leaf_of_slot[target_small]):
                sol = sol.at[jnp.maximum(prev, 0)].set(
                    jnp.where(valid & (prev >= 0), -1,
                              sol[jnp.maximum(prev, 0)]))
            sol = _masked_set(sol, large_leaf, target_large, valid)
            sol = _masked_set(sol, small_leaf, target_small, valid)
            los = _masked_set(pm.leaf_of_slot, target_large, large_leaf,
                              valid)
            los = _masked_set(los, target_small, small_leaf, valid)
            stamp = (t + 1).astype(jnp.int32)
            lu = _masked_set(pm.last_used, target_large, stamp, valid)
            lu = _masked_set(lu, target_small, stamp, valid)
            pool_map = PoolMap(slot_of_leaf=sol, leaf_of_slot=los,
                               last_used=lu)
        with jax.named_scope("lgbm.hist_subtract"):
            hist_pool = s.hist_pool.at[target_large].set(
                jnp.where(valid, hist_large, s.hist_pool[target_large]))
            hist_pool = hist_pool.at[target_small].set(
                jnp.where(valid, hist_small, hist_pool[target_small]))

        # ---- best splits for the two children ----------------------------
        depth_ok = (params.max_depth <= 0) | (depth < params.max_depth)
        hist_left = jnp.where(left_smaller, hist_small, hist_large)
        hist_right = jnp.where(left_smaller, hist_large, hist_small)

        mono = meta.monotone[cur.feature]
        p_min, p_max = s.leaf_min[leaf], s.leaf_max[leaf]
        l_min, l_max, r_min, r_max = propagate_monotone_bounds(
            mono, cur.left_output, cur.right_output, p_min, p_max)
        leaf_min = _masked_set(_masked_set(s.leaf_min, leaf, l_min, valid),
                               right_leaf, r_min, valid)
        leaf_max = _masked_set(_masked_set(s.leaf_max, leaf, l_max, valid),
                               right_leaf, r_max, valid)

        # ---- CEGB acquisition-state update (Split, :757, :766-774) -------
        cegb_state = s.cegb
        if cegb_state is not None:
            fu = jnp.where(valid,
                           cegb_state.feature_used.at[cur.feature].set(True),
                           cegb_state.feature_used)
            ru = cegb_state.row_used
            if params.with_cegb_lazy:
                # only bagged rows pay (the reference marks the rows in the
                # data partition, which holds the bagging subset, :766-774)
                in_split = ((leaf_id == leaf) | (leaf_id == right_leaf)) \
                    & valid & (sample_mask > 0)
                ru = ru.at[cur.feature].max(in_split.astype(ru.dtype))
            cegb_state = cegb_state._replace(feature_used=fu, row_used=ru)

        def child_bests(_):
            lp = rp = None
            if cegb_state is not None:
                lp = cegb_gain_penalty(cegb_state, cur.left_count,
                                       (leaf_id == leaf)
                                       .astype(jnp.float32) * sample_mask)
                rp = cegb_gain_penalty(cegb_state, cur.right_count,
                                       (leaf_id == right_leaf)
                                       .astype(jnp.float32) * sample_mask)
            if voting:
                bl = best_for(hist_left, cur.left_sum_grad,
                              cur.left_sum_hess, cur.left_count, depth_ok,
                              l_min, l_max, gain_penalty=lp)
                br = best_for(hist_right, cur.right_sum_grad,
                              cur.right_sum_hess, cur.right_count, depth_ok,
                              r_min, r_max, gain_penalty=rp)
                return bl, br
            # both children's split searches are independent — one vmapped
            # call instead of two sequential ones halves the small-op chain
            # of the scalar-heavy bin scans
            hist2 = jnp.stack([hist_left, hist_right])
            sg2 = jnp.stack([cur.left_sum_grad, cur.right_sum_grad])
            sh2 = jnp.stack([cur.left_sum_hess, cur.right_sum_hess])
            cc2 = jnp.stack([cur.left_count, cur.right_count])
            mn2 = jnp.stack([l_min, r_min])
            mx2 = jnp.stack([l_max, r_max])
            if lp is None:
                b2 = jax.vmap(
                    lambda hh, sg, sh, cc, mn, mx: best_for(
                        hh, sg, sh, cc, depth_ok, mn, mx))(
                    hist2, sg2, sh2, cc2, mn2, mx2)
            else:
                pen2 = jnp.stack([lp, rp])
                b2 = jax.vmap(
                    lambda hh, sg, sh, cc, mn, mx, pen: best_for(
                        hh, sg, sh, cc, depth_ok, mn, mx,
                        gain_penalty=pen))(
                    hist2, sg2, sh2, cc2, mn2, mx2, pen2)
            bl = jax.tree.map(lambda a: a[0], b2)
            br = jax.tree.map(lambda a: a[1], b2)
            return bl, br

        def dead_bests(_):
            dead = jax.tree.map(lambda a: a[0], _empty_best(1, hdt))
            return dead, dead

        if voting or fp_mode or (axis_name is not None
                                 and cegb_state is not None
                                 and params.with_cegb_lazy):
            # voting_best / sync_best_split / the lazy-CEGB unpaid-rows
            # psum hold collectives — they cannot sit under a cond branch;
            # dead iterations just reduce over zeros and are discarded by
            # the masked best-update below
            bl, br = child_bests(None)
        else:
            bl, br = lax.cond(valid, child_bests, dead_bests, operand=None)
        best = jax.tree.map(
            lambda arr, vl, vr: _masked_set(_masked_set(arr, leaf, vl, valid),
                                            right_leaf, vr, valid),
            s.best, bl, br)

        return _GrowState(leaf_id=leaf_id, hist_pool=hist_pool,
                          best=best, tree=tree,
                          leaf_min=leaf_min, leaf_max=leaf_max, part=part,
                          cegb=cegb_state, force_aborted=force_aborted,
                          pool_map=pool_map, work=work)

    if params.num_forced > 0 and forced is not None:
        nf = min(params.num_forced, l - 1)
        state = lax.fori_loop(
            0, nf, functools.partial(step, with_forced=True), state)
        state = lax.fori_loop(nf, l - 1, step, state)
    else:
        state = lax.fori_loop(0, l - 1, step, state)
    leaf_id_out = state.leaf_id
    if bag is not None:
        leaf_id_out = row_space_leaf_ids(state.leaf_id, n)
    elif use_partition and not maintain_lid:
        leaf_id_out = leaf_id_from_partition(state.part, n)
    # the model contract is f32 tree arrays regardless of the histogram
    # accumulation dtype (the reference also stores float leaf values)
    tree_out = jax.tree.map(
        # lgbm-lint: disable=LGL105 downcast guard: removes f64, never adds
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.float64 else a,
        state.tree)
    return Grown(tree_out, leaf_id_out, state.cegb, state.work)
