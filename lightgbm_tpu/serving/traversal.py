"""Serving-specialized SoA ensemble traversal: O(depth) steps, all trees at once.

The training-side replay path (core/tree.py) moves rows through a tree by
replaying its ``num_leaves - 1`` splits in creation order and sequences
trees through ``lax.scan`` — ~254 steps per 255-leaf tree, no cross-tree
vectorization. That is the right shape for training (it mirrors how
DataPartition evolves) but the wrong one for serving, where the model is
frozen and every microsecond of batch latency counts.

Here the whole ensemble is packed ONCE per model generation into a single
structure-of-arrays node table (``FlatForest``: ``[T, max_nodes]`` split
feature / threshold / default-left / missing-type / child pointers plus a
``[T, max_leaves]`` leaf-value table, ``T`` = iterations x classes), the
flattened node-array layout TF Boosted Trees and Booster serve from. All
rows x all trees then advance level-by-level: each of the ``depth`` fused
steps gathers the current node's fields for every (row, tree) pair, makes
the split decision (core/tree.py ``decision_go_left`` — the SAME routing
math as replay, so outputs are bit-identical), and follows a child
pointer. Leaves are encoded ``~leaf_index`` (negative) in the child
arrays, exactly the HostTree/LoadedTree on-disk convention, so landing on
a leaf freezes the row: ``depth`` steps suffice for every row and the
loop bound is a static property of the packed model.

Per-class summation replays iteration order through a sequential
``lax.scan`` — the identical f32 add order as ``predict_forest_scores``
— so serving outputs match ``Booster.predict`` bit-for-bit, not just to
tolerance.

Early-exit cascades (``serving_cascade_trees=k`` /
``serving_cascade_margin=m``): score the first ``k`` iterations for
everyone, then only continue through the remaining trees when some row's
margin (binary: ``2*|score|``; multiclass: top1-top2) is below ``m``.
The whole second stage sits under one ``lax.cond``, so a confident batch
skips it entirely on device; ``m = inf`` keeps every row uncertain and
reproduces the full-model output exactly (the parity test for the knob).

Optionally the leaf table is quantized to int16 with a per-tree f32
scale (``serving_quantize_leaves``) — halves leaf-table bandwidth at
~1e-4 relative output error, OFF by default to preserve exact parity.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tree import decision_go_left, threshold_f32
from ..log import check


class FlatForest(NamedTuple):
    """Whole-ensemble SoA node table; every field's leading axis is the
    flattened tree index ``T = iterations * num_tree_per_iteration``
    (iteration-major, matching the stacked replay layout)."""
    feature: jnp.ndarray        # [T, Nn] int32 split feature per node
    threshold: jnp.ndarray      # [T, Nn] f32 real-value threshold
    default_left: jnp.ndarray   # [T, Nn] bool
    missing_type: jnp.ndarray   # [T, Nn] int32
    is_categorical: jnp.ndarray  # [T, Nn] bool
    cat_bitset: jnp.ndarray     # [T, Nn, W] uint32 raw-category bitsets
    left: jnp.ndarray           # [T, Nn] int32 child; >=0 node, <0 = ~leaf
    right: jnp.ndarray          # [T, Nn] int32 child; >=0 node, <0 = ~leaf
    leaf_value: jnp.ndarray     # [T, L] f32 (or int16 when quantized)
    leaf_scale: jnp.ndarray     # [T] f32 dequant scale (ones unless quantized)


def _tree_depth(left: np.ndarray, right: np.ndarray) -> int:
    """Traversal steps needed for one tree: the max count of internal
    nodes on any root-to-leaf path (>= 1; a stump still takes one step to
    follow ``~0`` to leaf 0). Iterative — trees can be chain-shaped."""
    # NOTE: only a truly empty tree short-circuits. A root whose LEFT
    # child is a leaf is NOT a stump — its right subtree can be
    # arbitrarily deep (sparse-trained chain trees look exactly like
    # this), and under-counting depth freezes traversal mid-tree.
    if len(left) == 0:
        return 1
    depth = 1
    stack: List[Tuple[int, int]] = [(0, 1)]
    while stack:
        node, d = stack.pop()
        depth = max(depth, d)
        for child in (int(left[node]), int(right[node])):
            if child >= 0:
                stack.append((child, d + 1))
    return depth


def pack_flat_forest(models, quantize: bool = False
                     ) -> Tuple[FlatForest, int]:
    """Pack host trees (boosting.gbdt.HostTree / io.model_text.LoadedTree,
    iteration-major) into one numpy ``FlatForest`` plus the static
    traversal depth. Runs once per model generation on host; callers
    device-put the result."""
    check(len(models) > 0, "cannot pack an empty model")
    max_nodes = max(max(t.num_nodes, 1) for t in models)
    max_leaves = max(t.num_leaves for t in models)
    cat_words = max(t.cat_bitset.shape[1] for t in models)
    tcount = len(models)

    feature = np.zeros((tcount, max_nodes), np.int32)
    threshold = np.zeros((tcount, max_nodes), np.float32)
    default_left = np.zeros((tcount, max_nodes), bool)
    missing_type = np.zeros((tcount, max_nodes), np.int32)
    is_categorical = np.zeros((tcount, max_nodes), bool)
    cat_bitset = np.zeros((tcount, max_nodes, cat_words), np.uint32)
    # padding children point at leaf 0 (~0 == -1): a row that somehow
    # lands on a padded node freezes on a real leaf instead of escaping
    left = np.full((tcount, max_nodes), -1, np.int32)
    right = np.full((tcount, max_nodes), -1, np.int32)
    leaf_f32 = np.zeros((tcount, max_leaves), np.float32)
    depth = 1
    for ti, ht in enumerate(models):
        nn = len(ht.left_child)
        feature[ti, :nn] = ht.split_feature
        threshold[ti, :nn] = threshold_f32(ht.threshold)
        default_left[ti, :nn] = ht.default_left
        missing_type[ti, :nn] = ht.missing_type
        is_categorical[ti, :nn] = ht.is_categorical
        bw = ht.cat_bitset.shape[1]
        cat_bitset[ti, :len(ht.cat_bitset), :bw] = ht.cat_bitset
        left[ti, :nn] = ht.left_child
        right[ti, :nn] = ht.right_child
        nl = len(ht.leaf_value)
        leaf_f32[ti, :nl] = ht.leaf_value.astype(np.float32)
        depth = max(depth, _tree_depth(ht.left_child, ht.right_child))

    if quantize:
        scale = np.maximum(np.abs(leaf_f32).max(axis=1), 1e-30) / 32767.0
        leaf = np.round(leaf_f32 / scale[:, None]).astype(np.int16)
        leaf_scale = scale.astype(np.float32)
    else:
        leaf = leaf_f32
        leaf_scale = np.ones((tcount,), np.float32)

    return FlatForest(feature=feature, threshold=threshold,
                      default_left=default_left, missing_type=missing_type,
                      is_categorical=is_categorical, cat_bitset=cat_bitset,
                      left=left, right=right, leaf_value=leaf,
                      leaf_scale=leaf_scale), depth


def _terminal_nodes(forest: FlatForest, x: jnp.ndarray,
                    depth: int) -> jnp.ndarray:
    """[N, T] terminal encoded nodes (``~leaf_index``, all negative after
    ``depth`` steps): all rows x all trees, breadth-first gather + decide
    + follow-child."""
    n = x.shape[0]
    tcount = forest.left.shape[0]
    tr = jnp.arange(tcount, dtype=jnp.int32)[None, :]        # [1, T]
    max_cat = forest.cat_bitset.shape[-1] * 32

    def step(_, node):
        internal = node >= 0
        idx = jnp.maximum(node, 0)                           # [N, T]
        feat = forest.feature[tr, idx]
        fval = jnp.take_along_axis(x, feat, axis=1)          # [N, T]
        bits = forest.cat_bitset[tr, idx]                    # [N, T, W]
        go_left = decision_go_left(
            fval, forest.threshold[tr, idx], forest.default_left[tr, idx],
            forest.missing_type[tr, idx], forest.is_categorical[tr, idx],
            lambda wi: jnp.take_along_axis(bits, wi[..., None],
                                           axis=2)[..., 0],
            max_cat)
        nxt = jnp.where(go_left, forest.left[tr, idx], forest.right[tr, idx])
        return jnp.where(internal, nxt, node)

    return lax.fori_loop(0, depth, step,
                         jnp.zeros((n, tcount), jnp.int32))


def forest_leaf_ids(forest: FlatForest, x: jnp.ndarray,
                    depth: int) -> jnp.ndarray:
    """[N, T] int32 leaf index each row lands on in each tree — the
    routing half of the traversal without the leaf-table gather. This is
    the refit primitive (fleet/refit.py): leaf ids feed per-leaf
    segment-sums of gradients, so leaf OUTPUTS can be recomputed on fresh
    data while the structure that produced the ids stays frozen."""
    return ~_terminal_nodes(forest, x, depth)


def _leaf_values(forest: FlatForest, x: jnp.ndarray,
                 depth: int) -> jnp.ndarray:
    """[N, T] per-tree leaf values: all rows x all trees, ``depth``
    breadth-first steps of gather + decide + follow-child."""
    tcount = forest.left.shape[0]
    tr = jnp.arange(tcount, dtype=jnp.int32)[None, :]        # [1, T]
    node = _terminal_nodes(forest, x, depth)
    vals = forest.leaf_value[tr, ~node]                      # [N, T]
    if forest.leaf_value.dtype != jnp.float32:               # quantized table
        vals = vals.astype(jnp.float32) * forest.leaf_scale[None, :]
    return vals


def _sum_iterations(acc: jnp.ndarray, vals: jnp.ndarray,
                    k: int) -> jnp.ndarray:
    """Accumulate [N, T'] per-tree values into [N, K] scores, one
    iteration per scan step — the identical f32 add order as
    ``predict_forest_scores`` (bit-exact parity with Booster.predict)."""
    n = vals.shape[0]
    per_iter = vals.reshape(n, vals.shape[1] // k, k)

    def body(carry, v):                                      # v [N, K]
        return carry + v, None

    out, _ = lax.scan(body, acc, jnp.transpose(per_iter, (1, 0, 2)))
    return out


def _slice_trees(forest: FlatForest, lo: int, hi: int) -> FlatForest:
    return jax.tree.map(lambda a: a[lo:hi], forest)


def forest_scores_flat(forest: FlatForest, x: jnp.ndarray, k: int,
                       depth: int, cascade_trees: int = 0,
                       cascade_margin: float = 10.0) -> jnp.ndarray:
    """[N, K] raw ensemble scores from a packed ``FlatForest``.

    ``k`` is trees-per-iteration, ``depth`` the static bound from
    ``pack_flat_forest``. ``cascade_trees > 0`` enables the two-stage
    early-exit cascade; with ``cascade_trees == 0`` (or covering the
    whole model) this is a single traversal + per-iteration sum.
    """
    tcount = forest.left.shape[0]
    ck = min(max(int(cascade_trees), 0), tcount // k) * k
    acc_shape = (x.shape[0], k)
    if ck <= 0 or ck >= tcount:
        return _sum_iterations(
            jnp.zeros(acc_shape, jnp.float32),
            _leaf_values(forest, x, depth), k)

    acc1 = _sum_iterations(
        jnp.zeros(acc_shape, jnp.float32),
        _leaf_values(_slice_trees(forest, 0, ck), x, depth), k)
    if k > 1:
        top2 = lax.top_k(acc1, 2)[0]
        margin = top2[:, 0] - top2[:, 1]
    else:
        margin = 2.0 * jnp.abs(acc1[:, 0])
    uncertain = margin < jnp.float32(cascade_margin)

    def stage2(acc):
        vals = _leaf_values(_slice_trees(forest, ck, tcount), x, depth)
        full = _sum_iterations(acc, vals, k)
        return jnp.where(uncertain[:, None], full, acc)

    return lax.cond(jnp.any(uncertain), stage2, lambda acc: acc, acc1)
