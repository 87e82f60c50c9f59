"""Tree prediction on device.

TPU-native re-design of Tree::Predict / GetLeaf (include/LightGBM/tree.h:203-260,
src/boosting/gbdt_prediction.cpp:9-83). Instead of per-row pointer-chasing
node traversal, prediction replays splits in creation order: node ``t`` split
leaf ``split_leaf[t]``, so processing nodes 0..L-2 sequentially moves each row
through exactly the decisions it would make in a traversal — every step is one
vectorized compare over all rows. This mirrors how training's DataPartition
evolves, and maps to the TPU as L-1 fused elementwise passes.

Raw-value prediction uses real thresholds (converted from bin thresholds at
model-extraction time, like Tree::Split storing ``threshold_`` alongside
``threshold_in_bin_``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .split import MISSING_NAN, MISSING_NONE, MISSING_ZERO

K_ZERO_THRESHOLD = 1e-35


class PredictTree(NamedTuple):
    """Per-tree arrays needed for replay prediction; stack along axis 0 for a
    whole model ([T, L-1] / [T, L])."""
    split_leaf: jnp.ndarray      # [L-1] int32; -1 = unused node
    split_feature: jnp.ndarray   # [L-1] int32 (real feature index for raw)
    threshold: jnp.ndarray       # [L-1] f32 real threshold (raw predict)
    threshold_bin: jnp.ndarray   # [L-1] int32 (binned predict)
    default_left: jnp.ndarray    # [L-1] bool
    missing_type: jnp.ndarray    # [L-1] int32
    is_categorical: jnp.ndarray  # [L-1] bool
    cat_bitset: jnp.ndarray      # [L-1, 8] uint32
    leaf_value: jnp.ndarray      # [L] f32


def threshold_f32(threshold):
    """Real-valued thresholds as the float32 the device compares with. A
    split on missingness alone is stored as 1e300 (upstream's AvoidInf):
    past float32's range, so it becomes the largest float32, which every
    finite value still lies under."""
    import numpy as np
    top = float(np.finfo(np.float32).max)
    return np.clip(threshold, -top, top).astype(np.float32)


def pack_predict_table(ht, max_nodes: int, max_leaves: int,
                       cat_words: Optional[int] = None) -> "PredictTree":
    """Pad a host tree's SoA arrays to model-wide fixed shapes for stacked
    device prediction. ``ht`` is any object with the HostTree field layout
    (boosting.gbdt.HostTree or io.model_text.LoadedTree). ``cat_words``
    widens the categorical bitset so trees with different raw-category
    ranges stack (Tree cat_threshold_ is variable-width, tree.h:276-291)."""
    import numpy as np

    def pad(a, n, fill=0):
        out = np.full((n,) + a.shape[1:], fill, a.dtype)
        out[:len(a)] = a
        return out

    bitset = ht.cat_bitset
    if cat_words is not None and bitset.shape[1] < cat_words:
        bitset = np.pad(bitset, ((0, 0), (0, cat_words - bitset.shape[1])))

    return PredictTree(
        split_leaf=pad(ht.split_leaf, max_nodes, -1),
        split_feature=pad(ht.split_feature, max_nodes),
        threshold=pad(threshold_f32(ht.threshold), max_nodes),
        threshold_bin=pad(ht.threshold_bin, max_nodes),
        default_left=pad(ht.default_left, max_nodes),
        missing_type=pad(ht.missing_type, max_nodes),
        is_categorical=pad(ht.is_categorical, max_nodes),
        cat_bitset=pad(bitset, max_nodes),
        leaf_value=pad(ht.leaf_value.astype(np.float32), max_leaves),
    )


def decision_go_left(fval: jnp.ndarray, threshold: jnp.ndarray,
                     default_left: jnp.ndarray, missing_type: jnp.ndarray,
                     is_cat: jnp.ndarray, gather_cat_word,
                     max_cat: int) -> jnp.ndarray:
    """Tree::NumericalDecision / CategoricalDecision on raw values
    (tree.h:212-243), shared by the replay path below and the serving
    SoA traversal (serving/traversal.py) so both make bit-identical
    routing decisions. ``gather_cat_word(word_index)`` abstracts the
    bitset lookup — the two callers gather along different axes."""
    is_nan = jnp.isnan(fval)
    # NaN with non-NaN missing handling is treated as 0 (tree.h NumericalDecision)
    fval_safe = jnp.where(is_nan, 0.0, fval)
    is_zero = jnp.abs(fval_safe) <= K_ZERO_THRESHOLD
    use_default = jnp.where(
        missing_type == MISSING_NAN, is_nan,
        jnp.where(missing_type == MISSING_ZERO, is_zero | is_nan, False))
    numerical = jnp.where(use_default, default_left, fval_safe <= threshold)
    cat_i = jnp.clip(fval_safe, 0, max_cat - 1).astype(jnp.int32)
    word = gather_cat_word(cat_i >> 5)
    cat_ok = (~is_nan) & (fval >= 0) & (fval < max_cat)
    categorical = cat_ok & (((word >> (cat_i & 31).astype(jnp.uint32)) & 1) == 1)
    return jnp.where(is_cat, categorical, numerical)


def _raw_go_left(fval: jnp.ndarray, threshold: jnp.ndarray,
                 default_left: jnp.ndarray, missing_type: jnp.ndarray,
                 is_cat: jnp.ndarray, cat_bitset: jnp.ndarray) -> jnp.ndarray:
    """Replay-path decision: one node's ``[W]`` bitset, rows vectorized."""
    max_cat = cat_bitset.shape[0] * 32     # variable-width bitset
    return decision_go_left(fval, threshold, default_left, missing_type,
                            is_cat, lambda wi: cat_bitset[wi], max_cat)


def predict_tree_leaves_raw(tree: PredictTree, x: jnp.ndarray) -> jnp.ndarray:
    """Leaf index per row for raw [N, F] float input (Tree::GetLeaf analog)."""
    n = x.shape[0]
    num_nodes = tree.split_leaf.shape[0]

    def step(t, leaf_id):
        active = tree.split_leaf[t] >= 0
        fval = jnp.take(x, tree.split_feature[t], axis=1)
        go_left = _raw_go_left(fval, tree.threshold[t], tree.default_left[t],
                               tree.missing_type[t], tree.is_categorical[t],
                               tree.cat_bitset[t])
        in_node = leaf_id == tree.split_leaf[t]
        return jnp.where(active & in_node & ~go_left, t + 1, leaf_id)

    return lax.fori_loop(0, num_nodes, step, jnp.zeros((n,), jnp.int32))


def predict_tree_raw(tree: PredictTree, x: jnp.ndarray) -> jnp.ndarray:
    """Per-row tree output for raw input."""
    return tree.leaf_value[predict_tree_leaves_raw(tree, x)]


@functools.partial(jax.jit, static_argnames=())
def predict_forest_raw(trees: PredictTree, x: jnp.ndarray) -> jnp.ndarray:
    """Sum of all tree outputs; ``trees`` fields stacked [T, ...].

    Returns [N] raw scores (single output model). Multiclass callers vmap or
    reshape the tree axis.
    """
    def body(acc, tree):
        return acc + predict_tree_raw(tree, x), None

    init = jnp.zeros((x.shape[0],), jnp.float32)
    out, _ = lax.scan(body, init, trees)
    return out


def predict_forest_leaves_raw(trees: PredictTree, x: jnp.ndarray) -> jnp.ndarray:
    """[N, T] leaf indices (PredictLeafIndex analog, gbdt.cpp:564-583)."""
    def body(_, tree):
        return 0, predict_tree_leaves_raw(tree, x)

    _, leaves = lax.scan(body, 0, trees)
    return leaves.T


def predict_forest_scores(trees: PredictTree, x: jnp.ndarray) -> jnp.ndarray:
    """[N, K] raw scores from trees stacked [iters, K, ...] — the serving
    forward pass (lightgbm_tpu.serving): all K class trees of an iteration
    are applied in one vmapped step, so a whole multiclass model is ONE
    compiled program per batch shape instead of K per-class programs.

    Per-class summation order is iteration order — identical to the
    per-class path GBDT.predict takes, so f32 accumulation matches it
    bit-for-bit.
    """
    n = x.shape[0]
    k = trees.leaf_value.shape[1]

    def body(acc, tree_k):
        delta = jax.vmap(lambda t: predict_tree_raw(t, x))(tree_k)  # [K, N]
        return acc + delta.T, None

    init = jnp.zeros((n, k), jnp.float32)
    out, _ = lax.scan(body, init, trees)
    return out


def predict_forest_early_stop(trees: PredictTree, x: jnp.ndarray,
                              freq: int, margin: float,
                              is_multiclass: bool) -> jnp.ndarray:
    """Forest prediction with margin-based per-row early stop
    (src/boosting/prediction_early_stop.cpp): every ``freq`` iterations rows
    whose margin (binary: 2*|score|; multiclass: top1-top2) exceeds
    ``margin`` stop accumulating further trees.

    ``trees`` fields are stacked [iters, K, ...]; returns [N, K] raw scores.
    The reference stops the per-row tree loop on CPU; here the whole batch
    keeps running but stopped rows freeze — same results, SPMD-friendly.
    """
    n = x.shape[0]
    k = trees.leaf_value.shape[1]

    def margin_of(acc):  # acc [N, K]
        if is_multiclass and k > 1:
            top2 = lax.top_k(acc, 2)[0]
            return top2[:, 0] - top2[:, 1]
        return 2.0 * jnp.abs(acc[:, 0])

    def body(carry, tree_k):
        acc, stopped, it = carry
        delta = jax.vmap(lambda t: predict_tree_raw(t, x))(tree_k)  # [K, N]
        acc = acc + jnp.where(stopped[:, None], 0.0, delta.T)
        it = it + 1
        check_now = (it % freq) == 0
        stopped = stopped | (check_now & (margin_of(acc) >= margin))
        return (acc, stopped, it), None

    init = (jnp.zeros((n, k), jnp.float32), jnp.zeros((n,), bool),
            jnp.asarray(0, jnp.int32))
    (acc, _, _), _ = lax.scan(body, init, trees)
    return acc
