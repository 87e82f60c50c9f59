"""Device->host tree transport: one int32 buffer per trained tree.

The boosting driver (boosting/gbdt.py) trains asynchronously: each
iteration's TreeArrays stay on device, and host materialization happens in
batched flushes. A naive per-field fetch costs ~20 device->host round trips
per iteration (one per TreeArrays field) — ruinous when the accelerator
sits behind a high-latency transport, and with no analog in the reference,
whose learner and booster share one address space (GBDT::TrainOneIter,
src/boosting/gbdt.cpp:333-412, hands over a Tree* pointer). Packing every
field into a single flat int32 buffer makes a flush of P pending iterations
exactly ONE transfer of a [P, K, T] array.

Encoding: f32 and u32 fields are bitcast (lossless), bools widen to int32.
The spec is ordered and static given ``num_leaves``, so host unpacking is
pure numpy view/reshape — no per-element work.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# (field name, kind, shape builder) — kinds: i32 | f32 | u32 | bool.
# Order must match TreeArrays (core/grow.py) field-for-field semantics;
# shapes are functions of num_leaves ``l``.
_FIELDS: List[Tuple[str, str]] = [
    ("split_feature", "i32"),
    ("threshold_bin", "i32"),
    ("default_left", "bool"),
    ("missing_type", "i32"),
    ("is_categorical", "bool"),
    ("cat_bitset", "u32"),
    ("left_child", "i32"),
    ("right_child", "i32"),
    ("split_gain", "f32"),
    ("internal_value", "f32"),
    ("internal_weight", "f32"),
    ("internal_count", "i32"),
    ("split_leaf", "i32"),
    ("leaf_value", "f32"),
    ("leaf_weight", "f32"),
    ("leaf_count", "i32"),
    ("leaf_parent", "i32"),
    ("leaf_depth", "i32"),
    ("num_leaves", "i32"),
]


def _shapes(l: int) -> List[Tuple[int, ...]]:
    per_node = (l - 1,)
    per_leaf = (l,)
    by_name = {
        "cat_bitset": (l - 1, 8),
        "leaf_value": per_leaf, "leaf_weight": per_leaf,
        "leaf_count": per_leaf, "leaf_parent": per_leaf,
        "leaf_depth": per_leaf, "num_leaves": (),
    }
    return [by_name.get(name, per_node) for name, _ in _FIELDS]


def packed_size(l: int) -> int:
    return sum(int(np.prod(s)) if s else 1 for s in _shapes(l))


def pack_trees(trees) -> jnp.ndarray:
    """TreeArrays with a leading class axis [K, ...] -> [K, T] int32.

    Runs inside jit; all ops are bitcasts/casts + one concatenate.
    """
    k = trees.leaf_value.shape[0]
    parts = []
    with jax.named_scope("lgbm.tree_pack"):
        for name, kind in _FIELDS:
            a = getattr(trees, name).reshape(k, -1)
            if kind in ("f32", "u32"):
                a = lax.bitcast_convert_type(a, jnp.int32)
            else:
                a = a.astype(jnp.int32)
            parts.append(a)
        return jnp.concatenate(parts, axis=1)


def unpack_tree(row: np.ndarray, l: int) -> SimpleNamespace:
    """One packed [T] int32 host row -> namespace of typed numpy arrays.

    The result quacks like a single-tree TreeArrays (same field names and
    shapes), so GBDT._extract_host_tree consumes it unchanged.
    """
    row = np.ascontiguousarray(row, dtype=np.int32)
    out = {}
    off = 0
    for (name, kind), shape in zip(_FIELDS, _shapes(l)):
        size = int(np.prod(shape)) if shape else 1
        seg = row[off:off + size]
        off += size
        if kind == "f32":
            a = seg.view(np.float32)
        elif kind == "u32":
            a = seg.view(np.uint32)
        elif kind == "bool":
            a = seg.astype(bool)
        else:
            a = seg
        out[name] = a.reshape(shape) if shape else a[0]
    return SimpleNamespace(**out)
