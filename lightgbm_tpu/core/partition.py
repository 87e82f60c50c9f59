"""Device-side row partition: per-leaf contiguous index ranges.

TPU-native re-design of DataPartition (src/treelearner/data_partition.hpp:
20-37, 100+) — the component that makes histogram construction cost
O(rows_in_leaf) instead of O(num_data) per split. The reference keeps
``indices_`` grouped by leaf with ``leaf_begin_``/``leaf_count_`` and
partitions a leaf's range with per-thread counts + prefix sums; here the
same invariant is maintained functionally:

- ``order``   [N + chunk] int32 — row ids grouped by leaf (the padded tail
  holds one trash slot that no leaf range ever covers).
- ``leaf_begin`` / ``leaf_count`` [L] int32 — each leaf's contiguous range.

Design notes from profiling on a v5e chip: inside a sequential growth loop,
dynamic-indexed ops (gather/scatter) cost ~0.4-0.8 ms *each* in latency
regardless of size up to ~64k elements, while dense full-array ops run at
memory bandwidth. The layout below therefore minimizes the NUMBER of
indexed ops per split rather than the elements they touch:

- per-row bins AND values ride behind one make_row_gather closure —
  bit-packed side by side on the normal path, so a histogram trip does
  ONE row gather total (two only under vmapped class batching, where
  packing would copy the shared bin matrix per class);
- every gather/scatter is annotated promise-in-bounds (indices are clamped
  or routed to the trash slot first);
- ``leaf_id`` is NOT maintained per split — it is reconstructed once per
  tree from the final ranges (leaf_id_from_partition), replacing
  O(N x depth) scattered writes with one dense searchsorted + one scatter.

Both maintenance and consumption are chunked ``lax.while_loop``s whose trip
count is data-dependent (ceil(count / chunk)); with the default chunk most
leaves take a single trip. The partition scatter fills the left child
forward from the range start and the right child backward from the range
end, so a single pass suffices (within-leaf row order is irrelevant to
histogram sums).

Histogram builds gather the leaf's rows through ``order`` (the analog of the
reference's ordered-gradient gather, dataset.cpp ConstructHistograms) and
feed fixed-size [chunk, F] tiles to the same one-hot-matmul / Pallas kernels
as the full-data path.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import hist_tile_vals


class RowPartition(NamedTuple):
    order: jnp.ndarray       # [N + chunk] int32
    leaf_begin: jnp.ndarray  # [L] int32
    leaf_count: jnp.ndarray  # [L] int32


def init_partition(num_data: int, num_leaves: int, chunk: int) -> RowPartition:
    order = jnp.concatenate([
        jnp.arange(num_data, dtype=jnp.int32),
        jnp.full((chunk,), num_data, jnp.int32)])  # padded tail -> dropped
    leaf_begin = jnp.zeros((num_leaves,), jnp.int32)
    leaf_count = jnp.zeros((num_leaves,), jnp.int32) \
        .at[0].set(jnp.int32(num_data))
    return RowPartition(order, leaf_begin, leaf_count)


def stack_vals(grad: jnp.ndarray, hess: jnp.ndarray,
               mask: jnp.ndarray) -> jnp.ndarray:
    """[N, 3] (grad*mask, hess*mask, mask) — one gather per histogram trip
    instead of three (the ordered-gradients copy of the reference,
    dataset.cpp ConstructHistograms)."""
    m = mask.astype(grad.dtype)
    return jnp.stack([grad * m, hess * m, m], axis=1)


def make_row_gather(xb: jnp.ndarray, vals: jnp.ndarray,
                    packed: bool = True):
    """Build the per-tile ``gather_rows(idx_safe) -> (rows, v)`` closure
    the partition loops use, owning the bins/values layout in ONE place.

    packed=True bit-packs [N, C] uint8 bins and [N, 3] float values side
    by side into one [N, C + 3*itemsize] uint8 array, so a histogram
    trip does ONE row gather instead of two (round-4 measurement: trip
    cost is bound by the NUMBER of indexed ops, not the bytes they
    move); the per-tile unpack is a free bitcast. packed=False keeps
    two gathers — required under vmapped class-batched growth, where
    the concat would materialize a PER-CLASS copy of the shared bin
    matrix."""
    if not packed:
        def gather_rows(idx_safe):
            rows = xb.at[idx_safe].get(mode="promise_in_bounds")
            v = vals.at[idx_safe].get(mode="promise_in_bounds")
            return rows, v
        return gather_rows
    n, c = xb.shape
    nbytes = jnp.dtype(vals.dtype).itemsize
    vb = lax.bitcast_convert_type(vals, jnp.uint8).reshape(n, -1)
    xv = jnp.concatenate([xb, vb], axis=1)
    val_dtype = vals.dtype

    def gather_rows(idx_safe):
        p = xv.at[idx_safe].get(mode="promise_in_bounds")
        rows = p[:, :c]
        v = lax.bitcast_convert_type(
            p[:, c:].reshape(p.shape[0], 3, nbytes), val_dtype)
        return rows, v
    return gather_rows


def tpu_shaped_backend() -> bool:
    """Allow-list backend sniff, shared by the bin-packing policy and the
    GBDT multiclass class-batching decision — an unknown plugin backend
    counts as NOT TPU-shaped so untested backends keep the conservative
    paths."""
    import jax
    return jax.default_backend() == "tpu"


def sort_placement_profitable(hist_impl: str, vmapped: bool) -> bool:
    """Single policy for partition_and_hist's use_sort flag.

    Round-4 on-chip re-measurement INVERTED the round-2 decision: at the
    new auto row_chunk (4096; also at 8192/16384) the scatter loop beats
    the single-trip sort placement on a v5e chip — 2.31 vs 1.97 iters/s
    at the 1M x 28 bench shape (a 4096-key lax.sort per split costs more
    than the scatter it replaced). Default is therefore OFF everywhere;
    ``LIGHTGBM_TPU_SORT_PLACEMENT=1`` re-enables it for experiments, the
    interpret spellings opt in so CPU tests keep covering the sort
    branch, and vmapped class-batched growth can never use it
    (lax.switch under vmap runs every branch per split)."""
    if vmapped:
        return False
    import os
    ov = os.environ.get("LIGHTGBM_TPU_SORT_PLACEMENT", "").strip().lower()
    if ov in ("1", "true", "yes", "on"):
        return True
    if ov in ("0", "false", "no", "off"):
        return False
    if ov:
        from ..log import Log
        Log.warning("ignoring unrecognized LIGHTGBM_TPU_SORT_PLACEMENT=%r "
                    "(use 0 or 1)" % ov)
    return hist_impl.startswith("pallas") and hist_impl.endswith("interpret")


def partition_and_hist(part: RowPartition, leaf_id, leaf, right_leaf,
                       go_left_from_rows, valid, chunk: int,
                       gather_rows, num_cols: int, num_bins: int,
                       impl: str, maintain_leaf_id: bool = False,
                       use_sort: bool = False, val_dtype=jnp.float32):
    """One pass over ``leaf``'s rows that BOTH partitions the range and
    builds both children's [F, B, 3] histograms.

    This fuses DataPartition::Split with ConstructHistograms and replaces
    the histogram-subtraction dance (serial_tree_learner.cpp:383-397): with
    the parent's rows already gathered for the partition decision, weighting
    them into six value channels (3 per child) prices both children at one
    row visit — fewer total rows touched than smaller-child + subtraction
    (P vs 1.5P per split), and two fewer indexed ops per split, which is
    what actually dominates on TPU (see module docstring).

    ``go_left_from_rows(rows[chunk, F]) -> bool[chunk]`` evaluates the split
    decision directly on the gathered feature bytes. ``gather_rows`` is a
    make_row_gather() closure owning the bins+values layout (packed:
    ONE row gather per tile serves both the routing bytes and the value
    channels). ``use_sort`` selects the single-trip sort placement (keep
    it off under vmap — the batching rule for lax.switch lowers to a
    select that runs every branch per split, semantically fine but a
    performance cliff).

    Returns (new_part, new_leaf_id, hist_left, hist_right).
    """
    n_rows = leaf_id.shape[0]
    f = num_cols
    order_len = part.order.shape[0]
    trash = order_len - 1                  # never inside any leaf range
    beg = part.leaf_begin[leaf]
    cnt = jnp.where(valid, part.leaf_count[leaf], 0)

    def load_tile(start, in_range):
        """Shared tile load: gather the tile's bins+values rows, decide
        the split, weight the six child channels, add the histogram
        tile."""
        with jax.named_scope("lgbm.row_gather"):
            idx = lax.dynamic_slice(part.order, (start,), (chunk,))
            idx_safe = jnp.minimum(idx, n_rows - 1)
            rows, v = gather_rows(idx_safe)                    # [chunk, F/3]
        with jax.named_scope("lgbm.route_rows"):
            v = v * in_range[:, None].astype(v.dtype)
            go_left = go_left_from_rows(rows)
            is_l = go_left & in_range
            is_r = (~go_left) & in_range
            v6 = jnp.concatenate([v * is_l[:, None].astype(v.dtype),
                                  v * is_r[:, None].astype(v.dtype)],
                                 axis=1)                       # [chunk, 6]
        hist = hist_tile_vals(rows, v6, num_bins, impl)
        return idx, idx_safe, go_left, is_l, is_r, hist

    def maybe_lid(lid, idx_safe, is_r):
        if not maintain_leaf_id:
            return lid
        # max-scatter: right_leaf exceeds every id assigned so far; left
        # rows keep their id; padded/OOB duplicates contribute 0
        with jax.named_scope("lgbm.leaf_ids"):
            val = jnp.where(is_r, right_leaf, 0).astype(lid.dtype)
            return lid.at[idx_safe].max(val, mode="promise_in_bounds")

    def cond(c):
        i = c[0]
        return i * chunk < cnt

    def body(c):
        i, nl, nr, order_new, lid, acc = c
        j = jnp.arange(chunk, dtype=jnp.int32)
        in_range = (i * chunk + j) < cnt
        idx, idx_safe, go_left, is_l, is_r, hist = load_tile(
            beg + i * chunk, in_range)
        acc = acc + hist
        # in_range is a prefix mask, so within range the right-side running
        # count is (position + 1) - left count: one cumsum covers both
        with jax.named_scope("lgbm.partition_scatter"):
            cl = jnp.cumsum(is_l.astype(jnp.int32), dtype=jnp.int32)
            cr = (j + 1) - cl
            kl = cl[-1]
            kr = jnp.sum(in_range.astype(jnp.int32), dtype=jnp.int32) - kl
            lpos = beg + nl + (cl - is_l)
            rpos = beg + cnt - 1 - nr - (cr - is_r)
            pos = jnp.where(go_left, lpos, rpos)
            pos = jnp.where(in_range, pos, trash)
            order_new = order_new.at[pos].set(idx,
                                              mode="promise_in_bounds")
        lid = maybe_lid(lid, idx_safe, is_r)
        return (i + 1, nl + kl, nr + kr, order_new, lid, acc)

    def multi_trip(_):
        init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), part.order,
                leaf_id, jnp.zeros((f, num_bins, 6), val_dtype))
        _, nl, nr, order_new, lid, acc = lax.while_loop(cond, body, init)
        return order_new, lid, nl, nr, acc

    if not use_sort:
        # two reasons to stay on the bare while_loop (which already handles
        # cnt == 0 and single trips): on CPU XLA's scatter is cheap and the
        # sort is not, and under vmap (multiclass class-batched growth)
        # lax.switch would execute ALL branches per split
        order_new, leaf_id, n_left, n_right, acc6 = multi_trip(None)
    else:
        def single_trip(_):
            # cnt <= chunk: the whole leaf fits in one tile, and the stable
            # partition becomes a SORT + one contiguous
            # dynamic-update-slice — no scatter, no cumsum (both are
            # latency-bound on TPU). The tail of the slice reads whatever
            # follows the leaf's range (the next leaf's rows / the
            # padding); keyed 2 it sorts stably to the back and is written
            # back unchanged, so the rest of ``order`` is untouched.
            in_range = jnp.arange(chunk, dtype=jnp.int32) < cnt
            idx, idx_safe, _, is_l, is_r, acc = load_tile(beg, in_range)
            with jax.named_scope("lgbm.partition_scatter"):
                key = jnp.where(is_l, 0,
                                jnp.where(is_r, 1, 2)).astype(jnp.uint8)
                _, sidx = lax.sort((key, idx), num_keys=1, is_stable=True)
                order_new = lax.dynamic_update_slice(part.order, sidx,
                                                     (beg,))
            lid = maybe_lid(leaf_id, idx_safe, is_r)
            return (order_new, lid,
                    jnp.sum(is_l.astype(jnp.int32), dtype=jnp.int32),
                    jnp.sum(is_r.astype(jnp.int32), dtype=jnp.int32), acc)

        def dead(_):
            return (part.order, leaf_id, jnp.int32(0), jnp.int32(0),
                    jnp.zeros((f, num_bins, 6), val_dtype))

        which = jnp.where(cnt == 0, 0, jnp.where(cnt <= chunk, 1, 2))
        order_new, leaf_id, n_left, n_right, acc6 = lax.switch(
            which, [dead, single_trip, multi_trip], None)

    leaf_begin = part.leaf_begin.at[right_leaf].set(
        jnp.where(valid, beg + n_left, part.leaf_begin[right_leaf]))
    leaf_count = part.leaf_count.at[leaf].set(
        jnp.where(valid, n_left, part.leaf_count[leaf]))
    leaf_count = leaf_count.at[right_leaf].set(
        jnp.where(valid, n_right, leaf_count[right_leaf]))
    return (RowPartition(order_new, leaf_begin, leaf_count), leaf_id,
            acc6[:, :, :3], acc6[:, :, 3:])


def hist_for_leaf(part: RowPartition, leaf, gather_rows, num_rows: int,
                  num_cols: int, num_bins: int, chunk: int, valid=True,
                  impl: str = "matmul",
                  val_dtype=jnp.float32) -> jnp.ndarray:
    """Build [F, B, 3] (grad, hess, count) histograms over one leaf's rows.

    Touches ceil(leaf_count / chunk) fixed-size tiles: row ids come from a
    contiguous slice of ``order``; ``gather_rows`` (make_row_gather) loads
    each tile's bins+values — one gather when packed.
    """
    f = num_cols
    beg = part.leaf_begin[leaf]
    cnt = jnp.where(valid, part.leaf_count[leaf], 0)

    def cond(c):
        i, _ = c
        return i * chunk < cnt

    def body(c):
        i, acc = c
        start = beg + i * chunk
        with jax.named_scope("lgbm.row_gather"):
            idx = lax.dynamic_slice(part.order, (start,), (chunk,))
            j = jnp.arange(chunk, dtype=jnp.int32)
            in_range = (i * chunk + j) < cnt
            idx_safe = jnp.minimum(jnp.where(in_range, idx, 0),
                                   num_rows - 1)
            rows, v = gather_rows(idx_safe)                    # [chunk, F/3]
        v = v * in_range[:, None].astype(v.dtype)
        return i + 1, acc + hist_tile_vals(rows, v, num_bins, impl)

    _, hist = lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros((f, num_bins, 3), val_dtype)))
    return hist


def leaf_id_from_partition(part: RowPartition, num_data: int,
                           num_leaves: int) -> jnp.ndarray:
    """Reconstruct the per-row leaf assignment from the final ranges.

    The leaf ranges tile [0, num_data) exactly (DataPartition invariant), so
    position -> leaf is a searchsorted over the count-filtered sorted begins,
    and row -> leaf is one scatter through ``order`` — O(N log L) dense work
    once per tree instead of O(N x depth) scattered writes during growth.
    """
    with jax.named_scope("lgbm.leaf_ids"):
        # empty leaves sort past every real range
        begins = jnp.where(part.leaf_count > 0, part.leaf_begin,
                           jnp.int32(num_data + 1))
        sort_begins, sort_leaf = lax.sort(
            (begins, jnp.arange(num_leaves, dtype=jnp.int32)), num_keys=1)
        pos = jnp.arange(num_data, dtype=jnp.int32)
        block = jnp.searchsorted(sort_begins, pos, side="right") - 1
        pos_leaf = sort_leaf[jnp.clip(block, 0, num_leaves - 1)]
        rows = jnp.minimum(part.order[:num_data], num_data - 1)
        return jnp.zeros((num_data,), jnp.int32).at[rows].set(
            pos_leaf, mode="promise_in_bounds")


def frontier_slots_from_partition(part: RowPartition, leaves: jnp.ndarray,
                                  num_data: int) -> jnp.ndarray:
    """Per-row frontier slot from the row partition: rows inside
    ``leaves[i]``'s range get slot i, every other row -1.

    This is the hand-off from the partition to
    histogram.build_histogram_frontier — the partition gives the builder
    the wave's LEAF IDS and the builder sweeps the dataset once for all
    of them, instead of extracting one leaf's row list per histogram.
    Same searchsorted-over-sorted-begins shape as leaf_id_from_partition,
    except the selected leaves cover only PART of [0, num_data), so a
    positional hit also range-checks against the owning leaf's count.
    """
    k = leaves.shape[0]
    leaf_begin = part.leaf_begin[leaves]
    leaf_count = part.leaf_count[leaves]
    # empty/unselected ranges sort past every real one
    begins = jnp.where(leaf_count > 0, leaf_begin, jnp.int32(num_data + 1))
    sort_begins, sort_slot = lax.sort(
        (begins, jnp.arange(k, dtype=jnp.int32)), num_keys=1)
    pos = jnp.arange(num_data, dtype=jnp.int32)
    block = jnp.searchsorted(sort_begins, pos, side="right") - 1
    cand = sort_slot[jnp.clip(block, 0, k - 1)]
    inside = ((block >= 0) & (pos >= leaf_begin[cand])
              & (pos < leaf_begin[cand] + leaf_count[cand]))
    pos_slot = jnp.where(inside, cand, -1)
    rows = jnp.minimum(part.order[:num_data], num_data - 1)
    return jnp.full((num_data,), -1, jnp.int32).at[rows].set(
        pos_slot, mode="promise_in_bounds")
