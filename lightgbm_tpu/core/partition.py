"""Device-side row partition: per-leaf contiguous index ranges.

TPU-native re-design of DataPartition (src/treelearner/data_partition.hpp:
20-37, 100+) — the component that makes histogram construction cost
O(rows_in_leaf) instead of O(num_data) per split. The reference keeps
``indices_`` grouped by leaf with ``leaf_begin_``/``leaf_count_`` and
partitions a leaf's range with per-thread counts + prefix sums; here the
same invariant is maintained functionally:

- ``order``   [N + chunk] int32 — row ids grouped by leaf, then a
  chunk-long tail pad that no leaf range ever covers.
- ``leaf_begin`` / ``leaf_count`` [L] int32 — each leaf's contiguous range.

Both maintenance and consumption are chunked ``lax.while_loop``s whose trip
count is data-dependent (ceil(count / chunk)). A split is TWO passes. The
first (partition_rows) walks the split leaf's tiles: each tile's row ids
come from a contiguous slice of ``order``, its rows from one gather through
them (the analog of the reference's ordered-gradient gather, dataset.cpp
ConstructHistograms), the split decision from the gathered bytes, and the
tile's ids are placed — lefts forward from the range start, rights backward
from the range end — so one pass suffices. The second (hist_for_leaf) walks
the SMALLER child's new range and runs the histogram kernel on its tiles;
the sibling is parent - smaller, from the grower's per-leaf pool
(serial_tree_learner.cpp:383-397).

Every row's leaf comes one of two ways. On one device, where a
feature-major copy of the bins (bins_by_column) fits beside the table
(row_space_fits), a THIRD pass a split routes all N rows in row space
(route_in_row_space): the split column read contiguously from the copy,
the leaf ids in and out, one fused elementwise op and no index, 0.127 ms
a split at 26.6M rows. Elsewhere (a mesh, or a table too large for its
copy) ``leaf_id`` is reconstructed once per tree from the final ranges
(leaf_id_from_partition), one scatter through ``order``: 0.22 s a tree at
26.6M rows, which row space replaces by ~32 ms at 255 leaves.

A tree grown on a bag (bag_partition: GOSS on the single-device exact
grower) always routes in row space, for the rows OUT of the bag: no
histogram ever reads them, so they have no place in ``order`` and all
they need is a leaf. There ``order`` lists the bag alone and the two
passes above run over its ranges. Before row space those rows kept a
second range a leaf and walked the tile loop again: 79 B gathered to read
one routing byte, 1.95 s of a 4.15-s iteration against 0.03 s this way.

What a v5e charges for these ops (PERF.md sections 5 and 6; traced
iterations at 26.6M x 67, 255 leaves, ~52,000 tiles of 4,096 rows a tree
of which the smaller children hold ~21,000, PR 30's standalone runs at
26.6M positions and PR 32's lab):

| op                                                | a call | an element |
| element scatter of the tile's ids into ``order``  | 187 us | 45 ns      |
| sort of the tile + two window writes (PR 28)      | 7.8 us | 1.9 ns     |
| row gather, 4,096 rows x 79 B from 2.1 GB         |  37 us |  9 ns      |
| histogram kernel on the tile, 67 cols x 3 chans   |  43 us | 10.5 ns    |
| the same at 6 channels (both children, until PR 32) |  83 us | 20 ns      |
| full-size scatter / gather, 26.6M elements        | 220 / 260 ms | 8.3 / 9.8 ns |
| position -> leaf: 510 marks + one prefix sum (PR 30) | 6.4 ms | 0.24 ns |
| row-space routing pass, 26.6M rows, uint8 ids (PR 34) | 0.127 ms a split | 0.005 ns |
| the same on int32 ids / column from [C, N] or xb[:, c] | 0.373 / 1.52 ms | 0.014 / 0.057 ns |

The kernel is linear in its value channels (43.1 and 82.9 us a call
standalone: a call's fixed cost is ~3 us), so what it costs is rows x
channels. Until PR 32 ONE fused pass priced both children of a split
through six channels, on every row of the parent: it saved the second
pass's gather (37 us) and paid 83 us of kernel on 2.5-4 times the rows the
smaller child holds (over one recorded tree's splits: 7.22 s fused, 5.31 s
fused at three channels, 4.81 s this way; 5.80, 4.31, 3.33 s on a click-log
tree). Now the kernel sees min(left, right) rows of a split at three
channels, and the partition pass's gather serves the routing column alone.

(103 / 129 ms until PR 30: a capture shows a gather as two instructions,
index clamp and gather, a scatter as a sort and the scatter.) A gather costs
that from a 255-entry table too, so a position finds its leaf by a prefix
sum over marks, not by a search (_range_owner).

An XLA scatter into HBM costs per ELEMENT, whatever the operand's size, and
a tile's ids only ever go to two contiguous runs; so where the tile loop is
TPU-shaped (window_placement) the ids are packed in the tile and written as
two windows, and the element scatter stays where it is cheap (the CPU
backends) and under vmap, where a batched window start would turn each
window back into a scatter.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .histogram import compensated_add, hist_tile_vals


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


def bag_partition(in_bag: jnp.ndarray, chunk: int) -> RowPartition:
    """The row partition a bagged tree starts from: ``order`` holds the
    rows of ``in_bag`` (bool [N]) at its front, then every other row, both
    ascending. Two ranges: 0 the bag, 1 the rest, which the grower never
    reads again (its rows are routed in row space: route_in_row_space).
    One stable two-key sort (72.6 ms at 26.6M rows on a v5e;
    partition_rows' tile loop over the identity order 77.6, a prefix sum
    and a full-size scatter 225.9: PERF.md section 6, PR 33)."""
    n = in_bag.shape[0]
    with jax.named_scope("lgbm.bag_compact"):
        _, rows = lax.sort(((~in_bag).astype(jnp.int32),
                            jnp.arange(n, dtype=jnp.int32)),
                           num_keys=1, is_stable=True)
        n_bag = jnp.sum(in_bag.astype(jnp.int32), dtype=jnp.int32)
        order = jnp.concatenate([rows, jnp.full((chunk,), n, jnp.int32)])
        return RowPartition(order, jnp.stack([jnp.int32(0), n_bag]),
                            jnp.stack([n_bag, n - n_bag]))


ROUTE_LANES = 1024


def bins_by_column(xb: jnp.ndarray) -> jnp.ndarray:
    """[N, C] bins -> [C, ceil(N / ROUTE_LANES), ROUTE_LANES]: the stored
    columns as ``xb`` holds them, feature-major, so that one column is read
    contiguously. A v5e tiles a uint8 array (32, 128) over its last two
    dimensions: a row of a [C, N] copy is fetched with the 31 beside it
    (1.52 ms a split at 26.6M rows, what ``xb[:, c]`` costs from the
    row-major table), a [rows / 1024, 1024] slab is whole tiles of its own
    (0.127 ms; lanes of 128 read the same). The tail pad is routed like any
    row and never read back (row_space_leaf_ids).

    One column a step, not ``pad(xb.T).reshape``: that one transpose of
    26.6M x 67 runs in 49 ms and takes the v5e's compiler 1,000 s (PERF.md
    section 6, PR 34); this loop compiles in 13 s."""
    n, c = xb.shape
    m = -(-n // ROUTE_LANES)

    def column(j):
        col = lax.dynamic_index_in_dim(xb, j, 1, keepdims=False)
        return jnp.pad(col, (0, m * ROUTE_LANES - n)).reshape(m, ROUTE_LANES)

    return lax.map(column, jnp.arange(c, dtype=jnp.int32))


# What a tree over all rows holds on the device a row besides the table
# and its copy: the packed rows the tile loops gather from
# (make_row_gather) and the table they are made from, each a row of
# uint8 lanes that XLA:TPU pads to a multiple of 128, and the per-row
# state (scores, gradients, ``order``, leaf ids, masks). Read from the
# v5e compiler's memory analysis of the categorical cell's block (35.4M x
# 39, 12.72 GB with the copy; PERF.md section 6); a tenth of the device
# is left to what else the process holds.
ROW_STATE_BYTES = 32


def device_bytes_limit(device) -> Optional[int]:
    """The memory limit of ``device``, or None where the backend reports
    none (the CPU)."""
    stats = device.memory_stats()
    return None if not stats else stats.get("bytes_limit")


def row_space_bytes(rows: int, cols: int) -> int:
    """The device bytes a tree over all rows of a [rows, cols] uint8 table
    holds with its feature-major copy (bins_by_column): the table, the
    copy, and a row's lanes of packed rows, of their source and of per-row
    state."""
    lanes = -(-max(MIN_PACKED_WIDTH, cols + 12) // 128) * 128
    return 2 * rows * cols + rows * (2 * lanes + ROW_STATE_BYTES)


def row_space_fits(rows: int, cols: int, bytes_limit: Optional[int]) -> bool:
    """Whether a tree over all rows may keep its leaf ids in row space:
    what it holds with the copy (row_space_bytes) fits the device's limit
    with room to spare. Where the backend gives no limit, yes, so that the
    CPU runs the chip's path."""
    return bytes_limit is None or \
        row_space_bytes(rows, cols) <= bytes_limit - bytes_limit // 10


def row_space_leaf_ids0(bins_by_col: jnp.ndarray,
                        num_leaves: int) -> jnp.ndarray:
    """Every row at the root, in ``bins_by_col``'s row shape and the
    narrowest of uint8 / int32 that holds a leaf: the pass is bound by its
    bytes, 0.127 ms a split on bytes against 0.373 on words."""
    return jnp.zeros(bins_by_col.shape[1:],
                     jnp.uint8 if num_leaves <= 256 else jnp.int32)


def route_in_row_space(leaf_id, bins_by_col, stored_col, go_left_from_bins,
                       leaf, right_leaf, valid):
    """One split over ALL rows, in row space: a row of ``leaf`` that does
    not go left takes ``right_leaf``. ``leaf_id`` has ``bins_by_col``'s row
    shape (row_space_leaf_ids0); ``go_left_from_bins`` maps the split
    column's stored bins to the decision, as the tile loop's routing does.

    This is how a tree grown on a bag routes its rows: those out of the
    bag are never listed for a histogram, so they need no place in
    ``order``, only a leaf. A tree over all rows on one device routes
    them so too, where the copy fits (row_space_fits): ``order`` then
    serves the two tile loops alone. With every row's leaf known when the
    last split is done, leaf_id_from_partition is not run. One fused
    elementwise pass a split, O(N) and streaming: 0.127 ms at 26.6M rows
    on a v5e = 0.005 ns a row, against 11.5 ns a row of the split leaf
    through the tile loop (gather, sort, windows). A tree pays
    (L - 1) x N x 0.005 ns here (0.014 on int32 ids) and the sum of its
    out-of-bag internal counts x 11.5 ns there: 1.2 against ~90 ns a row
    at 255 leaves, 57 against 150-180 at 4,095, so row space wins
    wherever a bag exists and there is one path (PERF.md section 6).
    Over all rows it replaces leaf_id_from_partition's scatter through
    ``order`` (~0.22 s a tree at 26.6M rows) by (L - 1) such passes
    (~32 ms at 255 leaves). A dead split costs nothing."""
    def route(lid):
        # the branch is traced apart: it names its own scope
        with jax.named_scope("lgbm.route_only"):
            col = lax.dynamic_index_in_dim(bins_by_col, stored_col, 0,
                                           keepdims=False)
            return jnp.where((lid == leaf.astype(lid.dtype))
                             & ~go_left_from_bins(col),
                             right_leaf.astype(lid.dtype), lid)

    with jax.named_scope("lgbm.route_only"):
        return lax.cond(valid, route, lambda lid: lid, leaf_id)


def row_space_leaf_ids(leaf_id: jnp.ndarray, num_data: int) -> jnp.ndarray:
    """The int32 [N] leaf assignment from route_in_row_space's state."""
    with jax.named_scope("lgbm.leaf_ids"):
        return leaf_id.reshape(-1)[:num_data].astype(jnp.int32)


def stack_vals(grad: jnp.ndarray, hess: jnp.ndarray,
               mask: jnp.ndarray) -> jnp.ndarray:
    """[N, 3] (grad*mask, hess*mask, mask) — one gather per histogram trip
    instead of three (the ordered-gradients copy of the reference,
    dataset.cpp ConstructHistograms)."""
    m = mask.astype(grad.dtype)
    return jnp.stack([grad * m, hess * m, m], axis=1)


# The packed row's least width in bytes. The v5e's compiler emits a gather
# of rows narrower than about 57 bytes through another path (its result
# column-major, five times the scoped memory), which costs four times as
# much a row: 148 us against 37 us a 4,096-row tile at 39 columns + 12 value
# bytes (PERF.md section 6, PR 35). A table of 52 columns or more is wider
# than this already, and its packed rows are left as they are.
MIN_PACKED_WIDTH = 64


def make_row_gather(xb: jnp.ndarray, vals: jnp.ndarray,
                    packed: bool = True):
    """Build the per-tile ``gather_rows(idx_safe) -> (rows, v)`` closure
    the partition loops use, owning the bins/values layout in ONE place.

    packed=True bit-packs [N, C] uint8 bins and [N, 3] float values side
    by side into one [N, C + 3*itemsize] uint8 array (filled with zero
    bytes up to MIN_PACKED_WIDTH where it is narrower), so a histogram
    trip does ONE row gather instead of two; the per-tile unpack shifts
    the value bytes back into words. packed=False keeps
    two gathers — required under vmapped class-batched growth, where
    the packing would materialize a PER-CLASS copy of the shared bin
    matrix."""
    if not packed:
        def gather_rows(idx_safe):
            rows = xb.at[idx_safe].get(mode="promise_in_bounds")
            v = vals.at[idx_safe].get(mode="promise_in_bounds")
            return rows, v
        return gather_rows
    c = xb.shape[1]
    nbytes = jnp.dtype(vals.dtype).itemsize
    word = jnp.dtype("uint%d" % (8 * nbytes))
    width = max(MIN_PACKED_WIDTH, c + 3 * nbytes)
    # byte b of a value word at shift 8 b, both ways: one select a packed
    # byte over the lane-padded table and the value words, so the values'
    # bytes are never an [N, 3 * itemsize] array of their own (XLA:TPU pads
    # such an array to 128 lanes: 4.2 GB at 35.4M rows, PERF.md section 6)
    j = jnp.arange(width, dtype=jnp.int32)
    k = jnp.clip((j - c) // nbytes, 0, 2)
    shift = (jnp.clip(j - c, 0, 3 * nbytes - 1) % nbytes).astype(word) * 8
    words = lax.bitcast_convert_type(vals, word)                 # [N, 3]
    by_byte = jnp.where(k == 0, words[:, 0:1],
                        jnp.where(k == 1, words[:, 1:2], words[:, 2:3]))
    xv = jnp.where((j >= c) & (j < c + 3 * nbytes),
                   ((by_byte >> shift) & 0xFF).astype(jnp.uint8),
                   jnp.pad(xb, ((0, 0), (0, width - c))))
    val_dtype = vals.dtype
    shifts = jnp.arange(nbytes, dtype=word) * 8

    def gather_rows(idx_safe):
        p = xv.at[idx_safe].get(mode="promise_in_bounds")
        rows = p[:, :c]
        packed_bytes = p[:, c:c + 3 * nbytes].astype(word).reshape(
            p.shape[0], 3, nbytes)
        v = lax.bitcast_convert_type(
            jnp.sum(packed_bytes << shifts, axis=2, dtype=word), val_dtype)
        return rows, v
    return gather_rows


def tpu_shaped_backend(backend: Optional[str] = None) -> bool:
    """Allow-list backend sniff of the dataset's bin-packing policy, the
    rule a training plan reads too (boosting/plan.py TrainFacts) — an
    unknown plugin backend counts as NOT TPU-shaped so untested backends
    keep the conservative paths. ``backend``: a ``jax.default_backend()``
    name; None reads this process's."""
    return (backend or jax.default_backend()) == "tpu"


def window_placement(pallas: bool, vmapped: bool) -> bool:
    """Which of partition_rows' two placements its tile loop is built
    with: the contiguous windows where the loop is TPU-shaped (the Pallas
    histogram kernels, the interpreted ones too, so that the CPU tests
    cover what the chip runs), the element scatter elsewhere — and always
    under vmapped class batching, where a batched start index turns each
    window write back into a scatter."""
    return pallas and not vmapped


def _write_window(order, packed, k, start):
    """order[start : start + k] = packed[:k], as one read-modify-write of
    a chunk-long window; every position past the first ``k`` keeps what it
    held."""
    chunk = packed.shape[0]
    w = lax.dynamic_slice(order, (start,), (chunk,))
    w = jnp.where(jnp.arange(chunk, dtype=jnp.int32) < k, packed, w)
    return lax.dynamic_update_slice(order, w, (start,))


def partition_rows(part: RowPartition, leaf_id, leaf, right_leaf,
                   go_left_from_rows, valid, chunk: int, gather_rows,
                   maintain_leaf_id: bool = False, windows: bool = False):
    """One pass over ``leaf``'s rows that splits its range of ``order`` in
    two (DataPartition::Split): the left child keeps the front of the range
    and ``leaf``'s id, ``right_leaf`` takes the back. No histogram is built
    here: the grower prices the SMALLER child from its new range with
    hist_for_leaf and takes the sibling as parent - smaller
    (serial_tree_learner.cpp:383-397), so the kernel sees min(left, right)
    rows of a split and not all of them.

    ``go_left_from_rows(rows[chunk, F]) -> bool[chunk]`` evaluates the split
    decision directly on the gathered feature bytes. ``gather_rows`` is a
    make_row_gather() closure owning the bins+values layout; the values it
    returns are not read here.

    A tile's lefts go forward from the left cursor in tile order, its
    rights backward from the right cursor; ``windows`` (window_placement)
    says how, and both ways give the same ``order`` inside [0, N):

    - False: one element scatter of the tile's ids; rows past the leaf's
      count go to the trash slot at the very end of the tail pad.
    - True: a sort of the tile packs lefts at the front and rights,
      reversed, at the back, and two window writes place them. Both
      windows are front-aligned: the left one starts at ``beg + nl``, the
      right one at ``beg + cnt - nr - kr`` with the packed tile rolled by
      ``kr``, so every start is >= ``beg`` >= 0 and start + chunk <=
      N + chunk, which the tail pad covers — dynamic_slice never clamps
      and ``order`` needs no front pad. The masks leave the neighbours'
      ranges and the tail pad as they were.

    Returns (new_part, new_leaf_id); ``leaf_id`` is touched only with
    ``maintain_leaf_id``.
    """
    n_rows = part.order.shape[0] - chunk
    trash = part.order.shape[0] - 1        # never inside any leaf range
    beg = part.leaf_begin[leaf]
    cnt = jnp.where(valid, part.leaf_count[leaf], 0)

    def cond(c):
        i = c[0]
        return i * chunk < cnt

    def body(c):
        i, nl, nr, order_new, lid = c
        j = jnp.arange(chunk, dtype=jnp.int32)
        # ahead of the gather, where the audited jaxpr has it
        with jax.named_scope("lgbm.route_rows"):
            in_range = (i * chunk + j) < cnt
        with jax.named_scope("lgbm.row_gather"):
            idx = lax.dynamic_slice(part.order, (beg + i * chunk,), (chunk,))
            idx_safe = jnp.minimum(idx, n_rows - 1)
            rows, _ = gather_rows(idx_safe)                    # [chunk, F]
        with jax.named_scope("lgbm.route_rows"):
            go_left = go_left_from_rows(rows)
            is_l = go_left & in_range
            is_r = (~go_left) & in_range
        with jax.named_scope("lgbm.partition_scatter"):
            if windows:
                kl = jnp.sum(is_l.astype(jnp.int32), dtype=jnp.int32)
                kr = jnp.sum(is_r.astype(jnp.int32), dtype=jnp.int32)
                # unique keys: lefts by tile position, then the rows past
                # the count, then rights by tile position from the back
                key = jnp.where(is_l, j, jnp.where(is_r, 3 * chunk - j,
                                                   chunk + j))
                _, packed = lax.sort((key, idx), num_keys=1,
                                     is_stable=False)
                order_new = _write_window(order_new, packed, kl, beg + nl)
                order_new = _write_window(order_new, jnp.roll(packed, kr),
                                          kr, beg + cnt - nr - kr)
            else:
                # in_range is a prefix mask, so within range the right-side
                # running count is (position + 1) - left count: one cumsum
                # covers both
                cl = jnp.cumsum(is_l.astype(jnp.int32), dtype=jnp.int32)
                cr = (j + 1) - cl
                kl = cl[-1]
                kr = jnp.sum(in_range.astype(jnp.int32),
                             dtype=jnp.int32) - kl
                lpos = beg + nl + (cl - is_l)
                rpos = beg + cnt - 1 - nr - (cr - is_r)
                pos = jnp.where(go_left, lpos, rpos)
                pos = jnp.where(in_range, pos, trash)
                order_new = order_new.at[pos].set(idx,
                                                  mode="promise_in_bounds")
        if maintain_leaf_id:
            # max-scatter: right_leaf exceeds every id assigned so far; left
            # rows keep their id; padded/OOB duplicates contribute 0
            with jax.named_scope("lgbm.leaf_ids"):
                val = jnp.where(is_r, right_leaf, 0).astype(lid.dtype)
                lid = lid.at[idx_safe].max(val, mode="promise_in_bounds")
        return (i + 1, nl + kl, nr + kr, order_new, lid)

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0), part.order, leaf_id)
    _, n_left, n_right, order_new, leaf_id = lax.while_loop(cond, body, init)

    leaf_begin = part.leaf_begin.at[right_leaf].set(
        jnp.where(valid, beg + n_left, part.leaf_begin[right_leaf]))
    leaf_count = part.leaf_count.at[leaf].set(
        jnp.where(valid, n_left, part.leaf_count[leaf]))
    leaf_count = leaf_count.at[right_leaf].set(
        jnp.where(valid, n_right, leaf_count[right_leaf]))
    return RowPartition(order_new, leaf_begin, leaf_count), leaf_id


def hist_for_leaf(part: RowPartition, leaf, gather_rows, num_rows: int,
                  num_cols: int, num_bins: int, chunk: int, valid=True,
                  impl: str = "matmul",
                  val_dtype=jnp.float32,
                  scope: Optional[str] = None) -> jnp.ndarray:
    """Build [F, B, 3] (grad, hess, count) histograms over one leaf's rows.

    Touches ceil(leaf_count / chunk) fixed-size tiles: row ids come from a
    contiguous slice of ``order``; ``gather_rows`` (make_row_gather) loads
    each tile's bins+values — one gather when packed. The tiles' histograms
    are summed with the rounding carried (compensated_add): siblings are
    taken from this one by subtraction. ``scope`` names the whole pass (a
    bag's root), in place of the two tile scopes.
    """
    f = num_cols
    beg = part.leaf_begin[leaf]
    cnt = jnp.where(valid, part.leaf_count[leaf], 0)

    def cond(c):
        return c[0] * chunk < cnt

    def body(c):
        i, acc, lost = c
        start = beg + i * chunk
        with jax.named_scope(scope or "lgbm.row_gather"):
            idx = lax.dynamic_slice(part.order, (start,), (chunk,))
            j = jnp.arange(chunk, dtype=jnp.int32)
            in_range = (i * chunk + j) < cnt
            idx_safe = jnp.minimum(jnp.where(in_range, idx, 0),
                                   num_rows - 1)
            rows, v = gather_rows(idx_safe)                    # [chunk, F/3]
        with jax.named_scope(scope or "lgbm.hist_tile"):
            v = v * in_range[:, None].astype(v.dtype)
            acc, lost = compensated_add(
                acc, lost, hist_tile_vals(rows, v, num_bins, impl))
        return i + 1, acc, lost

    zero = jnp.zeros((f, num_bins, 3), val_dtype)
    _, hist, lost = lax.while_loop(cond, body, (jnp.int32(0), zero, zero))
    return hist - lost


def _range_owner(order: jnp.ndarray, begin: jnp.ndarray, count: jnp.ndarray,
                 num_data: int) -> jnp.ndarray:
    """Per row, the index i of the range [begin[i], begin[i] + count[i]) of
    ``order`` that holds it, -1 for a row in none.

    The ranges are disjoint (DataPartition invariant), so position -> owner
    is one prefix sum over marks: +(i + 1) at range i's start, -(i + 1) at
    its end, 2 x len(begin) scalars scattered into zeros. An empty range's
    two marks cancel wherever its start lies, so it needs no filtering and
    nothing is sorted. Row -> owner is then one scatter through ``order``.
    No lookup by a vector index and no search (module docstring).
    """
    ids = jnp.arange(1, begin.shape[0] + 1, dtype=jnp.int32)
    marks = jnp.zeros((num_data + 1,), jnp.int32) \
        .at[begin].add(ids).at[begin + count].add(-ids)
    pos_owner = jnp.cumsum(marks[:num_data], dtype=jnp.int32) - 1
    rows = jnp.minimum(order[:num_data], num_data - 1)
    return jnp.zeros((num_data,), jnp.int32).at[rows].set(
        pos_owner, mode="promise_in_bounds")


def leaf_id_from_partition(part: RowPartition, num_data: int) -> jnp.ndarray:
    """Reconstruct the per-row leaf assignment from the final ranges.

    The leaf ranges tile [0, num_data) exactly (DataPartition invariant),
    so every row has an owner: O(N) dense work once per tree, whatever the
    number of leaves, instead of O(N x depth) scattered writes during
    growth. Run where the grower does not route in row space
    (route_in_row_space): on a mesh, and where the feature-major copy
    does not fit (row_space_fits).
    """
    with jax.named_scope("lgbm.leaf_ids"):
        return _range_owner(part.order, part.leaf_begin, part.leaf_count,
                            num_data)
