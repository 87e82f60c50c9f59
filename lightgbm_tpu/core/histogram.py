"""Histogram construction — the hottest op in GBDT training.

TPU-native re-design of the reference histogram kernels (dense_bin.hpp:66-130
ConstructHistogram, the OpenCL kernels ocl/histogram{16,64,256}.cl, and
Dataset::ConstructHistograms, src/io/dataset.cpp). Instead of per-thread /
per-workgroup scatter with atomics, bins are accumulated as a one-hot matmul
so the contraction runs on the MXU:

    hist[f, b, k] = sum_n onehot(X[n, f] == b) * vals[n, k]

chunked over rows with ``lax.scan`` so the transient one-hot tile stays small.
A scatter-add (segment-sum) variant is kept for CPU meshes where XLA scatter
is fast. Accumulation follows the value dtype: float32 by default, like the
GPU learner's single-precision histograms (gpu_tree_learner.h:74-78), or
float64 when gpu_use_dp / tpu_hist_dtype=float64 casts the stacked values
(the reference's double-precision histograms, config.h:784).

The entry ``build_histogram`` returns ``[F, B, 3]`` with channels
(sum_grad, sum_hess, count), the HistogramBinEntry layout (bin.h:29-57) as a
structure-of-arrays stack.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .binpack import unpack_words


def _hist_chunk_matmul(xb_chunk: jnp.ndarray, vals_chunk: jnp.ndarray,
                       num_bins: int) -> jnp.ndarray:
    """One row-chunk via one-hot contraction on the MXU.

    xb_chunk: [C, F] uint8/int32; vals_chunk: [C, 3] f32 -> [F, B, 3] f32.
    """
    c, f = xb_chunk.shape
    onehot = (xb_chunk[:, :, None] == jnp.arange(num_bins, dtype=xb_chunk.dtype)
              ).astype(vals_chunk.dtype)  # [C, F, B]
    # contract over rows: [F*B, C] @ [C, 3]. HIGHEST keeps full-precision
    # accumulation in the value dtype on the MXU (TPU matmuls default to
    # bf16 inputs, which breaks the 1e-4 AUC parity budget).
    return lax.dot_general(onehot, vals_chunk,
                           (((0,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST)  # [F, B, 3]


def _hist_scatter(xb: jnp.ndarray, vals: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Scatter-add variant: good on CPU, used for small row counts."""
    n, f = xb.shape
    flat = xb.astype(jnp.int32) + jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins
    hist = jnp.zeros((f * num_bins, vals.shape[-1]), dtype=vals.dtype)
    hist = hist.at[flat.reshape(-1)].add(
        jnp.broadcast_to(vals[:, None, :], (n, f, vals.shape[-1])
                         ).reshape(n * f, vals.shape[-1]))
    return hist.reshape(f, num_bins, vals.shape[-1])


def compensated_add(total: jnp.ndarray, lost: jnp.ndarray,
                    term: jnp.ndarray):
    """One Kahan step of a long sum of histograms: ``total + term`` with
    the add's rounding carried in ``lost`` to the next step, so that the
    sum over thousands of tiles keeps the accuracy of its terms and not
    that of a running total thousands of times their size. The sum so far
    is ``total - lost``. A leaf's sibling is parent - smaller, so a small
    leaf's bins inherit the ABSOLUTE error of every larger ancestor's. Two
    sums carry their rounding for that: the root's pass over the row
    partition, in blocks (build_histogram ``compensated``), and the tiles
    of a smaller child's range (partition.hist_for_leaf). On the chip at
    26.6M rows the worst split gain of two trees read 0.0236 off the
    float64 reference's with neither, 0.0225 with the tiles alone, 0.0011
    with the root alone, 0.000045 with both; the six-channel pass that
    subtracted nothing read 0.0001-0.00025 (PERF.md section 6, PR 32;
    tools/compensation_lab.py)."""
    y = term - lost
    t = total + y
    return t, (t - total) - y


def hist_tile_vals(xb_rows: jnp.ndarray, vals: jnp.ndarray, num_bins: int,
                   impl: str) -> jnp.ndarray:
    """One fixed-size row tile with pre-stacked [rows, 3] values
    (grad*mask, hess*mask, mask) -> [F, B, 3]. Used by the row-partition
    path (core/partition.py), which gathers the stacked values in a single
    indexed read per tile."""
    with jax.named_scope("lgbm.hist_tile"):
        if impl.startswith("pallas"):
            from .histogram_pallas import build_histogram_pallas_vals
            return build_histogram_pallas_vals(
                xb_rows, vals.T, num_bins,
                interpret=impl.endswith("interpret"))
        if impl == "scatter":
            return _hist_scatter(xb_rows, vals, num_bins)
        return _hist_chunk_matmul(xb_rows, vals, num_bins)


@functools.partial(jax.jit, static_argnames=("num_bins", "row_chunk", "impl",
                                             "packed_cols", "compensated"))
def build_histogram(xb: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                    mask: jnp.ndarray, num_bins: int,
                    row_chunk: int = 16384, impl: str = "matmul",
                    packed_cols: int = 0,
                    compensated: bool = False) -> jnp.ndarray:
    """Build (grad, hess, count) histograms for every feature.

    Args:
      xb: [N, F] binned features (uint8), or — when ``packed_cols`` > 0 —
        [N, ceil(F/4)] int32 words holding 4 eight-bit codes each
        (core/binpack.py; unpack happens inside the chosen impl, never as
        a second device-resident copy of the matrix).
      grad, hess: [N] f32 gradients/hessians (already weighted by objective).
      mask: [N] f32 row inclusion (leaf membership x bagging); 0 excludes.
      num_bins: static total bin count B (max over features).
      row_chunk: rows per scan step (bounds transient one-hot memory).
      impl: "matmul" (MXU one-hot) or "scatter" (XLA scatter-add).
      packed_cols: the real column count F when xb is word-packed; 0 =
        xb is the plain uint8 matrix.
      compensated: the pallas impls sum the pass in blocks of rows with the
        rounding carried (compensated_add): for the exact grower's root,
        which every other leaf's histogram is subtracted from. The masked
        passes of the other learners stay one call.

    Returns: [F, B, 3] f32.
    """
    n = xb.shape[0]
    f = packed_cols or xb.shape[1]
    if impl.startswith("pallas"):
        # pallas | pallas_interpret
        from .histogram_pallas import ROW_BLOCK, build_histogram_pallas
        return build_histogram_pallas(xb, grad, hess, mask, num_bins,
                                      interpret=impl.endswith("interpret"),
                                      packed_cols=packed_cols,
                                      row_block=ROW_BLOCK * compensated)
    vals = jnp.stack([grad * mask, hess * mask, mask], axis=-1)  # [N, 3]
    if impl == "scatter" or n <= row_chunk:
        if packed_cols:
            xb = unpack_words(xb, packed_cols)
        if impl == "scatter":
            return _hist_scatter(xb, vals, num_bins)
        return _hist_chunk_matmul(xb, vals, num_bins)

    num_chunks = (n + row_chunk - 1) // row_chunk
    pad = num_chunks * row_chunk - n
    if pad:
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))  # padded rows have mask 0
    xb_c = xb.reshape(num_chunks, row_chunk, xb.shape[1])
    vals_c = vals.reshape(num_chunks, row_chunk, 3)

    def step(acc, chunk):
        xbc, vc = chunk
        if packed_cols:
            # per-chunk unpack keeps the transient uint8 tile row_chunk-
            # sized — the full matrix only ever exists as words
            xbc = unpack_words(xbc, packed_cols)
        return acc + _hist_chunk_matmul(xbc, vc, num_bins), None

    init = jnp.zeros((f, num_bins, 3), dtype=vals.dtype)
    hist, _ = lax.scan(step, init, (xb_c, vals_c))
    return hist


def _frontier_scatter(xb: jnp.ndarray, slot: jnp.ndarray, vals: jnp.ndarray,
                      num_bins: int, num_slots: int) -> jnp.ndarray:
    """Leaf-indexed segment scatter: one combined (slot, feature, bin)
    index per row-feature, one scatter-add over the whole dataset.
    Rows with slot -1 are deactivated by zeroing their value channels (the
    clamped slot-0 writes then add zeros)."""
    n, f = xb.shape
    k = vals.shape[-1]
    active = slot >= 0
    vals = vals * active[:, None].astype(vals.dtype)
    s_c = jnp.where(active, slot, 0).astype(jnp.int32)
    flat = (s_c[:, None] * f + jnp.arange(f, dtype=jnp.int32)[None, :]) \
        * num_bins + xb.astype(jnp.int32)
    hist = jnp.zeros((num_slots * f * num_bins, k), dtype=vals.dtype)
    hist = hist.at[flat.reshape(-1)].add(
        jnp.broadcast_to(vals[:, None, :], (n, f, k)).reshape(n * f, k))
    return hist.reshape(num_slots, f, num_bins, k)


def _frontier_chunk_matmul(xb_chunk: jnp.ndarray, slot_chunk: jnp.ndarray,
                           vals_chunk: jnp.ndarray, num_bins: int,
                           num_slots: int) -> jnp.ndarray:
    """One row chunk of the (leaf, bin) one-hot MXU path: the slot one-hot
    spreads each row's value channels into its slot's lane group, then one
    bin-one-hot contraction prices every (slot, feature, bin) cell:

        hist[s, f, b, k] = sum_c onehot(bin)[c, f, b] * onehot(slot x val)[c, s, k]

    Each row lands in exactly one slot, so this pays num_slots x the MXU
    work of a plain histogram — the price of batching a whole frontier
    wave into one pass (the Pallas slot kernel removes the redundancy on
    real devices). slot -1 matches no one-hot column, deactivating the row.
    """
    c, f = xb_chunk.shape
    k = vals_chunk.shape[-1]
    onehot_s = (slot_chunk[:, None] == jnp.arange(num_slots, dtype=jnp.int32)
                ).astype(vals_chunk.dtype)                     # [C, S]
    svals = (onehot_s[:, :, None] * vals_chunk[:, None, :]
             ).reshape(c, num_slots * k)                       # [C, S*K]
    onehot_b = (xb_chunk[:, :, None]
                == jnp.arange(num_bins, dtype=xb_chunk.dtype)
                ).astype(vals_chunk.dtype)                     # [C, F, B]
    out = lax.dot_general(onehot_b, svals, (((0,), (0,)), ((), ())),
                          precision=lax.Precision.HIGHEST)     # [F, B, S*K]
    return jnp.moveaxis(out.reshape(f, num_bins, num_slots, k), 2, 0)


@functools.partial(jax.jit, static_argnames=("num_bins", "num_slots",
                                             "row_chunk", "impl",
                                             "packed_cols"))
def build_histogram_frontier(xb: jnp.ndarray, slot: jnp.ndarray,
                             grad: jnp.ndarray, hess: jnp.ndarray,
                             mask: jnp.ndarray, num_bins: int, num_slots: int,
                             row_chunk: int = 16384,
                             impl: str = "matmul",
                             packed_cols: int = 0) -> jnp.ndarray:
    """Histograms for EVERY live frontier leaf in ONE pass over the rows.

    The multi-leaf generalization of build_histogram (the level-indexed
    pass of the GPU GBDT literature — arXiv:1706.08359 §4, arXiv:1806.11248
    §3.2): instead of sweeping the dataset once per leaf, every row carries
    its leaf's frontier slot and one fused pass produces the whole wave's
    [num_slots, F, B, 3] tensor. A tree then costs O(depth) dataset sweeps
    instead of O(num_leaves).

    Args:
      xb: [N, F] binned features (uint8), or int32 packed words when
        ``packed_cols`` > 0 (same contract as build_histogram).
      slot: [N] int32 frontier slot in [0, num_slots), or -1 for rows in no
        frontier leaf (excluded from every slot).
      grad, hess, mask: [N] f32, same contract as build_histogram.
      num_bins, num_slots: static sizes.
      impl: "matmul" ((leaf, bin) one-hot MXU contraction) | "scatter"
        (combined-index scatter-add) | pallas spellings (the slot kernel,
        histogram_pallas.build_histogram_slots).
      packed_cols: real column count F when xb is word-packed; 0 = plain.

    Returns: [num_slots, F, B, 3] f32 (sum_grad, sum_hess, count).
    """
    n = xb.shape[0]
    f = packed_cols or xb.shape[1]
    if impl.startswith("pallas"):
        from .histogram_pallas import build_histogram_slots
        vals = jnp.stack([grad * mask, hess * mask, mask], axis=0)  # [3, N]
        return build_histogram_slots(
            xb, slot, vals, num_bins=num_bins, n_slots=num_slots,
            interpret=impl.endswith("interpret"), packed_cols=packed_cols)
    vals = jnp.stack([grad * mask, hess * mask, mask], axis=-1)     # [N, 3]
    if impl == "scatter":
        if packed_cols:
            xb = unpack_words(xb, packed_cols)
        return _frontier_scatter(xb, slot, vals, num_bins, num_slots)
    slot = slot.astype(jnp.int32)
    if n <= row_chunk:
        if packed_cols:
            xb = unpack_words(xb, packed_cols)
        return _frontier_chunk_matmul(xb, slot, vals, num_bins, num_slots)
    num_chunks = (n + row_chunk - 1) // row_chunk
    pad = num_chunks * row_chunk - n
    if pad:
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        slot = jnp.pad(slot, (0, pad), constant_values=-1)

    def step(acc, chunk):
        xbc, sc, vc = chunk
        if packed_cols:
            xbc = unpack_words(xbc, packed_cols)
        return acc + _frontier_chunk_matmul(xbc, sc, vc, num_bins,
                                            num_slots), None

    init = jnp.zeros((num_slots, f, num_bins, 3), dtype=vals.dtype)
    hist, _ = lax.scan(step, init,
                       (xb.reshape(num_chunks, row_chunk, xb.shape[1]),
                        slot.reshape(num_chunks, row_chunk),
                        vals.reshape(num_chunks, row_chunk, 3)))
    return hist


def subtract_histogram(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """Histogram subtraction trick: sibling = parent - child
    (FeatureHistogram::Subtract, feature_histogram.hpp:67-75)."""
    return parent - child


def fix_histogram(hist: jnp.ndarray, default_bins: jnp.ndarray,
                  sum_grad: jnp.ndarray, sum_hess: jnp.ndarray,
                  count: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct a skipped default bin from leaf totals
    (Dataset::FixHistogram, dataset.h:411-412).

    Our kernels always accumulate every bin, so this is only used to repair
    float32 drift on the default bin after repeated subtraction: the default
    bin is recomputed so per-feature totals equal the (exact) leaf totals.

    hist: [F, B, 3]; default_bins: [F] int32; sums: scalars.
    """
    f, b, _ = hist.shape
    arange_b = jnp.arange(b, dtype=jnp.int32)[None, :]
    is_default = arange_b == default_bins[:, None]  # [F, B]
    totals = jnp.stack([sum_grad, sum_hess, count])  # [3]
    sum_wo_default = jnp.sum(jnp.where(is_default[..., None], 0.0, hist), axis=1)
    fixed = totals[None, :] - sum_wo_default  # [F, 3]
    return jnp.where(is_default[..., None], fixed[:, None, :], hist)
