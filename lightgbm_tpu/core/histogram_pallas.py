"""Pallas TPU histogram kernel — the device analog of the reference's OpenCL
histogram kernels (ocl/histogram256.cl workgroup local-memory design,
gpu_tree_learner.cpp:951-1045).

Digit-factorized design (measured 4.3x faster than a direct one-hot kernel
on a v5e chip at 1M x 28 x 256): split each bin index into high/low base-16
digits, b = 16*hi + lo. The [B]-wide one-hot comparison then factorizes into
two 16-wide one-hots whose *outer product* is the full one-hot — and the
outer-product contraction over rows is exactly a matmul:

    hist[k, hi, lo] = sum_c (vals[k, c] * eqhi[hi, c]) * eqlo[c, lo]

so the bin axis is materialized by the MXU as a [3*Hi, C] @ [C, 16] product
instead of by N*F*B vector comparisons; the VPU only builds N*F*(Hi+16)
comparisons. All intermediates live in VMEM: per-pass HBM traffic is just
xb (N*F bytes) + vals (12N bytes) + the [3, F, B] output.

Precision: the values operand is split into two bfloat16 terms
(a = hi16(a) + lo16(a)) and contracted with the exactly-representable
one-hot in two default-precision MXU passes. Per-ELEMENT error is
~|v|*2^-17; summed over a bin this lands within ~3e-6 of float64 relative
to the bin's sum of |values| (measured), though a bin whose gradients
nearly cancel can see a larger error relative to its small net sum — same
caveat as any fixed-precision accumulation, and the same stance as the GPU
learner's single-precision histograms (gpu_tree_learner.h:74-78).

Grid = (feature_tiles, row_tiles); rows are the innermost sequential
reduction so each feature tile's accumulator stays resident in VMEM across
all row tiles (the "workgroup local histogram" without atomics — one grid
cell owns its bin slice).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .binpack import unpack_words
from .histogram import compensated_add

# The joint (slot, lo) kernels materialize a [n_slots*16, row_tile] f32
# one-hot per feature. At the 2048-row tile that is 16 MiB at 128 slots
# and 32 MiB at 254 — past Mosaic's default scoped-VMEM limit (16 MiB on
# a v5e) before the bf16 copy and the double-buffered output block are
# counted. So the row tile shrinks with the slot count to hold the
# one-hot at this budget, and the call states the VMEM it needs.
_ONE_HOT_BUDGET = 4 << 20


# Rows a call of the root kernel takes where a pass is cut in blocks
# (build_histogram_pallas ``row_block``). A call adds its row tiles' partial
# histograms into one float32 accumulator, each add rounding at the running
# total's size: 12,970 such adds a bin over 26.6M rows in one call.
ROW_BLOCK = 1 << 15


def _slot_row_tile(row_tile: int, n_slots: int) -> int:
    """Largest pow-2 row tile <= ``row_tile`` (>= 128 lanes) whose
    [n_slots*16, tile] f32 one-hot fits ``_ONE_HOT_BUDGET``."""
    cap = max(128, _ONE_HOT_BUDGET // (4 * 16 * n_slots))
    return min(row_tile, 1 << (cap.bit_length() - 1))


def _slot_compiler_params(out_block: tuple, row_tile: int,
                          n_slots: int) -> pltpu.CompilerParams:
    """Scoped-VMEM request of a joint slot kernel: the double-buffered
    output block, the one-hot in f32 + bf16 + its select mask (4x the f32
    one-hot covers them), and headroom for the input blocks and the
    [K*Hi, tile] value operands — 36 MiB at 254 slots x 3 channels, never
    below Mosaic's 16 MiB default."""
    one_hot = 4 * 16 * n_slots * row_tile
    need = 2 * 4 * math.prod(out_block) + 4 * one_hot + (8 << 20)
    return pltpu.CompilerParams(vmem_limit_bytes=max(need, 16 << 20))


def _digit_contract(a, eq):
    """Shared MXU contraction of every digit kernel in this file:
    [M, C] values-by-digit LHS against a [Nw, C] one-hot RHS, contracted
    over rows. The values operand is split into two bfloat16 terms — the
    one-hot side is exactly representable, so two default-precision
    passes land within ~3e-6 of f32."""
    a_top = a.astype(jnp.bfloat16)
    a_rem = (a - a_top.astype(jnp.float32)).astype(jnp.bfloat16)
    eqb = eq.astype(jnp.bfloat16)
    part = jax.lax.dot_general(
        a_top, eqb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return part + jax.lax.dot_general(
        a_rem, eqb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _hist_kernel(xb_ref, vals_ref, out_ref, *, hi_n: int):
    """One (feature_tile, row_tile) grid cell.

    xb_ref: [Ft, C] uint8 binned values; vals_ref: [K, C] f32 value
    channels (K = 3: grad*mask, hess*mask, mask; the cost is linear in K);
    out_ref: [K, Ft, Hi, 16] f32 accumulator.
    """
    r = pl.program_id(1)
    xb = xb_ref[...].astype(jnp.int32)                       # [Ft, C]
    vals = vals_ref[...]                                     # [K, C]
    ft, c = xb.shape
    k = vals.shape[0]

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, c), 0)
    for j in range(ft):
        x = xb[j:j + 1, :]                                   # [1, C]
        hi_eq = iota_hi == (x >> 4)                          # [Hi, C]
        lo_eq = iota_lo == (x & 15)                          # [16, C]
        a = jnp.where(hi_eq[None, :, :], vals[:, None, :],
                      0.0).reshape(k * hi_n, c)              # [K*Hi, C]
        # NB: build the one-hot in f32 and let _digit_contract downcast —
        # a direct bf16 select on the i1 mask fails in Mosaic ("Invalid
        # relayout ... vector<16x2048xi1>"; re-tested on a v5e with
        # jax 0.9.0 / libtpu 0.0.34)
        eqlo = jnp.where(lo_eq, 1.0, 0.0)
        part = _digit_contract(a, eqlo)                      # [K*Hi, 16]
        out_ref[:, j, :, :] += part.reshape(k, hi_n, 16)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_tile", "feature_tile",
                                    "interpret", "packed_cols", "row_block"))
def build_histogram_pallas(xb: jnp.ndarray, grad: jnp.ndarray,
                           hess: jnp.ndarray, mask: jnp.ndarray,
                           num_bins: int, row_tile: int = 2048,
                           feature_tile: int = 8,
                           interpret: bool = False,
                           packed_cols: int = 0,
                           row_block: int = 0) -> jnp.ndarray:
    """[N, F] uint8 bins + per-row values -> [F, B, 3] f32 histograms.

    Same contract as histogram.build_histogram (incl. int32-word-packed
    xb via ``packed_cols``). In one call (``row_block`` 0: every masked
    pass) the feature-major transpose of ``xb`` is loop-invariant across
    the splits of one tree, so XLA hoists it out of the growth loop.

    ``row_block`` > 0 (build_histogram ``compensated``: a pass made once a
    tree, which every leaf's histogram is subtracted from) cuts a longer
    pass into calls of that many rows, so that a call's accumulator stays
    small, and sums their results with the rounding carried
    (histogram.compensated_add), as the exact grower sums a leaf's tiles.
    """
    vals = jnp.stack([grad * mask, hess * mask, mask], axis=0)   # [3, N]
    call = functools.partial(
        build_histogram_pallas_vals, num_bins=num_bins, row_tile=row_tile,
        feature_tile=feature_tile, interpret=interpret,
        packed_cols=packed_cols)
    n = xb.shape[0]
    if row_block <= 0 or n <= row_block:
        return call(xb, vals)

    last = n - row_block

    def body(i, c):
        # the last block is moved back to end on the last row; the rows it
        # shares with the block before it count there and not here
        first = i * row_block
        start = jnp.minimum(first, last)
        fresh = start + jax.lax.iota(jnp.int32, row_block) >= first
        v = jax.lax.dynamic_slice_in_dim(vals, start, row_block, axis=1)
        return compensated_add(*c, call(
            jax.lax.dynamic_slice_in_dim(xb, start, row_block, axis=0),
            v * fresh[None, :].astype(v.dtype)))

    zero = jnp.zeros((packed_cols or xb.shape[1], num_bins, 3), jnp.float32)
    total, lost = jax.lax.fori_loop(0, -(-n // row_block), body,
                                    (zero, zero))
    return total - lost


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_tile", "feature_tile",
                                    "interpret", "packed_cols"))
def build_histogram_pallas_vals(xb: jnp.ndarray, vals: jnp.ndarray,
                                num_bins: int, row_tile: int = 2048,
                                feature_tile: int = 8,
                                interpret: bool = False,
                                packed_cols: int = 0) -> jnp.ndarray:
    """Same kernel with pre-stacked value channels: vals [K, N] -> output
    [F, B, K] (K = 3 for one histogram: root, masked pass, a leaf's
    tile)."""
    if packed_cols:
        # unpack int32 words straight to int32 lanes (the kernels cast to
        # int32 anyway and Mosaic has no uint8 casts, so the word layout
        # is kernel-native: shift/mask, no narrowing)
        xb = unpack_words(xb, packed_cols, dtype=jnp.int32)
    n, f = xb.shape
    k = vals.shape[0]
    hi_n = max(1, (num_bins + 15) // 16)   # bins above num_bins stay zero

    f_pad = (-f) % feature_tile
    n_pad = (-n) % row_tile
    # NB: uint8, not int8 — bins >= 128 must not wrap negative (packed
    # lanes stay int32, already masked non-negative)
    xb_t = jnp.pad(xb.T, ((0, f_pad), (0, n_pad)))
    if not packed_cols:
        xb_t = xb_t.astype(jnp.uint8)
    vals = jnp.pad(vals, ((0, 0), (0, n_pad)))   # padded rows carry mask 0
    fp = f + f_pad

    kernel = functools.partial(_hist_kernel, hi_n=hi_n)
    out = pl.pallas_call(
        kernel,
        grid=(fp // feature_tile, (n + n_pad) // row_tile),
        in_specs=[
            pl.BlockSpec((feature_tile, row_tile), lambda i, r: (i, r)),
            pl.BlockSpec((k, row_tile), lambda i, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((k, feature_tile, hi_n, 16),
                               lambda i, r: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, fp, hi_n, 16), jnp.float32),
        interpret=interpret,
    )(xb_t, vals)
    out = out.reshape(k, fp, hi_n * 16)
    return jnp.moveaxis(out, 0, -1)[:f, :num_bins]           # [F, B, 3]


def _hist_slot6_kernel(xb_ref, slot_ref, sel_ref, vals_ref, out_ref, *,
                       hi_n: int, n_slots: int):
    """Joint slot kernel, PARENT-slot x 6-channel variant (round-4 MXU
    fix): rows carry their splitting PARENT's rank (n_slots = K) and a
    go-left selector; the kernel routes (g, h, m) into left/right channel
    triples, so both children come out of half the slot one-hot width of
    the child-slot variant below — 2x fewer MXU column passes AND 2x the
    systolic-row utilization (M = 6*Hi = 96 vs 48).
    """
    r = pl.program_id(1)
    slot = slot_ref[...].astype(jnp.int32)                   # [1, C]
    sel = sel_ref[...]                                       # [1, C]
    v3 = vals_ref[...]                                       # [3, C]
    xb = xb_ref[...].astype(jnp.int32)                       # [Ft, C]
    ft, c = xb.shape

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.any(slot >= 0))
    def _body():
        v6 = jnp.concatenate([v3 * sel, v3 * (1.0 - sel)],
                             axis=0)                         # [6, C]
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
        iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, c), 0)
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (n_slots, c), 0)
        s_eq = iota_s == slot                                # [S, C]
        for j in range(ft):
            x = xb[j:j + 1, :]
            hi_eq = iota_hi == (x >> 4)
            lo_eq = iota_lo == (x & 15)
            a = jnp.where(hi_eq[None, :, :], v6[:, None, :],
                          0.0).reshape(6 * hi_n, c)          # [6*Hi, C]
            eqj = jnp.where(s_eq[:, None, :] & lo_eq[None, :, :], 1.0,
                            0.0).reshape(n_slots * 16, c)    # [S*16, C]
            part = _digit_contract(a, eqj)
            out_ref[:, j, :, :] += part.reshape(6, hi_n, n_slots * 16)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "n_slots", "row_tile",
                                    "feature_tile", "interpret"))
def build_histogram_slots6(xb: jnp.ndarray, slot: jnp.ndarray,
                           sel: jnp.ndarray, vals: jnp.ndarray,
                           num_bins: int, n_slots: int,
                           row_tile: int = 2048, feature_tile: int = 8,
                           interpret: bool = False) -> jnp.ndarray:
    """[N, F] uint8 bins + per-row PARENT-slot ids (-1 = inactive) +
    per-row go-left selector + [3, N] value channels ->
    [n_slots, F, B, 6] f32: channels [g,h,m]*sel then [g,h,m]*(1-sel) —
    both children of every splitting parent in one pass, at half the
    one-hot width of build_histogram_slots."""
    n, f = xb.shape
    hi_n = max(1, (num_bins + 15) // 16)
    row_tile = _slot_row_tile(row_tile, n_slots)
    f_pad = (-f) % feature_tile
    n_pad = (-n) % row_tile
    xb_t = jnp.pad(xb.T, ((0, f_pad), (0, n_pad))).astype(jnp.uint8)
    slot2 = jnp.minimum(slot.astype(jnp.int32), n_slots - 1)
    slot2 = jnp.pad(slot2, (0, n_pad), constant_values=-1)[None, :]
    sel2 = jnp.pad(sel.astype(jnp.float32), (0, n_pad))[None, :]
    vals = jnp.pad(vals, ((0, 0), (0, n_pad)))
    fp = f + f_pad

    kernel = functools.partial(_hist_slot6_kernel, hi_n=hi_n,
                               n_slots=n_slots)
    out_block = (6, feature_tile, hi_n, n_slots * 16)
    out = pl.pallas_call(
        kernel,
        grid=(fp // feature_tile, (n + n_pad) // row_tile),
        in_specs=[
            pl.BlockSpec((feature_tile, row_tile), lambda i, r: (i, r)),
            pl.BlockSpec((1, row_tile), lambda i, r: (0, r)),
            pl.BlockSpec((1, row_tile), lambda i, r: (0, r)),
            pl.BlockSpec((3, row_tile), lambda i, r: (0, r)),
        ],
        out_specs=pl.BlockSpec(out_block, lambda i, r: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((6, fp, hi_n, n_slots * 16),
                                       jnp.float32),
        compiler_params=_slot_compiler_params(out_block, row_tile, n_slots),
        interpret=interpret,
    )(xb_t, slot2, sel2, vals)
    # [6, F, Hi, S, 16] -> [S, F, B, 6]
    out = out.reshape(6, fp, hi_n, n_slots, 16)
    out = jnp.transpose(out, (3, 1, 2, 4, 0)).reshape(
        n_slots, fp, hi_n * 16, 6)
    return out[:, :f, :num_bins]


def _hist_slot_kernel(xb_ref, slot_ref, vals_ref, out_ref, *, hi_n: int,
                      n_slots: int):
    """One (feature_tile, row_tile) grid cell of the SLOT-EXTENDED digit
    kernel (frontier-wave growth, core/grow_frontier.py): every row
    carries a slot id in [0, n_slots) — its leaf's rank in this wave —
    and the kernel accumulates a separate [B] histogram per (slot,
    feature).

    The combined index slot*B + 16*hi + lo factorizes into THREE one-hots;
    grouping (vals x hi) on the left and (slot x lo) on the right keeps
    one MXU contraction per feature: [K*Hi, C] @ [C, S*16]. Rows whose
    value channels are zero (masked / not in any split leaf) contribute
    nothing regardless of slot id.

    xb_ref: [Ft, C] uint8; slot_ref: [1, C] int32 (-1 = row inactive this
    step); vals_ref: [K, C] f32; out_ref: [K, Ft, Hi, S*16] f32 (lo is
    minor so the RHS one-hot needs no in-kernel transpose; the caller
    reorders to [S, F, B, K]).

    A row tile whose slots are ALL -1 (a frontier wave marks every row
    in no splitting leaf so) skips its entire compute body.
    """
    r = pl.program_id(1)
    slot = slot_ref[...].astype(jnp.int32)                   # [1, C]
    vals = vals_ref[...]                                     # [K, C]
    k = vals.shape[0]
    ft = xb_ref.shape[0]
    c = slot.shape[1]

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.any(slot >= 0))
    def _body():
        _hist_slot_tile(xb_ref, slot, vals, out_ref, hi_n=hi_n,
                        n_slots=n_slots, k=k, ft=ft, c=c)


def _hist_slot_tile(xb_ref, slot, vals, out_ref, *, hi_n, n_slots, k, ft,
                    c):
    xb = xb_ref[...].astype(jnp.int32)                       # [Ft, C]
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, c), 0)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (n_slots, c), 0)
    s_eq = iota_s == slot                                    # [S, C]
    for j in range(ft):
        x = xb[j:j + 1, :]                                   # [1, C]
        hi_eq = iota_hi == (x >> 4)                          # [Hi, C]
        lo_eq = iota_lo == (x & 15)                          # [16, C]
        a = jnp.where(hi_eq[None, :, :], vals[:, None, :],
                      0.0).reshape(k * hi_n, c)              # [K*Hi, C]
        # RHS one-hot of (slot, lo) jointly: column index s*16 + lo
        eqj = jnp.where(s_eq[:, None, :] & lo_eq[None, :, :], 1.0,
                        0.0).reshape(n_slots * 16, c)        # [S*16, C]
        part = _digit_contract(a, eqj)                       # [K*Hi, S*16]
        out_ref[:, j, :, :] += part.reshape(k, hi_n, n_slots * 16)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "n_slots", "row_tile",
                                    "feature_tile", "interpret",
                                    "packed_cols"))
def build_histogram_slots(xb: jnp.ndarray, slot: jnp.ndarray,
                          vals: jnp.ndarray, num_bins: int, n_slots: int,
                          row_tile: int = 2048, feature_tile: int = 8,
                          interpret: bool = False,
                          packed_cols: int = 0) -> jnp.ndarray:
    """[N, F] uint8 bins + per-row slot ids + [K, N] value channels ->
    [n_slots, F, B, K] f32 histograms — every slot's histogram in ONE pass
    over the rows: one frontier wave's histograms, slot = the row's
    frontier rank (the device path of histogram.build_histogram_frontier).

    Rows outside every slot should carry slot -1 (matches no one-hot AND
    lets an all-inactive row tile skip its compute body entirely); zero
    value channels keep them harmless either way. Padding rows are
    slot -1. ``packed_cols`` > 0: xb is int32 words (core/binpack.py),
    unpacked here to kernel-native int32 lanes."""
    if packed_cols:
        xb = unpack_words(xb, packed_cols, dtype=jnp.int32)
    n, f = xb.shape
    k = vals.shape[0]
    hi_n = max(1, (num_bins + 15) // 16)
    row_tile = _slot_row_tile(row_tile, n_slots)

    f_pad = (-f) % feature_tile
    n_pad = (-n) % row_tile
    xb_t = jnp.pad(xb.T, ((0, f_pad), (0, n_pad)))
    if not packed_cols:
        xb_t = xb_t.astype(jnp.uint8)
    slot2 = jnp.minimum(slot.astype(jnp.int32), n_slots - 1)
    slot2 = jnp.pad(slot2, (0, n_pad),
                    constant_values=-1)[None, :]             # [1, N+pad]
    vals = jnp.pad(vals, ((0, 0), (0, n_pad)))
    fp = f + f_pad

    kernel = functools.partial(_hist_slot_kernel, hi_n=hi_n,
                               n_slots=n_slots)
    out_block = (k, feature_tile, hi_n, n_slots * 16)
    out = pl.pallas_call(
        kernel,
        grid=(fp // feature_tile, (n + n_pad) // row_tile),
        in_specs=[
            pl.BlockSpec((feature_tile, row_tile), lambda i, r: (i, r)),
            pl.BlockSpec((1, row_tile), lambda i, r: (0, r)),
            pl.BlockSpec((k, row_tile), lambda i, r: (0, r)),
        ],
        out_specs=pl.BlockSpec(out_block, lambda i, r: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, fp, hi_n, n_slots * 16),
                                       jnp.float32),
        compiler_params=_slot_compiler_params(out_block, row_tile, n_slots),
        interpret=interpret,
    )(xb_t, slot2, vals)
    # [K, F, Hi, S, 16] -> [S, F, B, K]
    out = out.reshape(k, fp, hi_n, n_slots, 16)
    out = jnp.transpose(out, (3, 1, 2, 4, 0)).reshape(
        n_slots, fp, hi_n * 16, k)
    return out[:, :f, :num_bins]
