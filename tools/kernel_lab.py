"""On-chip histogram-kernel experiments (round 4).

Findings this script established (see docs/Performance.md):
- the per-feature digit kernel is BANDWIDTH-bound when fed feature-major
  input directly (~0.2-0.5 ms per 1M x 28 x 256 pass) — the 29 ms
  production number was the un-hoisted [N, F] -> [F, N] uint8 transpose
  plus dispatch, not the matmuls;
- a data-dependent (scalar-prefetch) OUTPUT BlockSpec index defeats the
  output pipeliner (per-cell fetch+writeback, ~14 ms per pass); keeping
  the whole per-slot accumulator as ONE constant-index block restores
  full speed;
- the joint slot one-hot's S-factor is real MXU work: measured cost vs
  n_slots quantifies what tile-pure partitioning saves.

Usage: python tools/kernel_lab.py            (the round-4 experiments)
       python tools/kernel_lab.py onepass    (the one-pass contraction)

``onepass`` times the tile / root histogram kernel four ways at the exact
grower's shapes (4,096-row tiles and 32,768-row root blocks of 67 columns,
4,096-row tiles of 39), over ``row_tile`` 2,048 / 4,096 and
``feature_tile`` 8 / 16:
- ``two_pass``: a feature's values split into two bfloat16 terms in the
  kernel and each term contracted against the lo one-hot, [3*16, C] x
  [C, 16] twice (the kernel before the one-pass contraction);
- ``one_pass``: the values split once a row outside (split_words) and a
  feature contracted once, the 16-row hi one-hot streamed against
  [C, 2*3*16] words reinterpreted as bfloat16 (pltpu.bitcast);
- ``one_pass_wide``: the same operands with the wide side streamed,
  [2*3*16, C] x [C, 16];
- ``one_pass_split``: ``one_pass`` without the bitcast: the selected
  words' halves widened to float32 and converted, exactly, the tops' rows
  over the rems' (core/histogram_pallas.py ``_hist_kernel``).
It first pins pltpu.bitcast's row pairing (int32 row i -> bfloat16 rows
2i, the low half, and 2i + 1, the high half) and one feature's raw
product on the device against the interpreter, reads each variant's and
the program's error against float64 sums beside what the top terms alone
read, then compares every variant and the program's
build_histogram_pallas_vals with ``two_pass`` bit for bit on values that
are zero, negative, subnormal and >= 2^20. A call
is timed inside one jitted loop over four input sets (the loop's own
slicing, measured alone, is subtracted). Writes
chiprun_out/kernel_lab_onepass.json.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))                             # the program, from the checkout

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


N, F, B = 1_048_576, 28, 256
FP = 32
HI = 16


def timed(run, args_list, n_iter=20):
    out = run(*args_list[0])
    jax.block_until_ready(out)
    t0 = time.time()
    for i in range(n_iter):
        out = run(*args_list[i % len(args_list)])
    jax.block_until_ready(out)
    return (time.time() - t0) / n_iter * 1000, out


def v0_kernel(xb_ref, vals_ref, out_ref):
    r = pl.program_id(1)
    xb = xb_ref[...].astype(jnp.int32)
    vals = vals_ref[...]
    ft, c = xb.shape
    k = vals.shape[0]

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (HI, c), 0)
    for j in range(ft):
        x = xb[j:j + 1, :]
        hi_eq = iota_hi == (x >> 4)
        lo_eq = iota_lo == (x & 15)
        a = jnp.where(hi_eq[None], vals[:, None, :], 0.0).reshape(k * HI, c)
        a_top = a.astype(jnp.bfloat16)
        a_rem = (a - a_top.astype(jnp.float32)).astype(jnp.bfloat16)
        eqlo = jnp.where(lo_eq, 1.0, 0.0).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            a_top, eqlo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            a_rem, eqlo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[:, j, :, :] += part.reshape(k, HI, 16)


def mk_v0(row_tile, feature_tile=8, k=3):
    @jax.jit
    def run(xb_t, vals):
        return pl.pallas_call(
            v0_kernel,
            grid=(FP // feature_tile, N // row_tile),
            in_specs=[
                pl.BlockSpec((feature_tile, row_tile), lambda i, r: (i, r)),
                pl.BlockSpec((k, row_tile), lambda i, r: (0, r)),
            ],
            out_specs=pl.BlockSpec((k, feature_tile, HI, 16),
                                   lambda i, r: (0, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((k, FP, HI, 16), jnp.float32),
        )(xb_t, vals)
    return run


def slot_scratch_kernel(tile_slot_ref, xb_ref, sel_ref, vals_ref, out_ref,
                        *, n_slots):
    """Partitioned-tile kernel, VMEM-resident accumulator: out is ONE
    constant-index block [S, 6, ft, Hi, 16]; the prefetched tile slot
    only selects the accumulator SLICE (dynamic leading index), so the
    output pipeliner sees a resident block for the whole row sweep."""
    r = pl.program_id(1)
    slot = tile_slot_ref[r]

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(slot >= 0)
    def _body():
        xb = xb_ref[...].astype(jnp.int32)
        sel = sel_ref[...]
        v3 = vals_ref[...]
        ft, c = xb.shape
        v6 = jnp.concatenate([v3 * sel, v3 * (1.0 - sel)], axis=0)
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
        iota_hi = jax.lax.broadcasted_iota(jnp.int32, (HI, c), 0)
        for j in range(ft):
            x = xb[j:j + 1, :]
            hi_eq = iota_hi == (x >> 4)
            lo_eq = iota_lo == (x & 15)
            a = jnp.where(hi_eq[None], v6[:, None, :], 0.0) \
                .reshape(6 * HI, c)
            a_top = a.astype(jnp.bfloat16)
            a_rem = (a - a_top.astype(jnp.float32)).astype(jnp.bfloat16)
            eqlo = jnp.where(lo_eq, 1.0, 0.0).astype(jnp.bfloat16)
            part = jax.lax.dot_general(
                a_top, eqlo, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            part += jax.lax.dot_general(
                a_rem, eqlo, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            out_ref[slot, :, j, :, :] += part.reshape(6, HI, 16)


def mk_slot_scratch(n_slots, row_tile=2048, feature_tile=8):
    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(slot_scratch_kernel, n_slots=n_slots)

    @jax.jit
    def run(xb_t, sel, vals, tile_slot):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(FP // feature_tile, N // row_tile),
            in_specs=[
                pl.BlockSpec((feature_tile, row_tile),
                             lambda i, r, *_: (i, r)),
                pl.BlockSpec((1, row_tile), lambda i, r, *_: (0, r)),
                pl.BlockSpec((3, row_tile), lambda i, r, *_: (0, r)),
            ],
            out_specs=pl.BlockSpec(
                (n_slots, 6, feature_tile, HI, 16),
                lambda i, r, *_: (0, 0, i, 0, 0)),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_slots, 6, FP, HI, 16),
                                           jnp.float32),
        )(tile_slot.astype(jnp.int32), xb_t, sel[None, :], vals)
    return run


def joint_kernel(xb_ref, slot_ref, vals_ref, out_ref, *, n_slots):
    """Existing joint (slot x lo) design: RHS width n_slots*16."""
    r = pl.program_id(1)
    slot = slot_ref[...].astype(jnp.int32)
    vals = vals_ref[...]
    k = vals.shape[0]
    xb = xb_ref[...].astype(jnp.int32)
    ft, c = xb.shape

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (HI, c), 0)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (n_slots, c), 0)
    s_eq = iota_s == slot
    for j in range(ft):
        x = xb[j:j + 1, :]
        hi_eq = iota_hi == (x >> 4)
        lo_eq = iota_lo == (x & 15)
        a = jnp.where(hi_eq[None], vals[:, None, :], 0.0).reshape(k * HI, c)
        eqj = jnp.where(s_eq[:, None, :] & lo_eq[None], 1.0, 0.0) \
            .reshape(n_slots * 16, c)
        a_top = a.astype(jnp.bfloat16)
        a_rem = (a - a_top.astype(jnp.float32)).astype(jnp.bfloat16)
        eqb = eqj.astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            a_top, eqb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            a_rem, eqb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[:, j, :, :] += part.reshape(k, HI, n_slots * 16)


def mk_joint(n_slots, row_tile=2048, feature_tile=8):
    kernel = functools.partial(joint_kernel, n_slots=n_slots)

    @jax.jit
    def run(xb_t, slot, vals):
        return pl.pallas_call(
            kernel,
            grid=(FP // feature_tile, N // row_tile),
            in_specs=[
                pl.BlockSpec((feature_tile, row_tile), lambda i, r: (i, r)),
                pl.BlockSpec((1, row_tile), lambda i, r: (0, r)),
                pl.BlockSpec((3, row_tile), lambda i, r: (0, r)),
            ],
            out_specs=pl.BlockSpec((3, feature_tile, HI, n_slots * 16),
                                   lambda i, r: (0, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((3, FP, HI, n_slots * 16),
                                           jnp.float32),
        )(xb_t, slot[None, :], vals)
    return run


def pertile_kernel(act_ref, xb_ref, sel_ref, vals_ref, out_ref):
    """Per-TILE histogram output, STATIC index maps only: cell (i, r)
    writes its tile's [6, ft, Hi, 16] block to out[r]; the caller
    reduces tiles -> slots with one [S, T] one-hot matmul (inactive
    tiles carry one-hot weight 0). act_ref gates compute: inactive
    tiles just zero their block (garbage x 0 would still poison via
    NaN, so the zero matters)."""
    r = pl.program_id(1)
    act = act_ref[r]

    @pl.when(act == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(act != 0)
    def _body():
        xb = xb_ref[...].astype(jnp.int32)
        sel = sel_ref[...]
        v3 = vals_ref[...]
        ft, c = xb.shape
        v6 = jnp.concatenate([v3 * sel, v3 * (1.0 - sel)], axis=0)
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
        iota_hi = jax.lax.broadcasted_iota(jnp.int32, (HI, c), 0)
        for j in range(ft):
            x = xb[j:j + 1, :]
            hi_eq = iota_hi == (x >> 4)
            lo_eq = iota_lo == (x & 15)
            a = jnp.where(hi_eq[None], v6[:, None, :], 0.0) \
                .reshape(6 * HI, c)
            a_top = a.astype(jnp.bfloat16)
            a_rem = (a - a_top.astype(jnp.float32)).astype(jnp.bfloat16)
            eqlo = jnp.where(lo_eq, 1.0, 0.0).astype(jnp.bfloat16)
            part = jax.lax.dot_general(
                a_top, eqlo, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            part += jax.lax.dot_general(
                a_rem, eqlo, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            out_ref[0, :, j, :, :] = part.reshape(6, HI, 16)


def mk_pertile(n_slots, row_tile=2048, feature_tile=8):
    from jax.experimental.pallas import tpu as pltpu
    t = N // row_tile

    @jax.jit
    def run(xb_t, sel, vals, tile_slot):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(FP // feature_tile, t),
            in_specs=[
                pl.BlockSpec((feature_tile, row_tile),
                             lambda i, r, *_: (i, r)),
                pl.BlockSpec((1, row_tile), lambda i, r, *_: (0, r)),
                pl.BlockSpec((3, row_tile), lambda i, r, *_: (0, r)),
            ],
            out_specs=pl.BlockSpec(
                (1, 6, feature_tile, HI, 16),
                lambda i, r, *_: (r, 0, i, 0, 0)),
        )
        act = (tile_slot >= 0).astype(jnp.int32)
        tiles = pl.pallas_call(
            pertile_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t, 6, FP, HI, 16),
                                           jnp.float32),
        )(act, xb_t, sel[None, :], vals)
        seg = (tile_slot[None, :]
               == jnp.arange(n_slots, dtype=jnp.int32)[:, None]) \
            .astype(jnp.float32)                        # [S, T]
        return jnp.einsum("st,tcfhl->scfhl", seg, tiles)
    return run


def main():
    r = np.random.RandomState(0)
    xb_np = r.randint(0, B, (F, N)).astype(np.uint8)
    xb_t = jnp.asarray(np.concatenate(
        [xb_np, np.zeros((FP - F, N), np.uint8)], axis=0))
    xb_rm = jnp.asarray(np.ascontiguousarray(xb_np.T))   # [N, F] row-major
    vals_sets = [jnp.asarray(r.randn(3, N).astype(np.float32))
                 for _ in range(4)]
    sel = jnp.asarray((r.rand(N) > 0.5).astype(np.float32))

    # 0) methodology guard: exact numpy reference for the LAST input set
    run = mk_v0(2048)
    ms, out = timed(run, [(xb_t, v) for v in vals_sets])
    ref = np.zeros((3, F, B), np.float32)
    v_last = np.asarray(vals_sets[(20 - 1) % 4])
    for ch in range(3):
        for f in range(F):
            np.add.at(ref[ch, f], xb_np[f], v_last[ch])
    got = np.asarray(out).reshape(3, FP, B)[:, :F]
    print("v0 rt=2048 (varied inputs)   : %6.2f ms  err=%.1e"
          % (ms, np.abs(got - ref).max()), flush=True)

    # 1) transpose cost (what build_histogram pays when not hoisted)
    tr = jax.jit(lambda x: jnp.pad(x.T, ((0, FP - F), (0, 0))))
    ms, _ = timed(tr, [(xb_rm,)])
    print("uint8 [N,F]->[F,N] transpose : %6.2f ms" % ms, flush=True)

    # 2) per-tile + segment-matmul (partition-pure tiles, static index)
    for s, frac in ((16, 2), (16, 1), (32, 1)):
        ts = np.full(N // 2048, -1, np.int32)
        nact = N // (2048 * frac)
        ts[:nact] = np.arange(nact) % s
        args = [(xb_t, sel, v, jnp.asarray(ts)) for v in vals_sets]
        try:
            ms, out = timed(mk_pertile(s), args)
            # spot parity on slot 0 of the last set
            sel_np = np.asarray(sel)
            refs = np.zeros((F, B, 6), np.float32)
            rows = np.concatenate([np.arange(t * 2048, (t + 1) * 2048)
                                   for t in range(nact)
                                   if ts[t] == 0])
            for ch in range(6):
                w = sel_np[rows] if ch < 3 else 1 - sel_np[rows]
                v = v_last[ch % 3, rows] * w
                for f in range(F):
                    np.add.at(refs[f, :, ch], xb_np[f, rows], v)
            got = np.transpose(np.asarray(out[0]).reshape(6, FP, B),
                               (1, 2, 0))[:F]
            print("per-tile S=%-3d 1/%d active  : %6.2f ms  err=%.1e"
                  % (s, frac, ms, np.abs(got - refs).max()), flush=True)
        except Exception as e:  # noqa: BLE001
            print("per-tile S=%-3d 1/%d active  : FAIL %s"
                  % (s, frac, repr(e)[:150]), flush=True)

    # 3) joint slot kernel (existing design) vs S
    slot_ids = jnp.asarray(r.randint(0, 32, (N,)).astype(np.int32))
    for s in (8, 16, 32):
        sl = jnp.minimum(slot_ids, s - 1)
        try:
            ms, _ = timed(mk_joint(s), [(xb_t, sl, v) for v in vals_sets])
            print("joint slots S=%-3d full-N    : %6.2f ms" % (s, ms),
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print("joint slots S=%-3d full-N    : FAIL %s"
                  % (s, repr(e)[:150]), flush=True)


# ---------------------------------------------------------------------------
# The one-pass contraction of the tile / root kernel (``onepass``).
def two_pass_kernel(xb_ref, vals_ref, out_ref, *, hi_n):
    """The two-pass formulation: out [K, Ft, Hi, 16]."""
    r = pl.program_id(1)
    xb = xb_ref[...].astype(jnp.int32)
    vals = vals_ref[...]
    ft, c = xb.shape
    k = vals.shape[0]

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, c), 0)
    for j in range(ft):
        x = xb[j:j + 1, :]
        a = jnp.where((iota_hi == (x >> 4))[None], vals[:, None, :],
                      0.0).reshape(k * hi_n, c)
        a_top = a.astype(jnp.bfloat16)
        a_rem = (a - a_top.astype(jnp.float32)).astype(jnp.bfloat16)
        eqlo = jnp.where(iota_lo == (x & 15), 1.0, 0.0).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            a_top, eqlo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            a_rem, eqlo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[:, j, :, :] += part.reshape(k, hi_n, 16)


def one_pass_kernel(xb_ref, w_ref, out_ref, *, hi_n, streamed):
    """One contraction a feature on the words reinterpreted as bfloat16
    by pltpu.bitcast: out [Ft, K*16, Hi]. ``streamed`` "hi": the hi
    one-hot is the LHS; "wide": the value words are."""
    from jax.experimental.pallas import tpu as pltpu
    r = pl.program_id(1)
    xb = xb_ref[...].astype(jnp.int32)
    w = w_ref[...]
    ft, c = xb.shape
    m = 16 * w.shape[0]

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (hi_n, c), 0)
    dims = (((1,), (1,)), ((), ()))
    for j in range(ft):
        x = xb[j:j + 1, :]
        eqhi = jnp.where(iota_hi == (x >> 4), 1.0, 0.0).astype(jnp.bfloat16)
        wide = jnp.where((iota_lo == (x & 15))[None], w[:, None, :],
                         0).reshape(m, c)
        wide = pltpu.bitcast(wide, jnp.bfloat16)
        if streamed == "hi":
            part = jax.lax.dot_general(
                eqhi, wide, dims, preferred_element_type=jnp.float32).T
        else:
            part = jax.lax.dot_general(
                wide, eqhi, dims, preferred_element_type=jnp.float32)
        out_ref[j] += part.reshape(m, 2, hi_n).sum(axis=1)


def mk_onepass_call(variant, fp, n, k, row_tile, feature_tile, hi_n=16):
    """pallas_call of one variant on feature-major [fp, n] uint8 bins and
    [k, n] values (f32 for two_pass, split_words' int32 otherwise) ->
    [fp, hi_n * 16, k] f32."""
    grid = (fp // feature_tile, n // row_tile)
    in_specs = [pl.BlockSpec((feature_tile, row_tile), lambda i, r: (i, r)),
                pl.BlockSpec((k, row_tile), lambda i, r: (0, r))]
    if variant == "two_pass":
        kernel = functools.partial(two_pass_kernel, hi_n=hi_n)
        out_block = (k, feature_tile, hi_n, 16)
        out_full = (k, fp, hi_n, 16)
        index = lambda i, r: (0, i, 0, 0)                    # noqa: E731

        def layout(o):
            return jnp.moveaxis(o.reshape(k, fp, hi_n * 16), 0, -1)
    else:
        from lightgbm_tpu.core.histogram_pallas import _hist_kernel
        kernel = functools.partial(
            one_pass_kernel, hi_n=hi_n,
            streamed="hi" if variant == "one_pass" else "wide")
        if variant == "one_pass_split":
            kernel = functools.partial(_hist_kernel, hi_n=hi_n)
        out_block = (feature_tile, k * 16, hi_n)
        out_full = (fp, k * 16, hi_n)
        index = lambda i, r: (i, 0, 0)                       # noqa: E731

        def layout(o):
            o = jnp.transpose(o.reshape(fp, k, 16, hi_n), (0, 3, 2, 1))
            return o.reshape(fp, hi_n * 16, k)

    def call(xb_t, v):
        return layout(pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec(out_block, index),
            out_shape=jax.ShapeDtypeStruct(out_full, jnp.float32),
        )(xb_t, v))
    return call


def lab_values(r, k, n):
    """[k, n] f32 with zeros, negatives, subnormals and values >= 2^20."""
    v = (r.randn(k, n) * np.exp(2 * r.randn(k, n))).astype(np.float32)
    v[:, 0::7] = 0.0
    v[:, 1::11] = np.float32(3e-39) * r.randn(k, v[:, 1::11].shape[1])
    v[:, 2::13] = np.float32(2.0 ** 20) * (1 + r.rand(k, v[:, 2::13].shape[1]))
    v[:, 3::17] *= -1
    if k >= 3:
        v[2] = (r.rand(n) > 0.3)                             # a count channel
    return v


def per_call_us(call, sets, reps, repeats=5):
    """Median over ``repeats`` of one jitted loop of ``reps`` calls, each
    on the next of the stacked input sets, in us a call."""
    stacked = tuple(jnp.stack(a) for a in zip(*sets))
    s = len(sets)

    @jax.jit
    def loop(*st):
        def body(i, acc):
            args = tuple(jax.lax.dynamic_index_in_dim(a, i % s, 0, False)
                         for a in st)
            return acc + call(*args).reshape(-1)[0]
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0))

    jax.block_until_ready(loop(*stacked))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*stacked))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) / reps * 1e6


def pairing_check():
    """pltpu.bitcast int32 [8, 128] -> bfloat16 [16, 128] on the device
    against the interpreter: word (2i + 1) << 16 | 2i (as bfloat16 bits of
    the small integers) must give row r the value r."""
    from jax.experimental.pallas import tpu as pltpu

    def kern(w_ref, o_ref):
        o_ref[...] = pltpu.bitcast(w_ref[...], jnp.bfloat16).astype(
            jnp.float32)

    rows = np.arange(16, dtype=np.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.asarray(rows).astype(jnp.bfloat16), jnp.uint16)
    bits = np.asarray(bits).astype(np.uint32)
    word = (bits[1::2] << 16) | bits[0::2]                   # [8]
    w = jnp.asarray(np.repeat(word.view(np.int32)[:, None], 128, 1))
    out = {}
    for interp in (False, True):
        out[interp] = np.asarray(pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=interp)(w))
    want = np.repeat(rows[:, None], 128, 1)
    return {"device_rows": out[False][:, 0].tolist(),
            "device_equals_interpreter": bool(
                np.array_equal(out[False], out[True])),
            "row_r_holds_r": bool(np.array_equal(out[False], want))}


def product_check():
    """One feature's raw [16, 2*3*16] product and its pair sums, on the
    device against the interpreter, at 512 rows."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.core.histogram_pallas import split_words

    def kern(x_ref, w_ref, part_ref, sum_ref):
        x = x_ref[...].astype(jnp.int32)
        w = w_ref[...]
        c = x.shape[1]
        m = 16 * w.shape[0]
        eqhi = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (16, c), 0)
                         == (x >> 4), 1.0, 0.0).astype(jnp.bfloat16)
        lo = jax.lax.broadcasted_iota(jnp.int32, (16, c), 0) == (x & 15)
        wide = pltpu.bitcast(jnp.where(lo[None], w[:, None, :], 0)
                             .reshape(m, c), jnp.bfloat16)
        part = jax.lax.dot_general(eqhi, wide, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        part_ref[...] = part
        sum_ref[...] = part.T.reshape(m, 2, 16).sum(axis=1)

    r = np.random.RandomState(3)
    x = jnp.asarray(r.randint(0, 255, (1, 512)).astype(np.int32))
    w = split_words(jnp.asarray(r.randn(3, 512).astype(np.float32)))
    out = {}
    for interp in (False, True):
        out[interp] = [np.asarray(o) for o in pl.pallas_call(
            kern, out_shape=(jax.ShapeDtypeStruct((16, 96), jnp.float32),
                             jax.ShapeDtypeStruct((48, 16), jnp.float32)),
            interpret=interp)(x, w)]
    return {"part_max_diff": float(np.abs(out[0][0] - out[1][0]).max()),
            "sum_max_diff": float(np.abs(out[0][1] - out[1][1]).max()),
            "part_cols_nonzero_device": int((out[0][0] != 0).any(0).sum()),
            "part_cols_nonzero_interp": int((out[1][0] != 0).any(0).sum())}


def accuracy_check():
    """Each variant's histograms at a 4,096 x 16 tile of ordinary values
    against float64 sums, in units of the bin's sum of |v|; beside them
    what the top bfloat16 terms alone read (a variant that loses the rem
    term reads that), and split_words on the device against numpy."""
    import ml_dtypes
    from lightgbm_tpu.core.histogram_pallas import (
        build_histogram_pallas_vals, split_words)
    r = np.random.RandomState(7)
    n, f, k = 4096, 16, 3
    x = r.randint(0, 255, (n, f)).astype(np.uint8)
    v = r.randn(k, n).astype(np.float32)
    top = v.astype(ml_dtypes.bfloat16)
    rem = (v - top.astype(np.float32)).astype(ml_dtypes.bfloat16)
    want = (top.view(np.uint16).astype(np.uint32) << 16
            | rem.view(np.uint16).astype(np.uint32))
    words = np.asarray(split_words(jnp.asarray(v))).view(np.uint32)
    exact, top_only, mag = (np.zeros((f, 256, k)) for _ in range(3))
    for j in range(f):
        np.add.at(exact[j], x[:, j], v.T.astype(np.float64))
        np.add.at(top_only[j], x[:, j], top.T.astype(np.float64))
        np.add.at(mag[j], x[:, j], np.abs(v.T).astype(np.float64))
    mag = np.maximum(mag, 1e-30)
    res = {"split_words_bits_differing": int((words != want).sum()),
           "top_only_err": float((np.abs(top_only - exact) / mag).max())}
    xt = jnp.asarray(x.T)
    for var in ("two_pass", "one_pass", "one_pass_wide", "one_pass_split"):
        vin = jnp.asarray(v) if var == "two_pass" else split_words(
            jnp.asarray(v))
        h = np.asarray(jax.jit(mk_onepass_call(var, f, n, k, 2048, 8))(
            xt, vin))[:, :256]
        res[var + "_err"] = float((np.abs(h - exact) / mag).max())
    h = np.asarray(build_histogram_pallas_vals(jnp.asarray(x),
                                               jnp.asarray(v), 256))
    res["program_err"] = float((np.abs(h - exact) / mag).max())
    return res


def onepass():
    from lightgbm_tpu.core.histogram_pallas import (
        build_histogram_pallas_vals, split_words)
    dev = jax.devices()[0]
    res = {"device": dev.platform, "device_kind": dev.device_kind,
           "pairing": pairing_check(), "product": product_check(),
           "accuracy": accuracy_check(), "equal": [], "timing": []}
    print("device %s %s" % (dev.platform, dev.device_kind), flush=True)
    for key in ("pairing", "product", "accuracy"):
        print(key, res[key], flush=True)
    r = np.random.RandomState(44)
    shapes = [("tile67", 4096, 67), ("root67", 32768, 67),
              ("tile39", 4096, 39)]
    variants = ("two_pass", "one_pass", "one_pass_wide", "one_pass_split")
    k = 3
    print("%-7s %-14s %5s %3s %10s %10s %9s" % (
        "shape", "variant", "rt", "ft", "us/call", "net us", "ns/(r,c)"),
        flush=True)
    for name, n, f in shapes:
        xbs = [r.randint(0, 255, (n, f)).astype(np.uint8) for _ in range(4)]
        vs = [lab_values(r, k, n) for _ in range(4)]
        reps = 200 if n <= 4096 else 40
        for ft in (8, 16):
            fp = -(-f // ft) * ft
            xts = [jnp.asarray(np.pad(x.T, ((0, fp - f), (0, 0))))
                   for x in xbs]
            sets = {"two_pass": [(x, jnp.asarray(v)) for x, v in
                                 zip(xts, vs)]}
            sets["one_pass"] = [
                (x, split_words(jnp.asarray(v))) for x, v in zip(xts, vs)]
            sets["one_pass_wide"] = sets["one_pass_split"] = sets["one_pass"]
            over = per_call_us(lambda x, v: x[0, :1].astype(jnp.float32),
                               sets["one_pass"], reps)
            for rt in (2048, 4096):
                ref = None
                for var in variants:
                    call = mk_onepass_call(var, fp, n, k, rt, ft)
                    try:
                        got = np.asarray(jax.jit(call)(*sets[var][0]))
                        us = per_call_us(call, sets[var], reps)
                    except Exception as e:  # noqa: BLE001
                        print("%-7s %-14s %5d %3d FAIL %s" % (
                            name, var, rt, ft, repr(e)[:200]), flush=True)
                        continue
                    if var == "two_pass":
                        ref = got
                    elif ref is not None:
                        diff = got != ref
                        res["equal"].append({
                            "shape": name, "variant": var, "rt": rt,
                            "ft": ft, "bins_differing": int(diff.sum()),
                            "max_abs_diff": float(np.abs(got - ref).max())})
                    net = us - over
                    row = {"shape": name, "variant": var, "rows": n,
                           "cols": f, "padded_cols": fp, "rt": rt, "ft": ft,
                           "us_per_call": us, "loop_us": over,
                           "net_us": net,
                           "ns_per_row_col": net * 1e3 / (n * fp)}
                    res["timing"].append(row)
                    print("%-7s %-14s %5d %3d %10.2f %10.2f %9.4f" % (
                        name, var, rt, ft, us, net, row["ns_per_row_col"]),
                        flush=True)
        # the program's call as the grower makes it (row-major bins, f32
        # values, the words made inside), at its default tiles
        prog = functools.partial(build_histogram_pallas_vals, num_bins=255)
        psets = [(jnp.asarray(x), jnp.asarray(v)) for x, v in zip(xbs, vs)]
        us = per_call_us(prog, psets, reps)
        ref_call = mk_onepass_call("two_pass", -(-f // 8) * 8, n, k, 2048, 8)
        ref = np.asarray(jax.jit(ref_call)(
            jnp.asarray(np.pad(xbs[0].T, ((0, -(-f // 8) * 8 - f), (0, 0)))),
            jnp.asarray(vs[0])))[:f, :255]
        got = np.asarray(prog(*psets[0]))
        res["equal"].append({"shape": name, "variant": "program",
                             "bins_differing": int((got != ref).sum()),
                             "max_abs_diff": float(np.abs(got - ref).max())})
        res["timing"].append({"shape": name, "variant": "program",
                              "rows": n, "cols": f, "us_per_call": us})
        print("%-7s %-14s %10.2f us a call (its words and layout included)"
              % (name, "program", us), flush=True)
    print("bit-equality against two_pass:", flush=True)
    for e in res["equal"]:
        print("  ", e, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kernel_lab_onepass.json", "w") as fh:
        json.dump(res, fh, indent=1)


if __name__ == "__main__":
    if sys.argv[1:] == ["onepass"]:
        onepass()
    else:
        main()


# ---------------------------------------------------------------------------
# Round-4 feasibility probe for the partition-step mega-kernel (north-star
# section of docs/Performance.md): IN-TILE stable partition as a
# permutation one-hot matmul — EXACT for byte payloads (each output row is
# one one-hot row of P times integer values <= 255; a single nonzero
# product per output element, so no accumulation error), with the prefix
# sum done as a lower-triangular f32 matvec (Mosaic has no cumsum).
#
# Measured on a v5e chip: ~8.8 ms per 1M x 128-byte-payload pass at
# row_tile 256/512 (per-tile-overhead bound — the skinny [1, t] prefix
# matvec and per-tile setup dominate, not the P @ data matmul), exact
# output, per-tile left-counts delivered in an i32 side output.
#
# Mosaic lowering gotchas hit on the way (all worked around below):
#   - uint8 -> bfloat16 casts unsupported (go via int32);
#   - jnp.cumsum unsupported (triangular matmul instead);
#   - f32 iota unsupported (int iota + cast);
#   - scalar extraction like cl[-1] lowers to dynamic_slice (unsupported)
#     — keep everything 2D and use keepdims reductions.
def partition_tile_kernel(xb_ref, gl_ref, out_ref, cnt_ref):
    xb = xb_ref[...].astype(jnp.int32).astype(jnp.bfloat16)   # [t, C]
    gl2 = gl_ref[...]                                         # [1, t] f32
    t = xb.shape[0]
    iota0 = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    iota1 = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    ut = jnp.where(iota1 <= iota0, 1.0, 0.0)
    cl2 = jax.lax.dot_general(gl2, ut, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # [1, t]
    nl2 = jnp.sum(gl2, axis=1, keepdims=True)
    ii = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1).astype(jnp.float32)
    pos2 = jnp.where(gl2 > 0, cl2 - 1.0, nl2 + (ii + 1.0) - cl2 - 1.0)
    perm = jnp.where(iota0 == pos2.astype(jnp.int32), 1.0, 0.0) \
        .astype(jnp.bfloat16)
    out = jax.lax.dot_general(perm, xb, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    out_ref[...] = out.astype(jnp.int32).astype(jnp.uint8)
    cnt_ref[...] = jnp.broadcast_to(nl2, cnt_ref.shape).astype(jnp.int32)


def mk_partition_tiles(n, c, row_tile):
    @jax.jit
    def run(xb, gl):
        return pl.pallas_call(
            partition_tile_kernel,
            grid=(n // row_tile,),
            in_specs=[pl.BlockSpec((row_tile, c), lambda r: (r, 0)),
                      pl.BlockSpec((1, row_tile), lambda r: (0, r))],
            out_specs=[pl.BlockSpec((row_tile, c), lambda r: (r, 0)),
                       pl.BlockSpec((8, 128), lambda r: (r, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, c), jnp.uint8),
                       jax.ShapeDtypeStruct((n // row_tile * 8, 128),
                                            jnp.int32)],
        )(xb, gl)
    return run
