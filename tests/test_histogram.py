"""Histogram kernel properties (reference: dense_bin.hpp ConstructHistogram,
dataset.h FixHistogram; SURVEY.md §4 property tests)."""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core.histogram import (build_histogram, fix_histogram,
                                         subtract_histogram)


def _ref_hist(xb, g, h, mask, b):
    n, f = xb.shape
    out = np.zeros((f, b, 3), np.float64)
    for i in range(n):
        if mask[i] == 0:
            continue
        for j in range(f):
            out[j, xb[i, j], 0] += g[i]
            out[j, xb[i, j], 1] += h[i]
            out[j, xb[i, j], 2] += 1
    return out


@pytest.mark.parametrize("impl", ["matmul", "scatter"])
def test_histogram_matches_reference_loop(impl):
    r = np.random.RandomState(0)
    n, f, b = 500, 6, 16
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = r.rand(n).astype(np.float32)
    mask = (r.rand(n) < 0.7).astype(np.float32)
    hist = np.asarray(build_histogram(jnp.asarray(xb), jnp.asarray(g),
                                      jnp.asarray(h), jnp.asarray(mask),
                                      num_bins=b, impl=impl))
    ref = _ref_hist(xb, g, h, mask, b)
    np.testing.assert_allclose(hist, ref, rtol=1e-4, atol=1e-4)


def test_histogram_chunked_equals_unchunked():
    r = np.random.RandomState(1)
    n, f, b = 70000, 4, 32
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = r.rand(n).astype(np.float32)
    mask = np.ones(n, np.float32)
    h1 = np.asarray(build_histogram(jnp.asarray(xb), jnp.asarray(g),
                                    jnp.asarray(h), jnp.asarray(mask),
                                    num_bins=b, row_chunk=16384))
    h2 = np.asarray(build_histogram(jnp.asarray(xb), jnp.asarray(g),
                                    jnp.asarray(h), jnp.asarray(mask),
                                    num_bins=b, row_chunk=200000))
    np.testing.assert_allclose(h1, h2, rtol=1e-3, atol=1e-2)


def test_subtraction_consistency():
    """SURVEY §4: child = parent - sibling must hold exactly in f32."""
    r = np.random.RandomState(2)
    n, f, b = 2000, 5, 16
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = r.rand(n).astype(np.float32)
    left = (r.rand(n) < 0.5).astype(np.float32)
    parent = np.asarray(build_histogram(jnp.asarray(xb), jnp.asarray(g),
                                        jnp.asarray(h),
                                        jnp.ones(n, np.float32), num_bins=b))
    hl = np.asarray(build_histogram(jnp.asarray(xb), jnp.asarray(g),
                                    jnp.asarray(h), jnp.asarray(left),
                                    num_bins=b))
    hr = np.asarray(build_histogram(jnp.asarray(xb), jnp.asarray(g),
                                    jnp.asarray(h), jnp.asarray(1 - left),
                                    num_bins=b))
    np.testing.assert_allclose(
        np.asarray(subtract_histogram(jnp.asarray(parent), jnp.asarray(hl))),
        hr, rtol=1e-3, atol=1e-2)


def test_fix_histogram_restores_totals():
    r = np.random.RandomState(3)
    f, b = 4, 16
    hist = r.rand(f, b, 3).astype(np.float32)
    default_bins = np.array([0, 3, 5, 15], np.int32)
    sg, sh, cnt = 100.0, 50.0, 1000.0
    fixed = np.asarray(fix_histogram(jnp.asarray(hist),
                                     jnp.asarray(default_bins),
                                     jnp.float32(sg), jnp.float32(sh),
                                     jnp.float32(cnt)))
    np.testing.assert_allclose(fixed[:, :, 0].sum(1), sg, rtol=1e-5)
    np.testing.assert_allclose(fixed[:, :, 1].sum(1), sh, rtol=1e-5)
    np.testing.assert_allclose(fixed[:, :, 2].sum(1), cnt, rtol=1e-5)


def test_pallas_kernel_matches_scatter():
    """The Pallas TPU histogram kernel (core/histogram_pallas.py), in
    interpreter mode on CPU, must match the scatter reference within the
    kernel's two-term bf16 contraction budget (~1e-5 relative) — the
    GPU_DEBUG_COMPARE discipline (gpu_tree_learner.cpp:992-1010) as a
    test."""
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram import build_histogram
    r = np.random.RandomState(3)
    for (n, f, b) in [(700, 5, 16), (1500, 13, 256), (513, 8, 64)]:
        xb = r.randint(0, b, (n, f)).astype(np.uint8)
        g = r.randn(n).astype(np.float32)
        h = np.abs(r.randn(n)).astype(np.float32)
        m = (r.rand(n) > 0.4).astype(np.float32)
        ref = np.asarray(build_histogram(
            jnp.asarray(xb), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
            num_bins=b, impl="scatter"))
        pal = np.asarray(build_histogram(
            jnp.asarray(xb), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
            num_bins=b, impl="pallas_interpret"))
        np.testing.assert_allclose(pal, ref, rtol=1e-4, atol=1e-3)


def test_pallas_long_pass_runs_in_row_blocks():
    """``compensated`` (the exact grower's root over the row partition): a
    pass longer than one row block makes one kernel call a block, the last
    block moved back to end on the last row with the rows it shares masked,
    the blocks summed with the rounding carried. Every row counts once: the
    count channel is exact. Without it (every masked pass) the pass stays
    one call on the whole matrix."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core import histogram_pallas
    from lightgbm_tpu.core.histogram import build_histogram
    r = np.random.RandomState(5)
    n, f, b = 2 * histogram_pallas.ROW_BLOCK + 4321, 3, 16
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = np.abs(r.randn(n)).astype(np.float32)
    m = (r.rand(n) > 0.4).astype(np.float32)
    args = (jnp.asarray(xb), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m))
    ref = np.zeros((f, b, 3))
    for j in range(f):
        np.add.at(ref[j], xb[:, j], np.stack([g * m, h * m, m], 1)
                  .astype(np.float64))
    for compensated in (True, False):
        fn = lambda *a: build_histogram(                        # noqa: E731
            *a, num_bins=b, impl="pallas_interpret", compensated=compensated)
        pal = np.asarray(fn(*args))
        np.testing.assert_array_equal(pal[:, :, 2], ref[:, :, 2])
        np.testing.assert_allclose(pal, ref, rtol=2e-5, atol=2e-3)
        text = str(jax.make_jaxpr(fn)(*args))
        assert ("dynamic_slice" in text) == compensated


def test_compensated_add_keeps_the_terms_accuracy():
    """4,000 histograms of one size summed in float32: the plain running
    total drifts by thousands of its last place, the compensated one stays
    within a few (what a sibling taken by subtraction inherits)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram import compensated_add
    terms = jnp.asarray(np.random.RandomState(1).rand(4000, 64)
                        .astype(np.float32) + 0.5)

    def step(c, t):
        total, lost, plain = c
        total, lost = compensated_add(total, lost, t)
        return (total, lost, plain + t), None

    zero = jnp.zeros((64,), jnp.float32)
    (total, lost, plain), _ = jax.jit(lambda ts: jax.lax.scan(
        step, (zero, zero, zero), ts))(terms)
    want = np.asarray(terms, np.float64).sum(axis=0)
    ulp = np.spacing(want.astype(np.float32)).astype(np.float64)
    assert np.abs(np.asarray(total - lost, np.float64) - want).max() \
        <= ulp.max()
    assert np.abs(np.asarray(plain, np.float64) - want).max() > 8 * ulp.max()


def test_pallas_kernel_tile_matches_scatter():
    """One 4,096-row tile of pre-stacked (grad, hess, count) values, as the
    exact grower's second pass feeds the kernel (partition.hist_for_leaf):
    the channels come back in order from the digit-factorized kernel, and
    the count channel, integers in float32, exactly."""
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram import hist_tile_vals
    r = np.random.RandomState(7)
    n, f, b = 4096, 9, 256
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    vals = r.randn(n, 3).astype(np.float32)
    vals[:, 2] = r.rand(n) > 0.3
    ref = np.asarray(hist_tile_vals(jnp.asarray(xb), jnp.asarray(vals),
                                    b, "scatter"))
    pal = np.asarray(hist_tile_vals(jnp.asarray(xb), jnp.asarray(vals),
                                    b, "pallas_interpret"))
    assert pal.shape == (f, b, 3)
    np.testing.assert_allclose(pal, ref, rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(pal[:, :, 2], ref[:, :, 2])


@pytest.mark.parametrize("fake_backend", [
    "cpu", "gpu", "METAL", "neuron", "tpu", "tpu_plugin"])
def test_tpu_shaped_gate_is_allow_list(monkeypatch, fake_backend):
    """The TPU-shaped allow-list is exactly jax's own "tpu" backend: a
    plug-in that merely mentions it keeps the conservative paths. The tile
    loop's shape (row_chunk, the placement of a tile's ids) does not ask
    the backend at all: it follows the histogram impl, so the interpret
    spellings run on the CPU what "pallas" runs on the chip."""
    import jax
    from lightgbm_tpu.core import partition
    monkeypatch.setattr(jax, "default_backend", lambda: fake_backend)
    assert partition.tpu_shaped_backend() == (fake_backend == "tpu")
    for impl in ("pallas", "pallas_interpret"):
        assert partition.tpu_tiles(impl)
        assert partition.window_placement(impl, vmapped=False)
        # a batched window start would be a scatter again
        assert not partition.window_placement(impl, vmapped=True)
    for impl in ("matmul", "scatter"):
        assert not partition.tpu_tiles(impl)
        assert not partition.window_placement(impl, vmapped=False)
        assert not partition.window_placement(impl, vmapped=True)


def test_slot_kernel_matches_per_slot_scatter():
    """The slot-extended digit kernel (a frontier wave) must equal
    building each slot's histogram separately with the scatter reference."""
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram import build_histogram
    from lightgbm_tpu.core.histogram_pallas import build_histogram_slots
    r = np.random.RandomState(21)
    n, f, b, s = 1100, 6, 256, 8
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = np.abs(r.randn(n)).astype(np.float32)
    m = (r.rand(n) > 0.2).astype(np.float32)
    slot = r.randint(0, s, n).astype(np.int32)
    vals = jnp.stack([jnp.asarray(g * m), jnp.asarray(h * m),
                      jnp.asarray(m)], axis=0)
    out = np.asarray(build_histogram_slots(
        jnp.asarray(xb), jnp.asarray(slot), vals, num_bins=b, n_slots=s,
        interpret=True))
    assert out.shape == (s, f, b, 3)
    for si in range(s):
        msk = m * (slot == si)
        ref = np.asarray(build_histogram(
            jnp.asarray(xb), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(msk), num_bins=b, impl="scatter"))
        np.testing.assert_allclose(out[si], ref, rtol=1e-4, atol=1e-3)


def test_slot_kernel_sentinel_rows_skip_and_match():
    """slot = -1 rows contribute nothing (match no one-hot), and a row
    tile that is ALL -1 skips its compute body (pl.when) — results must
    equal the reference computed over the active prefix only."""
    import jax.numpy as jnp
    from lightgbm_tpu.core.histogram import build_histogram
    from lightgbm_tpu.core.histogram_pallas import build_histogram_slots
    r = np.random.RandomState(33)
    n, f, b, s = 6000, 4, 64, 4
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = np.abs(r.randn(n)).astype(np.float32)
    # actives at the front, as a frontier wave's -1 slots leave them once
    # most leaves are done; the tail spans multiple whole row tiles of -1
    n_active = 1500
    slot = np.full(n, -1, np.int32)
    slot[:n_active] = r.randint(0, s, n_active)
    m = np.zeros(n, np.float32)
    m[:n_active] = 1.0
    vals = jnp.stack([jnp.asarray(g * m), jnp.asarray(h * m),
                      jnp.asarray(m)], axis=0)
    out = np.asarray(build_histogram_slots(
        jnp.asarray(xb), jnp.asarray(slot), vals, num_bins=b, n_slots=s,
        interpret=True))
    for si in range(s):
        msk = (slot == si).astype(np.float32)
        ref = np.asarray(build_histogram(
            jnp.asarray(xb), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(msk), num_bins=b, impl="scatter"))
        np.testing.assert_allclose(out[si], ref, rtol=1e-4, atol=1e-3)


def _frontier_ref(xb, slot, g, h, mask, b, k):
    """Per-slot numpy reference for the frontier builder."""
    n, f = xb.shape
    out = np.zeros((k, f, b, 3), np.float64)
    for i in range(n):
        s = slot[i]
        if s < 0 or mask[i] == 0:
            continue
        for j in range(f):
            out[s, j, xb[i, j], 0] += g[i]
            out[s, j, xb[i, j], 1] += h[i]
            out[s, j, xb[i, j], 2] += mask[i]
    return out


def _frontier_data(seed=41, n=4000, f=6, b=64, k=5):
    """Random binned data with bundled/default-bin-shaped columns: column
    0 is ~90% one default bin (the EFB bundle shape — most rows carry no
    value), column 1 is a narrow 2-bin indicator."""
    r = np.random.RandomState(seed)
    xb = r.randint(0, b, (n, f)).astype(np.uint8)
    default_rows = r.rand(n) < 0.9
    xb[default_rows, 0] = 7                     # the bundle's default bin
    xb[:, 1] = r.randint(0, 2, n)               # near-empty value range
    g = r.randn(n).astype(np.float32)
    h = np.abs(r.randn(n)).astype(np.float32)
    mask = (r.rand(n) < 0.8).astype(np.float32)
    slot = r.randint(-1, k, n).astype(np.int32)  # -1 = inactive rows
    return xb, slot, g, h, mask


FRONTIER_IMPLS = ["matmul", "scatter", "pallas_interpret"]


@pytest.mark.parametrize("impl", FRONTIER_IMPLS)
def test_frontier_builder_matches_reference(impl):
    """Cross-impl equivalence property (ISSUE 2 satellite): every
    spelling of the frontier builder agrees with a per-slot reference
    loop to fp32 tolerance, including bundled/default-bin columns and
    slot = -1 (inactive) rows."""
    from lightgbm_tpu.core.histogram import build_histogram_frontier
    b, k = 64, 5
    xb, slot, g, h, mask = _frontier_data(b=b, k=k)
    out = np.asarray(build_histogram_frontier(
        jnp.asarray(xb), jnp.asarray(slot), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(mask), num_bins=b, num_slots=k, impl=impl))
    assert out.shape == (k, xb.shape[1], b, 3)
    ref = _frontier_ref(xb, slot, g, h, mask, b, k)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-3)


def test_frontier_builder_cross_impl_agreement():
    """matmul vs scatter vs pallas(.interpret) agree with each other (and
    with per-slot build_histogram masks) to fp32 tolerance."""
    from lightgbm_tpu.core.histogram import build_histogram_frontier
    b, k = 64, 5
    xb, slot, g, h, mask = _frontier_data(seed=42, b=b, k=k)
    outs = {impl: np.asarray(build_histogram_frontier(
        jnp.asarray(xb), jnp.asarray(slot), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(mask), num_bins=b, num_slots=k, impl=impl))
        for impl in FRONTIER_IMPLS}
    for impl in FRONTIER_IMPLS[1:]:
        np.testing.assert_allclose(outs[impl], outs["matmul"],
                                   rtol=1e-4, atol=1e-3)
    # and against the single-leaf builder, one mask per slot
    for si in range(k):
        msk = mask * (slot == si)
        ref = np.asarray(build_histogram(
            jnp.asarray(xb), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(msk.astype(np.float32)), num_bins=b,
            impl="scatter"))
        np.testing.assert_allclose(outs["scatter"][si], ref,
                                   rtol=1e-4, atol=1e-3)


def test_frontier_builder_chunked_equals_unchunked():
    """The lax.scan row-chunked matmul path must equal the one-shot
    path (same slots, same totals)."""
    from lightgbm_tpu.core.histogram import build_histogram_frontier
    b, k = 32, 4
    xb, slot, g, h, mask = _frontier_data(seed=43, n=5000, b=b, k=k)
    a1 = np.asarray(build_histogram_frontier(
        jnp.asarray(xb), jnp.asarray(slot), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(mask), num_bins=b, num_slots=k, row_chunk=1024,
        impl="matmul"))
    a2 = np.asarray(build_histogram_frontier(
        jnp.asarray(xb), jnp.asarray(slot), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(mask), num_bins=b, num_slots=k, row_chunk=100000,
        impl="matmul"))
    np.testing.assert_allclose(a1, a2, rtol=1e-3, atol=1e-2)
