"""Split-search parity vs a brute-force scan (reference semantics:
feature_histogram.hpp:83-271,443-499)."""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core.split import (FeatureMeta, SplitParams,
                                     MISSING_NAN, MISSING_NONE, MISSING_ZERO,
                                     calculate_leaf_output,
                                     find_best_split_numerical,
                                     leaf_split_gain)


def _params(**kw):
    d = dict(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
             min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3,
             min_gain_to_split=0.0, max_cat_threshold=32, cat_smooth=10.0,
             cat_l2=10.0, max_cat_to_onehot=4, min_data_per_group=100)
    d.update(kw)
    return SplitParams(**d)


def _meta(num_bins, missing=None, default_bin=None, is_cat=None):
    f = len(num_bins)
    return FeatureMeta(
        num_bin=jnp.asarray(num_bins, jnp.int32),
        missing_type=jnp.asarray(missing if missing is not None
                                 else [MISSING_NONE] * f, jnp.int32),
        default_bin=jnp.asarray(default_bin if default_bin is not None
                                else [0] * f, jnp.int32),
        is_categorical=jnp.asarray(is_cat if is_cat is not None
                                   else [False] * f, bool),
        penalty=jnp.ones((f,), jnp.float32),
        monotone=jnp.zeros((f,), jnp.int32))


def _brute_force_best(hist, num_bin, p, sum_g, sum_h, cnt):
    """Simple one-direction scan (no missing handling) for MISSING_NONE."""
    best = (-np.inf, -1, -1)
    gain_shift = float(leaf_split_gain(sum_g, sum_h, p.lambda_l1, p.lambda_l2,
                                       p.max_delta_step))
    for fidx in range(hist.shape[0]):
        lg = lh = lc = 0.0
        for t in range(num_bin[fidx] - 1):
            lg += hist[fidx, t, 0]
            lh += hist[fidx, t, 1]
            lc += hist[fidx, t, 2]
            rg, rh, rc = sum_g - lg, sum_h - lh, cnt - lc
            if lc < p.min_data_in_leaf or rc < p.min_data_in_leaf:
                continue
            if lh < p.min_sum_hessian_in_leaf or rh < p.min_sum_hessian_in_leaf:
                continue
            gain = lg * lg / (lh + p.lambda_l2) + rg * rg / (rh + p.lambda_l2)
            if gain - gain_shift > best[0]:
                best = (gain - gain_shift, fidx, t)
    return best


def test_numerical_split_matches_bruteforce():
    r = np.random.RandomState(0)
    f, b = 5, 16
    num_bin = [16, 12, 16, 8, 16]
    hist = np.zeros((f, b, 3), np.float32)
    for j in range(f):
        nb = num_bin[j]
        hist[j, :nb, 2] = r.randint(5, 50, nb)
        hist[j, :nb, 0] = r.randn(nb) * hist[j, :nb, 2]
        hist[j, :nb, 1] = hist[j, :nb, 2] * (0.5 + 0.5 * r.rand(nb))
    # make totals consistent across features
    hist[:, :, 0] *= 0
    base_g = r.randn(b)
    for j in range(f):
        nb = num_bin[j]
        w = hist[j, :nb, 2]
        hist[j, :nb, 0] = base_g[:nb] * w * (1 + 0.1 * j)
    # totals must agree per feature; recompute per-feature and use feature 0's
    sums = hist.sum(axis=1)
    # normalize: scale each feature's grad/hess/count to match feature 0
    for j in range(1, f):
        for k in range(3):
            if sums[j, k] != 0:
                hist[j, :, k] *= sums[0, k] / sums[j, k]
    sum_g, sum_h, cnt = [float(x) for x in hist[0].sum(axis=0)]

    p = _params()
    meta = _meta(num_bin)
    bs = find_best_split_numerical(
        jnp.asarray(hist), meta, p, jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.float32(cnt), jnp.ones((f,), bool))
    bg, bf, bt = _brute_force_best(hist, num_bin, p, sum_g, sum_h, cnt)
    assert int(bs.feature) == bf
    assert int(bs.threshold) == bt
    np.testing.assert_allclose(float(bs.gain), bg, rtol=1e-4, atol=1e-4)


def test_split_outputs_match_leaf_output_formula():
    r = np.random.RandomState(1)
    f, b = 3, 8
    hist = np.abs(r.rand(f, b, 3).astype(np.float32)) + 0.1
    hist[:, :, 0] = r.randn(f, b)
    hist[:, :, 2] = 10
    # consistent totals
    s = hist[0].sum(0)
    for j in range(1, f):
        sj = hist[j].sum(0)
        hist[j] *= (s / sj)[None, :]
    sum_g, sum_h, cnt = [float(x) for x in s]
    p = _params(lambda_l1=0.5, lambda_l2=2.0)
    meta = _meta([b] * f)
    bs = find_best_split_numerical(
        jnp.asarray(hist), meta, p, jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.float32(cnt), jnp.ones((f,), bool))
    lo = calculate_leaf_output(bs.left_sum_grad, bs.left_sum_hess, 0.5, 2.0, 0.0)
    np.testing.assert_allclose(float(bs.left_output), float(lo), rtol=1e-4)


def test_min_data_in_leaf_blocks_split():
    f, b = 1, 4
    hist = np.zeros((f, b, 3), np.float32)
    hist[0, :, 2] = [5, 5, 5, 5]
    hist[0, :, 0] = [-10, -10, 10, 10]
    hist[0, :, 1] = [5, 5, 5, 5]
    p = _params(min_data_in_leaf=100)
    meta = _meta([b])
    bs = find_best_split_numerical(
        jnp.asarray(hist), meta, p, jnp.float32(0.0), jnp.float32(20.0),
        jnp.float32(20.0), jnp.ones((f,), bool))
    assert not np.isfinite(float(bs.gain))


def test_min_gain_to_split_filters():
    f, b = 1, 4
    hist = np.zeros((f, b, 3), np.float32)
    hist[0, :, 2] = [5, 5, 5, 5]
    hist[0, :, 0] = [-1e-3, 0, 0, 1e-3]
    hist[0, :, 1] = [5, 5, 5, 5]
    p = _params(min_gain_to_split=10.0)
    meta = _meta([b])
    bs = find_best_split_numerical(
        jnp.asarray(hist), meta, p, jnp.float32(0.0), jnp.float32(20.0),
        jnp.float32(20.0), jnp.ones((f,), bool))
    assert not np.isfinite(float(bs.gain))


def test_missing_nan_two_direction_scan():
    """With a NaN bin, the scan must consider sending missing either way."""
    f, b = 1, 6
    # numeric bins 0..4, NaN bin 5; strong negative grads on NaN rows
    hist = np.zeros((f, b, 3), np.float32)
    hist[0, :, 2] = [10, 10, 10, 10, 10, 30]
    hist[0, :, 0] = [1, 1, 1, 1, 1, -30]
    hist[0, :, 1] = hist[0, :, 2] * 0.25
    sum_g = float(hist[0, :, 0].sum())
    sum_h = float(hist[0, :, 1].sum())
    cnt = float(hist[0, :, 2].sum())
    p = _params()
    meta = _meta([b], missing=[MISSING_NAN])
    bs = find_best_split_numerical(
        jnp.asarray(hist), meta, p, jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.float32(cnt), jnp.ones((f,), bool))
    assert np.isfinite(float(bs.gain))
    # NaN rows (big negative grad → positive output) should be separable:
    # either default_left with NaN on one side, or threshold at top numeric bin
    left_has_nan = bool(bs.default_left)
    if left_has_nan:
        assert float(bs.left_sum_grad) < 0
    else:
        assert float(bs.right_sum_grad) < 0


def test_feature_mask_excludes_features():
    r = np.random.RandomState(5)
    f, b = 4, 8
    hist = np.abs(r.rand(f, b, 3).astype(np.float32))
    hist[:, :, 0] = r.randn(f, b) * 10
    s = hist[0].sum(0)
    for j in range(1, f):
        hist[j] *= (s / hist[j].sum(0))[None, :]
    p = _params()
    meta = _meta([b] * f)
    mask = np.array([True, False, True, False])
    bs = find_best_split_numerical(
        jnp.asarray(hist), meta, p, jnp.float32(float(s[0])),
        jnp.float32(float(s[1])), jnp.float32(float(s[2])), jnp.asarray(mask))
    assert int(bs.feature) in (0, 2)


def test_a_small_child_is_summed_from_its_bins_not_from_the_leaf_total():
    """float32 sums of one leaf taken in two orders disagree by more than
    a 25-row child holds, once one bin carries most of the rows: the
    leaf's total handed down from its parent's scan against the bins of
    its own histogram. A child priced as total minus the other side then
    wins with a gain it does not have (seen on click-log-shaped data at
    400k rows: a split of reference gain 0.001 recorded at 143.7)."""
    nb = 6                               # five numeric bins and the NaN bin
    hist = np.zeros((1, 8, 3), np.float32)
    hist[0, 0] = (-4.0, 2000.0, 60000)   # the zeros: most of the rows
    hist[0, 1] = (-12.0, 100.0, 3000)    # the real split is 0,1 | 2,3,4
    hist[0, 2] = (5.0, 100.0, 3000)
    hist[0, 3] = (6.0, 100.0, 3000)
    hist[0, 4] = (4.7, 100.0, 3000)
    hist[0, 5] = (0.3, 0.8, 25)          # a few NaN rows, nothing to gain
    sums = hist[0, :nb].sum(axis=0)
    # the total as float32 would hand it down: off by under 0.1%
    total_g, total_h = sums[0] + 2.0, sums[1] - 0.7
    bs = find_best_split_numerical(
        jnp.asarray(hist), _meta([nb], missing=[MISSING_NAN]),
        _params(min_data_in_leaf=20), jnp.float32(total_g),
        jnp.float32(total_h), jnp.float32(sums[2]), jnp.ones((1,), bool))
    assert int(bs.threshold) == 1, (int(bs.threshold), float(bs.gain))
    assert not bool(bs.default_left)     # the NaN rows' sum leans right
    # both children hold what their bins hold
    left = hist[0, :2].sum(axis=0)
    right = hist[0, 2:6].sum(axis=0)
    np.testing.assert_allclose(
        [bs.left_sum_grad, bs.left_sum_hess, bs.left_count], left, rtol=1e-6)
    np.testing.assert_allclose(
        [bs.right_sum_grad, bs.right_sum_hess, bs.right_count], right,
        rtol=1e-6)
