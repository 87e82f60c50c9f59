"""Best-split search over histograms.

TPU-native re-design of FeatureHistogram::FindBestThreshold*
(src/treelearner/feature_histogram.hpp:83-271, 443-643). The reference scans
bins sequentially per feature on one CPU thread; here every (feature, bin)
candidate is evaluated simultaneously as a prefix-scan over the bin axis —
bins are <=256 so the whole candidate tensor is tiny and the two missing-value
directions become two masked cumulative sums instead of two loops.

Semantics preserved exactly:
- gain math with L1 soft-threshold, L2, max_delta_step
  (ThresholdL1 / CalculateSplittedLeafOutput / GetLeafSplitGainGivenOutput,
  feature_histogram.hpp:443-499);
- two-direction scan for missing defaults: missing-left (dir=-1) first, the
  missing-right (dir=+1) candidate replaces it only on strictly greater gain;
- MissingType::Zero skips the default (zero) bin in both accumulations;
  MissingType::NaN keeps the NaN bin (last) with the defaulted side;
- tie-breaks: dir=-1 keeps the highest threshold, dir=+1 the lowest;
- validity: min_data_in_leaf / min_sum_hessian_in_leaf on both sides,
  gain strictly > parent gain + min_gain_to_split;
- monotone constraints reject splits with wrong output ordering and clamp
  leaf outputs to [min_constraint, max_constraint].
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf

# MissingType codes (bin.h:22-26)
MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class FeatureMeta(NamedTuple):
    """Per-feature metadata as device arrays (FeatureMetainfo analog)."""
    num_bin: jnp.ndarray        # [F] int32 (includes NaN bin when present)
    missing_type: jnp.ndarray   # [F] int32
    default_bin: jnp.ndarray    # [F] int32
    is_categorical: jnp.ndarray  # [F] bool
    penalty: jnp.ndarray        # [F] f32 feature_contri multiplier
    monotone: jnp.ndarray       # [F] int32 (-1/0/+1, config.h monotone_constraints)
    # EFB storage layout (feature_group.h:35-50): which stored column the
    # feature lives in and at which bin offset; None = identity (no bundles)
    col: Optional[jnp.ndarray] = None       # [F] int32
    offset: Optional[jnp.ndarray] = None    # [F] int32
    bundled: Optional[jnp.ndarray] = None   # [F] bool
    # joint-coded pair packing (io/dataset.py _pack_small_pairs): feature
    # bin = (stored // pack_div) % pack_mod; pack_partner = the pair-mate's
    # bin count (marginalization width). div=1/mod=0 = unpacked.
    pack_div: Optional[jnp.ndarray] = None      # [F] int32
    pack_mod: Optional[jnp.ndarray] = None      # [F] int32
    pack_partner: Optional[jnp.ndarray] = None  # [F] int32


class SplitParams(NamedTuple):
    """Static split hyper-parameters (subset of Config used by gain math)."""
    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    # categorical
    max_cat_threshold: int
    cat_smooth: float
    cat_l2: float
    max_cat_to_onehot: int
    min_data_per_group: int


class BestSplit(NamedTuple):
    """SplitInfo analog (split_info.hpp:48-130) as arrays over leading dims."""
    gain: jnp.ndarray          # f32; -inf when unsplittable
    feature: jnp.ndarray       # int32, inner feature index
    threshold: jnp.ndarray     # int32 bin threshold (left: bin <= thr)
    default_left: jnp.ndarray  # bool
    left_sum_grad: jnp.ndarray
    left_sum_hess: jnp.ndarray
    left_count: jnp.ndarray    # f32 (histogram count channel)
    right_sum_grad: jnp.ndarray
    right_sum_hess: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray
    # categorical: bitset over bins going LEFT (one uint32 x 8 = 256 bins)
    is_categorical: jnp.ndarray  # bool
    cat_bitset: jnp.ndarray      # [..., 8] uint32


def threshold_l1(s, l1):
    """ThresholdL1 (feature_histogram.hpp:449-452)."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:454-462)."""
    ret = -threshold_l1(sum_grad, l1) / (sum_hess + l2)
    if max_delta_step > 0.0:
        ret = jnp.clip(ret, -max_delta_step, max_delta_step)
    return ret


def leaf_split_gain_given_output(sum_grad, sum_hess, l1, l2, output):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp:494-497)."""
    sg_l1 = threshold_l1(sum_grad, l1)
    return -(2.0 * sg_l1 * output + (sum_hess + l2) * output * output)


def leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step):
    """GetLeafSplitGain (feature_histogram.hpp:487-491)."""
    out = calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return leaf_split_gain_given_output(sum_grad, sum_hess, l1, l2, out)


def _split_gains(lg, lh, rg, rh, p: SplitParams, min_c, max_c, monotone):
    """GetSplitGains incl. monotone rejection (feature_histogram.hpp:465-478).

    Returns (gain, left_output, right_output); any broadcastable shapes.
    """
    lo = calculate_leaf_output(lg, lh, p.lambda_l1, p.lambda_l2, p.max_delta_step)
    ro = calculate_leaf_output(rg, rh, p.lambda_l1, p.lambda_l2, p.max_delta_step)
    lo = jnp.clip(lo, min_c, max_c)
    ro = jnp.clip(ro, min_c, max_c)
    bad = ((monotone > 0) & (lo > ro)) | ((monotone < 0) & (lo < ro))
    gain = (leaf_split_gain_given_output(lg, lh, p.lambda_l1, p.lambda_l2, lo)
            + leaf_split_gain_given_output(rg, rh, p.lambda_l1, p.lambda_l2, ro))
    return jnp.where(bad, 0.0, gain), lo, ro


class PerFeatureSplit(NamedTuple):
    """Best numerical split of every feature (pre-argmax), fields [F]."""
    gain: jnp.ndarray          # shifted, penalty-scaled gain; -inf unusable
    threshold: jnp.ndarray     # int32
    default_left: jnp.ndarray  # bool
    left_sum_grad: jnp.ndarray
    left_sum_hess: jnp.ndarray
    left_count: jnp.ndarray
    right_sum_grad: jnp.ndarray
    right_sum_hess: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray


def per_feature_split_numerical(
        hist: jnp.ndarray,          # [F, B, 3] (grad, hess, count)
        meta: FeatureMeta,
        params: SplitParams,
        sum_grad: jnp.ndarray,      # scalar leaf totals
        sum_hess: jnp.ndarray,
        num_data: jnp.ndarray,      # scalar f32 count
        feature_mask: jnp.ndarray,  # [F] bool (feature_fraction sampling)
        monotone: Optional[jnp.ndarray] = None,   # [F] int8
        min_constraint: float | jnp.ndarray = -jnp.inf,
        max_constraint: float | jnp.ndarray = jnp.inf,
) -> PerFeatureSplit:
    """Vectorized FindBestThresholdNumerical over all features at once.

    Candidate layout: threshold t means left = bins <= t. The missing-left
    scan (reference dir=-1) accumulates the right side from the top numeric
    bin; missing-right (dir=+1) accumulates the left side from bin 0. With a
    full dense histogram (no ``bias`` offset — we always store bin 0) both
    reduce to masked prefix sums.

    Also the voting-parallel learner's local scorer: PV-Tree votes on each
    rank's per-feature best gains (voting_parallel_tree_learner.cpp:322-342),
    which is exactly this function applied to a local histogram.
    """
    f, b, _ = hist.shape
    sum_hess = sum_hess + 2 * K_EPSILON
    if monotone is None:
        monotone = meta.monotone

    bins = jnp.arange(b, dtype=jnp.int32)[None, :]            # [1, B]
    num_bin = meta.num_bin[:, None]                            # [F, 1]
    has_nan_bin = (meta.missing_type[:, None] == MISSING_NAN)
    nb_numeric = num_bin - has_nan_bin.astype(jnp.int32)       # numeric bins
    in_numeric = bins < nb_numeric                             # [F, B]
    skip_default = (meta.missing_type[:, None] == MISSING_ZERO) & \
        (bins == meta.default_bin[:, None])

    # Both sides of every candidate are summed from the histogram's own
    # bins: the accumulated (numeric, non-default) bins as a prefix and a
    # suffix, and the bins the scan leaves out (the NaN bin, a skipped
    # default bin) added to the side the missing rows fall on. The
    # reference takes the far side as leaf total minus scanned side, in
    # double; in float32 the leaf's total and its bins, summed in
    # different orders, disagree by more than a small child holds once one
    # bin (zeros, NaN) carries most of the rows, and a 20-row child priced
    # by that difference wins splits it should not. The leaf totals set
    # the gain to beat and nothing else.
    acc = in_numeric & ~skip_default
    out = (bins < num_bin) & ~acc
    hg, hh, hc = hist[..., 0], hist[..., 1], hist[..., 2]
    g, h, c = (jnp.where(acc, a, 0.0) for a in (hg, hh, hc))
    og, oh, oc = (jnp.sum(jnp.where(out, a, 0.0), axis=1, keepdims=True)
                  for a in (hg, hh, hc))

    def suffix(a):   # sum of the bins above t
        above = jnp.cumsum(a[:, ::-1], axis=1)[:, ::-1]
        return jnp.concatenate([above[:, 1:], jnp.zeros_like(a[:, :1])], 1)

    pg, ph, pc = (jnp.cumsum(a, axis=1) for a in (g, h, c))   # bins <= t
    sg, sh, sc = suffix(g), suffix(h), suffix(c)

    gain_shift = leaf_split_gain(sum_grad, sum_hess, params.lambda_l1,
                                 params.lambda_l2, params.max_delta_step)
    min_gain_shift = gain_shift + params.min_gain_to_split

    def eval_candidates(lg, lh, lc, rg_, rh_, rc_):
        ok = ((lc >= params.min_data_in_leaf)
              & (rc_ >= params.min_data_in_leaf)
              & (lh >= params.min_sum_hessian_in_leaf)
              & (rh_ >= params.min_sum_hessian_in_leaf))
        gain, lo, ro = _split_gains(lg, lh, rg_, rh_, params,
                                    min_constraint, max_constraint,
                                    monotone[:, None])
        ok = ok & (gain > min_gain_shift)
        return jnp.where(ok, gain, K_MIN_SCORE), lo, ro

    # ---- missing-left scan (reference dir=-1, runs first) -----------------
    # threshold t: right = accumulated bins > t; left = accumulated bins
    # <= t and the left-out bins (default/NaN). valid: 0 .. nb_numeric-2
    lgL, lhL, lcL = pg + og, ph + oh + K_EPSILON, pc + oc
    rgL, rhL, rcL = sg, sh + K_EPSILON, sc
    gainL, loL, roL = eval_candidates(lgL, lhL, lcL, rgL, rhL, rcL)
    validL = (bins <= nb_numeric - 2) & (bins >= 0)
    # reference dir=-1 skips evaluating at scanned bin == default_bin,
    # i.e. threshold == default_bin - 1
    validL = validL & ~((meta.missing_type[:, None] == MISSING_ZERO)
                        & (bins == meta.default_bin[:, None] - 1))
    gainL = jnp.where(validL, gainL, K_MIN_SCORE)
    # tie-break: highest threshold wins -> argmax over reversed bins
    idxL = (b - 1) - jnp.argmax(gainL[:, ::-1], axis=1)       # [F]
    bestL = jnp.take_along_axis(gainL, idxL[:, None], 1)[:, 0]

    # ---- missing-right scan (reference dir=+1) ----------------------------
    # threshold t: left = accumulated bins <= t; right = the rest and the
    # left-out bins. valid thresholds: 0 .. nb_numeric-2, plus
    # nb_numeric-1 when NaN bin exists (split purely on missingness).
    lgR, lhR, lcR = pg, ph + K_EPSILON, pc
    rgR, rhR, rcR = sg + og, sh + oh + K_EPSILON, sc + oc
    gainR, loR, roR = eval_candidates(lgR, lhR, lcR, rgR, rhR, rcR)
    validR = (bins <= nb_numeric - 2 + has_nan_bin.astype(jnp.int32))
    validR = validR & ~((meta.missing_type[:, None] == MISSING_ZERO)
                        & (bins == meta.default_bin[:, None]))
    # only two-direction features run this scan (missing type != None and
    # num_bin > 2, feature_histogram.hpp:88-99)
    two_dir = (meta.missing_type[:, None] != MISSING_NONE) & (num_bin > 2)
    gainR = jnp.where(validR & two_dir, gainR, K_MIN_SCORE)
    idxR = jnp.argmax(gainR, axis=1)
    bestR = jnp.take_along_axis(gainR, idxR[:, None], 1)[:, 0]

    # dir=+1 replaces dir=-1 only on strictly greater gain
    use_right = bestR > bestL
    per_feat_gain = jnp.where(use_right, bestR, bestL)
    per_feat_thr = jnp.where(use_right, idxR, idxL).astype(jnp.int32)
    # default_left = (winning dir == -1); "fix direction error" for 2-bin NaN
    # features (feature_histogram.hpp:101-104)
    default_left = ~use_right
    fix2bin = (meta.missing_type == MISSING_NAN) & (meta.num_bin <= 2)
    default_left = jnp.where(fix2bin, False, default_left)

    take = lambda a, i: jnp.take_along_axis(a, i[:, None], 1)[:, 0]
    best = lambda aR, aL: jnp.where(use_right, take(aR, idxR), take(aL, idxL))

    # feature-level masks: sampled out, trivial, categorical handled elsewhere
    usable = feature_mask & ~meta.is_categorical & (meta.num_bin > 1)
    per_feat_gain = jnp.where(usable, per_feat_gain, K_MIN_SCORE)
    # feature penalty multiplies the (shifted) gain (FindBestThreshold :81)
    out_gain = (per_feat_gain - min_gain_shift) * meta.penalty

    return PerFeatureSplit(
        gain=out_gain,
        threshold=per_feat_thr,
        default_left=default_left,
        left_sum_grad=best(lgR, lgL),
        left_sum_hess=best(lhR, lhL) - K_EPSILON,   # strip the safety pad
        left_count=best(lcR, lcL),
        right_sum_grad=best(rgR, rgL),
        right_sum_hess=best(rhR, rhL) - K_EPSILON,
        right_count=best(rcR, rcL),
        left_output=best(loR, loL),
        right_output=best(roR, roL),
    )


def find_best_split_numerical(
        hist: jnp.ndarray, meta: FeatureMeta, params: SplitParams,
        sum_grad: jnp.ndarray, sum_hess: jnp.ndarray, num_data: jnp.ndarray,
        feature_mask: jnp.ndarray,
        monotone: Optional[jnp.ndarray] = None,
        min_constraint: float | jnp.ndarray = -jnp.inf,
        max_constraint: float | jnp.ndarray = jnp.inf,
) -> BestSplit:
    """ArgMax over per-feature best splits (SplitInfo selection,
    serial_tree_learner.cpp:506-591)."""
    pf = per_feature_split_numerical(
        hist, meta, params, sum_grad, sum_hess, num_data, feature_mask,
        monotone, min_constraint, max_constraint)
    best_f = jnp.argmax(pf.gain).astype(jnp.int32)
    sel = lambda a: a[best_f]
    gain = pf.gain[best_f]
    splittable = jnp.isfinite(gain)
    zeros8 = jnp.zeros((8,), dtype=jnp.uint32)
    return BestSplit(
        gain=jnp.where(splittable, gain, K_MIN_SCORE),
        feature=best_f,
        threshold=sel(pf.threshold),
        default_left=sel(pf.default_left),
        left_sum_grad=sel(pf.left_sum_grad),
        left_sum_hess=sel(pf.left_sum_hess),
        left_count=sel(pf.left_count),
        right_sum_grad=sel(pf.right_sum_grad),
        right_sum_hess=sel(pf.right_sum_hess),
        right_count=sel(pf.right_count),
        left_output=sel(pf.left_output),
        right_output=sel(pf.right_output),
        is_categorical=jnp.asarray(False),
        cat_bitset=zeros8,
    )


def _split_gains_l2(lg, lh, rg, rh, p: SplitParams, l2, min_c, max_c):
    """GetSplitGains with an explicit l2 (categorical adds cat_l2,
    feature_histogram.hpp:171)."""
    lo = calculate_leaf_output(lg, lh, p.lambda_l1, l2, p.max_delta_step)
    ro = calculate_leaf_output(rg, rh, p.lambda_l1, l2, p.max_delta_step)
    lo = jnp.clip(lo, min_c, max_c)
    ro = jnp.clip(ro, min_c, max_c)
    gain = (leaf_split_gain_given_output(lg, lh, p.lambda_l1, l2, lo)
            + leaf_split_gain_given_output(rg, rh, p.lambda_l1, l2, ro))
    return gain, lo, ro


def _bin_membership_bitset(member: jnp.ndarray) -> jnp.ndarray:
    """[B] bool -> [8] uint32 bitset over bin indices (SplitInfo
    cat_threshold as a fixed 256-bit set)."""
    b = member.shape[0]
    idx = jnp.arange(b, dtype=jnp.uint32)
    bits = member.astype(jnp.uint32) << (idx & 31)
    return jax.ops.segment_sum(bits, (idx >> 5).astype(jnp.int32),
                               num_segments=8).astype(jnp.uint32)


def per_feature_split_categorical(
        hist: jnp.ndarray,          # [F, B, 3]
        meta: FeatureMeta,
        params: SplitParams,
        sum_grad: jnp.ndarray,
        sum_hess: jnp.ndarray,
        num_data: jnp.ndarray,
        feature_mask: jnp.ndarray,
        min_constraint: float | jnp.ndarray = -jnp.inf,
        max_constraint: float | jnp.ndarray = jnp.inf,
) -> Tuple[PerFeatureSplit, jnp.ndarray]:
    """Vectorized FindBestThresholdCategorical
    (feature_histogram.hpp:110-271).

    Two candidate generators, selected per feature by
    ``num_bin <= max_cat_to_onehot``:

    - one-vs-rest: every real category bin t as left = {t};
    - sorted-subset: bins with count >= cat_smooth sorted by
      sum_grad/(sum_hess + cat_smooth); prefix scans from both ends, at most
      min(max_cat_threshold, (used+1)/2) categories, evaluating only when the
      accumulated group reaches min_data_per_group, with l2 += cat_l2.

    Bin 0 is this framework's catch-all (unseen categories / NaN,
    binning.py:_find_bin_categorical) and always stays on the right — the
    raw-value bitset could not express "unknown goes left" at predict time.

    Returns per-feature best splits plus [F, 8] uint32 bin-space bitsets of
    the categories going left.
    """
    f, b, _ = hist.shape
    sp = params
    sum_hess = sum_hess + 2 * K_EPSILON
    bins = jnp.arange(b, dtype=jnp.int32)

    gain_shift = leaf_split_gain(sum_grad, sum_hess, sp.lambda_l1,
                                 sp.lambda_l2, sp.max_delta_step)
    min_gain_shift = gain_shift + sp.min_gain_to_split
    l2_cat = sp.lambda_l2 + sp.cat_l2
    steps = min(b, max(int(sp.max_cat_threshold), 1))

    def one_feature(hist_f, num_bin):
        is_real = (bins >= 1) & (bins < num_bin)
        g = jnp.where(is_real, hist_f[:, 0], 0.0)
        h = jnp.where(is_real, hist_f[:, 1], 0.0)
        c = jnp.where(is_real, hist_f[:, 2], 0.0)

        # ---- one-vs-rest (use_onehot branch, :130-161) -------------------
        oh_g = sum_grad - g
        oh_h = sum_hess - h - K_EPSILON
        oh_c = num_data - c
        ok1 = (is_real & (c >= sp.min_data_in_leaf)
               & (h >= sp.min_sum_hessian_in_leaf)
               & (oh_c >= sp.min_data_in_leaf)
               & (oh_h >= sp.min_sum_hessian_in_leaf))
        gain1, lo1, ro1 = _split_gains_l2(
            g, h + K_EPSILON, oh_g, oh_h, sp, sp.lambda_l2,
            min_constraint, max_constraint)
        gain1 = jnp.where(ok1 & (gain1 > min_gain_shift), gain1, K_MIN_SCORE)
        t1 = jnp.argmax(gain1)
        onehot = dict(
            gain=gain1[t1], lg=g[t1], lh=h[t1], lc=c[t1],
            lo=lo1[t1], ro=ro1[t1], member=bins == t1)

        # ---- sorted-subset scan (:162-235) -------------------------------
        elig = is_real & (c >= sp.cat_smooth)
        n_elig = jnp.sum(elig.astype(jnp.int32))
        ctr = g / (h + sp.cat_smooth)
        max_num_cat = jnp.minimum(sp.max_cat_threshold, (n_elig + 1) // 2)

        def one_direction(key):
            # one stable sort carries the sums along (jnp.argsort's order
            # and tie-breaks); only the first ``steps`` sorted categories
            # can enter a subset (in_range), so nothing past them is read
            _, order, gs, hs, cs = jax.lax.sort(
                (key, bins, g, h, c), num_keys=1, is_stable=True)
            order, gs, hs, cs = (a[:steps] for a in (order, gs, hs, cs))
            pg = jnp.cumsum(gs)
            ph = jnp.cumsum(hs) + K_EPSILON
            pc = jnp.cumsum(cs)
            i = jnp.arange(steps, dtype=jnp.int32)
            in_range = (i < max_num_cat) & (i < n_elig)
            left_ok = (pc >= sp.min_data_in_leaf) \
                & (ph >= sp.min_sum_hessian_in_leaf)
            rc = num_data - pc
            rh = sum_hess - ph
            stop = (rc < sp.min_data_in_leaf) | (rc < sp.min_data_per_group) \
                | (rh < sp.min_sum_hessian_in_leaf)
            # `break` fires only when reached (left_ok passed), killing the
            # current position and everything after (:204-210)
            alive = jnp.cumsum((left_ok & stop).astype(jnp.int32)) == 0
            can = in_range & alive & left_ok

            def gstep(cnt_group, inp):
                cs_i, can_i = inp
                cnt_group = cnt_group + cs_i
                do_eval = can_i & (cnt_group >= sp.min_data_per_group)
                return jnp.where(do_eval, 0.0, cnt_group), do_eval

            # the group counter resets where it fires, so it is a chain:
            # unrolled, its ``steps`` links are one fused elementwise op
            _, do_eval = jax.lax.scan(gstep, jnp.asarray(0.0), (cs, can),
                                      unroll=True)
            gain2, lo2, ro2 = _split_gains_l2(
                pg, ph, sum_grad - pg, sum_hess - ph, sp, l2_cat,
                min_constraint, max_constraint)
            gain2 = jnp.where(do_eval & (gain2 > min_gain_shift), gain2,
                              K_MIN_SCORE)
            ib = jnp.argmax(gain2)
            # a bin goes left where it sorted at or before the best prefix
            member = jnp.any((order[None, :] == bins[:, None])
                             & (i[None, :] <= ib), axis=1) & elig
            return dict(gain=gain2[ib], lg=pg[ib], lh=ph[ib] - K_EPSILON,
                        lc=pc[ib], lo=lo2[ib], ro=ro2[ib], member=member)

        # both directions in one batched pass
        both = jax.vmap(one_direction)(jnp.stack(
            [jnp.where(elig, ctr, jnp.inf), jnp.where(elig, -ctr, jnp.inf)]))
        sorted_best = jax.tree.map(
            lambda a: jnp.where(both["gain"][0] >= both["gain"][1],
                                a[0], a[1]), both)

        use_onehot = num_bin <= sp.max_cat_to_onehot
        return jax.tree.map(
            lambda o, s_: jnp.where(use_onehot, o, s_), onehot, sorted_best)

    res = jax.vmap(one_feature)(hist, meta.num_bin)
    usable = feature_mask & meta.is_categorical & (meta.num_bin > 1)
    out_gain = jnp.where(usable & jnp.isfinite(res["gain"]),
                         (res["gain"] - min_gain_shift) * meta.penalty,
                         K_MIN_SCORE)
    bitsets = jax.vmap(_bin_membership_bitset)(res["member"])
    pf = PerFeatureSplit(
        gain=out_gain,
        threshold=jnp.zeros((f,), jnp.int32),
        default_left=jnp.zeros((f,), bool),
        left_sum_grad=res["lg"],
        left_sum_hess=res["lh"],
        left_count=res["lc"],
        right_sum_grad=sum_grad - res["lg"],
        right_sum_hess=sum_hess - res["lh"],
        right_count=num_data - res["lc"],
        left_output=res["lo"],
        right_output=res["ro"],
    )
    return pf, bitsets


def find_best_split(
        hist: jnp.ndarray, meta: FeatureMeta, params: SplitParams,
        sum_grad: jnp.ndarray, sum_hess: jnp.ndarray, num_data: jnp.ndarray,
        feature_mask: jnp.ndarray,
        min_constraint: float | jnp.ndarray = -jnp.inf,
        max_constraint: float | jnp.ndarray = jnp.inf,
        with_categorical: bool = False,
        gain_penalty: jnp.ndarray | None = None,
) -> BestSplit:
    """Best split over all features, numerical and (when the dataset has any)
    categorical — the per-leaf SplitInfo argmax
    (serial_tree_learner.cpp:506-591).

    ``gain_penalty`` [F] is subtracted from each feature's best gain before
    the argmax — the CEGB cost model (serial_tree_learner.cpp:533-539):
    penalized gains both rank candidates and become the recorded split gain,
    exactly as the reference mutates SplitInfo::gain in place.
    """
    pf, bitsets = per_feature_split_merged(
        hist, meta, params, sum_grad, sum_hess, num_data, feature_mask,
        min_constraint, max_constraint, with_categorical)
    if gain_penalty is not None:
        pf = pf._replace(gain=jnp.where(jnp.isfinite(pf.gain),
                                        pf.gain - gain_penalty, pf.gain))
    best_f = jnp.argmax(pf.gain).astype(jnp.int32)
    sel = lambda a: a[best_f]
    gain = pf.gain[best_f]
    splittable = jnp.isfinite(gain)
    return BestSplit(
        gain=jnp.where(splittable, gain, K_MIN_SCORE),
        feature=best_f,
        threshold=sel(pf.threshold),
        default_left=sel(pf.default_left),
        left_sum_grad=sel(pf.left_sum_grad),
        left_sum_hess=sel(pf.left_sum_hess),
        left_count=sel(pf.left_count),
        right_sum_grad=sel(pf.right_sum_grad),
        right_sum_hess=sel(pf.right_sum_hess),
        right_count=sel(pf.right_count),
        left_output=sel(pf.left_output),
        right_output=sel(pf.right_output),
        is_categorical=(meta.is_categorical[best_f] if with_categorical
                        else jnp.asarray(False)),
        cat_bitset=bitsets[best_f],
    )


def per_feature_split_merged(
        hist: jnp.ndarray, meta: FeatureMeta, params: SplitParams,
        sum_grad: jnp.ndarray, sum_hess: jnp.ndarray, num_data: jnp.ndarray,
        feature_mask: jnp.ndarray,
        min_constraint: float | jnp.ndarray = -jnp.inf,
        max_constraint: float | jnp.ndarray = jnp.inf,
        with_categorical: bool = False,
) -> Tuple[PerFeatureSplit, jnp.ndarray]:
    """Per-feature best splits, each feature using its own finder
    (FindBestThreshold dispatch, feature_histogram.hpp:68-108).

    ``with_categorical``: False or 0 = no categorical column; an int n =
    the data set's n categorical columns (static a data set; WHICH they
    are is read from ``meta``, an argument of the compiled block) are
    taken out and searched alone; True = the caller does not know the
    number (a device's share of the columns): every column goes through
    the categorical finder and the numerical ones are masked afterwards.
    """
    f = hist.shape[0]
    pf = per_feature_split_numerical(
        hist, meta, params, sum_grad, sum_hess, num_data, feature_mask,
        None, min_constraint, max_constraint)
    if not with_categorical:
        return pf, jnp.zeros((f, 8), jnp.uint32)
    is_cat = meta.is_categorical
    with jax.named_scope("lgbm.split_search_cat"):
        if with_categorical is True or with_categorical >= f:
            cols = jnp.arange(f, dtype=jnp.int32)
        else:
            cols = jnp.nonzero(is_cat, size=int(with_categorical),
                               fill_value=0)[0]
        meta_c = FeatureMeta(*[None if a is None else a[cols]
                               for a in meta])
        pfc, bits_c = per_feature_split_categorical(
            hist[cols], meta_c, params, sum_grad, sum_hess, num_data,
            feature_mask[cols], min_constraint, max_constraint)
        # a column taken that is not categorical keeps its numerical split
        took = is_cat[cols]
        merged = PerFeatureSplit(*[
            nv.at[cols].set(jnp.where(took, cv, nv[cols]))
            for nv, cv in zip(pf, pfc)])
        bitsets = jnp.zeros((f, 8), jnp.uint32).at[cols].set(
            jnp.where(took[:, None], bits_c, 0).astype(jnp.uint32))
    return merged, bitsets
