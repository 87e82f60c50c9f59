"""Frontier-wave parallel tree learners as compiled collective schedules.

The frontier grower (core/grow_frontier.py) has exactly three collective
seams per tree: the root reduction, the once-per-wave reduction of the
``[K, C, B, 3]`` smaller-child histogram tensor, and the per-wave best-split
search over the 2K children. This module packages the reference's parallel
learners (parallel_tree_learner.h) as interchangeable implementations of
those seams, selected by ``tree_learner``:

- **serial** (:class:`PsumLearner`): the PR 2 schedule — one ``psum`` of the
  full histogram tensor per wave, every device searches all features. Emits
  byte-for-byte the ops the grower always emitted, so the serial-path jaxpr
  fingerprints in ANALYSIS_BASELINE.json are unchanged.
- **data** (:class:`DataRSLearner`, data_parallel_tree_learner.cpp:146-161):
  ``psum_scatter`` (tiled reduce-scatter) over the feature axis replaces the
  wave psum — device ``d`` receives the fully-reduced histograms of feature
  block ``[d*fs, (d+1)*fs)`` only, scans best splits for just that shard,
  and ONE small all_gather of packed per-slot best-split records elects the
  global winners (SyncUpGlobalBestSplit, parallel_tree_learner.h:186-230).
  Per-wave comm drops from ``K*F*B*3`` psum'd floats to ``K*F*B*3/P``
  scattered + ``P*K*R`` gathered record floats (R ~ 21), and the sibling-
  subtraction hist pool shrinks to its feature shard (~1/P memory).
- **voting** (:class:`VotingLearner`, PV-Tree,
  voting_parallel_tree_learner.cpp:166-360): histograms stay LOCAL. Each
  device nominates its local top-k features per slot from local-histogram
  gains, two tiny int32 all_gathers elect <=2k global candidates by vote,
  and one psum exchanges ONLY the elected columns — ``K*2k*B*3`` floats per
  wave, independent of the total feature count. The split search then runs
  on the candidate histograms with GLOBAL leaf totals, so elected gains are
  exact; the approximation is only in which candidates stand (PAPER.md /
  arXiv:1706.08359 analysis). With ``top_k >= F`` every feature is elected
  and the learner degenerates to the exact data-parallel search.

Tie-break contract: find_best_split's argmax takes the FIRST maximum
(lowest feature index). DataRSLearner preserves it exactly because feature
blocks are contiguous in rank order: the cross-device argmax takes the
lowest rank among gain-maximal records, whose local search already took the
lowest local index — composing to the lowest global feature index.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.split import BestSplit, find_best_split, per_feature_split_merged

# f32 lanes in a packed BestSplit record: gain, feature, threshold,
# default_left, 6 child sums, 2 child outputs, is_categorical, 8 bitset words
RECORD_LANES = 21


def pack_best_record(bs: BestSplit) -> jnp.ndarray:
    """Flatten a batched BestSplit (fields ``[K]``/``[K, 8]``) into one
    ``[K, RECORD_LANES]`` f32 tensor so the election needs a single
    all_gather. Lane 0 is the gain (the argmax key); int/uint fields are
    BITCAST to f32 (lossless round-trip), bools value-cast (0.0/1.0)."""

    def lanes(v):
        v2 = v.reshape(v.shape[0], -1)
        if v2.dtype == jnp.bool_:
            return v2.astype(jnp.float32)
        if v2.dtype in (jnp.int32, jnp.uint32):
            return lax.bitcast_convert_type(v2, jnp.float32)
        return v2.astype(jnp.float32)

    rec = jnp.concatenate([lanes(v) for v in bs], axis=1)
    assert rec.shape[1] == RECORD_LANES, rec.shape
    return rec


def unpack_best_record(rec: jnp.ndarray) -> BestSplit:
    """Inverse of :func:`pack_best_record` (``[K, RECORD_LANES]`` f32)."""
    i32 = lambda c: lax.bitcast_convert_type(rec[:, c], jnp.int32)
    return BestSplit(
        gain=rec[:, 0],
        feature=i32(1),
        threshold=i32(2),
        default_left=rec[:, 3] > 0.5,
        left_sum_grad=rec[:, 4],
        left_sum_hess=rec[:, 5],
        left_count=rec[:, 6],
        right_sum_grad=rec[:, 7],
        right_sum_hess=rec[:, 8],
        right_count=rec[:, 9],
        left_output=rec[:, 10],
        right_output=rec[:, 11],
        is_categorical=rec[:, 12] > 0.5,
        cat_bitset=lax.bitcast_convert_type(rec[:, 13:21], jnp.uint32))


def elect_best_records(bs: BestSplit, axis_name: str) -> BestSplit:
    """Per-slot global best-split election: one all_gather of the packed
    ``[K, R]`` records, then a per-slot argmax on the gain lane. The first
    maximum wins, i.e. the lowest rank — see the module tie-break note."""
    rec = pack_best_record(bs)                         # [K, R]
    allrec = lax.all_gather(rec, axis_name)            # [D, K, R]
    winner = jnp.argmax(allrec[:, :, 0], axis=0)       # [K] lowest-rank max
    sel = jnp.take_along_axis(allrec, winner[None, :, None], axis=0)[0]
    return unpack_best_record(sel)


class PsumLearner:
    """The serial / one-psum-per-wave schedule (identical ops to the
    pre-learner grower; also the single-device no-op when axis_name=None)."""
    kind = "serial"
    varying_pool = False

    def __init__(self, psum: Callable, child_best: Callable):
        self._psum = psum
        self._child_best = child_best

    def reduce(self, hist):
        return self._psum(hist)

    def best_root(self, hist, sum_g, sum_h, cnt):
        return self._child_best(hist, sum_g, sum_h, cnt, -jnp.inf, jnp.inf)

    def best_children(self, ch_hist, sg, sh, cnt, mn, mx):
        return jax.vmap(self._child_best)(ch_hist, sg, sh, cnt, mn, mx)


class DataRSLearner:
    """Data-parallel with reduce-scattered wave histograms + packed
    best-record election. Requires C % P == 0 (gbdt pads features)."""
    kind = "data_rs"
    varying_pool = True

    def __init__(self, params, axis_name, meta, feature_mask):
        assert not params.with_efb, \
            "reduce-scatter learner is incompatible with EFB bundles"
        self.axis_name = axis_name
        self.params = params
        self.meta = meta
        self.feature_mask = feature_mask

    def reduce(self, hist):
        # tiled reduce-scatter over the feature axis: device d receives the
        # fully-summed block d (rank-ordered contiguous feature blocks)
        return lax.psum_scatter(hist, self.axis_name,
                                scatter_dimension=hist.ndim - 3, tiled=True)

    def _local(self, fs):
        """Slice meta/mask to this device's [base, base+fs) feature block."""
        base = lax.axis_index(self.axis_name).astype(jnp.int32) * fs
        sl = lambda a: (None if a is None
                        else lax.dynamic_slice_in_dim(a, base, fs, axis=0))
        return base, jax.tree.map(sl, self.meta), sl(self.feature_mask)

    def _search(self, hist_local, sum_g, sum_h, cnt, mn, mx,
                base, meta_l, fmask_l):
        p = self.params
        # a device's slice of the columns holds an unknown number of the
        # categorical ones: every local column through the finder
        bs = find_best_split(hist_local, meta_l, p.split, sum_g, sum_h, cnt,
                             fmask_l, min_constraint=mn, max_constraint=mx,
                             with_categorical=bool(p.with_categorical))
        return bs._replace(feature=base + bs.feature)

    def best_root(self, hist, sum_g, sum_h, cnt):
        base, meta_l, fmask_l = self._local(hist.shape[0])
        bs = self._search(hist, sum_g, sum_h, cnt, -jnp.inf, jnp.inf,
                          base, meta_l, fmask_l)
        bs1 = jax.tree.map(lambda a: a[None], bs)
        return jax.tree.map(lambda a: a[0],
                            elect_best_records(bs1, self.axis_name))

    def best_children(self, ch_hist, sg, sh, cnt, mn, mx):
        base, meta_l, fmask_l = self._local(ch_hist.shape[1])
        bs = jax.vmap(self._search, in_axes=(0,) * 6 + (None,) * 3)(
            ch_hist, sg, sh, cnt, mn, mx, base, meta_l, fmask_l)
        return elect_best_records(bs, self.axis_name)


class VotingLearner:
    """PV-Tree: local histograms, top-k vote election, exchange only the
    elected columns (the frontier-wave port of grow.py's voting_best)."""
    kind = "voting"
    varying_pool = True

    def __init__(self, params, axis_name, meta, feature_mask):
        assert not params.with_efb, \
            "voting learner is incompatible with EFB bundles"
        self.axis_name = axis_name
        self.params = params
        self.meta = meta
        self.feature_mask = feature_mask
        f = int(feature_mask.shape[0])
        self.k = min(params.voting_top_k, f)
        self.k2 = min(2 * params.voting_top_k, f)

    def reduce(self, hist):
        return hist      # histograms stay device-local; election reduces

    def _vote(self, ch_hist, sg, sh, cnt, mn, mx):
        """Batched election + exact search over [K, F, B, 3] LOCAL hists
        with GLOBAL totals sg/sh/cnt (fields [K])."""
        p, ax = self.params, self.axis_name
        f = self.feature_mask.shape[0]
        bdim = ch_hist.shape[2]
        # local leaf totals from the local histogram itself: every local
        # row lands in exactly one bin of feature 0
        lsg = jnp.sum(ch_hist[:, 0, :, 0], axis=1)
        lsh = jnp.sum(ch_hist[:, 0, :, 1], axis=1)
        lsc = jnp.sum(ch_hist[:, 0, :, 2], axis=1)

        def local_gains(h, g, hh, c):
            pf, _ = per_feature_split_merged(
                h, self.meta, p.split, g, hh, c, self.feature_mask,
                with_categorical=p.with_categorical)
            return pf.gain

        gains = jax.vmap(local_gains)(ch_hist, lsg, lsh, lsc)     # [K, F]
        top_gain, top_idx = lax.top_k(gains, self.k)              # [K, k]
        w = jnp.isfinite(top_gain).astype(jnp.int32)  # real proposals only
        all_idx = jnp.moveaxis(lax.all_gather(top_idx, ax), 0, 1)
        all_w = jnp.moveaxis(lax.all_gather(w, ax), 0, 1)         # [K, D, k]
        kk = all_idx.shape[0]
        votes = jax.vmap(
            lambda i, v: jnp.zeros((f,), jnp.int32).at[i].add(v))(
                all_idx.reshape(kk, -1), all_w.reshape(kk, -1))   # [K, F]
        elected = lax.top_k(votes, self.k2)[1]                    # [K, k2]
        # THE wave exchange: only the elected columns cross the mesh
        cand = lax.psum(jnp.take_along_axis(
            ch_hist, elected[:, :, None, None], axis=1), ax)  # [K, k2, B, 3]
        gh = jax.vmap(lambda e, c: jnp.zeros(
            (f, bdim, 3), jnp.float32).at[e].set(c))(elected, cand)
        cand_mask = jax.vmap(
            lambda e: jnp.zeros((f,), bool).at[e].set(True))(elected)

        def search(h, m, g, hh, c, lo, hi):
            return find_best_split(h, self.meta, p.split, g, hh, c,
                                   self.feature_mask & m, min_constraint=lo,
                                   max_constraint=hi,
                                   with_categorical=p.with_categorical)

        # elected/votes are all_gather-derived (replicated), cand is psum'd
        # and the totals are global, so the result is replicated — no
        # sync_best_split needed
        return jax.vmap(search)(gh, cand_mask, sg, sh, cnt, mn, mx)

    def best_root(self, hist, sum_g, sum_h, cnt):
        one = lambda v: jnp.asarray(v)[None]
        bs = self._vote(hist[None], one(sum_g), one(sum_h), one(cnt),
                        one(-jnp.inf), one(jnp.inf))
        return jax.tree.map(lambda a: a[0], bs)

    def best_children(self, ch_hist, sg, sh, cnt, mn, mx):
        return self._vote(ch_hist, sg, sh, cnt, mn, mx)


def make_frontier_learner(params, axis_name: Optional[str], meta,
                          feature_mask, psum: Callable,
                          child_best: Callable):
    """Select the wave-collective schedule for grow_tree_frontier.

    ``psum``/``child_best`` are the grower's own closures; PsumLearner uses
    them verbatim so the serial path's compiled program never changes."""
    if axis_name is not None and params.voting_top_k > 0:
        return VotingLearner(params, axis_name, meta, feature_mask)
    if axis_name is not None and params.frontier_rs:
        return DataRSLearner(params, axis_name, meta, feature_mask)
    return PsumLearner(psum, child_best)
