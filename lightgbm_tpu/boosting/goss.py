"""GOSS boosting (Gradient-based One-Side Sampling).

TPU-native re-design of src/boosting/goss.hpp. The sampling runs on device
inside the jitted iteration. Like the reference, it is off for the first
``1 / learning_rate`` iterations (goss.hpp Bagging :137-140). After them
every iteration draws a BAG (goss.hpp BaggingHelper :87-135):

- the ``top_cnt = int(N * top_rate)`` rows of largest sum-over-classes
  |grad * hess| at weight 1, ties at the threshold broken by row id (the
  lower id first, as ``lax.top_k`` orders equal values);
- exactly ``other_cnt = int(N * other_rate)`` of the rest, drawn uniformly
  without replacement, their grad/hess amplified by
  ``(N - top_cnt) / other_cnt``.

Both selections are ``top_k_mask``: the k-th largest key found bit by bit
in 32 counting passes, no sort (PERF.md section 6, PR 33: at 26.6M rows a
``lax.top_k`` or a full sort costs a multiple of it). The draw of the rest
is the same selection over one random 32-bit key a row, from the
``bagging_seed`` chain.

Where the exact grower runs over the row partition on one device
(``GBDT._goss_bag``) the bag IS the partition: ``sample_bag``'s rows go to
the front of ``order`` (partition.bag_partition), the histogram passes, the
smaller-child choice and the tree's counts see the bag's rows only, and the
rows out of the bag are routed in row space (partition.route_in_row_space)
and still get the tree's score. The unsampled and the sampled iterations are
then two device programs, and the first is ``boosting=gbdt``'s. Everywhere
else (vmapped multiclass, the ``batched`` / ``frontier`` growers, streaming,
every mesh learner, CEGB) the sampler stays a multiplier on grad, hess and
the sample mask under a ``lax.cond``: ``lax.top_k`` for the threshold, a
Bernoulli draw at ``other_cnt / (N - top_cnt)`` for the rest.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..config import Config
from ..log import LightGBMError
from .gbdt import GBDT


def _kth_largest(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest of uint32 ``keys``: the largest v with at least k
    keys >= v, built from the top bit down in 32 counting passes."""
    def body(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        cnt = jnp.sum((keys >= cand).astype(jnp.int32), dtype=jnp.int32)
        return jnp.where(cnt >= k, cand, prefix)
    return lax.fori_loop(0, 32, body, jnp.uint32(0))


def top_k_mask(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """bool [N], true at exactly the ``k`` largest uint32 ``keys``; among
    keys equal to the k-th the lower row ids win."""
    thr = _kth_largest(keys, k)
    above = keys > thr
    tie = keys == thr
    need = k - jnp.sum(above.astype(jnp.int32), dtype=jnp.int32)
    rank = jnp.cumsum(tie.astype(jnp.int32), dtype=jnp.int32)
    return above | (tie & (rank <= need))


def bag_counts(num_data: int, top_rate: float,
               other_rate: float) -> Tuple[int, int, float]:
    """(top_cnt, other_cnt, the others' multiplier) — upstream's counts;
    ``other_cnt`` never exceeds the rows the top leaves."""
    top_cnt = max(1, int(num_data * top_rate))
    other_cnt = max(1, min(int(num_data * other_rate), num_data - top_cnt))
    return top_cnt, other_cnt, float(num_data - top_cnt) / other_cnt


# a row's place in a bag, as sample_bag codes it
OUT_OF_BAG, BAG_TOP, BAG_OTHER = 0, 1, 2


def sample_bag(gh: jnp.ndarray, key: jnp.ndarray, top_cnt: int,
               other_cnt: int) -> jnp.ndarray:
    """One iteration's bag as a uint8 code a row: BAG_TOP at the
    ``top_cnt`` largest ``gh`` (>= 0, float32), BAG_OTHER at exactly
    ``other_cnt`` of the rest, OUT_OF_BAG elsewhere."""
    # a non-negative float32 orders as its bits do
    is_top = top_k_mask(lax.bitcast_convert_type(gh, jnp.uint32), top_cnt)
    # the rest draw a key with its low bit set: a top row's 0 is never
    # among the other_cnt largest while other_cnt <= N - top_cnt
    draw = jnp.where(is_top, jnp.uint32(0),
                     jax.random.bits(key, gh.shape, jnp.uint32)
                     | jnp.uint32(1))
    is_other = top_k_mask(draw, other_cnt)
    return jnp.where(is_top, BAG_TOP,
                     jnp.where(is_other, BAG_OTHER, OUT_OF_BAG)) \
        .astype(jnp.uint8)


class GOSS(GBDT):
    boosting_type = "goss"

    def __init__(self, config: Config, train_data, objective, metrics=None):
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            raise LightGBMError("Cannot use bagging in GOSS")
        if not (config.top_rate > 0.0 and config.other_rate > 0.0):
            raise LightGBMError("GOSS needs top_rate > 0 and other_rate > 0")
        self._goss_activated_logged = False
        super().__init__(config, train_data, objective, metrics)

    def _goss_warmup(self) -> int:
        return int(1.0 / max(self.config.learning_rate, 1e-12))

    def _goss_active(self, iter_idx: int) -> float:
        active = iter_idx >= self._goss_warmup()
        if active and not self._goss_activated_logged:
            # one obs event at the warmup->sampling transition — bagging
            # semantics change here, worth a mark on the event stream
            self._goss_activated_logged = True
            self.obs.event("goss_sampling_active", iteration=iter_idx,
                           warmup_iters=self._goss_warmup())
        return 1.0 if active else 0.0
