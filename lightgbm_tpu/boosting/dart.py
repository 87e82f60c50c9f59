"""DART boosting (Dropouts meet Multiple Additive Regression Trees).

TPU-native re-design of src/boosting/dart.hpp:40-205. Semantics preserved:
per-iteration drop set chosen by ``drop_rate`` (uniform or tree-weighted),
skipped entirely with probability ``skip_drop``, capped at ``max_drop``;
the new tree is trained against scores with the dropped trees removed and
shrunk by ``lr / (1 + k)`` (or ``lr / (lr + k)`` in xgboost mode); dropped
trees are then normalized by ``k / (k + 1)`` (xgboost: ``k / (lr + k)``) and
train/valid scores adjusted to match (dart.hpp Normalize :141-186).

The drop set is drawn on the host (upstream's rule; its draws come from a
``numpy.random.RandomState(drop_seed)``, not upstream's own generator). Its
trees leave the scores in ONE device program (core/replay.py: every dropped
tree replayed over every row from the feature-major copy of the bins, one
summed output) and come back in one more, that sum times the normalization
factor: every dropped tree is scaled by the same factor, so the one sum is
all the iteration keeps. Between the two, the iteration's tree grows as
``boosting=gbdt``'s does, in the same block program of one iteration.

On a TPU the replay is one Pallas kernel that reads the table once for the
whole set (replay.forest_total_kernel): 11.4 ms a dropped tree of 255
leaves at 26.6M rows x 67 columns on a v5e, where the XLA passes it
replaced took 40 (0.140 ms a node). A table with joint-coded pair,
categorical or EFB-bundled columns, and every validation set's row-major
table, keep the passes (PERF.md section 5).
"""
from __future__ import annotations

from typing import List

import numpy as np

from ..config import Config
from ..core import replay as replay_mod
from ..obs.trace import recorder
from .gbdt import GBDT


class DART(GBDT):
    boosting_type = "dart"
    FOREST_SCORES_SPAN = "train.dart_forest_scores"
    REPLAY_SCOPE = "lgbm.dart_replay"
    RESTORE_SCOPE = "lgbm.dart_restore"

    def __init__(self, config: Config, train_data, objective, metrics=None):
        super().__init__(config, train_data, objective, metrics)
        self._drop_rng = np.random.RandomState(config.drop_seed)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        # the next drop set may hold the newest tree, which has to be on
        # the host by then: flush each iteration (one tree's arrays)
        self._flush_every = 1
        # the drop set's stacked size: one program for every count up to
        # max_drop (replay.bucket)
        self._drop_size = replay_mod.bucket(
            config.max_drop * self.num_tree_per_iteration
            if config.max_drop > 0 else replay_mod.REPLAY_BUCKET_MAX)
        # (iteration, dropped iterations, their summed output [N, K] on
        # the device or None) of the newest iteration: what its tree grew
        # from is the scores less that sum
        self.last_drop = None
        self._drop_counts = {}

    # ------------------------------------------------- checkpoint state
    def training_state(self):
        from ..checkpoint import snapshot as snap_mod
        meta, arrays = super().training_state()
        drop_meta, drop_keys = snap_mod.rng_state_split(self._drop_rng)
        # JSON float round-trips are exact (repr/shortest-roundtrip), so
        # tree_weight/sum_weight come back bit-identical
        meta["dart"] = {"rng": drop_meta,
                        "tree_weight": [float(w) for w in self.tree_weight],
                        "sum_weight": float(self.sum_weight)}
        arrays["dart_rng_keys"] = drop_keys
        return meta, arrays

    def load_training_state(self, meta, arrays) -> None:
        from ..checkpoint import snapshot as snap_mod
        super().load_training_state(meta, arrays)
        d = meta.get("dart")
        if d is not None and "dart_rng_keys" in arrays:
            self._drop_rng.set_state(
                snap_mod.rng_state_join(d["rng"], arrays["dart_rng_keys"]))
            self.tree_weight = [float(w) for w in d["tree_weight"]]
            self.sum_weight = float(d["sum_weight"])

    def warn_lossy_continuation(self) -> None:
        from ..log import Log
        Log.warning(
            "Continued DART training from init_model: the drop-set "
            "RandomState and per-tree weights cannot be reconstructed from "
            "a model file, so dropping probabilities restart from scratch "
            "and results WILL diverge from an uninterrupted run. Use "
            "checkpoints (engine.train(resume_from=<dir>)) for exact "
            "continuation.")
        super().warn_lossy_continuation()

    def _dropping_trees(self) -> List[int]:
        """Select iteration indices to drop (dart.hpp DroppingTrees:88-139)."""
        cfg = self.config
        drop_index: List[int] = []
        if self._drop_rng.rand() < cfg.skip_drop:
            return drop_index
        drop_rate = cfg.drop_rate
        n_iter = self.iter_
        if not cfg.uniform_drop and self.sum_weight > 0:
            inv_avg = len(self.tree_weight) / self.sum_weight
            if cfg.max_drop > 0:
                drop_rate = min(drop_rate, cfg.max_drop * inv_avg / self.sum_weight)
            for i in range(n_iter):
                if self._drop_rng.rand() < drop_rate * self.tree_weight[i] * inv_avg:
                    drop_index.append(i)
                    if len(drop_index) >= cfg.max_drop > 0:
                        break
        else:
            if cfg.max_drop > 0 and n_iter > 0:
                drop_rate = min(drop_rate, cfg.max_drop / float(n_iter))
            for i in range(n_iter):
                if self._drop_rng.rand() < drop_rate:
                    drop_index.append(i)
                    if len(drop_index) >= cfg.max_drop > 0:
                        break
        return drop_index

    # ------------------------------------------------------------ training
    def train_one_iter(self, grad=None, hess=None) -> bool:
        return self._dart_iteration(
            lambda: GBDT.train_one_iter(self, grad, hess))

    def train_many(self, num_iters: int) -> bool:
        """Iterations one block of one at a time, each between its drop
        set's two programs; the per-iteration path where no block runs."""
        if not self._block_eligible():
            return GBDT.train_many(self, num_iters)
        for _ in range(num_iters):
            if self._dart_iteration(lambda: GBDT.train_many(self, 1)):
                return True
        return False

    def _count_block(self, block_span, start_iter: int, count: int) -> None:
        """The drop set's work on its iteration's ``train.block`` span:
        the trees dropped, the rows x trees and the nodes walked to take
        them out of the training scores, and the trees the kernel walked
        there (replay.forest_total_kernel)."""
        super()._count_block(block_span, start_iter, count)
        block_span.counts.update(self._drop_counts)

    def _replays(self):
        """The score arrays a drop set is replayed over: the training
        table's (None) and every validation cache's row-major table."""
        return [(None, None)] + [(vi, cache["xb"]) for vi, cache
                                 in self._valid_pred_cache.items()]

    def _scores_of(self, vi):
        return self.scores if vi is None else \
            self._valid_pred_cache[vi]["scores"]

    def _set_scores(self, vi, scores) -> None:
        if vi is None:
            self.scores = scores
        else:
            self._valid_pred_cache[vi]["scores"] = scores

    def _dart_iteration(self, grow) -> bool:
        """One DART iteration around ``grow`` (one GBDT iteration): the
        drop set out of the scores, the tree, the drop set back scaled."""
        if self._stopped:
            return True
        cfg = self.config
        k_cls = self.num_tree_per_iteration
        models = self.models          # flushed: the newest tree may drop
        iteration = self.iter_
        drop_index = self._dropping_trees()
        k = float(len(drop_index))
        self.obs.event("dart_drop", iteration=iteration,
                       dropped=len(drop_index))
        dropped = [models[i * k_cls + c] for i in drop_index
                   for c in range(k_cls)]
        classes = [c for _ in drop_index for c in range(k_cls)]
        self._drop_counts = {
            "dart_dropped": len(drop_index),
            "dart_replay_rows": len(dropped) * self.num_data,
            "dart_replay_nodes": sum(max(t.num_leaves_actual - 1, 0)
                                     for t in dropped),
            "dart_replay_kernel_trees": len(dropped) * self.plan.replay_kernel}

        # remove dropped trees from train/valid scores (DroppingTrees
        # :125-131): one program a table, one summed output kept
        totals = {}
        if dropped:
            with self.obs.span("dart_drop_adjust", dropped=len(drop_index)):
                for vi, xb in self._replays():
                    scores, totals[vi] = self._replay_add(
                        self._scores_of(vi), dropped, classes, -1.0, xb=xb,
                        size=self._drop_size)
                    self._set_scores(vi, scores)

        # new-tree shrinkage (dart.hpp :133-139)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + k)
        else:
            self.shrinkage_rate = (cfg.learning_rate if not drop_index else
                                   cfg.learning_rate / (cfg.learning_rate + k))

        ret = grow()
        self._drop_counts = {}
        # Normalize (dart.hpp :141-186): dropped trees scaled in place and
        # their scaled output restored to the scores; a stop restores
        # them as they were
        if ret:
            factor = 1.0
        elif not cfg.xgboost_dart_mode:
            factor = k / (k + 1.0)
        else:
            factor = k / (cfg.learning_rate + k)
        for vi, total in totals.items():
            self._set_scores(vi, self._replay_call(
                replay_mod.restore_program(self.RESTORE_SCOPE),
                (self._scores_of(vi), total, np.float32(factor)), {}))
        self.last_drop = (iteration, tuple(drop_index), totals.get(None))
        if ret:
            return ret

        for i in drop_index:
            for c in range(k_cls):
                self._models[i * k_cls + c].shrink(factor)
            if not cfg.uniform_drop:
                if not cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[i] * (1.0 / (k + 1.0))
                else:
                    self.sum_weight -= self.tree_weight[i] * \
                        (1.0 / (k + cfg.learning_rate))
                self.tree_weight[i] *= factor

        if not cfg.uniform_drop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
        return False

    def compile_replay(self) -> None:
        """Trace, lower and compile (or load from the persistent cache) the
        two programs of a dropping iteration over the training table, the
        drop set out and back at its stacked size, and run nothing: as
        compile_block readies a block, for a caller that times its
        iterations. Draws nothing from the drop RNG."""
        models = self.models
        if not models:
            return
        with recorder.span("train.compile_replay", trees=self._drop_size):
            stacked = self._replay_layout_of().stack(
                models[:1], [0], self._drop_size)
            bins, statics = self._replay_input()
            self._ready_replay(replay_mod.replay_program(self.REPLAY_SCOPE),
                               (self.scores, stacked, np.int32(1),
                                np.float32(-1.0), bins), statics)
            self._ready_replay(replay_mod.restore_program(self.RESTORE_SCOPE),
                               (self.scores, self.scores, np.float32(1.0)),
                               {})
