"""GBDT training driver.

TPU-native re-design of src/boosting/gbdt.cpp (Init :45-115, TrainOneIter
:333-412, Bagging :159-241, UpdateScore :451-470, early stopping :476-533).
The whole boosting iteration — gradients, bagging mask, K class trees, score
update — is one jit-compiled function; the host loop only sequences
iterations, snapshots tiny tree arrays, and runs metrics every
``metric_freq`` rounds.

Key mappings:
- ScoreUpdater (score_updater.hpp) -> a device score array updated via the
  final per-row ``leaf_id`` from growth (the "by learner partition" fast path,
  serial_tree_learner.h:58-70) — out-of-bag rows get their leaf the same way,
  so no separate OOB pass is needed.
- Multiclass K trees/iteration (gbdt.cpp:348-398) -> ``jax.vmap`` of tree
  growth over the class axis.
- Tree::Shrinkage (tree.h:139) -> leaf values scaled by learning_rate when a
  tree is extracted into the host-side model list.
- RenewTreeOutput for percentile objectives (serial_tree_learner.cpp:850-928)
  -> in-graph segmented weighted percentile (core/renew.py): one sort +
  cumsum + searchsorted renews every leaf at once, no host round-trip.
"""
from __future__ import annotations

import copy
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
import numpy as np

from ..config import Config
from ..log import Log, LightGBMError, check
from ..io.dataset import BinnedDataset
from ..io.binning import BinType, MissingType as BinMissingType
from ..core.split import FeatureMeta, SplitParams
from ..core.grow import (EXCHANGE_COUNTS, WORK_COUNTS, WORK_LIMB,
                         GrowParams, TreeArrays, empty_tree, grow_tree)
from ..core import partition as partition_mod
from ..core.pack import pack_trees, unpack_tree
from ..core import replay as replay_mod
from ..core import tree as tree_mod
from . import plan as plan_mod
from ..objectives import ObjectiveFunction
from ..metrics import Metric
from ..resilience import faults as _faults
from ..obs.trace import record_span, recorder


def _work_counts(work) -> Dict[str, int]:
    """A block's work counts (grow.Grown ``work``: [..., 2 x chips, width]
    int32 limbs, a mesh's chips stacked) by name: the rows and tiles summed
    over the chips, the exchange as one chip issued it (every chip issues
    the same collectives)."""
    a = np.asarray(work, np.int64)
    limbs = a.reshape(-1, a.shape[-2] // 2, 2, a.shape[-1]).sum(axis=0)
    counts = limbs[:, 1] * WORK_LIMB + limbs[:, 0]    # [chips, width]
    w = len(WORK_COUNTS)
    out = dict(zip(WORK_COUNTS, counts[:, :w].sum(axis=0).tolist()))
    out.update(zip(EXCHANGE_COUNTS, counts[0, w:].tolist()))
    return out


class HostTree:
    """One trained tree pulled to host: numpy SoA + real-value thresholds.

    The analog of the serialized Tree model (tree.h:404-517) — what gets
    saved, loaded, and used for raw-input prediction.
    """

    def __init__(self, num_leaves: int):
        n = max(num_leaves - 1, 1)
        self.num_leaves = num_leaves
        self.split_feature = np.zeros(n, np.int32)       # real feature index
        self.split_gain = np.zeros(n, np.float32)
        self.threshold = np.zeros(n, np.float64)         # real-value threshold
        self.threshold_bin = np.zeros(n, np.int32)
        self.default_left = np.zeros(n, bool)
        self.missing_type = np.zeros(n, np.int32)
        self.is_categorical = np.zeros(n, bool)
        self.cat_bitset = np.zeros((n, 8), np.uint32)      # raw category values
        self.cat_bitset_bin = np.zeros((n, 8), np.uint32)  # bin indices (train replay)
        self.left_child = np.full(n, -1, np.int32)
        self.right_child = np.full(n, -1, np.int32)
        self.split_leaf = np.full(n, -1, np.int32)
        self.internal_value = np.zeros(n, np.float64)
        self.internal_weight = np.zeros(n, np.float64)
        self.internal_count = np.zeros(n, np.int64)
        self.leaf_value = np.zeros(num_leaves, np.float64)
        self.leaf_weight = np.zeros(num_leaves, np.float64)
        self.leaf_count = np.zeros(num_leaves, np.int64)
        self.shrinkage = 1.0

    @property
    def num_nodes(self) -> int:
        return self.num_leaves - 1

    def shrink(self, rate: float) -> None:
        """Tree::Shrinkage (tree.h:139-147)."""
        self.leaf_value *= rate
        self.internal_value *= rate
        self.shrinkage *= rate

    def predict_table(self, max_nodes: int, max_leaves: int,
                      cat_words: Optional[int] = None) -> tree_mod.PredictTree:
        """Pad to model-wide fixed shapes for stacked device prediction."""
        return tree_mod.pack_predict_table(self, max_nodes, max_leaves,
                                           cat_words)


def _pad_feature_meta(meta: FeatureMeta, fpad: int) -> FeatureMeta:
    """Append `fpad` unusable (num_bin=1) features for even column sharding."""
    if fpad <= 0:
        return meta
    return FeatureMeta(
        num_bin=jnp.concatenate([meta.num_bin,
                                 jnp.ones((fpad,), jnp.int32)]),
        missing_type=jnp.concatenate([meta.missing_type,
                                      jnp.zeros((fpad,), jnp.int32)]),
        default_bin=jnp.concatenate([meta.default_bin,
                                     jnp.zeros((fpad,), jnp.int32)]),
        is_categorical=jnp.concatenate([meta.is_categorical,
                                        jnp.zeros((fpad,), bool)]),
        penalty=jnp.concatenate([meta.penalty,
                                 jnp.ones((fpad,), jnp.float32)]),
        monotone=jnp.concatenate([meta.monotone,
                                  jnp.zeros((fpad,), jnp.int32)]),
        # padding only happens on meshes, where EFB is off -> identity layout
        col=jnp.concatenate([meta.col,
                             jnp.arange(meta.col.shape[0],
                                        meta.col.shape[0] + fpad,
                                        dtype=jnp.int32)]),
        offset=jnp.concatenate([meta.offset, jnp.zeros((fpad,), jnp.int32)]),
        bundled=jnp.concatenate([meta.bundled, jnp.zeros((fpad,), bool)]),
        pack_div=jnp.concatenate([meta.pack_div,
                                  jnp.ones((fpad,), jnp.int32)]),
        pack_mod=jnp.concatenate([meta.pack_mod,
                                  jnp.zeros((fpad,), jnp.int32)]),
        pack_partner=jnp.concatenate([meta.pack_partner,
                                      jnp.ones((fpad,), jnp.int32)]))


def _feature_meta_from_dataset(ds: BinnedDataset, config: Config) -> FeatureMeta:
    f = ds.num_features
    num_bin = np.array([ds.feature_num_bin(j) for j in range(f)], np.int32)
    missing = np.array(
        [ds.bin_mappers[ds.used_features[j]].missing_type for j in range(f)],
        np.int32)
    default_bin = np.array(
        [ds.bin_mappers[ds.used_features[j]].default_bin for j in range(f)],
        np.int32)
    is_cat = np.array(
        [ds.bin_mappers[ds.used_features[j]].bin_type == BinType.CATEGORICAL
         for j in range(f)], bool)
    penalty = np.ones(f, np.float32)
    if config.feature_contri:
        fc = np.asarray(config.feature_contri, np.float32)
        for j in range(f):
            rj = ds.used_features[j]
            if rj < len(fc):
                penalty[j] = fc[rj]
    monotone = np.zeros(f, np.int32)
    if config.monotone_constraints:
        mc = np.asarray(config.monotone_constraints, np.int32)
        # reference CHECKs the constraint list covers every feature
        # (dataset.cpp:295); silently zero-filling would violate the
        # constraints the user asked for
        check(len(mc) == ds.num_total_features,
              "monotone_constraints has %d entries but the dataset has %d "
              "features" % (len(mc), ds.num_total_features))
        for j in range(f):
            monotone[j] = mc[ds.used_features[j]]
    (feat_col, feat_offset, feat_bundled, pack_div, pack_mod,
     pack_partner) = ds.feature_layout()
    return FeatureMeta(
        num_bin=jnp.asarray(num_bin), missing_type=jnp.asarray(missing),
        default_bin=jnp.asarray(default_bin), is_categorical=jnp.asarray(is_cat),
        penalty=jnp.asarray(penalty), monotone=jnp.asarray(monotone),
        col=jnp.asarray(feat_col), offset=jnp.asarray(feat_offset),
        bundled=jnp.asarray(feat_bundled),
        pack_div=jnp.asarray(pack_div), pack_mod=jnp.asarray(pack_mod),
        pack_partner=jnp.asarray(pack_partner))


def _host_device_split(block_span, dispatch_span):
    """(wall, host busy, device wait) seconds of one dispatched block, from
    its ``train.block`` span and the ``train.block_dispatch`` span inside
    it: the host is busy until the dispatch returns, the rest is the wait."""
    return (block_span.duration_s,
            (dispatch_span.end_ns - block_span.start_ns) / 1e9,
            (block_span.end_ns - dispatch_span.end_ns) / 1e9)


def _obj_row_names(obj, n: int, n0: int) -> Tuple[str, ...]:
    """The objective's per-row device arrays (label, weights, onehot, ...)
    by name: anything get_gradients reads, handed to a step as arguments."""
    return tuple(sorted(
        nm for nm, v in (obj.__dict__.items() if obj is not None else ())
        if isinstance(v, jnp.ndarray) and v.ndim >= 1
        and v.shape[0] in (n, n0)))


def _bind(obj, names, rows):
    """A shallow copy of ``obj`` with the argument arrays bound: the
    traced values, not the captured originals, feed get_gradients."""
    o = copy.copy(obj)
    for nm, v in zip(names, rows):
        setattr(o, nm, v)
    return o


def _gradients(o, scores):
    """The objective's [N, K] gradients and hessians (gbdt.cpp:333-347)."""
    if scores.shape[1] == 1:
        g, h = o.get_gradients(scores[:, 0])
        return g[:, None], h[:, None]
    return o.get_gradients(scores)


def _renew_of(obj):
    """(alpha, weight attribute) of an objective's in-graph leaf refit
    (RenewTreeOutput: L1/Quantile/MAPE, core/renew.py), else None."""
    if obj is None or getattr(obj, "renew_percentile", None) is None:
        return None
    return (float(obj.renew_percentile()),
            "label_weight" if obj.name == "mape" else "weights")


def _goss_as_multiplier(g, h, sample_mask, goss_active, key, counts,
                        n_real, row_valid=None):
    """GOSS one-side sampling as a multiplier (goss.hpp:87-135): keep all
    of the top |g*h| rows, Bernoulli-sample the rest, amplify their
    grad/hess by (n - top)/other so expectations are unbiased; every row
    stays in the grower's passes, the dropped ones at weight 0. Warmup
    iterations (goss_active == 0) skip the sort entirely. ``row_valid``
    masks padded rows out of the ranking where their |g*h| is not zero."""
    n = g.shape[0]
    top_cnt, other_cnt, goss_multiply = counts

    def goss_mult(_):
        gh = jnp.sum(jnp.abs(g * h), axis=1)
        if row_valid is not None:
            gh = gh * row_valid
        thr = jax.lax.top_k(gh, top_cnt)[0][-1]
        is_top = gh >= thr
        u = jax.random.uniform(key, (n,))
        p_rest = other_cnt / max(n_real - top_cnt, 1)
        keep_other = (~is_top) & (u < p_rest)
        return jnp.where(is_top, 1.0,
                         jnp.where(keep_other, goss_multiply, 0.0))

    mult = jax.lax.cond(goss_active > 0, goss_mult,
                        lambda _: jnp.ones((n,), jnp.float32), operand=None)
    return (g * mult[:, None], h * mult[:, None],
            sample_mask * (mult > 0).astype(jnp.float32))


def _add_trees(o, renew, trees, leaf_ids, scores, sample_mask, lr,
               stopped_in, num_leaves):
    """(trees, new scores, stop latch, any class tree split). Renewed
    leaves (RenewTreeOutput, serial_tree_learner.cpp:850-928) refit to the
    residuals against the PRE-update scores; each row adds its leaf's
    value x lr. A fully-stumped iteration means convergence (gbdt.cpp:
    379-396): the latch accumulates ON DEVICE, so every later dispatched
    iteration freezes the scores too and the async driver discards the
    overshoot trees at the next flush without rewinding anything."""
    if renew is not None:
        from ..core.renew import renew_leaf_values
        alpha, w_attr = renew
        rw = getattr(o, w_attr, None)
        if rw is None:
            rw = jnp.ones_like(o.label)

        def renew_one(t, li, sc_col):
            # scores live in the (possibly reg_sqrt-transformed) label
            # space the gradients were computed in
            lab = getattr(o, "trans_label", None)
            lab = o.label if lab is None else lab
            new_lv = renew_leaf_values(lab - sc_col, rw, li, sample_mask,
                                       num_leaves, alpha, t.leaf_value)
            return t._replace(leaf_value=new_lv)

        trees = jax.vmap(renew_one, in_axes=(0, 0, 1))(
            trees, leaf_ids, scores)
    deltas = jax.vmap(
        lambda t, li: tree_mod.leaf_value_of(t.leaf_value, li) * lr)(
            trees, leaf_ids)                                    # [K, N]
    any_split = jnp.any(trees.num_leaves > 1)
    stopped_out = stopped_in | ~any_split
    apply = (any_split & ~stopped_in).astype(jnp.float32)
    return trees, scores + deltas.T * apply, stopped_out, any_split


def _grower_aux(plan, health_on: bool, aux):
    """(health, model statistics, CEGB state) of the growers' third output,
    stacked over the classes: on the frontier path the obs aux, the [K, 2]
    health accumulator with obs_health or the (health_or_None,
    [K, F, MS_WIDTH] mstats) tuple with obs_modelstats; CEGB state on the
    exact path (the frontier and CEGB paths are config-exclusive)."""
    if plan.obs_modelstats:
        return aux[0], aux[1], None
    if plan.obs_aux and health_on:
        return aux, None, None
    return None, None, aux


def _health(on: bool, g, h, any_split, grower_health):
    """The device-side health flags (lightgbm_tpu.obs) from what the step
    holds; off, a constant zero vector and no health compute."""
    if not on:
        return jnp.zeros((4,), jnp.float32)
    from ..obs.health import health_vec
    return health_vec(g, h, any_split, grower_health)


class GBDT:
    """Boosting driver (include/LightGBM/boosting.h:22-294, gbdt.{h,cpp})."""

    boosting_type = "gbdt"
    average_output = False
    # the span over a resumed forest's scores, replayed (_scores_from_trees),
    # and the scope of every stored-tree replay (core/replay.py)
    FOREST_SCORES_SPAN = "train.forest_scores"
    REPLAY_SCOPE = "lgbm.replay"

    def __init__(self, config: Config, train_data: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction],
                 metrics: Optional[List[Metric]] = None):
        self.config = config
        if getattr(config, "fault_inject", ""):
            # arm the deterministic fault plan (docs/Resilience.md) before
            # any seam can fire; identical (spec, seed) re-installs keep
            # fire counts across in-process supervised restarts
            from ..resilience import faults
            faults.install_plan(config.fault_inject, config.fault_seed)
        # persistent XLA compile cache: placed before the first jit so
        # every executable this booster builds is cacheable — warm starts
        # (same shapes, same jax) then compile nothing
        from ..profiling import enable_compile_cache
        enable_compile_cache(config.compile_cache_dir)
        if plan_mod.hist_dtype(config) == "f64" \
                and not jax.config.jax_enable_x64:
            # reference gpu_use_dp = double-precision histograms
            # (config.h:784); jax needs x64 enabled for f64 to exist at
            # trace time. Process-wide, explicit user opt-in.
            Log.info("gpu_use_dp=true: enabling jax x64 mode for "
                     "double-precision histogram accumulation")
            # lgbm-lint: disable=LGL105 explicit gpu_use_dp user opt-in
            jax.config.update("jax_enable_x64", True)
        self.train_data = train_data
        self.objective = objective
        self.train_metrics = metrics or []
        self.valid_data: List[BinnedDataset] = []
        self.valid_metrics: List[List[Metric]] = []
        # Async driver state: trained trees stay on device ([K, T] packed
        # int32 buffers, core/pack.py) and are materialized to HostTrees in
        # batched flushes — one device->host transfer per flush instead of
        # ~20 per iteration. `_models` is the materialized list; `models` is
        # a flushing property.
        self._models: List[HostTree] = []
        self._pending: List[Dict[str, Any]] = []
        self._stopped = False
        self._stopped_dev = jnp.asarray(False)  # device-side stop latch
        self._flush_every = 64
        self.iter_ = 0
        self.num_init_iteration = 0
        self.best_score: Dict[Any, Dict[str, float]] = {}
        self.num_class = config.num_class
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective is not None
            else max(1, config.num_class))
        self.shrinkage_rate = config.learning_rate
        # subclasses (RF) force the grad_in/hess_in path even with an objective
        self._use_input_grads = False
        self.mesh = None
        self._row_valid = None
        # which path training takes (boosting/plan.py); None: no training
        self.plan: Optional[plan_mod.TrainPlan] = None
        # out-of-core streamed training (lightgbm_tpu.stream): the chunk
        # pipeline, the host-driven grower, and its pre/post jits — set by
        # _setup_train when the dataset is a StreamedDataset
        self._stream = None
        self._stream_grower = None
        self._stream_pre = None
        self._stream_post = None
        self._stream_capture = ()
        self._stream_layout = None   # host [n0,...] -> padded-layout rows
        self._stream_perm = None     # padded index of each original row
        self._stream_col_pad = 0
        # observability facade (lightgbm_tpu.obs): replaced by the
        # config-driven one in _setup_train; loaded/predict-only boosters
        # keep the disabled no-op
        from ..obs.runtime import TrainingObs
        self.obs = TrainingObs.disabled()
        # the stored-tree replay (core/replay.py): the table's layout by
        # real feature, made at set-up where the table stores a column,
        # and executables readied ahead of their first call
        # (compile_replay), by scope
        self._replay_layout = None
        self._aot_replay: Dict[Tuple, Any] = {}

        if train_data is not None:
            with recorder.span("train.setup",
                               rows=train_data.num_data) as span:
                self._setup_train(train_data)
                plan = self.plan
                # the chips of the mesh, and whether the exact grower's
                # counts over them are the row partition's integers
                # (psummed as int32) and not a float32 histogram's
                span.counts["mesh_chips"] = (
                    0 if self.mesh is None else int(self.mesh.devices.size))
                span.counts["mesh_exact_counts"] = int(plan.mesh_exact_counts)
                # a replay of stored trees reads its columns from the
                # feature-major copy of the bins
                if self._bins_by_col is not None \
                        and self.boosting_type == "dart":
                    span.counts["replay_feature_major"] = 1
                # the columns whose split search prices a missing direction
                f = train_data.num_features     # not a mesh's padding
                span.counts["features_with_missing"] = int(np.count_nonzero(
                    np.asarray(self.feature_meta.missing_type)[:f]))
                # categorical columns, and whether the grower tests a
                # split's category set without a gather over the rows it
                # routes (the exact grower's one-split form; the wave
                # growers' per-row sets keep their take_along_axis)
                span.counts["features_categorical"] = plan.facts.categorical
                span.counts["cat_route_gather_free"] = int(
                    plan.cat_route_gather_free)

    # ------------------------------------------------------------ setup
    def _setup_stream_mesh(self, ds) -> np.ndarray:
        """Chunks x chips: validate the topology, build the sharded chunk
        pipeline, and fix the SHARD-MAJOR padded row layout (see
        stream/pipeline.py). Returns ``row_valid`` in that layout.

        Two topologies land here: a multi-process run whose dataset was
        ingested through a ``ShardedSource`` (each process holds exactly
        its rank's row block — ``shard_world`` must equal the data-axis
        size), and a single-process multi-device run whose resident chunk
        list is split into contiguous rank-ordered blocks on the spot
        with the same shard-assignment contract.
        """
        cfg = self.config
        from ..parallel import mesh as mesh_mod
        from ..stream.pipeline import (ShardedChunkPipeline,
                                       shard_rows_host, shard_rows_perm,
                                       split_chunks_rows)
        from ..stream.source import shard_offsets
        mesh = self.mesh
        dsize = int(mesh.shape[mesh_mod.DATA_AXIS])
        # reduce-scatter wave histograms need the stored columns to tile
        # over the data axis (DataRSLearner); pad columns here and the
        # feature metadata below with unusable num_bin=1 entries
        ncols = int(ds.chunks[0].shape[1]) if ds.chunks \
            else int(ds.num_columns)
        col_pad = (-ncols) % self.plan.col_multiple
        self._stream_col_pad = col_pad
        world = self.plan.facts.shard_world
        if world > 1:
            if world != dsize:
                raise LightGBMError(
                    "dataset is sharded %d ways but the mesh data axis "
                    "has %d positions; ShardedSource world must equal "
                    "the data-axis size" % (world, dsize))
            counts = [int(c) for c in ds.shard_row_counts]
            shard_chunks = [ds.chunks]
        else:
            if jax.process_count() > 1:
                raise LightGBMError(
                    "multi-process streamed training needs a sharded "
                    "ingest: wrap the source in stream.source."
                    "ShardedSource(rank, world) so each process streams "
                    "only its row block")
            offs = shard_offsets(ds.num_data, dsize)
            counts = [offs[p + 1] - offs[p] for p in range(dsize)]
            shard_chunks = split_chunks_rows(ds.chunks, offs)
        self._stream = ShardedChunkPipeline(
            shard_chunks, counts, int(cfg.data_stream_chunk_rows), mesh,
            prefetch=int(cfg.data_stream_prefetch), col_pad=col_pad)
        if world > 1 and \
                self._stream.local_shards != [int(ds.shard_rank)]:
            raise LightGBMError(
                "shard/mesh misalignment: this process ingested shard %d "
                "but addresses mesh position(s) %s — keep process rank "
                "order equal to shard rank order"
                % (int(ds.shard_rank), self._stream.local_shards))
        offs = self._stream.shard_offsets()
        local_padded = self._stream.local_padded
        self._stream_layout = (
            lambda a, _o=offs, _n=local_padded: shard_rows_host(a, _o, _n))
        self._stream_perm = shard_rows_perm(offs, local_padded)
        return shard_rows_host(np.ones(ds.num_data, np.float32), offs,
                               local_padded)

    def _setup_train(self, ds: BinnedDataset) -> None:
        cfg = self.config
        from ..parallel import mesh as mesh_mod
        self.mesh = mesh_mod.build_mesh(cfg)
        self.num_data_orig = ds.num_data
        self._forced_splits, num_forced = self._setup_forced_splits()
        if ds.num_features:     # a table of no stored column has no layout
            self._replay_layout = replay_mod.TableLayout(ds)
        self.plan = plan = plan_mod.resolve_plan(cfg, plan_mod.TrainFacts.of(
            ds, self.mesh, self.boosting_type, self.num_tree_per_iteration,
            num_forced, bool(ds.num_features and self._replay_layout.single)))
        for level, message in plan.log:
            getattr(Log, level)(message)
        xb_np = ds.X_binned
        row_valid = None
        streamed = plan.facts.streamed
        padded = lambda pad: np.concatenate(   # noqa: E731 - row_valid
            [np.ones(ds.num_data, np.float32), np.zeros(pad, np.float32)])
        if streamed:
            # out-of-core path: the bin matrix exists only as host chunks;
            # everything per-row stays device-resident at padded length
            if self.mesh is not None:
                row_valid = self._setup_stream_mesh(ds)
            else:
                from ..stream.pipeline import ChunkPipeline
                chunk_cap = int(cfg.data_stream_chunk_rows) or \
                    max(1, max(ds.chunk_row_counts))
                # packed host chunks (core/binpack.py): word-packed at
                # repack time, so every host->device transfer ships the
                # kernel-native int32-word layout
                self._stream = ChunkPipeline(
                    ds.chunks, chunk_cap,
                    prefetch=int(cfg.data_stream_prefetch),
                    packed=plan.word_packed_cols > 0)
                pad = self._stream.num_padded - ds.num_data
                if pad:
                    row_valid = padded(pad)
        elif self.mesh is not None:
            # pad rows so every shard is even; padded rows carry mask 0
            # everywhere (the distributed loader's row partition,
            # dataset_loader.cpp:469-495, without the loss of remainder
            # rows). Padded columns get num_bin=1 metadata, which the
            # split search treats as unusable
            pad = (-ds.num_data) % plan.row_multiple
            if pad:
                xb_np = np.concatenate(
                    [xb_np, np.zeros((pad, xb_np.shape[1]), xb_np.dtype)])
                row_valid = padded(pad)
            fpad = (-xb_np.shape[1]) % plan.col_multiple
            if fpad:
                xb_np = np.concatenate(
                    [xb_np, np.zeros((xb_np.shape[0], fpad), xb_np.dtype)],
                    axis=1)
        self.num_data = (self._stream.num_padded if streamed
                         else xb_np.shape[0])
        self._feature_pad = (self._stream_col_pad if streamed
                             else xb_np.shape[1] - ds.num_columns)
        self._row_valid = (jnp.asarray(row_valid) if row_valid is not None
                           else None)
        self.feature_meta = _pad_feature_meta(
            _feature_meta_from_dataset(ds, cfg), self._feature_pad)
        self.num_bins = max(ds.max_col_bins(), 2)
        self.num_feat_bins = max(ds.max_num_bin(), 2)
        explicit_fp = plan.learner == plan_mod.FEATURE
        # the host side of the transfer: no barrier waits for the device
        with recorder.span("train.device_put_bins",
                           bytes=0 if streamed else xb_np.nbytes):
            self._fp_capture = None
            if streamed:
                self.xb = None
            elif self.mesh is not None and not explicit_fp:
                # each chip's rows go from the host straight to that chip
                self.xb = jax.device_put(
                    xb_np, mesh_mod.feature_sharding(self.mesh))
            else:
                self.xb = jnp.asarray(xb_np)
            if explicit_fp:
                # xb stays replicated (every FP worker holds the full data,
                # like the reference's feature-parallel machines); each
                # device additionally gets its own column slice for
                # histogram work
                self._fp_capture = self._setup_feature_parallel(xb_np)
        with recorder.span("train.objective_init"):
            if self.objective is not None:
                self.objective.init(ds.metadata, ds.num_data)
                if self.mesh is not None:
                    # streamed mesh: per-row arrays go to the shard-major
                    # padded layout instead of trailing-padding
                    self.objective.pad_to(self.num_data, self.mesh,
                                          layout=self._stream_layout)
                elif streamed and self.num_data > ds.num_data:
                    # chunk-uniform padding: per-row objective arrays
                    # stretch to the padded length; padded rows are masked
                    # everywhere
                    self.objective.pad_to(self.num_data)
            for m in self.train_metrics:
                m.init(ds.metadata, ds.num_data)

        self._cegb_state = self._setup_cegb()
        # read by the benchmark's GOSS job (bench/jobs/goss_window.py)
        self._goss_bag = plan.goss_bag
        # the bins feature-major, made on the device: a tree on a bag or
        # over all rows routes every row by one contiguous column a split
        # (partition.route_in_row_space), and a replay of stored trees
        # reads its columns so (core/replay.py). An argument of the blocks
        # that route so, as xb is of every block
        self._bins_by_col = (jax.jit(partition_mod.bins_by_column)(self.xb)
                             if plan.copy else None)
        # (top_cnt, other_cnt, the others' multiplier) from the REAL row
        # count, not the mesh-padding-inflated one — padded rows carry
        # |g·h| = 0 and sort last, so top-k over the padded array with real
        # counts is exact (goss.hpp:87-135)
        self._goss_counts = None
        if self.boosting_type == "goss":
            from .goss import bag_counts
            self._goss_counts = bag_counts(
                self.num_data_orig, cfg.top_rate, cfg.other_rate)

        # observability: the device-side health piggy-back
        # (GrowParams.obs_health) keys off the resolved health action
        from ..obs.runtime import TrainingObs
        self.obs = TrainingObs.from_config(cfg)
        self.grow_params = GrowParams(
            num_leaves=cfg.num_leaves,
            num_bins=self.num_bins,
            max_depth=cfg.max_depth,
            num_forced=num_forced,
            pool_slots=plan.pool_slots,
            cegb_split_penalty=float(cfg.cegb_tradeoff
                                     * cfg.cegb_penalty_split),
            with_cegb_coupled=bool(len(cfg.cegb_penalty_feature_coupled)),
            with_cegb_lazy=bool(len(cfg.cegb_penalty_feature_lazy)),
            split=SplitParams(
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                max_delta_step=cfg.max_delta_step,
                min_data_in_leaf=cfg.min_data_in_leaf,
                min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
                min_gain_to_split=cfg.min_gain_to_split,
                max_cat_threshold=cfg.max_cat_threshold,
                cat_smooth=cfg.cat_smooth, cat_l2=cfg.cat_l2,
                max_cat_to_onehot=cfg.max_cat_to_onehot,
                min_data_per_group=cfg.min_data_per_group),
            row_chunk=plan.row_chunk, hist_impl=plan.hist_impl,
            hist_dtype=plan.hist_dtype, voting_top_k=plan.voting_top_k,
            with_categorical=plan.facts.categorical,
            all_rows_in_bag=plan.all_rows_in_bag,
            use_partition=plan.use_partition,
            partition_on_mesh=plan.partition_on_mesh,
            vmapped_classes=plan.vmapped, batch_splits=plan.batch_splits,
            frontier_mode=plan.frontier_mode, frontier_rs=plan.frontier_rs,
            frontier_bucketing=plan.frontier_bucketing,
            word_packed_cols=plan.word_packed_cols,
            with_efb=plan.facts.bundled, num_feat_bins=self.num_feat_bins,
            # single source of truth: the marginalization width IS the
            # largest pack_partner the layout recorded, and the packed
            # subset is wherever a mod was recorded
            pack_j=int(np.asarray(self.feature_meta.pack_partner).max()
                       if self.feature_meta.pack_partner is not None
                       and self.feature_meta.pack_partner.size else 1),
            packed_features=tuple(
                int(i) for i in np.nonzero(
                    np.asarray(self.feature_meta.pack_mod))[0])
            if self.feature_meta.pack_mod is not None else (),
            obs_health=plan.obs_aux and self.obs.health_enabled,
            obs_modelstats=plan.obs_modelstats)

        if plan.word_packed_cols and not streamed:
            # replace the device matrix with its packed words NOW — the
            # uint8 copy was never materialized on device (self.xb above
            # is only committed lazily by jnp.asarray at first use on
            # CPU backends; repacking from the host array keeps this a
            # single transfer of the halved/word layout)
            from ..core.binpack import pack_words_np
            with recorder.span("train.device_put_bins") as span:
                words = pack_words_np(xb_np)
                span.counts["bytes"] = words.nbytes
                self.xb = jnp.asarray(words)
            Log.info("bin packing: %d uint8 columns stored as %d int32 "
                     "words/row on device (tpu_bin_packing=%s)",
                     plan.word_packed_cols, self.xb.shape[1],
                     cfg.tpu_bin_packing)

        if streamed:
            from ..stream.grow_stream import StreamFrontierGrower
            self._stream_grower = StreamFrontierGrower(
                self._stream, self.feature_meta, self.grow_params,
                mesh=self.mesh)

        k = self.num_tree_per_iteration
        n = self.num_data
        n0 = self.num_data_orig
        init_scores = np.zeros((n, k), np.float32)
        # init score from file/metadata (ScoreUpdater ctor :32-51)
        if ds.metadata.init_score is not None:
            isc = np.asarray(ds.metadata.init_score, np.float32).reshape(-1)
            if len(isc) == n0 * k:
                vals = isc.reshape(k, n0).T
            else:
                vals = np.tile(isc.reshape(-1, 1), (1, k))
            if self._stream_layout is not None:
                init_scores = self._stream_layout(
                    np.asarray(vals, np.float32))
            else:
                init_scores[:n0] = vals
        self._init_scores_provided = ds.metadata.init_score is not None
        self.scores = jnp.asarray(init_scores)
        if self.mesh is not None:
            self.scores = jax.device_put(
                self.scores, mesh_mod.row_sharding(self.mesh, extra_dims=1))
        self.boost_from_average_done = False
        self._rng = np.random.RandomState(cfg.feature_fraction_seed)
        self._bag_key = jax.random.PRNGKey(cfg.bagging_seed)
        self._bag_mask = jnp.ones((n,), jnp.float32)
        if self.mesh is not None and not streamed:
            # where the block hands them back (rows over the data axis, the
            # latch replicated), or its second call compiles it again
            self._bag_mask = jax.device_put(
                self._bag_mask, mesh_mod.row_sharding(self.mesh))
            self._stopped_dev = jax.device_put(
                self._stopped_dev, mesh_mod.replicated(self.mesh))
        # group-aware bagging: under a ranking objective, bagging samples
        # whole QUERY GROUPS — one uniform per query broadcast to its rows
        # — never fractions of a query (a partial query corrupts every
        # pairwise lambda and NDCG normalizer within it). row_group maps
        # row -> query index; mesh-padding rows get a synthetic trailing
        # group (they are masked out by _row_valid regardless).
        self._row_group = None
        qb_meta = ds.metadata.query_boundaries
        if qb_meta is not None and \
                getattr(self.objective, "name", "") == "lambdarank":
            qb_arr = np.asarray(qb_meta, np.int64)
            groups = np.repeat(np.arange(len(qb_arr) - 1, dtype=np.int32),
                               np.diff(qb_arr))
            if len(groups) < n:
                groups = np.concatenate([
                    groups, np.full(n - len(groups), len(qb_arr) - 1,
                                    np.int32)])
            self._row_group = jnp.asarray(groups[:n])
            self._num_groups = int(len(qb_arr))  # num_queries + pad group
        self._compiled_iter = None
        self._iter_core = None
        self._compiled_block = None
        # under a GOSS bag the three above are the CURRENT regime's (the
        # unsampled iterations' or the sampled ones'); the other regime's
        # wait in _programs (_enter_regime)
        self._sampled_regime = False
        # (iteration, uint8 [N] of goss.OUT_OF_BAG / BAG_TOP / BAG_OTHER):
        # the newest bag a sampled iteration grew its tree on, on the device
        self.last_bag: Optional[Tuple[int, jnp.ndarray]] = None
        self._programs: Dict[bool, Tuple[Any, Any, Any]] = {}
        self._goss_t0: Optional[float] = None
        # blocks compiled ahead of their first dispatch (compile_block),
        # by (regime, block length)
        self._aot_blocks: Dict[Tuple[bool, int], Any] = {}
        self._ladder_warmup: Optional[Dict[str, Any]] = None
        # shape bookkeeping for PULL-based cost-model extraction
        # (extract_cost_model): what the last fused block / flush looked
        # like, so extraction can mirror the exact programs that ran
        self._last_block_len = 0
        self._last_flush_shapes: List[Any] = []
        self._valid_pred_cache: Dict[int, jnp.ndarray] = {}
        # model statistics (obs.modelstats): host-side cumulative state,
        # fed from the frontier piggy-back when grow_params carries it
        # and recomputed from materialized trees otherwise
        self._modelstats = None
        if cfg.obs_modelstats:
            from ..obs.modelstats import ModelStats
            self._modelstats = ModelStats(
                ds.num_total_features, feature_names=ds.feature_names,
                inner_to_real=[ds.real_feature_index(i)
                               for i in range(ds.num_features)],
                registry=self.obs.registry, events=self.obs.events)

    def add_valid_data(self, ds: BinnedDataset, metrics: List[Metric]) -> None:
        for m in metrics:
            m.init(ds.metadata, ds.num_data)
        self.valid_data.append(ds)
        self.valid_metrics.append(metrics)
        # device copy of binned valid features + running scores
        k = self.num_tree_per_iteration
        init = np.zeros((ds.num_data, k), np.float32)
        if ds.metadata.init_score is not None:
            isc = np.asarray(ds.metadata.init_score, np.float32).reshape(-1)
            if len(isc) == ds.num_data * k:
                init = isc.reshape(k, ds.num_data).T.copy()
            else:
                init = np.tile(isc.reshape(-1, 1), (1, k))
        cache = {
            "xb": jnp.asarray(ds.X_binned),
            "scores": jnp.asarray(init),
        }
        self._valid_pred_cache[len(self.valid_data) - 1] = cache
        self._materialize()
        if self._models and ds.metadata.init_score is None:
            # continued training: valid scores must include the merged init
            # model's trees (score_updater.hpp:32-51). Binned replay works
            # for matrix- and file-backed valid sets alike.
            cache["scores"], _ = self._replay_add(
                cache["scores"], self._models,
                [i % k for i in range(len(self._models))], 1.0,
                xb=cache["xb"])

    # ------------------------------------------------------------ training
    def _boost_from_average(self) -> None:
        """gbdt.cpp:298-331: seed scores with the objective's init score."""
        if (self.boost_from_average_done or self.objective is None
                or not self.config.boost_from_average
                or self._init_scores_provided):
            self.boost_from_average_done = True
            return
        k = self.num_tree_per_iteration
        inits = np.array([self.objective.boost_from_score(c) for c in range(k)],
                         np.float32)
        if np.any(inits != 0):
            self.scores = self.scores + jnp.asarray(inits)[None, :]
            for vd in self._valid_pred_cache.values():
                vd["scores"] = vd["scores"] + jnp.asarray(inits)[None, :]
            self.init_score_offsets = inits
        else:
            self.init_score_offsets = np.zeros(k, np.float32)
        self.boost_from_average_done = True

    def _setup_forced_splits(self):
        """Parse forcedsplits_filename into BFS step arrays (the ForceSplits
        queue walk, serial_tree_learner.cpp:593-751, linearized at setup
        because the leaf numbering is deterministic: step t's right child
        is leaf t + 1). Returns (ForcedSplits | None, count)."""
        fname = self.config.forcedsplits_filename
        if not fname:
            return None, 0
        import json as _json
        from collections import deque
        with open(fname) as fh:
            root = _json.load(fh)
        if not root:
            return None, 0
        ds = self.train_data
        inner_of = {real: i for i, real in enumerate(ds.used_features)}
        leaf_arr: List[int] = []
        feat_arr: List[int] = []
        thr_arr: List[int] = []
        q = deque([(root, 0)])
        t = 0
        while q and t < self.config.num_leaves - 1:
            node, leaf = q.popleft()
            real_f = int(node["feature"])
            check(real_f in inner_of,
                  "forced split feature %d is trivial/unused" % real_f)
            mapper = ds.bin_mappers[real_f]
            check(mapper.bin_type != BinType.CATEGORICAL,
                  "forced splits on categorical features are not supported")
            # rows with bin < ValueToBin(threshold) go left (BinThreshold,
            # dataset.h:507); our convention is `<= bin`, so -1 legitimately
            # means "empty left" — the forced split then aborts on
            # left_count == 0, like the reference's negative-gain gather
            tb = mapper.value_to_bin(float(node["threshold"])) - 1
            leaf_arr.append(leaf)
            feat_arr.append(inner_of[real_f])
            thr_arr.append(tb)
            right_leaf = t + 1
            if isinstance(node.get("left"), dict):
                q.append((node["left"], leaf))
            if isinstance(node.get("right"), dict):
                q.append((node["right"], right_leaf))
            t += 1
        from ..core.grow import ForcedSplits
        return ForcedSplits(leaf=jnp.asarray(leaf_arr, jnp.int32),
                            feature=jnp.asarray(feat_arr, jnp.int32),
                            threshold=jnp.asarray(thr_arr, jnp.int32)), t

    def _setup_feature_parallel(self, xb_np: np.ndarray):
        """Bin-balanced per-device column assignment for the explicit
        feature-parallel learner (the reference balances workers by bin
        count, feature_parallel_tree_learner.cpp:30-60). Returns
        (xb_cols [D, N, Cd], meta_local FeatureMeta of [D, Fd] arrays,
        global_of_local [D, Fd]) device_put so device d holds row d.

        Requires no EFB/packing (columns == features), which the plan
        refuses on a mesh (boosting/plan.py)."""
        from ..parallel import mesh as mesh_mod
        from jax.sharding import NamedSharding, PartitionSpec as P
        d = self.mesh.shape[mesh_mod.FEATURE_AXIS]
        n, f = xb_np.shape
        meta = self.feature_meta
        num_bin = np.asarray(meta.num_bin)
        # greedy: biggest feature to the least-loaded device
        order = np.argsort(-num_bin, kind="stable")
        loads = np.zeros(d, np.int64)
        assign: List[List[int]] = [[] for _ in range(d)]
        for j in order:
            dev = int(np.argmin(loads))
            assign[dev].append(int(j))
            loads[dev] += max(int(num_bin[j]), 1)
        fd = max(max(len(a) for a in assign), 1)
        xb_cols = np.zeros((d, n, fd), xb_np.dtype)
        gofl = np.full((d, fd), -1, np.int32)
        local = {"num_bin": np.ones((d, fd), np.int32),
                 "missing_type": np.zeros((d, fd), np.int32),
                 "default_bin": np.zeros((d, fd), np.int32),
                 "is_categorical": np.zeros((d, fd), bool),
                 "penalty": np.ones((d, fd), np.float32),
                 "monotone": np.zeros((d, fd), np.int32)}
        for dev, cols in enumerate(assign):
            if not cols:
                continue
            cc = np.asarray(cols, np.int64)
            xb_cols[dev, :, :len(cols)] = xb_np[:, cc]
            gofl[dev, :len(cols)] = cc
            for name in local:
                local[name][dev, :len(cols)] = np.asarray(
                    getattr(meta, name))[cc]
        meta_local = FeatureMeta(
            num_bin=jnp.asarray(local["num_bin"]),
            missing_type=jnp.asarray(local["missing_type"]),
            default_bin=jnp.asarray(local["default_bin"]),
            is_categorical=jnp.asarray(local["is_categorical"]),
            penalty=jnp.asarray(local["penalty"]),
            monotone=jnp.asarray(local["monotone"]),
            col=jnp.tile(jnp.arange(fd, dtype=jnp.int32)[None], (d, 1)),
            offset=jnp.zeros((d, fd), jnp.int32),
            bundled=jnp.zeros((d, fd), bool))
        ax = mesh_mod.FEATURE_AXIS
        sh1 = NamedSharding(self.mesh, P(ax))
        # device_put straight from numpy: one sharded transfer, never a
        # full [D, N, Fd] copy committed to a single device first
        put = lambda a: jax.device_put(np.asarray(a), sh1)
        return (put(xb_cols),
                jax.tree.map(lambda a: put(a), meta_local),
                put(gofl))

    def _setup_cegb(self):
        """CEGB acquisition state (device-resident, persists across trees —
        SerialTreeLearner feature_used / feature_used_in_data,
        serial_tree_learner.cpp:103-112). None when CEGB is off."""
        cfg = self.config
        if not plan_mod.cegb_set(cfg):
            return None
        coupled = list(cfg.cegb_penalty_feature_coupled)
        lazy = list(cfg.cegb_penalty_feature_lazy)
        from ..core.grow import CegbState
        f = int(self.feature_meta.num_bin.shape[0])
        ds = self.train_data
        coupled_arr = np.zeros(f, np.float32)
        lazy_arr = np.zeros(f, np.float32)
        for i, real in enumerate(ds.used_features):
            if coupled:
                check(real < len(coupled), "cegb_penalty_feature_coupled "
                      "must cover every feature")
                coupled_arr[i] = cfg.cegb_tradeoff * float(coupled[real])
            if lazy:
                check(real < len(lazy), "cegb_penalty_feature_lazy "
                      "must cover every feature")
                lazy_arr[i] = cfg.cegb_tradeoff * float(lazy[real])
        n_lazy = self.num_data if lazy else 0
        return CegbState(
            coupled_penalty=jnp.asarray(coupled_arr),
            lazy_penalty=jnp.asarray(lazy_arr),
            feature_used=jnp.zeros((f,), bool),
            row_used=jnp.zeros((f, n_lazy), jnp.uint8))

    def _sample_feature_mask(self) -> jnp.ndarray:
        """Per-tree column sampling (serial_tree_learner.cpp:271-292)."""
        f = self.train_data.num_features
        fpad = getattr(self, "_feature_pad", 0)
        frac = self.config.feature_fraction
        if frac >= 1.0 or f == 0:
            return jnp.ones((f + fpad,), bool)
        used = max(1, int(f * frac))
        idx = self._rng.choice(f, used, replace=False)
        mask = np.zeros(f + fpad, bool)
        mask[idx] = True
        return jnp.asarray(mask)

    def _sample_bagging_mask(self, iter_idx: int) -> jnp.ndarray:
        """Row bagging (gbdt.cpp:180-241); resampled every bagging_freq.
        Ranking models bag whole query groups (one uniform per query,
        broadcast through ``_row_group``)."""
        cfg = self.config
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            return self._apply_row_valid(self._bag_mask)
        if iter_idx % cfg.bagging_freq == 0:
            self._bag_key, sub = jax.random.split(self._bag_key)
            if self._row_group is not None:
                u = jax.random.uniform(sub, (self._num_groups,))
                u = u[self._row_group]
            else:
                u = jax.random.uniform(sub, (self.num_data,))
            self._bag_mask = (u < cfg.bagging_fraction).astype(jnp.float32)
        return self._apply_row_valid(self._bag_mask)

    def _apply_row_valid(self, mask: jnp.ndarray) -> jnp.ndarray:
        """Exclude padded rows (even-sharding padding) from training."""
        if self._row_valid is not None:
            return mask * self._row_valid
        return mask

    def _make_train_iter_fn(self) -> Callable:
        """Build the jitted per-iteration function.

        Mesh-sharded constants (the binned matrix, the objective's per-row
        arrays) are ARGUMENTS, not closure captures: a multi-controller jit
        may not close over arrays that span non-addressable devices, and
        the single-process path costs nothing by sharing the convention.
        So is the per-feature metadata (zero bins, missing types, bin
        counts): closed over it would be HLO constants, and the compiled
        block's cache key would move with the data's values.
        ``self._iter_capture`` holds the tuple to pass each call.
        """
        params = self.grow_params
        plan = self.plan
        mesh = self.mesh
        obj = self.objective
        k = self.num_tree_per_iteration
        n = self.num_data
        use_input = self._use_input_grads or obj is None
        obj_row_names = _obj_row_names(obj, n, self.num_data_orig)
        self._iter_capture = (
            self.xb, tuple(getattr(obj, nm) for nm in obj_row_names),
            self._fp_capture, self.feature_meta)
        health_on = self.obs.health_enabled
        # which GOSS sampler this program holds. None: no GOSS, or under a
        # bag (_goss_bag) the unsampled iterations' program, which is
        # boosting=gbdt's; "bag": the sampled iterations' program under a
        # bag; "mask": both regimes in one program under a lax.cond
        goss_mode = None
        if self.boosting_type == "goss":
            from .goss import BAG_OTHER, BAG_TOP, sample_bag
            if not self._goss_bag:
                goss_mode = "mask"
            elif self._sampled_regime:
                goss_mode = "bag"
            n_real = self.num_data_orig
            top_cnt, other_cnt, goss_multiply = self._goss_counts

        forced_splits = self._forced_splits
        renew = None if use_input else _renew_of(obj)

        def run_iter(xb, obj_rows, fp_capture, meta, scores, sample_mask,
                     feature_mask, grad_in, hess_in, lr, goss_active,
                     goss_key, cegb_state, stopped_in, bins_by_col):
            with jax.named_scope("lgbm.gradients"):
                # gradients: objective or custom (grad_in)
                o = None if use_input else _bind(obj, obj_row_names,
                                                 obj_rows)
                g, h = (grad_in, hess_in) if use_input else \
                    _gradients(o, scores)
                if goss_mode == "mask":
                    g, h, sample_mask = _goss_as_multiplier(
                        g, h, sample_mask, goss_active, goss_key,
                        self._goss_counts, n_real)

            bag = bag_code = None
            if goss_mode == "bag":
                # GOSS one-side sampling as a bag (boosting/goss.py): a
                # code a row (the top at weight 1, exactly other_cnt of
                # the rest at the multiplier, the others out), and the row
                # partition the tree starts from, the bag's rows at its
                # front. No bagging under GOSS and no padding off a mesh:
                # the mask that comes in is all ones
                with jax.named_scope("lgbm.goss_sample"):
                    bag_code = sample_bag(jnp.sum(jnp.abs(g * h), axis=1),
                                          goss_key, top_cnt, other_cnt)
                    mult = jnp.where(
                        bag_code == BAG_TOP, 1.0,
                        jnp.where(bag_code == BAG_OTHER, goss_multiply, 0.0))
                    g = g * mult[:, None]
                    h = h * mult[:, None]
                    sample_mask = (bag_code > 0).astype(jnp.float32)
                bag = partition_mod.bag_partition(bag_code > 0,
                                                  plan.row_chunk)

            # the plan's grower and learner pick the growth call: the
            # wave-batched growers' (the shard_map and single-device
            # branches below both use it), then the learner's
            grow_batched_fn = None
            if plan.grower == plan_mod.FRONTIER:
                from ..core.grow_frontier import \
                    grow_tree_frontier as grow_batched_fn
            elif plan.grower == plan_mod.BATCHED:
                from ..core.grow_batched import \
                    grow_tree_batched as grow_batched_fn

            if plan.learner == plan_mod.FEATURE:
                # explicit feature-parallel: one shard_map over the feature
                # axis; rows replicated, column slices + local metas device-
                # varying, best splits struct-allreduced inside grow_tree
                from jax.sharding import PartitionSpec as P
                from ..parallel.mesh import FEATURE_AXIS
                from ..core.grow import FeatureParallelCtx
                tree_spec = jax.tree.map(lambda _: P(),
                                         empty_tree(params.num_leaves))
                xb_cols, meta_loc, gofl = fp_capture
                ml_specs = jax.tree.map(lambda _: P(FEATURE_AXIS), meta_loc)
                meta_specs = jax.tree.map(lambda _: P(), meta)

                def _fp_core(xbg, xbl, ml, go, gj, hj, mj, mt, fm):
                    ctx = FeatureParallelCtx(
                        xb_local=xbl[0],
                        meta_local=jax.tree.map(lambda a: a[0], ml),
                        global_of_local=go[0])
                    return grow_tree(xbg, gj, hj, mj, mt, fm, params,
                                     axis_name=FEATURE_AXIS, fp=ctx)[:2]

                grow_fp = shard_map(
                    _fp_core, mesh=mesh,
                    in_specs=(P(), P(FEATURE_AXIS), ml_specs,
                              P(FEATURE_AXIS), P(), P(), P(), meta_specs,
                              P()),
                    out_specs=(tree_spec, P()), check_vma=False)

                def grow_one(gk, hk, cs):
                    t, li = grow_fp(xb, xb_cols, meta_loc, gofl, gk, hk,
                                    sample_mask, meta, feature_mask)
                    return t, li, None, None
            elif plan.shard_mapped:
                # explicit shard_map learners (mutually exclusive configs):
                # - data-parallel partition: each device partitions its
                #   local rows and histograms the globally smaller child's,
                #   psum only on that [F, B, 3] histogram;
                # - voting-parallel: manual PV-Tree election collectives
                #   (all_gather of proposals, psum of elected candidates).
                # check_vma=False: the replicated tree output is
                # device-identical by construction (psum'd histograms /
                # identical election), but the varying-axes type system
                # cannot prove it through the growth loop
                from jax.sharding import PartitionSpec as P
                from ..parallel.mesh import DATA_AXIS
                tree_spec = jax.tree.map(lambda _: P(),
                                         empty_tree(params.num_leaves))
                meta_specs = jax.tree.map(lambda _: P(), meta)
                # neither the wave growers nor the voting learner carry
                # CEGB state: plan._refuse
                has_cegb = self._cegb_state is not None

                if grow_batched_fn is not None:
                    def _grow_core(xbj, gj, hj, mj, mt, fm):
                        return grow_batched_fn(
                            xbj, gj, hj, mj, mt, fm, params,
                            axis_name=DATA_AXIS)[:2]
                elif has_cegb:
                    from ..core.grow import CegbState

                    def _grow_core_cegb(xbj, gj, hj, mj, mt, fm, cs):
                        return tuple(grow_tree(xbj, gj, hj, mj, mt, fm,
                                               params, axis_name=DATA_AXIS,
                                               forced=forced_splits,
                                               cegb=cs))
                    # acquisition state: per-feature fields replicated,
                    # lazy per-row accounting sharded with the rows; each
                    # chip's work counts stacked over the data axis
                    cegb_specs = CegbState(
                        coupled_penalty=P(), lazy_penalty=P(),
                        feature_used=P(), row_used=P(None, DATA_AXIS))
                    grow_cegb = shard_map(
                        _grow_core_cegb,
                        mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS),
                                             P(DATA_AXIS), P(DATA_AXIS),
                                             meta_specs, P(), cegb_specs),
                        out_specs=(tree_spec, P(DATA_AXIS), cegb_specs,
                                   P(DATA_AXIS)),
                        check_vma=False)

                    def grow_one(gk, hk, cs):
                        return grow_cegb(xb, gk, hk, sample_mask, meta,
                                         feature_mask, cs)
                else:
                    # over each chip's row partition the tree's work counts
                    # leave beside it, a chip's a row
                    def _grow_core(xbj, gj, hj, mj, mt, fm):
                        grown = grow_tree(xbj, gj, hj, mj, mt, fm, params,
                                          axis_name=DATA_AXIS,
                                          forced=forced_splits)
                        return grown[:2] + ((grown.work,) if with_work
                                            else ())
                if not has_cegb:
                    with_work = plan.exact_partition
                    grow_sharded = shard_map(
                        _grow_core,
                        mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS),
                                             P(DATA_AXIS), P(DATA_AXIS),
                                             meta_specs, P()),
                        out_specs=(tree_spec, P(DATA_AXIS))
                        + ((P(DATA_AXIS),) if with_work else ()),
                        check_vma=False)

                    def grow_one(gk, hk, cs):
                        out = grow_sharded(xb, gk, hk, sample_mask, meta,
                                           feature_mask)
                        return out[0], out[1], None, \
                            out[2] if with_work else None
            elif grow_batched_fn is not None:
                def grow_one(gk, hk, cs):
                    return grow_batched_fn(xb, gk, hk, sample_mask, meta,
                                           feature_mask, params) + (None,)
            else:
                def grow_one(gk, hk, cs):
                    return grow_tree(xb, gk, hk, sample_mask, meta,
                                     feature_mask, params,
                                     forced=forced_splits, cegb=cs, bag=bag,
                                     bins_by_col=bins_by_col)

            # class batching: k == 1 calls directly, else the classes grow
            # under one vmap or in sequence, as plan.vmapped says (the
            # growers read the same flag as GrowParams.vmapped_classes).
            # every grow_one returns (tree, leaf ids, the grower's third
            # output, the tree's work counts or None: grow.Grown)
            if k == 1:
                t1, li1, cb1, wk1 = grow_one(g[:, 0], h[:, 0], cegb_state)
                trees = jax.tree.map(lambda a: a[None], t1)
                leaf_ids = li1[None]
                cegb_out = (jax.tree.map(lambda a: a[None], cb1)
                            if cb1 is not None else None)
                work = wk1[None] if wk1 is not None else None
            elif plan.vmapped:
                if plan.grower == plan_mod.FRONTIER \
                        and not plan.shard_mapped:
                    # class-batched frontier growth with the wave-width
                    # switch OUTSIDE the vmap (grow_frontier.py): the
                    # branch index is an unbatched max-live scalar, so
                    # bucketing dispatches ONE ladder branch per wave
                    # instead of vmap's execute-all-branches lowering
                    from ..core.grow_frontier import \
                        grow_tree_frontier_classes
                    trees, leaf_ids, cegb_out = grow_tree_frontier_classes(
                        xb, g.T, h.T, sample_mask, meta, feature_mask,
                        params)
                    work = None
                else:
                    trees, leaf_ids, cegb_out, work = jax.vmap(
                        grow_one, in_axes=(1, 1, None))(g, h, cegb_state)
            else:
                trees, leaf_ids, cegb_out, work = lax.map(
                    lambda gh: grow_one(gh[0], gh[1], cegb_state),
                    (g.T, h.T))
            grower_health, grower_mstats, cegb_out = _grower_aux(
                plan, health_on, cegb_out)
            if cegb_state is not None:
                # classes train from the iteration-start state; acquisitions
                # merge across class trees for the next iteration (the
                # sequential-classes analog of the reference's shared
                # learner state)
                cegb_new = cegb_state._replace(
                    feature_used=jnp.any(cegb_out.feature_used, axis=0),
                    row_used=jnp.max(cegb_out.row_used, axis=0))
            else:
                cegb_new = None
            with jax.named_scope("lgbm.score_update"):
                trees, new_scores, stopped_out, any_split = _add_trees(
                    o, renew, trees, leaf_ids, scores, sample_mask, lr,
                    stopped_in, params.num_leaves)
            health = _health(health_on, g, h, any_split, grower_health)
            # grower_mstats is None unless obs_modelstats, the class
            # trees' work counts ([K, 2, W]: grow.Grown) unless they grew
            # over the single-device row partition, the bag's codes unless
            # on a bag: a None output is an empty pytree leaf, so the
            # compiled program (and every jaxpr fingerprint) is unchanged
            # when the feature is off
            return pack_trees(trees), leaf_ids, new_scores, cegb_new, \
                stopped_out, health, grower_mstats, (work, bag_code)

        self._iter_core = run_iter   # unjitted: train_many scans over it
        return jax.jit(run_iter)

    def _make_stream_iter_fns(self) -> None:
        """Build the two jitted halves of a streamed iteration.

        The grower itself (StreamFrontierGrower) is host-driven, so the
        per-iteration device work splits around it: ``stream_pre`` turns
        scores into (possibly GOSS-resampled) gradients, ``stream_post``
        applies the grown trees to the scores with the same renew /
        stop-latch / health semantics as ``run_iter``. Both take the
        objective's per-row arrays as arguments (``_stream_capture``),
        matching the non-streamed capture convention.
        """
        obj = self.objective
        obj_row_names = _obj_row_names(obj, self.num_data,
                                       self.num_data_orig)
        self._stream_capture = tuple(getattr(obj, nm)
                                     for nm in obj_row_names)
        health_on = self.obs.health_enabled
        goss_counts = self._goss_counts
        # padded rows accumulate leaf deltas of whatever leaf id their
        # slot happens to carry, so unlike the mesh-padding case their
        # |g*h| is NOT zero — masked before ranking or they'd occupy top-k
        # slots
        row_valid = self._row_valid
        renew = _renew_of(obj)

        def stream_pre(obj_rows, scores, sample_mask, goss_active,
                       goss_key):
            g, h = _gradients(_bind(obj, obj_row_names, obj_rows), scores)
            if goss_counts is not None:
                return _goss_as_multiplier(g, h, sample_mask, goss_active,
                                      goss_key, goss_counts,
                                      self.num_data_orig, row_valid)
            return g, h, sample_mask

        def stream_post(obj_rows, trees, leaf_ids, scores, sample_mask,
                        g, h, grower_health, lr, stopped_in):
            trees, new_scores, stopped_out, any_split = _add_trees(
                _bind(obj, obj_row_names, obj_rows), renew, trees, leaf_ids,
                scores, sample_mask, lr, stopped_in,
                self.grow_params.num_leaves)
            health = _health(health_on, g, h, any_split, grower_health)
            return pack_trees(trees), new_scores, stopped_out, health

        self._stream_pre = jax.jit(stream_pre)
        self._stream_post = jax.jit(stream_post)

    def _train_one_iter_streamed(self) -> bool:
        """Streamed TrainOneIter: host wave loop over device chunks.

        Same dispatch/flush contract as ``train_one_iter`` — trees stay
        packed on device until `_materialize` — but the grower is the
        host-driven StreamFrontierGrower, so the iteration is three
        stages: jitted gradient pre-pass, per-class chunk-swept growth,
        jitted score/stop post-pass.
        """
        if self._stopped:
            return True
        _faults.inject("train_dispatch", iteration=self.iter_)
        self._boost_from_average()
        if self._stream_pre is None:
            self._make_stream_iter_fns()

        iter_idx = self.iter_
        obs = self.obs
        obs.perfetto_step(iter_idx, iter_idx + 1)
        k = self.num_tree_per_iteration
        # request-scoped iteration trace (obs/reqtrace.py): a no-op span
        # unless obs_trace is on; mirrors the serving span tree with
        # per-wave children under a per-iteration root
        tspan = obs.trace_iter(iter_idx)
        with obs.span("train.block", start_iter=iter_idx,
                      count=1) as block_span:
            self._count_block(block_span, iter_idx, 1)
            with obs.span("train.block_prepare"):
                sample_mask = self._sample_bagging_mask(iter_idx)
                feature_mask = self._sample_feature_mask()
                self._bag_key, goss_key = jax.random.split(self._bag_key)
            # the host wave loop IS the dispatch here: it returns when the
            # last stage is enqueued
            with obs.span("train.block_dispatch") as dispatch_span:
                gspan = tspan.child("gradients")
                g, h, sm = self._stream_pre(
                    self._stream_capture, self.scores, sample_mask,
                    jnp.float32(self._goss_active(iter_idx)), goss_key)
                gspan.end()
                trees_l, lids_l, aux_l = [], [], []
                for c in range(k):
                    cspan = tspan.child("tree", cls=c)
                    t, li, aux = self._stream_grower.grow(
                        g[:, c], h[:, c], sm, feature_mask,
                        trace_span=cspan if cspan else None)
                    cspan.end()
                    trees_l.append(t)
                    lids_l.append(li)
                    aux_l.append(aux)
                trees = jax.tree.map(lambda *a: jnp.stack(a), *trees_l)
                leaf_ids = jnp.stack(lids_l)
                grower_health, mstats, _ = _grower_aux(
                    self.plan, obs.health_enabled,
                    jax.tree.map(lambda *a: jnp.stack(a), *aux_l))
                pspan = tspan.child("score_commit")
                packed, new_scores, self._stopped_dev, health = \
                    self._stream_post(
                        self._stream_capture, trees, leaf_ids, self.scores,
                        sm, g, h, grower_health,
                        jnp.float32(self.shrinkage_rate), self._stopped_dev)
                pspan.end()
            if obs.enabled:
                with obs.span("train.block_wait"):
                    wspan = tspan.child("device_wait")
                    jax.block_until_ready(new_scores)  # lgbm-lint: disable=LGL103 span close
                    wspan.end()
        self.scores = new_scores

        pend: Dict[str, Any] = {"packed": packed[None],
                                "shrinkage": self.shrinkage_rate,
                                "count": 1,
                                "mstats": (mstats[None]
                                           if mstats is not None else None)}
        self._pending.append(pend)
        self.iter_ += 1
        self._dispatched(iter_idx, 1, health, block_span, dispatch_span)
        tspan.finish("ok")
        if sum(p["count"] for p in self._pending) >= self._flush_every:
            return self._materialize()
        return False

    # the block's threaded train-state buffers by run_block position:
    # scores [N, K] and the bagging mask [N].  One declaration, three
    # consumers: the executing jit below, the donation audit
    # (analysis/hlo_audit.py) and its regression test.
    TRAIN_BLOCK_DONATE = (4, 9)

    def _build_run_block(self) -> Callable:
        """The unjitted fused-block callable — separated from
        ``_make_train_block_fn`` so the donation audit can re-jit it
        with explicit ``donate_argnums`` on any backend without
        touching the executing program.

        Fuses ``block`` boosting iterations into ONE device program
        (lax.scan over the single-iteration core). The whole boosting loop
        — gradients, bagging refresh, GOSS sampling, tree growth, score
        update — runs on device with no host round trips; trees come back
        stacked [block, K, T] for the async flush. This is the TPU-native
        shape of GBDT::Train (gbdt.cpp:243-261): the reference's per-iter
        host loop exists because its learner lives in host memory; ours
        does not.
        """
        core = self._iter_core
        cfg = self.config
        n, k = self.num_data, self.num_tree_per_iteration
        bag_enabled = cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction \
            < 1.0
        freq = max(cfg.bagging_freq, 1)
        frac = cfg.bagging_fraction
        row_valid = self._row_valid
        row_group = self._row_group          # group-aware bagging (ranking)
        num_groups = getattr(self, "_num_groups", 0)
        goss_bag_sampled = self._goss_bag and self._sampled_regime

        def run_block(xb, obj_rows, fp_capture, meta, scores, feature_masks,
                      goss_actives, iter_idxs, keys, bag_mask0, cegb_state,
                      stopped_in, lr, bins_by_col):
            g0 = jnp.zeros((n, k), jnp.float32)
            h0 = jnp.ones((n, k), jnp.float32)

            def step(carry, xs):
                sc, bag_mask, cegb, stopped, _ = carry
                fm, ga, it, key = xs
                bkey, gkey = jax.random.split(key)
                if bag_enabled:
                    # bagging refresh on schedule (gbdt.cpp:180-241);
                    # ranking: one uniform per QUERY, broadcast to rows
                    with jax.named_scope("lgbm.gradients"):
                        refresh = (it % freq) == 0
                        if row_group is not None:
                            u = jax.random.uniform(bkey, (num_groups,))
                            u = u[row_group]
                        else:
                            u = jax.random.uniform(bkey, (n,))
                        new_mask = (u < frac).astype(jnp.float32)
                        bag_mask = jnp.where(refresh, new_mask, bag_mask)
                sm = bag_mask if row_valid is None else bag_mask * row_valid
                packed, _leaf_ids, sc2, cegb2, stopped2, health, ms, \
                    (work, code) = core(
                        xb, obj_rows, fp_capture, meta, sc, sm, fm, g0, h0,
                        lr, ga, gkey, cegb, stopped, bins_by_col)
                return (sc2, bag_mask, cegb2, stopped2, code), \
                    (packed, health, ms, work)

            code0 = jnp.zeros((n,), jnp.uint8) if goss_bag_sampled else None
            carry, (packs, healths, mstats, works) = lax.scan(
                step, (scores, bag_mask0, cegb_state, stopped_in, code0),
                (feature_masks, goss_actives, iter_idxs, keys))
            new_scores, bag_mask, cegb_out, stopped_out, last_code = carry
            # healths: [block, 4] per-iteration health vectors (zeros when
            # monitoring is off) — one tiny transfer per block, not per
            # iter. mstats: [block, K, F, MS_WIDTH] per-iteration model
            # statistics with obs_modelstats, else None (invisible in the
            # compiled program); works: [block, K, 2, W] work counts of
            # the trees grown over the single-device row partition, and
            # under a GOSS bag the last iteration's bag codes [N], else
            # None likewise
            return packs, healths, new_scores, bag_mask, cegb_out, \
                stopped_out, mstats, (works, last_code)

        return run_block

    def _make_train_block_fn(self) -> Callable:
        """The executing fused-block jit (see ``_build_run_block``)."""
        run_block = self._build_run_block()
        # donate the threaded train-state buffers (TRAIN_BLOCK_DONATE) —
        # both are rebound to the block's outputs by the caller, so XLA
        # may alias the output into the input allocation instead of
        # holding both live. Every backend donates (the CPU included), so
        # the CPU tests run the path the chip takes: a reference kept to
        # the old arrays across a block fails there too.
        donate = (self.TRAIN_BLOCK_DONATE
                  if self.config.tpu_donate_buffers else ())
        return jax.jit(run_block, donate_argnums=donate)

    def train_block_sds(self, block: int) -> Tuple[Any, ...]:
        """``jax.ShapeDtypeStruct`` mirrors of one ``run_block`` call at
        ``block`` fused iterations — the exact argument signature the
        executing program was compiled with.  Shared by cost-model
        extraction and the donation audit so the audited program IS the
        dispatched one (never a near-miss signature that would compile a
        second specialization)."""
        sds = jax.ShapeDtypeStruct

        def _mirror_leaf(a):
            if not hasattr(a, "shape") or not hasattr(a, "dtype"):
                return a
            try:
                return sds(a.shape, a.dtype,
                           sharding=getattr(a, "sharding", None))
            except Exception:  # noqa: BLE001 - sharding kwarg is optional
                return sds(a.shape, a.dtype)

        mirror = lambda tree: jax.tree_util.tree_map(_mirror_leaf, tree)  # noqa: E731
        f = self.train_data.num_features
        fpad = getattr(self, "_feature_pad", 0)
        key_arr = jnp.asarray(self._bag_key)
        return tuple(mirror(self._iter_capture)) + (
            mirror(self.scores),
            sds((block, f + fpad), jnp.bool_),      # feature_masks
            sds((block,), jnp.float32),             # goss_actives
            sds((block,), jnp.int32),               # iter_idxs
            sds((block,) + tuple(key_arr.shape), key_arr.dtype),
            mirror(self._bag_mask),
            mirror(self._cegb_state),
            mirror(self._stopped_dev),
            sds((), jnp.float32),                   # lr
            mirror(self._rowspace_bins()),
        )

    def warmup_wave_ladder(self) -> Dict[str, Any]:
        """Pre-compile ``build_histogram_frontier`` at every wave-width
        bucket the frontier grower can dispatch (the serving ``warmup()``
        analog for training): one all-inactive-slot call per ladder width
        on the real data shapes, so eager frontier calls after this never
        compile — and with ``compile_cache_dir`` set, later PROCESSES
        reload every specialization from disk. Returns per-bucket compile
        counts + seconds. No-op unless the booster grows frontier-mode.
        """
        from ..profiling import backend_compile_count, compile_cache_stats
        params = self.grow_params
        widths = self._frontier_widths()
        if not widths:
            return {"widths": [], "per_bucket_compiles": {},
                    "seconds": 0.0, "cache_hits": 0, "cache_misses": 0}
        from ..core.histogram import build_histogram_frontier
        n = self.num_data
        slot = jnp.full((n,), -1, jnp.int32)     # all-inactive: cheap sweep
        g = jnp.zeros((n,), jnp.float32)
        h = jnp.ones((n,), jnp.float32)
        mask = jnp.ones((n,), jnp.float32)
        before = compile_cache_stats()
        t0 = time.perf_counter()
        per_bucket: Dict[int, int] = {}
        for w in widths:
            c0 = backend_compile_count()
            # lgbm-lint: disable=LGL103 warmup probe, sync is the point
            jax.block_until_ready(build_histogram_frontier(
                self.xb, slot, g, h, mask, num_bins=params.num_bins,
                num_slots=w, row_chunk=self.plan.row_chunk,
                impl=self.plan.hist_impl,
                packed_cols=self.plan.word_packed_cols))
            per_bucket[w] = backend_compile_count() - c0
        after = compile_cache_stats()
        return {
            "widths": widths,
            "per_bucket_compiles": per_bucket,
            "seconds": time.perf_counter() - t0,
            "cache_hits": (after["persistent_cache_hits"]
                           - before["persistent_cache_hits"]),
            "cache_misses": (after["persistent_cache_misses"]
                             - before["persistent_cache_misses"]),
        }

    def _frontier_widths(self) -> List[int]:
        """The wave widths the frontier grower dispatches on one device,
        in memory (a mesh's growth compiles inside shard_map, a streamed
        one per chunk: a global-shape entry would match neither)."""
        from .. import bucketing
        p = self.grow_params
        if self.plan.grower != plan_mod.FRONTIER or self.mesh is not None:
            return []
        if self.plan.frontier_bucketing:
            return bucketing.wave_width_ladder(p.num_leaves, p.max_depth)
        return [bucketing.frontier_max_width(p.num_leaves, p.max_depth)]

    def _maybe_warm_ladder(self) -> None:
        """Run the bucket-ladder warmup once, at train start — only when a
        persistent compile cache is configured. In-process, every switch
        branch compiles INSIDE the first training block's program anyway;
        the eager ladder exists to populate the cross-process cache and to
        produce the per-bucket compile/hit/miss accounting, both of which
        only matter in compile_cache_dir runs (the CI smoke)."""
        if self._ladder_warmup is None and \
                getattr(self.config, "compile_cache_dir", ""):
            self._ladder_warmup = self.warmup_wave_ladder()

    def extract_cost_model(self, force: bool = False
                           ) -> Dict[str, Dict[str, float]]:
        """XLA cost-model extraction for this booster's compiled entry
        points (obs/costmodel.py): the fused train block at its last
        dispatched length, every frontier wave-width bucket's histogram
        sweep, and the materialize flush at its last shape.  Per-entry
        FLOPs / bytes / memory land as ``lgbm_costmodel_*`` gauges and
        feed ``GET /roofline`` and the perf gate.

        PULL-based by design: nothing in the training loop calls this,
        so ``observability=none`` runs do zero costmodel work — and with
        obs off it returns ``{}`` unless ``force=True`` (the perf gate
        forces it).  Arguments are mirrored as
        ``jax.ShapeDtypeStruct`` (sharding preserved), never sampled:
        extraction must not advance ``self._rng`` / ``self._bag_key`` or
        resumed-run byte-identity would break.  AOT lowering shares no
        cache with the executing programs, so this never recompiles or
        perturbs them (pinned by tests/test_costmodel.py).
        """
        if not (force or self.obs.enabled):
            return {}
        from ..obs.costmodel import get_cost_model
        cm = get_cost_model()

        out: Dict[str, Dict[str, float]] = {}
        block = int(getattr(self, "_last_block_len", 0) or 0)
        if self._compiled_block is not None and block > 0 \
                and getattr(self, "_iter_capture", None) is not None:
            out["train_block"] = cm.analyze(
                "train_block", self._compiled_block,
                *self.train_block_sds(block),
                extra_key="block=%d" % block)
        params = self.grow_params
        widths = self._frontier_widths()
        if widths:
            from ..core.grow_frontier import (wave_fused_entry,
                                              wave_hist_entry)
            n = self.xb.shape[0]
            # real stored-column count, not the word-matrix width: the
            # packed entry's SDS mirror derives its own word shape
            ncols = self.plan.word_packed_cols or self.xb.shape[1]
            fmask = jnp.ones((ncols,), bool)
            for w in widths:
                hfn, hargs, hkw = wave_hist_entry(
                    n, ncols, self.xb.dtype, params, w)
                name = "frontier_hist_w%d" % w
                out[name] = cm.analyze(name, hfn, *hargs, **hkw)
                # the whole fused wave region (hist -> sibling subtract
                # -> expand/fix -> 2K-child bin scan): unlike the sweep
                # alone — whose scatter update traffic is structurally
                # width-invariant (updates are [n, C, 3] whatever kw) —
                # this entry's flops/bytes genuinely scale with kw, so
                # per-bucket costs are distinguishable in the gate
                ffn, fargs, fkw = wave_fused_entry(
                    n, ncols, self.xb.dtype, self.feature_meta, fmask,
                    params, w)
                name = "frontier_wave_w%d" % w
                out[name] = cm.analyze(name, ffn, *fargs, **fkw)
        if self._stream is not None:
            # streamed growth: one fixed-width per-chunk sweep is the
            # whole kernel story — price it at the pipeline's chunk shape
            from .. import bucketing
            from ..core.grow_frontier import wave_hist_entry
            w = bucketing.frontier_max_width(params.num_leaves,
                                             params.max_depth)
            hfn, hargs, hkw = wave_hist_entry(
                self._stream.chunk_rows, self._stream.num_cols,
                jnp.uint8, params, w)
            name = "stream_chunk_hist_w%d" % w
            out[name] = cm.analyze(name, hfn, *hargs, **hkw)
        flush = list(getattr(self, "_last_flush_shapes", ()))
        if flush:
            concat = jax.jit(lambda *bufs: jnp.concatenate(bufs, axis=0))
            out["materialize"] = cm.analyze(
                "materialize", concat, *flush,
                extra_key="blocks=%d" % len(flush))
        return out

    def _block_eligible(self) -> bool:
        """Whether iterations may run as fused on-device blocks: no
        per-iteration host work inside the tree's step (TrainPlan
        .fuse_blocks; DART's drop set is host work BETWEEN iterations: it
        runs blocks of one), no gradients made on the host, and not
        observability=full, which wants TRUE per-iteration spans and
        health within one iteration (the documented basic/full trade)."""
        return (self.plan.fuse_blocks and not self._use_input_grads
                and not self.obs.per_iteration)

    def train_many(self, num_iters: int) -> bool:
        """Run ``num_iters`` iterations, fusing them into on-device blocks
        when no per-iteration host work is required. Returns True when
        training stopped. Boosting modes with per-iteration host logic
        (RF's re-averaging, custom gradients) fall back to the
        per-iteration path; percentile-renew objectives fuse fine —
        their leaf refit runs in-graph (core/renew.py).
        """
        if not self._block_eligible():
            for _ in range(num_iters):
                if self.train_one_iter():
                    return True
            return False

        self._boost_from_average()
        self._maybe_warm_ladder()

        done = 0
        while done < num_iters and not self._stopped:
            block = self._ready_block(num_iters - done)
            # train_dispatch seam (docs/Resilience.md): fires before the
            # block is dispatched; iteration = block start, round = the
            # per-point block ordinal. Two attribute checks when inert.
            _faults.inject("train_dispatch", iteration=self.iter_,
                           block_len=block)
            self._last_block_len = block
            obs = self.obs
            # before the block's span opens: a capture that starts inside
            # a span does not hold that span's annotation
            obs.perfetto_step(self.iter_, self.iter_ + block)
            with obs.span("train.block", start_iter=self.iter_,
                          count=block) as block_span:
                self._count_block(block_span, self.iter_, block)
                # feature sampling and the bag keys are host-side work: with
                # the dispatch they are the block's busy_s in the distributed
                # per-block comm/compute split
                with obs.span("train.block_prepare"):
                    fn = self._aot_blocks.get(
                        (self._sampled_regime, block), self._compiled_block)
                    fmasks, gactive, idxs, self._bag_key, keys = \
                        self._block_inputs(block)
                # the call of the compiled block until it returns; on the
                # first block: tracing, lowering, the compile or cache load
                with obs.span("train.block_dispatch") as dispatch_span:
                    packs, healths, self.scores, self._bag_mask, \
                        self._cegb_state, self._stopped_dev, mstats, \
                        grow_aux = fn(
                            *self._iter_capture,
                            self.scores, fmasks, gactive, idxs, keys,
                            self._bag_mask, self._cegb_state,
                            self._stopped_dev,
                            jnp.float32(self.shrinkage_rate),
                            self._rowspace_bins())
                if obs.enabled:
                    # basic mode's only added barrier, and the block
                    # boundary already is one for the flush cadence
                    with obs.span("train.block_wait"):
                        jax.block_until_ready(self.scores)  # lgbm-lint: disable=LGL103 span close
            self._pending.append({"packed": packs,
                                  "shrinkage": self.shrinkage_rate,
                                  "count": block,
                                  "mstats": mstats,
                                  "span": block_span})
            self._keep_aux(grow_aux, self.iter_ + block - 1)
            self.iter_ += block
            done += block
            self._dispatched(self.iter_ - block, block, healths, block_span,
                             dispatch_span, hbm=True)
            if sum(p.get("count", 1) for p in self._pending) \
                    >= self._flush_every:
                self._materialize()
        return self._stopped

    def _dispatched(self, start: int, count: int, healths, block_span,
                    dispatch_span, hbm: bool = False) -> None:
        """A dispatched block of ``count`` iterations from ``start`` to the
        observability facade (host-busy / device-wait split, rows, HBM
        after a fused block or under observability=full) and its health
        rows checked."""
        obs = self.obs
        if obs.enabled:
            hrows = np.asarray(healths).reshape(count, -1)
            dur_s, busy_s, wait_s = _host_device_split(block_span,
                                                       dispatch_span)
            obs.dispatch_done(start, count, dur_s, health_rows=hrows,
                              busy_s=busy_s, wait_s=wait_s)
            obs.account_rows(self.num_data_orig * count)
            if hbm or obs.per_iteration:
                obs.record_hbm()
            obs.check_health(hrows, start, booster=self)
        elif obs.health_enabled:
            obs.check_health(np.asarray(healths).reshape(count, -1), start,
                             booster=self)

    def _block_inputs(self, block: int):
        """A block's per-iteration inputs, made on the host: the feature
        masks, the GOSS switches, the iteration indices, and the bagging
        key chain (the next key, the block's keys)."""
        fmasks = jnp.stack([self._sample_feature_mask()
                            for _ in range(block)])
        gactive = jnp.asarray(
            [self._goss_active(self.iter_ + i) for i in range(block)],
            jnp.float32)
        # host-side arange: jnp.arange with a nonzero start compiles a
        # tiny convert_element_type on the SECOND block (start=0 takes the
        # iota path), breaking zero-recompiles-after-warmup
        idxs = jnp.asarray(np.arange(self.iter_, self.iter_ + block,
                                     dtype=np.int32))
        all_keys = jax.random.split(self._bag_key, block + 1)
        return fmasks, gactive, idxs, all_keys[0], all_keys[1:]

    def _goss_active(self, iter_idx: int) -> float:
        return 0.0

    def _enter_regime(self, iter_idx: int) -> None:
        """Under a GOSS bag the unsampled and the sampled iterations are
        two device programs of different shapes: make current the one that
        iteration ``iter_idx`` runs. A no-op everywhere else."""
        if not self._goss_bag:
            return
        now = time.perf_counter()
        if self._goss_t0 is None:
            self._goss_t0 = now
        sampled = self._goss_active(iter_idx) > 0
        if sampled == self._sampled_regime:
            return
        if sampled and iter_idx == self._goss_warmup():
            # what every GOSS job pays before its first sampled tree:
            # from its first block made ready to the switch
            record_span("train.goss_warmup", now - self._goss_t0,
                        iterations=iter_idx)
        self._programs[self._sampled_regime] = (
            self._compiled_iter, self._iter_core, self._compiled_block)
        self._compiled_iter, self._iter_core, self._compiled_block = \
            self._programs.pop(sampled, (None, None, None))
        self._sampled_regime = sampled

    def _rowspace_bins(self) -> Optional[jnp.ndarray]:
        """The last argument of a block: the feature-major bins where its
        trees route every row in row space (on a bag, or over all rows
        with TrainPlan.row_space_ids), else None (an empty pytree: a GOSS
        job's unsampled iterations keep the row partition's leaf ids)."""
        if self._sampled_regime or self.plan.row_space_ids:
            return self._bins_by_col
        return None

    def _ready_block(self, remaining: int) -> int:
        """Make current, and build if need be, the program the next block
        runs; return that block's length: at most 64 iterations, and none
        across a GOSS bag's switch from unsampled to sampled."""
        self._enter_regime(self.iter_)
        if self._iter_core is None or self._compiled_block is None:
            with self.obs.span("train.make_block_fn"):
                if self._iter_core is None:
                    self._compiled_iter = self._make_train_iter_fn()
                if self._compiled_block is None:
                    # one jitted scan; jax caches a compilation per block
                    # length
                    self._compiled_block = self._make_train_block_fn()
        block = min(remaining, 64)
        if self._goss_bag and not self._sampled_regime:
            block = min(block, self._goss_warmup() - self.iter_)
        return block

    def compile_block(self, num_iters: int) -> None:
        """Trace, lower and compile (or load from the persistent cache)
        the block the next ``train_many(num_iters)`` dispatches first, and
        run no iteration: a caller that times its blocks pays the compile
        when it chooses to, as a GOSS job about to cross from its unsampled
        iterations to the sampled ones, whose program is another, or a job
        resumed from a snapshot."""
        self._boost_from_average()
        block = self._ready_block(num_iters)
        with self.obs.span("train.compile_block", count=block):
            self._aot_blocks[(self._sampled_regime, block)] = \
                self._compiled_block.lower(
                    *self.train_block_sds(block)).compile()
            # the small programs that make its inputs, made once and
            # thrown away: the feature sampler's state is put back
            rng_state = self._rng.get_state()
            self._block_inputs(block)
            self._rng.set_state(rng_state)

    def _keep_aux(self, grow_aux, iteration: int) -> None:
        """What a block hands out besides its trees: their work counts
        (grow.Grown), which stay on the device until the host fetches the
        trees and join the block's span there, and on a GOSS bag its last
        iteration's bag, which stays on the device as ``last_bag``."""
        work, code = grow_aux
        if work is not None:
            self._pending[-1]["work"] = work
        if code is not None:
            self.last_bag = (iteration, code)

    def _count_block(self, block_span, start_iter: int, count: int) -> None:
        """What a ``train.block`` span counts of its boosting mode, known
        on the host when it opens. GOSS: whether its iterations sample
        and, where they do, the others drawn an iteration (the draw is
        exact: the count is other_cnt)."""
        if self.boosting_type != "goss":
            return
        active = self._goss_active(start_iter + count - 1) > 0
        block_span.counts["goss_active"] = int(active)
        if active:
            block_span.counts["bag_other_rows"] = self._goss_counts[1]

    @property
    def models(self) -> List[HostTree]:
        """Materialized HostTrees; flushes any pending device trees first."""
        self._materialize()
        return self._models

    @models.setter
    def models(self, value: List[HostTree]) -> None:
        # wholesale assignment (model load / refit) discards pending work
        self._pending.clear()
        self._stopped = False
        self._stopped_dev = jnp.asarray(False)
        self._models = list(value)

    # ------------------------------------------------- checkpoint state
    def _capture_rows(self, arr) -> np.ndarray:
        """Host copy of a per-row device array for checkpointing. Under a
        multi-process mesh the array is row-sharded and NOT fully
        addressable; each process captures its OWN rows (sorted shard
        order), and ``_restore_rows`` rebuilds the global array from that
        local block — per-rank snapshots stay rank-local, matching the
        rank-folded dataset fingerprint that guards shard reassignment."""
        arr = jnp.asarray(arr)
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards])

    def _restore_rows(self, host, extra_dims: int = 0):
        """Inverse of ``_capture_rows``: device array in the training
        row layout from a host capture (global when fully addressable,
        this process's rows otherwise)."""
        host = np.asarray(host)
        if self.mesh is None:
            return jnp.asarray(host)
        from ..parallel import mesh as mesh_mod
        sh = mesh_mod.row_sharding(self.mesh, extra_dims=extra_dims)
        if host.shape[0] == self.num_data:
            return jax.device_put(host, sh)
        pid = jax.process_index()
        devices = list(np.asarray(self.mesh.devices).reshape(-1))
        local = [d for d in devices if d.process_index == pid]
        if not local or host.shape[0] % len(local):
            raise LightGBMError(
                "checkpointed row block of %d rows does not tile over %d "
                "local mesh devices — was the snapshot written under a "
                "different mesh?" % (host.shape[0], len(local)))
        blk = host.shape[0] // len(local)
        bufs = [jax.device_put(host[i * blk:(i + 1) * blk], d)
                for i, d in enumerate(local)]
        return jax.make_array_from_single_device_arrays(
            (self.num_data,) + host.shape[1:], sh, bufs)

    def training_state(self):
        """Complete mutable training state as ``(meta, arrays)`` — the
        checkpoint subsystem's capture point (lightgbm_tpu.checkpoint).

        ``meta`` is JSON-safe scalars (iteration cursors, RNG cursors,
        tree shape lists); ``arrays`` is numpy payloads (raw HostTree
        fields, f32 scores, PRNGKey, Mersenne-Twister keys, valid-set
        score caches, CEGB leaves). Restoring these verbatim — instead of
        replaying trees — is what keeps a resumed run bit-identical.
        """
        from ..checkpoint import snapshot as snap_mod
        self._materialize()
        meta: Dict[str, Any] = {
            "boosting_type": self.boosting_type,
            "iteration": int(self.iter_),
            "num_init_iteration": int(self.num_init_iteration),
            "stopped": bool(self._stopped),
            "shrinkage_rate": float(self.shrinkage_rate),
            "boost_from_average_done": bool(self.boost_from_average_done),
        }
        arrays: Dict[str, np.ndarray] = {
            "scores": self._capture_rows(self.scores),
            "bag_key": np.asarray(self._bag_key),
            "bag_mask": self._capture_rows(self._bag_mask),
            "stopped_dev": np.asarray(self._stopped_dev),
        }
        ff_meta, ff_keys = snap_mod.rng_state_split(self._rng)
        meta["ff_rng"] = ff_meta
        arrays["ff_rng_keys"] = ff_keys
        # training data profile (obs.drift): rides the JSON meta into
        # snapshot meta.json so serving can score drift against it.
        # Absence is legal (pre-profile snapshots keep loading; drift
        # surfaces report "no_profile"), so failures only warn.
        if self.train_data is not None:
            try:
                meta["data_profile"] = \
                    self.train_data.data_profile().to_json_dict()
            except Exception as e:  # noqa: BLE001 - profile is best-effort
                Log.warning("data profile capture failed (%s); snapshot "
                            "will carry none", e)
        inits = getattr(self, "init_score_offsets", None)
        if inits is not None:
            arrays["init_score_offsets"] = np.asarray(inits)
        if self._cegb_state is not None:
            for j, leaf in enumerate(
                    jax.tree_util.tree_leaves(self._cegb_state)):
                arrays["cegb_%d" % j] = np.asarray(leaf)
        for vi, cache in self._valid_pred_cache.items():
            arrays["valid%d_scores" % vi] = np.asarray(cache["scores"])
        tree_meta, tree_arrays = snap_mod.trees_to_arrays(self._models)
        meta["trees"] = tree_meta
        arrays.update(tree_arrays)
        return meta, arrays

    def load_training_state(self, meta, arrays) -> None:
        """Inverse of training_state; the driver must have been built with
        the same config/data (checkpoint.snapshot.check_compatibility)."""
        from ..checkpoint import snapshot as snap_mod
        # property setter clears pending work and the stop latches
        self.models = snap_mod.trees_from_arrays(meta["trees"], arrays)
        self.iter_ = int(meta["iteration"])
        self.num_init_iteration = int(meta["num_init_iteration"])
        self.shrinkage_rate = float(meta["shrinkage_rate"])
        self.boost_from_average_done = bool(meta["boost_from_average_done"])
        self._stopped = bool(meta["stopped"])
        self._stopped_dev = (jnp.asarray(bool(arrays["stopped_dev"]))
                             if "stopped_dev" in arrays
                             else jnp.asarray(self._stopped))
        if "scores" in arrays:
            self.scores = self._restore_rows(
                np.asarray(arrays["scores"], np.float32), extra_dims=1)
        else:
            # a snapshot of the trees alone: their scores replayed
            self._scores_from_trees()
        self._bag_key = jnp.asarray(arrays["bag_key"], dtype=jnp.uint32)
        if "bag_mask" in arrays:
            self._bag_mask = self._restore_rows(
                np.asarray(arrays["bag_mask"], np.float32))
        self._rng.set_state(snap_mod.rng_state_join(meta["ff_rng"],
                                                    arrays["ff_rng_keys"]))
        if "init_score_offsets" in arrays:
            self.init_score_offsets = np.asarray(
                arrays["init_score_offsets"], np.float32)
        if self._cegb_state is not None and "cegb_0" in arrays:
            leaves, treedef = jax.tree_util.tree_flatten(self._cegb_state)
            self._cegb_state = jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(arrays["cegb_%d" % j])
                          for j in range(len(leaves))])
        k = self.num_tree_per_iteration
        for vi, cache in self._valid_pred_cache.items():
            key = "valid%d_scores" % vi
            if key in arrays:
                # verbatim restore: bit-identical eval history on resume
                cache["scores"] = jnp.asarray(
                    np.asarray(arrays[key], np.float32))
            else:
                Log.warning(
                    "checkpoint has no score cache for validation set %d "
                    "(added after the snapshot was written?); replaying "
                    "trees — eval values may differ in the last ulp", vi)
                cache["scores"], _ = self._replay_add(
                    cache["scores"], self._models,
                    [i % k for i in range(len(self._models))], 1.0,
                    xb=cache["xb"])

    def warn_lossy_continuation(self) -> None:
        """Warn loudly when continued training from a bare ``init_model``
        silently restarts sampling state from the seeds (the trees survive
        the model file; the RNG cursors do not). Checkpoint resume
        (engine.train(resume_from=...)) restores them exactly."""
        cfg = self.config
        lost = []
        if cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction < 1.0:
            lost.append("bagging PRNGKey")
        if cfg.feature_fraction < 1.0:
            lost.append("feature_fraction RandomState")
        if self.boosting_type == "goss":
            lost.append("GOSS sampling key")
        if lost:
            Log.warning(
                "Continued training from init_model: %s restart(s) from "
                "the configured seed(s), so results WILL diverge from an "
                "uninterrupted run. Use checkpoints "
                "(engine.train(resume_from=<dir>)) for exact continuation.",
                ", ".join(lost))

    def enable_health_monitor(self, action: str = "warn") -> None:
        """Arm device-side health monitoring (``callback.health_monitor``).
        When armed before the first compile — the callback's
        ``before_iteration`` slot at iteration 0 — nothing rebuilds; arming
        mid-train discards the compiled step so the health branch enters
        the program from the next dispatch."""
        if not self.obs.arm_health(action):
            return
        if self._compiled_iter is not None or \
                self._compiled_block is not None:
            Log.warning("health_monitor armed after compilation; "
                        "rebuilding the training step with device-side "
                        "health flags")
        self._compiled_iter = None
        self._iter_core = None
        self._compiled_block = None
        self._programs.clear()
        self._aot_blocks.clear()
        if self.plan is not None and self.plan.obs_aux:
            self.grow_params = self.grow_params._replace(obs_health=True)

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (gbdt.cpp TrainOneIter:333-412).

        Returns True when training should stop (no splittable tree). The
        iteration is dispatched asynchronously: trees stay on device and
        host materialization is deferred to `_materialize` (so the stop may
        be reported up to `_flush_every` iterations late; the in-graph
        score gating makes the overshoot iterations exact no-ops).
        """
        if self._stopped:
            return True
        if self._stream is not None:
            if grad is not None or self._use_input_grads:
                raise LightGBMError(
                    "streamed training does not support externally "
                    "supplied gradients; use a built-in objective or "
                    "unset data_stream_chunk_rows")
            return self._train_one_iter_streamed()
        _faults.inject("train_dispatch", iteration=self.iter_)
        self._boost_from_average()
        self._maybe_warm_ladder()
        self._enter_regime(self.iter_)
        if self._compiled_iter is None:
            with self.obs.span("train.make_block_fn"):
                self._compiled_iter = self._make_train_iter_fn()

        iter_idx = self.iter_
        obs = self.obs
        obs.perfetto_step(iter_idx, iter_idx + 1)
        # one iteration dispatched alone is a block of one: the same spans
        # as train_many's
        with obs.span("train.block", start_iter=iter_idx,
                      count=1) as block_span:
            self._count_block(block_span, iter_idx, 1)
            with obs.span("train.block_prepare"):
                sample_mask = self._sample_bagging_mask(iter_idx)
                feature_mask = self._sample_feature_mask()

                n, k = self.num_data, self.num_tree_per_iteration
                if grad is not None:
                    g_in = jnp.asarray(
                        np.asarray(grad, np.float32).reshape(k, n).T
                        if np.asarray(grad).ndim == 1 and k > 1
                        else np.asarray(grad, np.float32).reshape(n, k))
                    h_in = jnp.asarray(
                        np.asarray(hess, np.float32).reshape(k, n).T
                        if np.asarray(hess).ndim == 1 and k > 1
                        else np.asarray(hess, np.float32).reshape(n, k))
                elif self._use_input_grads:
                    g_in, h_in = self._fixed_gradients()
                else:
                    g_in = jnp.zeros((n, k), jnp.float32)
                    h_in = jnp.ones((n, k), jnp.float32)

                self._bag_key, goss_key = jax.random.split(self._bag_key)
            with obs.span("train.block_dispatch") as dispatch_span:
                packed, leaf_ids, new_scores, cegb_new, self._stopped_dev, \
                    health, mstats, grow_aux = self._compiled_iter(
                        *self._iter_capture,
                        self.scores, sample_mask, feature_mask, g_in, h_in,
                        jnp.float32(self.shrinkage_rate),
                        jnp.float32(self._goss_active(iter_idx)), goss_key,
                        self._cegb_state, self._stopped_dev,
                        self._rowspace_bins())
            if obs.enabled:
                # the per-iteration path is already the slow
                # (full/host-logic) path, so one barrier per iteration is
                # the accepted cost of true spans
                with obs.span("train.block_wait"):
                    jax.block_until_ready(new_scores)  # lgbm-lint: disable=LGL103 span close
        self.scores = new_scores
        self._cegb_state = cegb_new

        pend: Dict[str, Any] = {"packed": packed[None],  # [1, K, T] block
                                "shrinkage": self.shrinkage_rate,
                                "count": 1,
                                "mstats": (mstats[None]
                                           if mstats is not None else None),
                                "span": block_span}
        self._pending.append(pend)
        self._keep_aux(grow_aux, iter_idx)
        self.iter_ += 1
        self._dispatched(iter_idx, 1, health, block_span, dispatch_span)
        if sum(p["count"] for p in self._pending) >= self._flush_every:
            return self._materialize()
        return False

    def _materialize(self) -> bool:
        """Flush pending device trees to HostTrees (one batched transfer).

        Returns True if training has stopped (a fully-stumped iteration was
        found; later pending iterations are no-ops by construction and are
        discarded).
        """
        if not self._pending:
            return self._stopped
        pend, self._pending = self._pending, []
        k = self.num_tree_per_iteration
        l = self.config.num_leaves
        # every pending entry is a [B_i, K, T] block (B_i == 1 for
        # per-iteration dispatches); ONE transfer for the whole backlog
        self._last_flush_shapes = [
            jax.ShapeDtypeStruct(p["packed"].shape, p["packed"].dtype)
            for p in pend]
        with self.obs.span("materialize", blocks=len(pend)):
            buf = np.asarray(jnp.concatenate([p["packed"] for p in pend],
                                             axis=0))  # [sum(B_i), K, T]
        for p in pend:
            # a span never waits for the device: the counts its block made
            # there join it here, where the host fetches the trees
            if p.get("work") is not None:
                p["span"].counts.update(_work_counts(p["work"]))
        row = 0
        with_cat = self.plan.facts.categorical > 0
        for p in pend:
            if self._stopped:
                break
            for bi in range(p["count"]):
                host_trees = []
                any_split = False
                for c in range(k):
                    t = unpack_tree(buf[row, c], l)
                    ht = self._extract_host_tree(t)
                    if ht.num_leaves_actual > 1:
                        any_split = True
                    host_trees.append(ht)
                    if p.get("span") is not None:
                        self._count_tree(p, ht, with_cat)
                row += 1
                if not any_split:
                    Log.warning("Stopped training because there are no "
                                "more leaves that meet the split "
                                "requirements")
                    if not self._models:
                        # keep a constant tree so the model reproduces the
                        # init score (AsConstantTree, gbdt.cpp:379-396)
                        inits = getattr(self, "init_score_offsets",
                                        np.zeros(k, np.float32))
                        for c in range(k):
                            ht = host_trees[c]
                            ht.num_leaves_actual = 1
                            ht.leaf_value[:] = 0.0
                            ht.leaf_value[0] = float(inits[c])
                            ht.split_leaf[:] = -1
                            self._models.append(ht)
                    self._stopped = True
                    self.iter_ = len(self._models) // max(k, 1)
                    break
                self._store_host_trees(host_trees, p)
                if self._modelstats is not None:
                    # model statistics track the KEPT model list exactly:
                    # stump/overshoot iterations broke out above, so this
                    # runs once per stored iteration. ingest after the
                    # store so leaf values are the final (shrunk,
                    # bias-folded) model values. Device accumulators
                    # transfer once per pending entry, lazily.
                    dev_rows = None
                    if p.get("mstats") is not None:
                        if "mstats_host" not in p:
                            p["mstats_host"] = np.asarray(p["mstats"])
                        dev_rows = p["mstats_host"][bi]
                    self._modelstats.ingest_iteration(
                        host_trees, len(self._models) // max(k, 1) - 1,
                        device_rows=dev_rows)
        return self._stopped

    @staticmethod
    def _count_tree(pend: Dict[str, Any], ht: HostTree,
                    with_cat: bool) -> None:
        """What a fetched tree adds to its block's span, joined here as the
        device's work counts are: its splits; under a grower with no tile
        (no work counts from the device) the rows they split, as the tree
        says them; on a table with categorical columns the splits on
        one."""
        counts = pend["span"].counts
        nn = max(ht.num_leaves_actual - 1, 0)
        counts["splits"] = counts.get("splits", 0) + nn
        if pend.get("work") is None:
            counts["split_rows"] = counts.get("split_rows", 0) + int(
                ht.internal_count[:nn].sum(dtype=np.int64))
        if with_cat:
            counts["cat_splits"] = counts.get("cat_splits", 0) + int(
                np.count_nonzero(ht.is_categorical[:nn]))

    def _store_host_trees(self, host_trees: List[HostTree],
                          pend: Dict[str, Any]) -> None:

        """Renew/shrink/bias-fold one flushed iteration's trees and append
        them to the model list (the tail of the reference's TrainOneIter)."""
        k = self.num_tree_per_iteration
        first_iter = not self._models
        for ht in host_trees:
            ht.shrink(pend["shrinkage"])
        # valid scores get the shrunk tree output (pre-bias; their init score
        # was added by _boost_from_average already)
        self._update_valid_scores(host_trees)
        if first_iter:
            # fold the init score into the first iteration's trees so the
            # saved model is self-contained (AddBias, gbdt.cpp:374-376)
            inits = getattr(self, "init_score_offsets", np.zeros(k, np.float32))
            for c, ht in enumerate(host_trees):
                if abs(float(inits[c])) > 1e-15:
                    ht.leaf_value += float(inits[c])
                    ht.internal_value += float(inits[c])
        self._models.extend(host_trees)

    def _extract_host_tree(self, t) -> HostTree:
        """TreeArrays (device) -> HostTree with real thresholds."""
        ds = self.train_data
        l = self.config.num_leaves
        ht = HostTree(l)
        nl = int(t.num_leaves)
        ht.num_leaves_actual = nl
        nn = nl - 1
        used = np.arange(nn)
        inner_feat = t.split_feature[:nn].astype(np.int64)
        ht.split_feature[:nn] = np.array(
            [ds.real_feature_index(int(j)) for j in inner_feat], np.int32)
        ht.split_gain[:nn] = t.split_gain[:nn]
        ht.threshold_bin[:nn] = t.threshold_bin[:nn]
        # raw-value bitsets are variable-width (Tree cat_threshold_,
        # tree.h:276-291): wide enough for the largest category value that
        # goes LEFT at any node of this tree (not the dataset's largest id:
        # at ids up to 10M that is 300 MB a tree, and a category that goes
        # right needs no bit)
        left_vals = {}
        for i in range(nn):
            if bool(t.is_categorical[i]):
                mapper = ds.bin_mappers[int(ht.split_feature[i])]
                words = np.asarray(t.cat_bitset[i], np.uint32)
                in_set = ((words[:, None] >> np.arange(32, dtype=np.uint32))
                          & 1).astype(bool).reshape(-1)[1:mapper.num_bin]
                left_vals[i] = np.asarray(mapper.bin_2_categorical,
                                          np.int64)[:len(in_set)][in_set]
        max_cat_val = max((int(v.max()) for v in left_vals.values()
                           if len(v)), default=0)
        cat_words = max(8, (max_cat_val + 32) // 32)
        ht.cat_bitset = np.zeros((max(nn, 1), cat_words), np.uint32)
        for i in range(nn):
            mapper = ds.bin_mappers[int(ht.split_feature[i])]
            if bool(t.is_categorical[i]):
                ht.threshold[i] = 0.0
                # the bin-space bitset as raw category values, for
                # raw-input prediction and model serialization (the
                # reference stores cat_threshold in value space, tree.cpp)
                v = left_vals[i]
                np.bitwise_or.at(ht.cat_bitset[i], v >> 5,
                                 np.uint32(1) << (v & 31).astype(np.uint32))
            else:
                tb = int(t.threshold_bin[i])
                # the last numeric bin of a column with a NaN bin is
                # bounded by +inf; a split there (NaN against the rest) is
                # stored as upstream's Common::AvoidInf stores it
                # (tree.h Split), so that the model text can hold it
                ht.threshold[i] = min(max(mapper.bin_to_value(tb), -1e300),
                                      1e300)
        ht.default_left[:nn] = t.default_left[:nn]
        ht.missing_type[:nn] = t.missing_type[:nn]
        ht.is_categorical[:nn] = t.is_categorical[:nn]
        ht.cat_bitset_bin[:nn] = t.cat_bitset[:nn]
        ht.left_child[:nn] = t.left_child[:nn]
        ht.right_child[:nn] = t.right_child[:nn]
        ht.split_leaf[:nn] = t.split_leaf[:nn]
        ht.internal_value[:nn] = t.internal_value[:nn]
        ht.internal_weight[:nn] = t.internal_weight[:nn]
        ht.internal_count[:nn] = np.round(t.internal_count[:nn]).astype(np.int64)
        ht.leaf_value[:] = t.leaf_value[:l]
        ht.leaf_weight[:] = t.leaf_weight[:l]
        ht.leaf_count[:] = np.round(t.leaf_count[:l]).astype(np.int64)
        return ht

    # ------------------------------------------------------------ scoring
    def _update_valid_scores(self, host_trees: List[HostTree]) -> None:
        """Add the new trees' output to each valid set's running scores via
        binned replay (ScoreUpdater::AddScore whole-tree path)."""
        if not self.valid_data:
            return
        classes = range(len(host_trees))
        for cache in self._valid_pred_cache.values():
            cache["scores"], _ = self._replay_add(
                cache["scores"], host_trees, classes, 1.0, xb=cache["xb"])

    def _replay_add(self, scores, trees: Sequence[HostTree],
                    classes: Sequence[int], scale: float, xb=None,
                    size: Optional[int] = None):
        """``scores`` + ``scale`` x the summed output of ``trees`` (each
        into its class's column) and that sum, ``[N, K]`` float32: one
        device program per REPLAY_BUCKET_MAX trees (core/replay.py). Over
        the training table (``xb`` None) from its feature-major copy where
        the booster holds one; over a validation cache's row-major ``xb``
        otherwise. ``size``: the trees' stacked count (replay.bucket).
        Under the booster's one REPLAY_SCOPE."""
        program = replay_mod.replay_program(self.REPLAY_SCOPE)
        total = None
        trees, classes = list(trees), list(classes)
        for lo in range(0, len(trees), replay_mod.REPLAY_BUCKET_MAX):
            part = trees[lo:lo + replay_mod.REPLAY_BUCKET_MAX]
            stacked = self._replay_layout_of().stack(
                part, classes[lo:lo + len(part)], size)
            bins, statics = self._replay_input(xb)
            scores, sub = self._replay_call(
                program, (scores, stacked, np.int32(len(part)),
                          np.float32(scale), bins), statics)
            total = sub if total is None else total + sub
        if total is None:
            total = jnp.zeros_like(scores)
        return scores, total

    def _replay_layout_of(self) -> "replay_mod.TableLayout":
        if self._replay_layout is None:
            self._replay_layout = replay_mod.TableLayout(self.train_data)
        return self._replay_layout

    def _replay_input(self, xb=None):
        """What a replay reads and how: the training table's feature-major
        copy where the booster holds one, else the row-major ``xb`` (the
        training table's where None); the replay program's statics, the
        kernel's over the copy where it engages (TrainPlan.replay_kernel)."""
        layout = self._replay_layout_of()
        feature_major = xb is None and self._bins_by_col is not None
        kernel = feature_major and self.plan.replay_kernel
        # the train matrix may be word-packed (int32 words); its
        # feature-major copy, made before the packing, and the valid
        # caches hold plain uint8 columns
        packed = (xb is None and not feature_major
                  and self.plan.word_packed_cols > 0
                  and self.xb.dtype == jnp.int32)
        bins = (self._bins_by_col if feature_major
                else self.xb if xb is None else xb)
        return bins, dict(feature_major=feature_major, packed=packed,
                          pairs=layout.pairs, categorical=layout.categorical,
                          kernel=kernel, interpret=kernel
                          and not self.plan.facts.tpu_shaped)

    @staticmethod
    def _replay_key(fn, args):
        return (fn.__name__, tuple((a.shape, str(a.dtype))
                                   for a in jax.tree_util.tree_leaves(args)))

    def _replay_call(self, fn, args, statics):
        """``fn(*args, **statics)``, through the executable compile_replay
        readied for these shapes where there is one."""
        exe = self._aot_replay.get(self._replay_key(fn, args))
        if exe is not None:
            return exe(*args)
        return fn(*args, **statics)

    def _ready_replay(self, fn, args, statics) -> None:
        """Trace, lower and compile (or load from the persistent cache)
        ``fn`` for ``args``' shapes, and run nothing (compile_block's
        counterpart for a replay)."""
        sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        self._aot_replay[self._replay_key(fn, args)] = \
            fn.lower(*sds, **statics).compile()

    def _scores_from_trees(self) -> None:
        """The training scores of a booster that has trained nothing,
        rebuilt from the stored trees (the first carries the init score):
        a snapshot that holds the trees and not the scores they sum to
        resumes through here."""
        k = self.num_tree_per_iteration
        with recorder.span(self.FOREST_SCORES_SPAN, trees=len(self._models),
                           rows=self.num_data):
            self.scores, _ = self._replay_add(
                self.scores, self._models,
                [i % k for i in range(len(self._models))], 1.0)
            jax.block_until_ready(self.scores)  # lgbm-lint: disable=LGL103 span close

    # ------------------------------------------------------------ evaluation
    def get_eval_at(self, data_idx: int) -> List[Tuple[str, str, float, bool]]:
        """Eval metrics for data_idx (0=train, 1..=valid); returns
        (data_name, metric_name, value, bigger_better) tuples
        (gbdt.cpp OutputMetric:476-533)."""
        # valid-set score caches advance at materialization time
        self._materialize()
        out = []
        conv = (self.objective.convert_output if self.objective is not None
                else None)
        if data_idx == 0:
            if self._stream_perm is not None:
                # streamed mesh: scores live in the shard-major padded
                # layout; gather original-row order back (train-set eval
                # under a MULTI-process mesh is not supported — the
                # global scores are not host-addressable from one rank)
                if not getattr(self.scores, "is_fully_addressable", True):
                    raise LightGBMError(
                        "train-set metrics are not available under "
                        "multi-process streamed training; evaluate on a "
                        "valid set or predict() from the saved model")
                scores = np.asarray(self.scores)[self._stream_perm]
            else:
                scores = np.asarray(self.scores)[:self.num_data_orig]
            for m in self.train_metrics:
                vals = m.eval(scores if self.num_tree_per_iteration > 1
                              else scores[:, 0], conv)
                for name, v in zip(m.names, vals):
                    out.append(("training", name, v, m.factor_to_bigger_better > 0))
        else:
            vi = data_idx - 1
            scores = np.asarray(self._valid_pred_cache[vi]["scores"])
            for m in self.valid_metrics[vi]:
                vals = m.eval(scores if self.num_tree_per_iteration > 1
                              else scores[:, 0], conv)
                for name, v in zip(m.names, vals):
                    out.append(("valid_%d" % (vi + 1) if vi > 0 else "valid_0",
                                name, v, m.factor_to_bigger_better > 0))
        return out

    # ------------------------------------------------------------ prediction
    def _stacked_predict_trees(self, start: int, end: int) -> tree_mod.PredictTree:
        trees = self.models[start:end]
        max_nodes = max((t.num_nodes for t in trees), default=1)
        max_leaves = max((t.num_leaves for t in trees), default=1)
        cat_words = max((t.cat_bitset.shape[1] for t in trees), default=8)
        tables = [t.predict_table(max_nodes, max_leaves, cat_words)
                  for t in trees]
        return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *tables)

    def predict(self, data: np.ndarray, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0) -> np.ndarray:
        """Batch prediction on raw feature values (GBDT::Predict,
        gbdt_prediction.cpp:49-83; early stop:
        src/boosting/prediction_early_stop.cpp)."""
        data = np.asarray(data, np.float32)
        if data.ndim == 1:
            data = data.reshape(1, -1)
        k = self.num_tree_per_iteration
        total_iters = len(self.models) // k
        use_iters = total_iters if num_iteration is None or num_iteration <= 0 \
            else min(num_iteration, total_iters)
        n = data.shape[0]
        if pred_early_stop and self.objective is not None \
                and self.objective.need_accurate_prediction:
            # reference only early-stops classification margins
            # (predictor.hpp:39, NeedAccuratePrediction)
            pred_early_stop = False
        if use_iters == 0:
            out = np.zeros((n, k), np.float64)
        elif pred_early_stop and not pred_leaf:
            x = jnp.asarray(data)
            flat = self._stacked_predict_trees(0, use_iters * k)
            stacked = jax.tree.map(
                lambda a: a.reshape((use_iters, k) + a.shape[1:]), flat)
            out = np.asarray(tree_mod.predict_forest_early_stop(
                stacked, x, max(pred_early_stop_freq, 1),
                pred_early_stop_margin, is_multiclass=(k > 1)), np.float64)
            if self.average_output:
                out = out / use_iters
            if not raw_score and self.objective is not None:
                out = np.asarray(self.objective.convert_output(jnp.asarray(out)))
            return out[:, 0] if k == 1 else out
        else:
            x = jnp.asarray(data)
            outs = []
            for c in range(k):
                idxs = [it * k + c for it in range(use_iters)]
                trees = [self.models[i] for i in idxs]
                max_nodes = max(t.num_nodes for t in trees)
                max_leaves = max(t.num_leaves for t in trees)
                tables = [t.predict_table(max_nodes, max_leaves) for t in trees]
                stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                                       *tables)
                if pred_leaf:
                    outs.append(np.asarray(
                        tree_mod.predict_forest_leaves_raw(stacked, x)))
                else:
                    outs.append(np.asarray(
                        tree_mod.predict_forest_raw(stacked, x), np.float64))
            if pred_leaf:
                return np.stack(outs, axis=1).reshape(n, -1) if k > 1 else outs[0]
            out = np.stack(outs, axis=1)
        if self.average_output and use_iters > 0:
            out = out / use_iters
        if not raw_score and self.objective is not None:
            out = np.asarray(self.objective.convert_output(jnp.asarray(out)))
        return out[:, 0] if k == 1 else out

    # ------------------------------------------------------------ management
    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:414-430)."""
        if self.iter_ <= 0:
            return
        if self._stream is not None:
            raise LightGBMError(
                "rollback_one_iter needs the full binned matrix to replay "
                "dropped trees; it is not supported with streamed "
                "training (data_stream_chunk_rows > 0)")
        k = self.num_tree_per_iteration
        dropped = self.models[-k:]
        del self.models[-k:]
        # recompute training scores by subtracting the dropped trees
        classes = range(len(dropped))
        self.scores, _ = self._replay_add(self.scores, dropped, classes, -1.0)
        for cache in self._valid_pred_cache.values():
            cache["scores"], _ = self._replay_add(
                cache["scores"], dropped, classes, -1.0, xb=cache["xb"])
        self.iter_ -= 1

    @property
    def current_iteration(self) -> int:
        # must materialize: dispatched iterations past a device-detected
        # stop get discarded at flush, so the pending count alone would
        # overstate the model length (and poison best_iteration)
        self._materialize()
        return len(self._models) // max(self.num_tree_per_iteration, 1)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """GBDT::FeatureImportance (gbdt.cpp era)."""
        num_feat = self.train_data.num_total_features if self.train_data \
            else (int(max((t.split_feature.max(initial=-1)
                           for t in self.models), default=-1)) + 1)
        imp = np.zeros(num_feat, np.float64)
        k = self.num_tree_per_iteration
        n_models = (len(self.models) if iteration is None or iteration <= 0
                    else min(iteration * k, len(self.models)))
        for t in self.models[:n_models]:
            for i in range(t.num_nodes):
                if t.split_leaf[i] >= 0:
                    if importance_type == "split":
                        imp[t.split_feature[i]] += 1
                    else:
                        imp[t.split_feature[i]] += t.split_gain[i]
        return imp
