"""Which path a training job takes, decided in one place:
``resolve_plan(cfg, facts)`` from the configuration and what set-up
observes before any device work, every refused combination in one table
(``_refuse``). It takes no jax array and makes no device call, so a
benchmark cell's plan at its true size resolves on any host
(tests/test_train_plan.py). GBDT._setup_train builds from the plan; the
growers read it through the GrowParams flags it fills."""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

from ..config import Config
from ..core import partition
from ..core import replay as replay_mod
from ..core.binpack import resolve_bin_packing
from ..core.histogram import hist_kernel
from ..log import LightGBMError
from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS

EXACT, BATCHED, FRONTIER, STREAM_FRONTIER = (
    "exact", "batched", "frontier", "stream_frontier")
# serial: no mesh. data: the explicit shard_map row partition, only the
# smaller child's histogram crossing the mesh
# (data_parallel_tree_learner.cpp:146-161); data_rs: the same under the
# frontier grower, the wave histograms reduce-scattered (parallel/
# learners.py DataRSLearner); feature: the explicit feature-parallel
# learner (feature_parallel_tree_learner.cpp:30-60); voting: PV-Tree's
# election; gspmd: any other mesh, the masked growth whose communication
# the partitioner infers
SERIAL, DATA, DATA_RS, FEATURE, VOTING, GSPMD = (
    "serial", "data", "data_rs", "feature", "voting", "gspmd")


@dataclasses.dataclass(frozen=True)
class TrainFacts:
    """What set-up observes before any device work."""
    rows: int                       # the table's rows, before any padding
    cols: int                       # stored columns (after EFB / pairs)
    col_num_bin: Tuple[int, ...] = ()
    categorical: int = 0            # categorical features among the used
    bundled: bool = False           # EFB bundles or joint-coded pairs
    # every stored column one numerical feature's bins as they are
    # (replay.TableLayout.single)
    single_columns: bool = True
    streamed: bool = False
    shard_world: int = 1
    shard_rank: int = 0
    mesh: Tuple[Tuple[str, int], ...] = ()    # (axis, size); () no mesh
    backend: str = "cpu"            # jax.default_backend()
    bytes_limit: Optional[int] = None   # the device's; None: unreported
    boosting: str = "gbdt"          # the booster's boosting_type
    num_forced: int = 0             # forced splits read from the file
    num_tree_per_iteration: int = 1

    @property
    def axes(self) -> Dict[str, int]:
        return dict(self.mesh)

    @property
    def tpu_shaped(self) -> bool:
        return partition.tpu_shaped_backend(self.backend)

    @classmethod
    def of(cls, ds, mesh, boosting: str, num_tree_per_iteration: int,
           num_forced: int, single_columns: bool) -> "TrainFacts":
        """A binned dataset's facts on this process's backend."""
        import jax
        from ..io.binning import BinType
        return cls(
            rows=ds.num_data, cols=(ds.num_columns if ds.X_binned is None
                                    else ds.X_binned.shape[1]),
            col_num_bin=tuple(ds.col_num_bin),
            categorical=sum(ds.bin_mappers[j].bin_type == BinType.CATEGORICAL
                            for j in ds.used_features),
            bundled=ds.has_bundles or ds.has_packed,
            single_columns=single_columns, streamed=ds.is_streamed,
            shard_world=int(getattr(ds, "shard_world", 1) or 1),
            shard_rank=int(getattr(ds, "shard_rank", 0) or 0),
            mesh=() if mesh is None else tuple(mesh.shape.items()),
            backend=jax.default_backend(),
            # the device the table goes to where it goes to one
            bytes_limit=(None if mesh is not None else
                         partition.device_bytes_limit(jax.local_devices()[0])),
            boosting=boosting, num_forced=num_forced,
            num_tree_per_iteration=num_tree_per_iteration)


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """Every decision of which path a training job takes."""
    facts: TrainFacts
    grower: str                     # EXACT | BATCHED | FRONTIER | STREAM_..
    learner: str                    # SERIAL | DATA | DATA_RS | FEATURE | ..
    batch_splits: int               # leaves a batched step splits; else 0
    hist_impl: str                  # the resolved tpu_hist_impl spelling
    hist_dtype: str                 # "f32" | "f64"
    vmapped: bool                   # classes grown under one jax.vmap
    pool_slots: int                 # histogram pool cap, 0 = a slot a leaf
    voting_top_k: int
    goss_bag: bool                  # the partition starts from GOSS's bag
    copy: bool                      # the bins' feature-major copy is made
    row_space_ids: bool             # exact over all rows: ids in row space
    # the exact grower's tile loop places a tile's ids by windows (no
    # scatter); False: by the element scatter, or no tile loop
    windows: bool
    row_chunk: int
    word_packed_cols: int           # stored columns held as int32 words
    row_multiple: int               # rows padded to a multiple of this
    col_multiple: int               # stored columns likewise
    frontier_rs: bool
    frontier_bucketing: bool
    # the wave grower's aux slot may carry the obs piggy-backs (health
    # where monitoring is on, model statistics where obs_modelstats)
    obs_aux: bool
    obs_modelstats: bool
    all_rows_in_bag: bool
    fuse_blocks: bool               # iterations may run as fused blocks
    replay_kernel: bool             # a replay over the table takes it
    log: Tuple[Tuple[str, str], ...] = ()   # (level, message), once

    @property
    def frontier_mode(self) -> bool:
        return self.grower in (FRONTIER, STREAM_FRONTIER)

    @property
    def partition_on_mesh(self) -> bool:
        return self.learner in (DATA, DATA_RS)

    @property
    def shard_mapped(self) -> bool:
        """The learners of one shard_map over the data axis."""
        return self.learner in (DATA, DATA_RS, VOTING)

    @property
    def use_partition(self) -> bool:
        return self.learner in (SERIAL, DATA, DATA_RS)

    @property
    def exact_partition(self) -> bool:
        """The exact grower over the row partition (its tile loops)."""
        return self.grower == EXACT and self.use_partition

    @property
    def mesh_exact_counts(self) -> bool:
        """Counts over a mesh are the partitions' int32-psummed integers."""
        return (self.exact_partition and self.partition_on_mesh
                and self.all_rows_in_bag)

    @property
    def cat_route_gather_free(self) -> bool:
        """A categorical split routes by selects, not a gather: the exact
        grower's form (the wave growers' per-row sets take_along_axis)."""
        return self.facts.categorical > 0 and self.grower == EXACT


def hist_dtype(cfg: Config) -> str:
    """Histogram accumulation dtype: tpu_hist_dtype is the explicit knob,
    gpu_use_dp (config.h:784) the reference-compatible alias for f64."""
    spelled = str(cfg.tpu_hist_dtype).strip().lower()
    if spelled in ("float64", "f64", "double"):
        return "f64"
    if spelled not in ("float32", "f32", "single", ""):
        raise LightGBMError("unknown tpu_hist_dtype %r "
                            "(use float32 or float64)" % cfg.tpu_hist_dtype)
    return "f64" if cfg.gpu_use_dp else "f32"


def _hist_impl(cfg: Config, facts: TrainFacts, dtype: str, log: list) -> str:
    """Histogram-kernel dispatch (the GPUTreeLearner device-path analog,
    tree_learner.cpp:9-31): CPU -> XLA scatter-add; device -> the Pallas
    VMEM-accumulator kernel (gpu_tree_learner.cpp:951-1045); the one-hot
    matmul only when named. ``auto`` resolves from the backend alone and
    says so in the log — never by catching a failed compile: a kernel
    that does not compile raises, whether it was named or resolved.
    gpu_use_dp (config.h:784) means what it means in the reference:
    DOUBLE-precision histogram accumulation. The Pallas kernels are
    f32-only, so dp routes to the XLA paths (scatter / one-hot matmul),
    which accumulate in the value dtype — f64 once the GBDT driver casts
    the stacked values (GrowParams.hist_dtype)."""
    impl = cfg.tpu_hist_impl
    cpu = facts.backend == "cpu"
    if dtype == "f64":
        if impl == "auto" or hist_kernel(impl).pallas:
            if impl != "auto":
                log.append(("warning", "f64 histograms: the f32-only Pallas "
                            "kernel %s is replaced by the f64 XLA path"
                            % impl))
            return "scatter" if cpu else "matmul"
        return impl
    if impl == "auto":
        impl = "scatter" if cpu else "pallas"
        log.append(("info", "tpu_hist_impl=auto resolved to %s (backend %s)"
                    % (impl, facts.backend)))
    return impl


def cegb_set(cfg: Config) -> bool:
    """Whether any CEGB term is set: GBDT._setup_cegb makes its state."""
    return bool(len(cfg.cegb_penalty_feature_coupled)
                or len(cfg.cegb_penalty_feature_lazy)
                or cfg.cegb_penalty_split > 0)


def _refuse(cfg: Config, facts: TrainFacts, dtype: str) -> None:
    """The combinations the program refuses, in the order it checks them:
    the first that holds raises its message. What reads the running
    processes (a sharded ingest's topology) is checked where it is known,
    in GBDT._setup_stream_mesh."""
    mesh, streamed = bool(facts.mesh), facts.streamed
    mode = "tree_growth=%s" % cfg.tree_growth
    waves = cfg.tree_growth in ("batched", "frontier")
    ordered = facts.num_forced > 0 or cegb_set(cfg)
    gates = (
        (streamed and cfg.tree_growth != "frontier",
         "streamed training requires tree_growth=frontier"),
        # every streamed run accumulates f32 wave histograms (config.py
        # pre-validates the mesh spelling)
        (streamed and dtype == "f64",
         "streamed training accumulates f32 wave histograms; "
         "set gpu_use_dp=false" + (" (streamed + mesh_shape requires f32)"
                                   if mesh else "")),
        (streamed and mesh and DATA_AXIS not in facts.axes,
         "streamed mesh training is data-parallel only: mesh_shape "
         "must map the %r axis (got axes %s)"
         % (DATA_AXIS, list(facts.axes))),
        (streamed and facts.axes.get(FEATURE_AXIS, 1) > 1,
         "streamed training cannot shard the feature axis (the "
         "chunk stream is row-partitioned); use a pure data mesh "
         "(tree_learner=data|voting) or set "
         "data_stream_chunk_rows=0"),
        (streamed and mesh and int(cfg.data_stream_chunk_rows) <= 0,
         "streamed mesh training needs an explicit "
         "data_stream_chunk_rows: the per-wave kernel shapes must "
         "agree on every process"),
        (streamed and not mesh and facts.shard_world > 1,
         "dataset was ingested as shard %d/%d but no mesh "
         "is configured; set mesh_shape=[%d] (or ingest "
         "without a ShardedSource)"
         % (facts.shard_rank, facts.shard_world, facts.shard_world)),
        (mesh and facts.bundled,
         "EFB bundles / nbit-packed columns are not yet supported "
         "with a device mesh; set enable_bundle=false and "
         "enable_nbit_packing=false for distributed training"),
        (cfg.tree_learner == "voting" and mesh and ordered,
         "forced splits / CEGB are not supported "
         "with the voting-parallel tree learner"),
        # both wave growers are incompatible with anything whose
        # bookkeeping depends on exact one-split-at-a-time ordering
        (waves and ordered,
         mode + " requires exact split ordering; disable forced "
         "splits / CEGB or use tree_growth=exact"),
        # the frontier wave grower carries the voting-parallel election
        # (parallel/learners.py VotingLearner); batched growth and the
        # explicit feature-parallel learner still need exact ordering /
        # the grow_tree fp context
        (waves and (cfg.tree_learner == "feature" or (
            cfg.tree_learner == "voting"
            and cfg.tree_growth != "frontier")),
         mode + " does not support tree_learner=%s (serial and "
         "data always work; voting needs tree_growth=frontier)"
         % cfg.tree_learner),
    )
    for refused, message in gates:
        if refused:
            raise LightGBMError(message)


def resolve_plan(cfg: Config, facts: TrainFacts) -> TrainPlan:
    """The path a job of ``cfg`` over a table of ``facts`` takes; raises
    LightGBMError for a combination the program refuses."""
    dtype = hist_dtype(cfg)
    _refuse(cfg, facts, dtype)
    log: list = []
    axes, mesh = facts.axes, bool(facts.mesh)
    one_device = not mesh and not facts.streamed    # and in memory

    grower, batch_splits = EXACT, 0
    if cfg.tree_growth in ("batched", "frontier"):
        if dtype == "f64":
            # both wave growers accumulate f32 (slot kernel layout);
            # silently downgrading would betray the dp promise
            log.append(("warning", "tree_growth=%s does not support f64 "
                        "histograms yet; falling back to exact growth"
                        % cfg.tree_growth))
        elif cfg.tree_growth == "frontier":
            grower = STREAM_FRONTIER if facts.streamed else FRONTIER
        else:
            grower = BATCHED
            batch_splits = min(cfg.tree_batch_splits, cfg.num_leaves - 1)
    frontier = grower in (FRONTIER, STREAM_FRONTIER)

    # explicit feature-parallel: rows REPLICATED, search work divided by a
    # bin-balanced column assignment, best splits argmax-allreduced as
    # structs (sync_best_split bitcasts f32). Order-dependent extras
    # (forced splits, CEGB) keep the GSPMD fallback. The data learner
    # rebuilds forced splits' leaf histograms straight-line + psum and
    # threads CEGB state through its shard_map
    frontier_rs = False
    if not mesh:
        learner = SERIAL
    elif cfg.tree_learner == "data" and DATA_AXIS in axes:
        frontier_rs = frontier and bool(cfg.tpu_frontier_rs)
        learner = DATA_RS if frontier_rs else DATA
    elif (cfg.tree_learner == "feature" and dtype == "f32"
          and FEATURE_AXIS in axes and not cfg.forcedsplits_filename
          and not cegb_set(cfg)):
        learner = FEATURE
    else:
        learner = VOTING if cfg.tree_learner == "voting" else GSPMD

    # histogram pool cap (histogram_pool_size MB, config.h; the
    # HistogramPool LRU of feature_histogram.hpp:646-820). Mesh modes
    # keep the full pool: the rebuild-on-miss cond cannot hold the psum a
    # sharded rebuild needs (the same SPMD constraint the growth loop
    # documents for its dead-iteration histograms)
    pool_slots = 0
    if cfg.histogram_pool_size > 0 and cfg.tree_learner != "voting" \
            and one_device:
        bytes_per_hist = facts.cols * max(facts.col_num_bin + (2,)) * 3 * 4
        pool_slots = int(cfg.histogram_pool_size * 1024 * 1024
                         // max(bytes_per_hist, 1))
        pool_slots = max(2, min(cfg.num_leaves, pool_slots))
        if pool_slots >= cfg.num_leaves:
            pool_slots = 0  # cap larger than the full pool: uncapped

    # multiclass class batching: vmapped growth measured 1.9x SLOWER than
    # sequential per-class growth on a v5e chip (1.65 vs 0.88 s/iter at
    # 500k x 28 x 5 classes, docs/Performance.md "Round 4", one pre-PR-1
    # datapoint) — vmap serializes the growth while_loop in lockstep AND
    # forces the element scatter (a batched window start is a scatter
    # again: partition.window_placement). TPU-shaped backends — NOT a
    # hist-impl proxy, so f64/matmul TPU runs are covered too — therefore
    # grow classes sequentially even with an uncapped pool; vmap remains
    # the CPU default, where it wins. A capped pool grows them in sequence
    # everywhere: vmap would make the rebuild-on-miss lax.cond a
    # both-branches select
    vmapped = (facts.num_tree_per_iteration > 1 and pool_slots == 0
               and not facts.tpu_shaped)

    exact_one_device = one_device and grower == EXACT
    # GOSS where the exact grower runs over the row partition on one
    # device: the sampler makes a bag and the partition starts from it
    # (boosting/goss.py). Everywhere else it stays a multiplier
    goss_bag = (facts.boosting == "goss" and exact_one_device
                and not vmapped and not cegb_set(cfg))
    # DART replays its drop set over every row twice an iteration, one
    # contiguous column a node (core/replay.py), from the copy
    dart_copy = facts.boosting == "dart" and one_device
    # the exact grower over all rows keeps its leaf ids in row space, a
    # routing pass a split and not a scatter through ``order`` once a
    # tree, where the copy exists or fits beside the table. A GOSS job's
    # unsampled iterations stay boosting=gbdt's program; CEGB's lazy cost
    # keeps its ids split by split
    row_space_ids = (
        exact_one_device and not goss_bag and facts.cols > 0
        and not len(cfg.cegb_penalty_feature_lazy)
        and (dart_copy or partition.row_space_fits(
            facts.rows, facts.cols, facts.bytes_limit)))
    copy = (goss_bag or dart_copy or row_space_ids) and facts.cols > 0

    impl = _hist_impl(cfg, facts, dtype, log)
    pallas, interpret = hist_kernel(impl)
    # packed-bin device matrix (core/binpack.py): the int32-word layout
    # rides the frontier grower on single-device in-memory runs, and the
    # streamed chunks off a mesh pack at repack time — mesh learners shard
    # the feature axis of the plain matrix. nibble vs byte only matters at
    # the DATASET level (pair coding); on the device both store 8-bit
    # codes 4-per-word, so the decision here is solely mode != "none"
    packed = (not mesh and frontier and resolve_bin_packing(
        cfg.tpu_bin_packing, streamed=facts.streamed,
        tpu_shaped=facts.tpu_shaped,
        col_num_bin=list(facts.col_num_bin)) != "none")
    # pad rows to the data axis and columns to the feature axis so every
    # shard is even; reduce-scatter wave histograms tile the stored
    # columns over the data axis too
    row_multiple = axes.get(DATA_AXIS, 1)
    col_multiple = axes.get(FEATURE_AXIS, 1)
    if frontier_rs:
        col_multiple = math.lcm(col_multiple, row_multiple)
    # the frontier health / model-statistics piggy-backs ride the
    # single-device or GSPMD growth call: the shard_map data learners
    # slice the aux slot off (they recompute model statistics on the host
    # at materialize), and the streamed mesh grower carries no aux slot
    obs_aux = frontier and learner not in (DATA, DATA_RS) \
        and not (facts.streamed and mesh)

    return TrainPlan(
        facts=facts, grower=grower, learner=learner,
        batch_splits=batch_splits, hist_impl=impl, hist_dtype=dtype,
        vmapped=vmapped, pool_slots=pool_slots,
        voting_top_k=cfg.top_k if learner == VOTING else 0,
        goss_bag=goss_bag, copy=copy, row_space_ids=row_space_ids,
        windows=(grower == EXACT and learner in (SERIAL, DATA)
                 and partition.window_placement(pallas, vmapped)),
        # 0 = auto: 4096 where the tile loop is TPU-shaped, the Pallas
        # spellings (round-4 on-chip sweep: 1.97 vs 1.80 iters/s at
        # 16384; 65536+ strictly worse), 16384 elsewhere (fewer
        # while-loop trips win when indexed ops are cheap)
        row_chunk=int(cfg.tpu_row_chunk) or (4096 if pallas else 16384),
        word_packed_cols=facts.cols if packed else 0,
        row_multiple=row_multiple, col_multiple=col_multiple,
        frontier_rs=frontier_rs,
        # wave-width bucketing: single-device vmapped multiclass routes to
        # grow_tree_frontier_classes, which hoists the width switch
        # OUTSIDE the vmap (an unbatched branch index), so bucketing stays
        # on there; it stays off for vmapped growth over a mesh, where
        # vmapping the shard_map'd grower would lower the switch to
        # execute-ALL-branches. Off too when streaming: a ladder would
        # multiply the per-chunk kernel set by its length and make the
        # compiled-program count depend on which widths a run visits (the
        # perf gate pins that count invariant in chunk count)
        frontier_bucketing=(frontier and not (vmapped and mesh)
                            and not facts.streamed
                            and bool(cfg.tpu_frontier_bucketing)),
        obs_aux=obs_aux, obs_modelstats=obs_aux and bool(cfg.obs_modelstats),
        # the row partition holds exactly the counted rows: no bagging, no
        # padded rows, and GOSS only where the partition starts from its
        # bag
        all_rows_in_bag=(
            not (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0)
            and facts.boosting != "rf"
            and (facts.boosting != "goss" or goss_bag)
            and not facts.streamed and facts.rows % row_multiple == 0),
        # streamed growth is host-driven (per-chunk kernels under a host
        # wave loop): it cannot fuse into one scanned device program
        fuse_blocks=(facts.boosting in ("gbdt", "goss", "dart")
                     and not facts.streamed),
        # a replay over the table takes the kernel (replay.
        # forest_total_kernel) over the copy, on a TPU (or under the
        # interpreted Pallas histogram, so that the CPU tests run what the
        # chip runs), where every stored column holds one numerical
        # feature, a row tile of the copy fits the kernel's VMEM and a
        # tree its SMEM
        replay_kernel=bool(
            copy and facts.single_columns
            and cfg.num_leaves <= replay_mod.KERNEL_LEAVES_MAX
            and (facts.tpu_shaped or interpret)
            and replay_mod.kernel_vmem_bytes(
                facts.cols, facts.num_tree_per_iteration)
            <= replay_mod.KERNEL_VMEM_MAX),
        log=tuple(log))
