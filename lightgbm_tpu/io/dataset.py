"""Binned Dataset + Metadata.

TPU-native re-design of the reference Dataset/Metadata/DatasetLoader
(include/LightGBM/dataset.h:36-627, src/io/dataset.cpp, src/io/metadata.cpp,
src/io/dataset_loader.cpp). Differences by design:

- Storage is a single dense ``[num_data, num_columns] uint8`` bin matrix —
  the TPU histogram kernels want one contiguous HBM operand, not per-group
  Bin objects (dense_bin.hpp / sparse_bin.hpp). ``max_bin <= 256`` keeps it
  one byte per value.
- Sparse inputs (scipy CSR/CSC) are binned column-by-column without ever
  materializing the dense float matrix, and EFB (io/bundle.py, the
  dataset.cpp:67-177 analog) packs mutually-exclusive sparse features into
  shared columns — so a 95%-sparse input stores ~#bundles columns, not F.
- Trivial-feature dropping keeps the used_feature mapping (dataset.h:613-618);
  ``col_features``/``col_offsets`` record the bundle layout
  (feature_group.h:35-50 bin_offsets_ analog).
- The "bin once, train many" artifact (dataset_loader.cpp:266 LoadFromBinFile)
  is an ``.npz`` cache of the bin matrix + mappers + metadata.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..log import Log, LightGBMError, check
from ..obs.trace import record_span, recorder
from .binning import K_ZERO_THRESHOLD, BinMapper, BinType, MissingType
from .bundle import bundle_offsets, find_bundles


def _is_sparse(data) -> bool:
    return hasattr(data, "tocsc") and hasattr(data, "nnz")


class Metadata:
    """Labels / weights / query boundaries / init scores (dataset.h:36-245)."""

    def __init__(self, num_data: int = 0):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None  # [num_queries+1] int
        self.init_score: Optional[np.ndarray] = None
        self._query_weights: Optional[np.ndarray] = None    # lazy cache

    def set_label(self, label: Sequence[float]) -> None:
        arr = np.ascontiguousarray(label, dtype=np.float32).reshape(-1)
        check(len(arr) == self.num_data or self.num_data == 0,
              "Length of label is not same with #data")
        self.label = arr
        self.num_data = len(arr)

    def set_weight(self, weight: Optional[Sequence[float]]) -> None:
        if weight is None:
            self.weight = None
            return
        arr = np.ascontiguousarray(weight, dtype=np.float32).reshape(-1)
        check(len(arr) == self.num_data, "Length of weight is not same with #data")
        self.weight = arr
        self._query_weights = None

    def set_query(self, group: Optional[Sequence[int]]) -> None:
        """Accepts per-query sizes (LightGBM group format) -> boundaries."""
        if group is None:
            self.query_boundaries = None
            return
        arr = np.ascontiguousarray(group, dtype=np.int64).reshape(-1)
        boundaries = np.concatenate([[0], np.cumsum(arr)])
        check(boundaries[-1] == self.num_data,
              "Sum of query counts is not same with #data")
        self.query_boundaries = boundaries.astype(np.int32)
        self._query_weights = None

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        arr = np.ascontiguousarray(init_score, dtype=np.float64).reshape(-1)
        self.init_score = arr

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    @property
    def query_weights(self) -> Optional[np.ndarray]:
        """Per-query weight = MEAN of the query's document weights
        (metadata.cpp LoadQueryWeights); None unless BOTH per-row weights
        and query boundaries are set. Derived lazily so binary-cache loads
        (which assign fields directly) and any set order all work."""
        if self.weight is None or self.query_boundaries is None:
            return None
        if self._query_weights is None \
                or len(self._query_weights) != self.num_queries:
            qb = np.asarray(self.query_boundaries, np.int64)
            sums = np.add.reduceat(self.weight.astype(np.float64), qb[:-1])
            counts = np.maximum(np.diff(qb), 1)
            self._query_weights = (sums / counts).astype(np.float32)
        return self._query_weights


def _parse_categorical(categorical_feature, feature_names: List[str]) -> List[int]:
    out: List[int] = []
    if not categorical_feature:
        return out
    if isinstance(categorical_feature, str):
        categorical_feature = [c for c in categorical_feature.split(",") if c]
    for c in categorical_feature:
        if isinstance(c, str) and not c.lstrip("-").isdigit():
            if c in feature_names:
                out.append(feature_names.index(c))
            else:
                raise LightGBMError("Unknown categorical feature name %s" % c)
        else:
            out.append(int(c))
    return sorted(set(out))


def _nonzeros(col: np.ndarray):
    """(positions ascending, values) of the entries of a float64 column
    that lie outside [-1e-35, 1e-35]: what feeds FindBin, like the
    reference's sampler (a NaN fails both comparisons and is kept)."""
    at = np.flatnonzero(~((col >= -K_ZERO_THRESHOLD)
                          & (col <= K_ZERO_THRESHOLD)))
    return at, col[at]


def bytes_copied(src, out: np.ndarray) -> int:
    """What a conversion to ``out`` copied: 0 when ``out`` is ``src``'s own
    memory (the input was taken as it came), else all of ``out``."""
    if isinstance(src, np.ndarray) and np.may_share_memory(src, out):
        return 0
    return int(out.nbytes)


class BinnedDataset:
    """The core training artifact: bin matrix + mappers + metadata.

    This is the analog of the reference ``Dataset`` (dataset.h:278-627); the
    user-facing lazy ``Dataset`` wrapper lives in ``lightgbm_tpu.basic``.
    """

    # overridden by stream.sampler.StreamedDataset, whose bin matrix lives
    # in host chunks (``chunks``) instead of ``X_binned``
    is_streamed = False

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.bin_mappers: List[BinMapper] = []          # per original feature
        self.used_features: List[int] = []              # original idx of used feats
        self.X_binned: Optional[np.ndarray] = None      # [num_data, num_cols] uint8
        # EFB layout (feature_group.h:35-50): stored column -> member original
        # features + their bin offsets; singletons have offsets == [0] (raw
        # encoding). With no bundling these mirror used_features 1:1.
        self.col_features: List[List[int]] = []
        self.col_offsets: List[List[int]] = []
        self.col_num_bin: List[int] = []
        # joint-coded pairs of small features (Dense4bitsBin analog):
        # stored value = bin_a * num_bin_b + bin_b
        self.col_packed: List[bool] = []
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.max_bin: int = 255
        # effective values of construction-time params that the binned
        # representation depends on (Dataset::ResetConfig's immutable set,
        # dataset.cpp:327-348); authoritative for post-construct
        # update-param checking even when the handle came from a .bin file
        self.bin_params: Dict[str, Any] = {}
        self._device_cache: Dict[Any, Any] = {}
        self._data_profile = None   # lazy obs.drift.DataProfile cache

    _BIN_PARAM_KEYS = ("max_bin", "bin_construct_sample_cnt",
                       "min_data_in_bin", "use_missing", "zero_as_missing",
                       "sparse_threshold")

    def _record_bin_params(self, config: Config) -> None:
        self.bin_params = {k: getattr(config, k)
                           for k in self._BIN_PARAM_KEYS
                           if hasattr(config, k)}

    # ------------------------------------------------------------ construct
    @classmethod
    def from_matrix(cls, data: np.ndarray, config: Config,
                    label: Optional[Sequence[float]] = None,
                    weight: Optional[Sequence[float]] = None,
                    group: Optional[Sequence[int]] = None,
                    init_score: Optional[Sequence[float]] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_feature: Optional[Union[str, List]] = None,
                    reference: Optional["BinnedDataset"] = None) -> "BinnedDataset":
        """Bin a raw [N, F] matrix — dense ndarray or scipy sparse CSR/CSC
        (DatasetLoader::CostructFromSampleData analog, dataset_loader.cpp:
        700-820; sparse path never densifies the float matrix)."""
        sparse = _is_sparse(data)
        if sparse:
            csc = data.tocsc()
            csc.sum_duplicates()
            n, f = csc.shape
            data64 = None
        else:
            csc = None
            data = np.asarray(data)
            if data.ndim != 2:
                raise LightGBMError("Data should be 2-D, got shape %s"
                                    % (data.shape,))
            n, f = data.shape
            with recorder.span("ingest.to_float64") as span:
                data64 = np.asarray(data, dtype=np.float64)
                span.counts["bytes_copied"] = bytes_copied(data, data64)
        self = cls()
        self.num_data = n
        self.num_total_features = f
        self.max_bin = config.max_bin
        self._record_bin_params(config)
        self.feature_names = feature_names or ["Column_%d" % i for i in range(f)]

        def column_nonzeros(j):
            """(rows, float64 values) of column j's stored/non-zero entries."""
            if sparse:
                sl = slice(csc.indptr[j], csc.indptr[j + 1])
                return csc.indices[sl], np.asarray(csc.data[sl], np.float64)
            return _nonzeros(data64[:, j])

        if reference is not None:
            # validation set: reuse the reference's bin mappers / layout
            check(f == reference.num_total_features,
                  "The number of features in data (%d) is not the same as it was "
                  "in training data (%d)" % (f, reference.num_total_features))
            self.bin_mappers = reference.bin_mappers
            self.used_features = reference.used_features
            self.feature_names = reference.feature_names
            self.col_features = reference.col_features
            self.col_offsets = reference.col_offsets
            self.col_num_bin = reference.col_num_bin
            self.col_packed = reference.col_packed
        else:
            cat_idx = set(_parse_categorical(
                categorical_feature if categorical_feature is not None
                else config.categorical_feature, self.feature_names))
            with recorder.span("ingest.sample_rows", rows=n):
                sample_cnt = min(n, config.bin_construct_sample_cnt)
                if sample_cnt < n:
                    rng = np.random.RandomState(config.data_random_seed)
                    sample_rows = np.sort(rng.choice(n, sample_cnt,
                                                     replace=False))
                else:
                    sample_rows = None      # the table is its own sample
                if sparse and sample_rows is not None:
                    # row id -> sample position (-1 = not sampled), for the
                    # stored entries; a dense column needs no such table
                    sample_pos = np.full(n, -1, np.int64)
                    sample_pos[sample_rows] = np.arange(sample_cnt)

            def sampled_nonzeros(j):
                """(sample positions ascending, values, values read) of
                column j's non-zero entries among the sampled rows."""
                if sample_rows is not None and not sparse:
                    # a dense table larger than the sample: the sampled
                    # rows' values first, the zero test on those alone
                    pos, vals = _nonzeros(data64[sample_rows, j])
                    return pos, vals, sample_cnt
                rows, vals = column_nonzeros(j)
                read = len(rows) if sparse else n
                if sample_rows is None:
                    return rows, vals, read
                pos = sample_pos[rows]
                keep = pos >= 0
                return pos[keep], vals[keep], read

            self.bin_mappers = []
            nz_sample: List[np.ndarray] = []   # per feature, sample positions
            with recorder.span("ingest.find_bins", columns=f,
                               sample_rows=sample_cnt) as span:
                scan_s = find_s = 0.0
                nan_values = zero_values = 0   # of the sampled rows
                values_scanned = 0             # read by the zero test
                t = time.perf_counter()
                for j in range(f):
                    rows_s, vals_s, scanned = sampled_nonzeros(j)
                    values_scanned += scanned
                    nz_sample.append(rows_s.astype(np.int64))
                    nan_values += int(np.isnan(vals_s).sum())
                    zero_values += sample_cnt - len(vals_s)
                    mapper = BinMapper()
                    t_scan = time.perf_counter()
                    # only non-zero values feed FindBin, like the reference's
                    # sampler (NaNs fail both comparisons and are kept)
                    mapper.find_bin(
                        vals_s, total_sample_cnt=sample_cnt,
                        max_bin=config.max_bin,
                        min_data_in_bin=config.min_data_in_bin,
                        min_split_data=config.min_data_in_leaf,
                        bin_type=(BinType.CATEGORICAL if j in cat_idx
                                  else BinType.NUMERICAL),
                        use_missing=config.use_missing,
                        zero_as_missing=config.zero_as_missing)
                    self.bin_mappers.append(mapper)
                    scan_s += t_scan - t
                    t = time.perf_counter()
                    find_s += t - t_scan
                span.counts.update(nonzero_scan_s=scan_s, find_bin_s=find_s,
                                   nan_values=nan_values,
                                   zero_values=zero_values,
                                   values_scanned=values_scanned)
            self.used_features = [j for j in range(f)
                                  if not self.bin_mappers[j].is_trivial]
            if not self.used_features:
                Log.warning("There are no meaningful features, as all feature "
                            "values are constant.")

            # ---- EFB grouping (dataset.cpp:67-177 analog) ----------------
            with recorder.span("ingest.bundle") as span:
                if config.enable_bundle and len(self.used_features) > 1:
                    bundles = find_bundles(
                        [nz_sample[j] for j in self.used_features],
                        sample_cnt,
                        [self.bin_mappers[j].num_bin
                         for j in self.used_features],
                        config.max_conflict_rate,
                        sparse_threshold=config.sparse_threshold)
                    # bundle entries index into used_features; map back
                    bundles = [[self.used_features[i] for i in b]
                               for b in bundles]
                else:
                    bundles = [[j] for j in self.used_features]
                self.col_features = bundles
                self.col_offsets = []
                self.col_num_bin = []
                num_bin_of = {j: self.bin_mappers[j].num_bin
                              for j in self.used_features}
                for b in bundles:
                    offs, total = bundle_offsets(b, num_bin_of)
                    self.col_offsets.append(offs)
                    self.col_num_bin.append(total)
                n_bundled = sum(1 for b in bundles if len(b) > 1)
                if n_bundled:
                    Log.info("EFB: %d features bundled into %d columns "
                             "(%d multi-feature bundles)",
                             len(self.used_features), len(bundles),
                             n_bundled)
                self.col_packed = [False] * len(self.col_features)
                # mesh learners shard/pad the feature axis assuming an
                # identity feature->column layout; keep packing
                # single-device-only (the booster raises if a packed dataset
                # reaches a mesh anyway)
                if config.enable_nbit_packing and \
                        config.tree_learner == "serial" \
                        and not config.mesh_shape:
                    # tpu_bin_packing=nibble raises the joint-code cap to
                    # the full byte (256) so every <=16-bin pair shares a
                    # column regardless of the dataset's histogram width —
                    # the Dense4bitsBin "two bins per byte" applied
                    # dataset-wide (core/binpack.py). Other modes keep the
                    # conservative cap (B never grows past the widest
                    # existing column).
                    from ..core.binpack import resolve_bin_packing
                    from ..core.partition import tpu_shaped_backend
                    mode = resolve_bin_packing(
                        getattr(config, "tpu_bin_packing", "auto"),
                        streamed=False, tpu_shaped=tpu_shaped_backend(),
                        col_num_bin=self.col_num_bin)
                    self._pack_small_pairs(
                        pair_cap=256 if mode == "nibble" else 0)
                span.counts["bundles"] = len(self.col_features)

        # ---- build the stored uint8 columns ------------------------------
        cat_s = [0.0, 0]    # seconds and columns of categorical binning

        def full_bin_column(j):
            m = self.bin_mappers[j]
            if sparse:
                zero_bin = int(m.values_to_bins(np.zeros(1))[0])
                colb = np.full(n, zero_bin, np.uint8)
                rows, vals = column_nonzeros(j)
                if len(rows):
                    colb[rows] = m.values_to_bins(vals).astype(np.uint8)
                return colb
            t_col = time.perf_counter()
            colb = m.values_to_bins(data64[:, j]).astype(np.uint8)
            if m.bin_type == BinType.CATEGORICAL:
                cat_s[0] += time.perf_counter() - t_col
                cat_s[1] += 1
            return colb

        cols = []
        with recorder.span("ingest.bin_columns",
                           columns=len(self.col_features),
                           values=n * len(self.used_features)):
            for ci, (feats, offs) in enumerate(zip(self.col_features,
                                                   self.col_offsets)):
                if self._col_is_packed(ci):
                    ja, jb = feats
                    nb_b = self.bin_mappers[jb].num_bin
                    colb = (full_bin_column(ja).astype(np.uint16) * nb_b
                            + full_bin_column(jb)).astype(np.uint8)
                elif len(feats) == 1 and offs[0] == 0:
                    colb = full_bin_column(feats[0])
                else:
                    colb = np.zeros(n, np.uint8)
                    for off, j in zip(offs, feats):
                        m = self.bin_mappers[j]
                        rows, vals = column_nonzeros(j)
                        bins = m.values_to_bins(vals)
                        sel = bins != m.default_bin
                        colb[rows[sel]] = (off
                                           + bins[sel]).astype(np.uint8)
                cols.append(colb)
            if cat_s[1]:
                # the dense categorical columns' share of the span above:
                # id -> bin through the kept categories (native binner)
                cat_maps = [m for m in self.bin_mappers
                            if m.bin_type == BinType.CATEGORICAL]
                record_span(
                    "ingest.bin_categorical", cat_s[0], columns=cat_s[1],
                    values=n * cat_s[1],
                    categories_kept=sum(len(m.bin_2_categorical)
                                        for m in cat_maps),
                    categories_seen=sum(m.categories_seen for m in cat_maps))
        with recorder.span("ingest.stack") as span:
            self.X_binned = (np.stack(cols, axis=1) if cols
                             else np.zeros((n, 0), dtype=np.uint8))
            span.counts["bytes"] = self.X_binned.nbytes

        self.metadata = Metadata(n)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_query(group)
        self.metadata.set_init_score(init_score)
        return self

    # ------------------------------------------------------------ sharded
    @classmethod
    def from_file_two_round(cls, path: str, config: Config,
                            chunk_rows: int = 262144,
                            reference: "BinnedDataset" = None,
                            feature_names=None, categorical_feature=None
                            ) -> "BinnedDataset":
        """Two-round streaming load (two_round / use_two_round_loading —
        dataset_loader.cpp:160-219's >memory path re-imagined host-side).

        Round 1 streams the file once, reservoir-sampling up to
        ``bin_construct_sample_cnt`` rows (bin mappers and the EFB/packing
        layout come from the sample, exactly like the reference's sampled
        bin finding) and collecting labels. Round 2 streams again, binning
        each chunk against that layout into the preallocated uint8 matrix.
        Peak float64 footprint is one chunk, not the whole file.
        """
        from .parser import parse_file_chunks
        from ..log import check as _check

        sample_cnt = int(config.bin_construct_sample_cnt)
        rng = np.random.RandomState(config.data_random_seed)
        sample_rows: list = []
        labels: list = []
        names = None
        first_row = None
        n_total = 0
        for Xc, yc, chunk_names in parse_file_chunks(
                path, has_header=config.header,
                label_column=config.label_column, chunk_rows=chunk_rows):
            labels.append(yc)
            names = names or chunk_names
            if first_row is None:
                first_row = Xc[:1].copy()
            if reference is None:
                # Algorithm R, vectorized per chunk: the fill phase keeps
                # original order (sample == full data when N <= sample_cnt);
                # afterwards each row i draws j ~ U[0, n_total+i] and
                # replaces slot j when j < sample_cnt. Rows are COPIED so
                # the parent float64 chunk can be freed — holding views
                # would keep every chunk alive, defeating the streaming
                # point.
                c = Xc.shape[0]
                fill = max(0, min(sample_cnt - n_total, c))
                for i in range(fill):
                    sample_rows.append(Xc[i].copy())
                if fill < c:
                    draws = (rng.random_sample(c - fill)
                             * (n_total + np.arange(fill, c) + 1)
                             ).astype(np.int64)
                    hits = np.nonzero(draws < sample_cnt)[0]
                    for i in hits:
                        sample_rows[draws[i]] = Xc[fill + i].copy()
            n_total += Xc.shape[0]
        _check(n_total > 0, "Data file %s is empty" % path)
        label = np.concatenate(labels)

        proto = reference
        if proto is None:
            proto = cls.from_matrix(
                np.asarray(sample_rows), config,
                feature_names=feature_names or names,
                categorical_feature=categorical_feature)

        xb = np.empty((n_total, proto.X_binned.shape[1]), np.uint8)
        row = 0
        for Xc, _yc, _names in parse_file_chunks(
                path, has_header=config.header,
                label_column=config.label_column, chunk_rows=chunk_rows):
            bc = cls.from_matrix(Xc, config, reference=proto)
            xb[row:row + Xc.shape[0]] = bc.X_binned
            row += Xc.shape[0]

        if reference is not None:
            # a validation set binned against the training layout: clone the
            # layout through the reference-alignment path (no sampling run)
            ds = cls.from_matrix(first_row, config, reference=reference)
        else:
            ds = proto
        ds.X_binned = xb
        ds.num_data = n_total
        ds.metadata = Metadata(n_total)
        ds.metadata.set_label(label)
        return ds

    @classmethod
    def from_sharded(cls, local_data, config: Config, comm=None,
                     label: Optional[Sequence[float]] = None,
                     weight: Optional[Sequence[float]] = None,
                     init_score: Optional[Sequence[float]] = None,
                     feature_names: Optional[List[str]] = None,
                     categorical_feature: Optional[Union[str, List]] = None
                     ) -> "BinnedDataset":
        """Distributed ingest: every host binds only its own row shard.

        The reference's distributed loading (dataset_loader.cpp:469-495 row
        partition, :548-640 feature-sharded bin finding + Allgather of
        BinMappers) re-designed for exact parity: each host samples its local
        rows, the per-feature samples are allgathered (bounded by
        bin_construct_sample_cnt), and every host runs FindBin on the merged
        sample — so bin boundaries are identical on all hosts (and identical
        to a single-host run over the union sample), without any host ever
        holding the full matrix.

        ``comm`` implements ``allgather(obj) -> list`` over hosts (see
        lightgbm_tpu.parallel.network; tests use a loopback). The returned
        dataset covers only the local rows; training on a 'data'-axis mesh
        then shards naturally.
        """
        local_data = np.asarray(local_data)
        check(local_data.ndim == 2, "local shard must be 2-D")
        n_local, f = local_data.shape
        if comm is None:
            from ..parallel import network as _net
            comm = _net.active_comm()
            if comm is None:
                raise LightGBMError(
                    "from_sharded needs a comm (or a transport registered "
                    "via LGBM_NetworkInitWithFunctions)")
        sizes = comm.allgather(n_local)
        total_n = int(sum(sizes))

        # per-host row sample, proportional share of the global sample budget
        budget = max(1, int(config.bin_construct_sample_cnt
                            * (n_local / max(total_n, 1))))
        sample_cnt = min(n_local, budget)
        if sample_cnt < n_local:
            rng = np.random.RandomState(config.data_random_seed + 1
                                        + len(sizes))
            rows = np.sort(rng.choice(n_local, sample_cnt, replace=False))
            sample = np.asarray(local_data[rows], np.float64)
        else:
            sample = np.asarray(local_data, np.float64)

        # merge per-feature non-zero sampled values across hosts (the
        # Allgather at dataset_loader.cpp:615-640, but of raw sample values
        # so FindBin sees the union sample -> identical mappers everywhere)
        local_nz = []
        for j in range(f):
            col = sample[:, j]
            local_nz.append(col[~((col >= -1e-35) & (col <= 1e-35))])
        gathered = comm.allgather((len(sample), local_nz))
        merged_cnt = int(sum(c for c, _ in gathered))
        merged = [np.concatenate([g[1][j] for g in gathered])
                  for j in range(f)]

        names = feature_names or ["Column_%d" % i for i in range(f)]
        cat_idx = set(_parse_categorical(
            categorical_feature if categorical_feature is not None
            else config.categorical_feature, names))
        mappers: List[BinMapper] = []
        for j in range(f):
            m = BinMapper()
            m.find_bin(merged[j], total_sample_cnt=merged_cnt,
                       max_bin=config.max_bin,
                       min_data_in_bin=config.min_data_in_bin,
                       min_split_data=config.min_data_in_leaf,
                       bin_type=(BinType.CATEGORICAL if j in cat_idx
                                 else BinType.NUMERICAL),
                       use_missing=config.use_missing,
                       zero_as_missing=config.zero_as_missing)
            mappers.append(m)

        self = cls()
        self.num_data = n_local
        self.num_total_features = f
        self.max_bin = config.max_bin
        self._record_bin_params(config)
        self.feature_names = names
        self.bin_mappers = mappers
        self.used_features = [j for j in range(f) if not mappers[j].is_trivial]
        # bundling needs a global conflict view; keep the identity layout in
        # sharded mode (EFB is a single-host/mesh-local optimization for now)
        self.col_features = [[j] for j in self.used_features]
        self.col_offsets = [[0] for _ in self.used_features]
        self.col_num_bin = [mappers[j].num_bin for j in self.used_features]
        self.col_packed = [False] * len(self.col_features)

        data64 = np.asarray(local_data, np.float64)
        cols = [mappers[j].values_to_bins(data64[:, j]).astype(np.uint8)
                for j in self.used_features]
        self.X_binned = (np.stack(cols, axis=1) if cols
                         else np.zeros((n_local, 0), np.uint8))
        self.metadata = Metadata(n_local)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_init_score(init_score)
        return self

    # ------------------------------------------------------------ accessors
    @property
    def num_features(self) -> int:
        """Number of stored (non-trivial) features."""
        return len(self.used_features)

    def feature_num_bin(self, used_idx: int) -> int:
        return self.bin_mappers[self.used_features[used_idx]].num_bin

    def real_feature_index(self, used_idx: int) -> int:
        """Inner (stored) -> original feature index (dataset.h:613)."""
        return self.used_features[used_idx]

    def inner_feature_index(self, real_idx: int) -> int:
        try:
            return self.used_features.index(real_idx)
        except ValueError:
            return -1

    def max_num_bin(self) -> int:
        return max((self.feature_num_bin(i) for i in range(self.num_features)),
                   default=1)

    def data_profile(self):
        """Per-feature bin-occupancy profile of the training data
        (obs.drift.DataProfile), computed lazily from the already-binned
        matrix — one bincount pass per feature — and cached. Persisted in
        checkpoint snapshot meta and the serving ModelBundle as the
        reference distribution for train/serve drift scoring."""
        if self._data_profile is None:
            from ..obs.drift import DataProfile
            self._data_profile = DataProfile.from_binned_dataset(self)
        return self._data_profile

    # ------------------------------------------------------------ EFB layout
    @property
    def num_columns(self) -> int:
        """Stored bin-matrix columns (== num_features when nothing bundled)."""
        return len(self.col_features)

    def max_col_bins(self) -> int:
        """Largest encoded bin count of any stored column (histogram B)."""
        return max(self.col_num_bin, default=1)

    @property
    def has_bundles(self) -> bool:
        return any(len(b) > 1 and not self._col_is_packed(ci)
                   for ci, b in enumerate(self.col_features))

    def _col_is_packed(self, ci: int) -> bool:
        return ci < len(self.col_packed) and self.col_packed[ci]

    @property
    def has_packed(self) -> bool:
        return any(self.col_packed)

    def _pack_small_pairs(self, pair_cap: int = 0) -> None:
        """Joint-code pairs of small singleton numerical features into one
        stored column (value = bin_a * num_bin_b + bin_b) — the
        Dense4bitsBin idea (dense_nbits_bin.hpp:38-82) re-shaped for the
        [N, C] uint8 matrix: instead of nibble-shifting inside a bin
        object, two features share a column whose joint histogram is
        marginalized per feature at split-search time. With ``pair_cap``
        0 a pair is only formed when it fits the dataset's existing
        histogram width, so B never grows; tpu_bin_packing=nibble passes
        256 (the uint8 code space) to force dataset-wide pairing — C
        halves for small-bin features at the price of a wider B."""
        b_max = int(pair_cap) or max(self.col_num_bin, default=0)
        cand = [ci for ci in range(len(self.col_features))
                if len(self.col_features[ci]) == 1
                and not self.col_packed[ci]
                and self.bin_mappers[self.col_features[ci][0]].bin_type
                != BinType.CATEGORICAL
                and self.bin_mappers[self.col_features[ci][0]].num_bin <= 16]
        # widest first, paired greedily while the product fits b_max
        cand.sort(key=lambda ci:
                  -self.bin_mappers[self.col_features[ci][0]].num_bin)
        drop = set()
        pairs = 0
        while len(cand) >= 2:
            ca = cand.pop(0)
            cb = cand.pop()          # widest with narrowest
            ja = self.col_features[ca][0]
            jb = self.col_features[cb][0]
            nb_a = self.bin_mappers[ja].num_bin
            nb_b = self.bin_mappers[jb].num_bin
            if nb_a * nb_b > b_max:
                # the widest can pair with no one (cb is the narrowest);
                # drop it and keep pairing the rest
                cand.append(cb)
                continue
            self.col_features[ca] = [ja, jb]
            self.col_offsets[ca] = [0, 0]
            self.col_num_bin[ca] = nb_a * nb_b
            self.col_packed[ca] = True
            drop.add(cb)
            pairs += 1
        if drop:
            keep = [i for i in range(len(self.col_features))
                    if i not in drop]
            self.col_features = [self.col_features[i] for i in keep]
            self.col_offsets = [self.col_offsets[i] for i in keep]
            self.col_num_bin = [self.col_num_bin[i] for i in keep]
            self.col_packed = [self.col_packed[i] for i in keep]
            Log.info("nbit packing: %d small-feature pairs share a column "
                     "(%d stored columns)", pairs, len(self.col_features))

    def feature_layout(self):
        """Per used-feature (inner index) storage arrays:
        (feat_col, feat_offset, feat_bundled, pack_div, pack_mod,
        pack_partner) — where each feature lives in the stored matrix, at
        which bin offset (EFB), and how to extract it from a joint-coded
        pair column (packing): feature bin = (value // div) % mod, with
        `partner` = the other feature's bin count (marginalization width).
        div/mod are 1/0 for unpacked features."""
        fcount = self.num_features
        feat_col = np.zeros(fcount, np.int32)
        feat_offset = np.zeros(fcount, np.int32)
        feat_bundled = np.zeros(fcount, bool)
        pack_div = np.ones(fcount, np.int32)
        pack_mod = np.zeros(fcount, np.int32)
        pack_partner = np.ones(fcount, np.int32)
        inner = {j: i for i, j in enumerate(self.used_features)}
        for ci, (feats, offs) in enumerate(zip(self.col_features,
                                               self.col_offsets)):
            if self._col_is_packed(ci):
                ja, jb = feats
                nb_a = self.bin_mappers[ja].num_bin
                nb_b = self.bin_mappers[jb].num_bin
                ia, ib = inner[ja], inner[jb]
                feat_col[ia] = feat_col[ib] = ci
                pack_div[ia], pack_mod[ia] = nb_b, nb_a
                pack_partner[ia] = nb_b
                pack_div[ib], pack_mod[ib] = 1, nb_b
                pack_partner[ib] = nb_a
                continue
            for off, j in zip(offs, feats):
                i = inner[j]
                feat_col[i] = ci
                feat_offset[i] = off
                feat_bundled[i] = len(feats) > 1
        return (feat_col, feat_offset, feat_bundled, pack_div, pack_mod,
                pack_partner)

    def get_feature_infos(self) -> List[str]:
        """Model-file ``feature_infos`` strings ([min:max] / categorical list)."""
        infos = []
        for j in range(self.num_total_features):
            m = self.bin_mappers[j] if j < len(self.bin_mappers) else None
            if m is None or m.is_trivial:
                infos.append("none")
            elif m.bin_type == BinType.CATEGORICAL:
                infos.append(":".join(str(c) for c in sorted(m.bin_2_categorical)))
            else:
                infos.append("[%s:%s]" % (repr(m.min_val), repr(m.max_val)))
        return infos

    # ------------------------------------------------------------ binary cache
    def save_binary(self, path: str) -> None:
        """Binary dataset cache (dataset.h:394 SaveBinaryFile analog)."""
        meta = {
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "used_features": self.used_features,
            "feature_names": self.feature_names,
            "max_bin": self.max_bin,
            "bin_params": self.bin_params,
            "bin_mappers": [m.to_dict() for m in self.bin_mappers],
            "col_features": self.col_features,
            "col_offsets": self.col_offsets,
            "col_num_bin": self.col_num_bin,
            "col_packed": self.col_packed,
        }
        arrays: Dict[str, np.ndarray] = {"X_binned": self.X_binned}
        if self.metadata.label is not None:
            arrays["label"] = self.metadata.label
        if self.metadata.weight is not None:
            arrays["weight"] = self.metadata.weight
        if self.metadata.query_boundaries is not None:
            arrays["query_boundaries"] = self.metadata.query_boundaries
        if self.metadata.init_score is not None:
            arrays["init_score"] = self.metadata.init_score
        # write through a file handle: savez appends ".npz" to bare paths,
        # but the caller's filename (e.g. via LGBM_DatasetSaveBinary) is a
        # contract
        with open(path, "wb") as fh:
            np.savez_compressed(fh, __meta__=np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8), **arrays)

    @classmethod
    def load_binary(cls, path: str) -> "BinnedDataset":
        with np.load(path) as z:
            meta = json.loads(bytes(z["__meta__"]).decode())
            self = cls()
            self.num_data = meta["num_data"]
            self.num_total_features = meta["num_total_features"]
            self.used_features = list(meta["used_features"])
            self.feature_names = list(meta["feature_names"])
            self.max_bin = meta["max_bin"]
            self.bin_params = dict(meta.get("bin_params", {}))
            self.bin_mappers = [BinMapper.from_dict(d) for d in meta["bin_mappers"]]
            self.col_features = [list(b) for b in meta.get(
                "col_features", [[j] for j in self.used_features])]
            self.col_offsets = [list(o) for o in meta.get(
                "col_offsets", [[0]] * len(self.col_features))]
            self.col_num_bin = list(meta.get("col_num_bin", []))
            if not self.col_num_bin:
                self.col_num_bin = [self.bin_mappers[b[0]].num_bin
                                    for b in self.col_features]
            self.col_packed = list(meta.get(
                "col_packed", [False] * len(self.col_features)))
            self.X_binned = z["X_binned"]
            self.metadata = Metadata(self.num_data)
            if "label" in z:
                self.metadata.set_label(z["label"])
            if "weight" in z:
                self.metadata.set_weight(z["weight"])
            if "query_boundaries" in z:
                qb = z["query_boundaries"]
                self.metadata.query_boundaries = qb.astype(np.int32)
            if "init_score" in z:
                self.metadata.set_init_score(z["init_score"])
        return self
