"""User-facing Dataset and Booster.

LightGBM-compatible Python API surface (reference:
python-package/lightgbm/basic.py — Dataset :656, Booster :1578), implemented
directly over the TPU-native core instead of ctypes into a C library. The
lazy-construction contract is preserved: a ``Dataset`` holds raw data + params
until ``construct()`` bins it (``_lazy_init`` analog, basic.py:693-800);
validation sets bin with the training set's mappers via ``reference``.
"""
from __future__ import annotations

import copy
import os
from typing import Any, Dict, Iterable, List, Optional, Union

import numpy as np

from .config import Config, param_dict_to_str
from .log import Log, LightGBMError, check
from .io.dataset import BinnedDataset, Metadata, bytes_copied
from .io import model_text
from .objectives import create_objective
from .metrics import create_metric, default_metric_for_objective
from .boosting import create_boosting
from .obs.trace import recorder

_label_from_pandas_warned = False


def _pandas_frame_to_array(df, pandas_categorical=None):
    """DataFrame -> (float64 array, cat column names, category lists).

    Category-dtype columns become their integer codes (NaN for missing/
    unseen) and their category orders are recorded at train time /
    re-applied at predict time, so raw category values map to identical
    codes across sessions — the semantics of the reference's
    _data_from_pandas (python-package/lightgbm/basic.py:255) and its
    pandas_categorical model-file sidecar.
    """
    cat_cols = [c for c in df.columns
                if str(df[c].dtype) == "category"]
    if pandas_categorical is not None:
        # prediction against a trained mapping: the frame must present the
        # same categorical columns (e.g. a CSV reload that lost the
        # category dtype would otherwise be misread as raw codes)
        check(len(pandas_categorical) == len(cat_cols),
              "train and predict data have different categorical columns")
    if not cat_cols:
        return df.values.astype(np.float64), [], pandas_categorical
    df = df.copy(deep=False)
    if pandas_categorical is None:     # training: record category order
        pandas_categorical = [list(df[c].cat.categories) for c in cat_cols]
    else:                              # prediction: align to trained order
        for c, cats in zip(cat_cols, pandas_categorical):
            df[c] = df[c].cat.set_categories(cats)
    for c in cat_cols:
        codes = df[c].cat.codes.astype(np.float64)
        df[c] = codes.where(codes >= 0, np.nan)
    return df.values.astype(np.float64), [str(c) for c in cat_cols], \
        pandas_categorical


def _to_2d_float(data) -> np.ndarray:
    """Accept ndarray / list / pandas DataFrame / scipy sparse."""
    if hasattr(data, "values") and hasattr(data, "dtypes"):  # DataFrame
        data = _pandas_frame_to_array(data)[0]
    if hasattr(data, "toarray"):  # scipy sparse
        data = data.toarray()
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    check(arr.ndim == 2, "Data must be 2-D")
    return arr


def _to_1d(x) -> Optional[np.ndarray]:
    if x is None:
        return None
    if hasattr(x, "values"):
        x = x.values
    return np.asarray(x, dtype=np.float64).reshape(-1)


class Dataset:
    """Dataset in LightGBM (basic.py:656): lazily-binned training data."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None, silent=False,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.silent = silent
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) if params else {}
        self.free_raw_data = free_raw_data
        self.used_indices: Optional[np.ndarray] = None
        self._binned: Optional[BinnedDataset] = None
        self._predictor = None  # _InnerPredictor for continued training
        self.pandas_categorical = None

    # ------------------------------------------------------------ construct
    def construct(self) -> "Dataset":
        """Lazy init (basic.py _lazy_init:693-800)."""
        if self._binned is not None:
            return self
        with recorder.span("ingest.construct") as span:
            self._construct()
            span.counts.update(
                rows=self._binned.num_data,
                columns=getattr(self._binned, "num_total_features", 0))
        return self

    def _construct(self) -> None:
        ref_binned = None
        if self.reference is not None:
            ref_binned = self.reference.construct()._binned
        params = dict(self.params)
        cfg = Config(params)

        if int(cfg.data_stream_chunk_rows) > 0 and self.reference is None \
                and self.used_indices is None \
                and not (isinstance(self.data, str)
                         and self.data.endswith((".npz", ".bin"))):
            # out-of-core path (docs/OutOfCore.md): the raw matrix is
            # consumed chunk-by-chunk and never materialized whole.
            # Validation sets (reference != None) and subsets stay on the
            # in-memory path — they are bounded by construction.
            self._construct_streamed(cfg)
            return

        data = self.data
        if isinstance(data, str):
            # file path; supports the "bin once" .npz cache
            if data.endswith(".npz") or data.endswith(".bin"):
                self._binned = BinnedDataset.load_binary(data)
                return
            from .io import parser as parser_mod
            if cfg.two_round and self.used_indices is None \
                    and not parser_mod.sniff_libsvm(data):
                # two-round streaming load: never materializes the float64
                # matrix (dataset_loader.cpp >memory path). Subsets fall
                # through to the one-shot path — they are in-memory anyway.
                cat = (self.categorical_feature
                       if self.categorical_feature != "auto" else None)
                fn = (self.feature_name
                      if self.feature_name != "auto" else None)
                self._binned = BinnedDataset.from_file_two_round(
                    data, cfg, reference=ref_binned,
                    feature_names=fn, categorical_feature=cat)
                if self.label is not None:
                    self._binned.metadata.set_label(_to_1d(self.label))
                w = (self.weight if self.weight is not None
                     else parser_mod.load_weight_file(data))
                if w is not None:
                    self._binned.metadata.set_weight(_to_1d(w))
                g = (self.group if self.group is not None
                     else parser_mod.load_query_file(data))
                if g is not None:
                    self._binned.metadata.set_query(_to_1d(g))
                isc = (self.init_score if self.init_score is not None
                       else parser_mod.load_init_score_file(data))
                if isc is not None:
                    self._binned.metadata.set_init_score(np.asarray(isc))
                return
            X, y, names = parser_mod.parse_file(data, has_header=cfg.header,
                                                label_column=cfg.label_column)
            if self.label is None:
                self.label = y
            if self.feature_name == "auto" and names:
                self.feature_name = names
            # sidecar metadata files (<data>.weight/.query/.init), the
            # Metadata file convention (src/io/metadata.cpp LoadFromFile)
            if self.weight is None:
                self.weight = parser_mod.load_weight_file(data)
            if self.group is None:
                self.group = parser_mod.load_query_file(data)
            if self.init_score is None:
                self.init_score = parser_mod.load_init_score_file(data)
            data = X

        pandas_cat_cols: List[str] = []
        if hasattr(data, "dtypes") and hasattr(data, "columns"):
            if self.pandas_categorical is None and self.reference is not None:
                # valid sets encode categories in the TRAINING set's order
                self.pandas_categorical = self.reference.pandas_categorical
            data, pandas_cat_cols, self.pandas_categorical = \
                _pandas_frame_to_array(data, self.pandas_categorical)

        from .io.dataset import _is_sparse
        if _is_sparse(data):
            # scipy sparse flows through un-densified: BinnedDataset bins it
            # column-wise and EFB packs exclusive features (io/bundle.py)
            X = data
        else:
            with recorder.span("ingest.to_float64") as span:
                X = _to_2d_float(data)
                span.counts["bytes_copied"] = bytes_copied(data, X)
        label = _to_1d(self.label)
        feature_names = None
        if isinstance(self.feature_name, (list, tuple)):
            feature_names = list(self.feature_name)
        elif hasattr(self.data, "columns"):
            feature_names = [str(c) for c in self.data.columns]

        cat = self.categorical_feature
        if cat == "auto" or cat is None:
            cat = None
        if pandas_cat_cols:
            # pandas category columns are categorical whether or not the
            # user listed them (auto-detection, _data_from_pandas)
            cat = list(cat) if cat else []
            cat.extend(c for c in pandas_cat_cols if c not in cat)
        if self.used_indices is not None:
            # subset construction (basic.py subset/used_indices path)
            X = X[self.used_indices] if not hasattr(X, "tocsr") \
                else X.tocsr()[self.used_indices]
            if label is not None:
                label = label[self.used_indices]

        weight = _to_1d(self.weight)
        init_score = _to_1d(self.init_score)
        group = self.group
        if self.used_indices is not None and weight is not None:
            weight = weight[self.used_indices]
        if self.used_indices is not None and init_score is not None:
            init_score = init_score[self.used_indices]

        self._binned = BinnedDataset.from_matrix(
            X, cfg, label=label, weight=weight, group=group,
            init_score=init_score, feature_names=feature_names,
            categorical_feature=cat, reference=ref_binned)
        self._raw_X = None if self.free_raw_data else X

    def _construct_streamed(self, cfg: Config) -> "Dataset":
        """Out-of-core construction through ``lightgbm_tpu.stream``.

        Picks a ChunkSource by input kind (.npy memory-map, delimited
        text, in-memory array) and two-round ingests it into a
        ``StreamedDataset`` whose uint8 chunks stay host-side until the
        trainer's pipeline sweeps them.
        """
        from .stream import ArraySource, CsvSource, NpyMmapSource
        from .stream.sampler import ingest
        R = int(cfg.data_stream_chunk_rows)
        data = self.data
        label = self.label
        weight, group, init_score = self.weight, self.group, self.init_score
        pandas_cat_cols: List[str] = []
        if isinstance(data, str):
            from .io import parser as parser_mod
            if data.endswith(".npy"):
                src = NpyMmapSource(data, label=label, chunk_rows=R)
            else:
                src = CsvSource(data, chunk_rows=R, has_header=cfg.header,
                                label_column=cfg.label_column)
            # sidecar metadata files, same convention as the in-memory
            # file path (src/io/metadata.cpp LoadFromFile)
            if weight is None:
                weight = parser_mod.load_weight_file(data)
            if group is None:
                group = parser_mod.load_query_file(data)
            if init_score is None:
                init_score = parser_mod.load_init_score_file(data)
        else:
            if hasattr(data, "dtypes") and hasattr(data, "columns"):
                data, pandas_cat_cols, self.pandas_categorical = \
                    _pandas_frame_to_array(data, self.pandas_categorical)
            from .io.dataset import _is_sparse
            if _is_sparse(data):
                raise LightGBMError(
                    "data_stream_chunk_rows does not support sparse "
                    "input; pass a dense array or stream from .npy/text")
            src = ArraySource(_to_2d_float(data), label=_to_1d(label),
                              chunk_rows=R)

        feature_names = None
        if isinstance(self.feature_name, (list, tuple)):
            feature_names = list(self.feature_name)
        elif hasattr(self.data, "columns"):
            feature_names = [str(c) for c in self.data.columns]
        cat = self.categorical_feature
        if cat == "auto" or cat is None:
            cat = None
        if pandas_cat_cols:
            cat = list(cat) if cat else []
            cat.extend(c for c in pandas_cat_cols if c not in cat)

        binned = ingest(src, cfg, feature_names=feature_names,
                        categorical_feature=cat)
        if label is not None and binned.metadata.label is None:
            binned.metadata.set_label(_to_1d(label))
        if weight is not None:
            binned.metadata.set_weight(_to_1d(weight))
        if group is not None:
            binned.metadata.set_query(_to_1d(group))
        if init_score is not None:
            binned.metadata.set_init_score(np.asarray(init_score))
        self._binned = binned
        self._raw_X = None
        return self

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent=False, params=None) -> "Dataset":
        """basic.py:843: validation set aligned to this Dataset's binning."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, silent=silent,
                       params=params or self.params)

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing this dataset's raw data (basic.py:1100s)."""
        ds = Dataset(self.data, label=self.label, reference=self.reference,
                     weight=self.weight, group=self.group,
                     init_score=self.init_score,
                     feature_name=self.feature_name,
                     categorical_feature=self.categorical_feature,
                     params=params or self.params,
                     free_raw_data=self.free_raw_data)
        ds.used_indices = np.asarray(sorted(used_indices), dtype=np.int64)
        if self._binned is not None and self.reference is None:
            ds.reference = self
        return ds

    # ------------------------------------------------------------ fields
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._binned is not None:
            self._binned.metadata.set_label(_to_1d(label))
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._binned is not None:
            self._binned.metadata.set_weight(_to_1d(weight))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._binned is not None:
            self._binned.metadata.set_query(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._binned is not None:
            self._binned.metadata.set_init_score(_to_1d(init_score))
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        check(self._binned is None,
              "Cannot set reference after dataset was constructed")
        self.reference = reference
        return self

    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "group" or field_name == "query":
            return self.set_group(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        raise LightGBMError("Unknown field name %s" % field_name)

    def get_field(self, field_name: str):
        m = self.construct()._binned.metadata
        if field_name == "label":
            return m.label
        if field_name == "weight":
            return m.weight
        if field_name in ("group", "query"):
            if m.query_boundaries is None:
                return None
            return np.diff(m.query_boundaries)
        if field_name == "init_score":
            return m.init_score
        raise LightGBMError("Unknown field name %s" % field_name)

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_group(self):
        return self.get_field("group")

    def get_init_score(self):
        return self.get_field("init_score")

    def num_data(self) -> int:
        return self.construct()._binned.num_data

    def num_feature(self) -> int:
        return self.construct()._binned.num_total_features

    def get_feature_name(self) -> List[str]:
        return list(self.construct()._binned.feature_names)

    def save_binary(self, filename: str) -> "Dataset":
        """basic.py:1312 / dataset.h:394 SaveBinaryFile."""
        self.construct()._binned.save_binary(filename)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        check(self._binned is None,
              "Cannot set categorical feature after dataset was constructed")
        self.categorical_feature = categorical_feature
        return self

    def _set_predictor(self, predictor) -> "Dataset":
        self._predictor = predictor
        return self


class _InnerPredictor:
    """Continued-training predictor (basic.py:346): supplies init scores for
    a new training run from an existing model."""

    def __init__(self, booster: "Booster", num_iteration: int = -1):
        self.booster = booster
        self.num_iteration = num_iteration

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        return self.booster.predict(
            X, num_iteration=self.num_iteration
            if self.num_iteration > 0 else None, raw_score=True)

    def models(self):
        """The init model's HostTrees — capped exactly like predict_raw
        (explicit num_iteration, else best_iteration, else all), so the
        merged trees always match the init scores training was seeded
        from."""
        all_models = self.booster._impl.models
        eff = self.num_iteration
        if eff <= 0:
            eff = self.booster.best_iteration
        if eff <= 0:
            return all_models
        k = max(self.booster._impl.num_tree_per_iteration, 1)
        return all_models[:eff * k]


class Booster:
    """Booster in LightGBM (basic.py:1578)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent=False):
        self.params = copy.deepcopy(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        self._loaded = None      # parsed model dict when created from file/str
        self._train_set: Optional[Dataset] = None
        self._impl = None        # boosting driver (GBDT/DART/GOSS/RF)
        self._objective = None
        self.pandas_categorical = None

        if train_set is not None:
            check(isinstance(train_set, Dataset),
                  "Training data should be Dataset instance")
            self._init_from_train_set(train_set)
        elif model_file is not None:
            with open(model_file, "r") as fh:
                self._init_from_string(fh.read())
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            # params-only booster (used by set_network-style workflows)
            self.config = Config(self.params)

    # ------------------------------------------------------------ init paths
    def _init_from_train_set(self, train_set: Dataset) -> None:
        train_set.params = {**train_set.params, **self.params} \
            if train_set._binned is None else train_set.params
        train_set.construct()
        self._train_set = train_set
        self.pandas_categorical = train_set.pandas_categorical
        self.config = Config(self.params)
        binned = train_set._binned

        self._objective = create_objective(self.config)
        metric_names = list(self.config.metric)
        if not metric_names:
            default = default_metric_for_objective(self.config.objective)
            if default:
                metric_names = [default]
        self._metric_names = [m for m in metric_names if m and m != "None"]
        train_metrics = [m for m in
                         (create_metric(n, self.config)
                          for n in self._metric_names) if m]

        # continued training: seed scores with the init model's predictions
        if train_set._predictor is not None:
            raw = train_set._predictor.predict_raw(
                _to_2d_float(train_set.data)
                if not isinstance(train_set.data, str) else None)
            binned.metadata.set_init_score(
                np.asarray(raw, np.float64).reshape(-1, order="F"))

        self._impl = create_boosting(self.config, binned, self._objective,
                                     train_metrics)
        if train_set._predictor is not None:
            # the returned booster must be self-contained: prepend the init
            # model's trees (LGBM_BoosterMerge -> GBDT::MergeFrom,
            # gbdt.h:53); deep copies so later shrink/rollback cannot
            # mutate the init booster
            init_models = train_set._predictor.models()
            init_k = max(train_set._predictor.booster._impl
                         .num_tree_per_iteration, 1)
            check(init_k == max(self._impl.num_tree_per_iteration, 1),
                  "init model has %d trees per iteration but the new "
                  "parameters produce %d" % (
                      init_k, max(self._impl.num_tree_per_iteration, 1)))
            self._impl._models = copy.deepcopy(init_models)
            self._impl.num_init_iteration = (
                len(init_models) // max(self._impl.num_tree_per_iteration, 1))
            self._impl.iter_ = self._impl.num_init_iteration
            # a bare init_model carries trees only — warn loudly when the
            # boosting mode has sampling/weight state that a model file
            # cannot restore (checkpoints can: docs/Checkpointing.md)
            self._impl.warn_lossy_continuation()
        self.train_set_name = "training"

    def _init_from_string(self, model_str: str) -> None:
        # pandas_categorical sidecar (may be absent in reference-written
        # files that predate it or carried 'null')
        for line in model_str.splitlines()[::-1]:
            if line.startswith("pandas_categorical:"):
                import json as _json
                try:
                    self.pandas_categorical = _json.loads(
                        line[len("pandas_categorical:"):])
                except ValueError:
                    pass
                break
        parsed = model_text.parse_model_string(model_str)
        self._loaded = parsed
        params = dict(self.params)
        obj_tokens = parsed["objective"].split()
        if obj_tokens:
            params.setdefault("objective", obj_tokens[0])
            for tok in obj_tokens[1:]:
                if ":" in tok:
                    k, v = tok.split(":", 1)
                    params.setdefault(k, v)
                elif tok == "sqrt":
                    params.setdefault("reg_sqrt", True)
        if parsed["num_class"] > 1:
            params["num_class"] = parsed["num_class"]
        self.config = Config(params)
        self._objective = (create_objective(self.config)
                           if obj_tokens and obj_tokens[0] != "custom" else None)
        # build a predict-only driver
        from .boosting.gbdt import GBDT
        impl = GBDT(self.config, None, None, [])
        impl.objective = self._objective
        impl.num_class = parsed["num_class"]
        impl.num_tree_per_iteration = parsed["num_tree_per_iteration"]
        impl.models = parsed["trees"]
        impl.average_output = parsed["average_output"]
        self._impl = impl
        self._feature_names_loaded = parsed["feature_names"]
        self._feature_infos_loaded = parsed["feature_infos"]

    # ------------------------------------------------------------ training
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        check(isinstance(data, Dataset), "Validation data should be Dataset")
        data.construct()
        metrics = [m for m in (create_metric(n, self.config)
                               for n in self._metric_names) if m]
        self._impl.add_valid_data(data._binned, metrics)
        self._valid_sets.append(data)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting round (basic.py:1843). Returns True if stopped."""
        if train_set is not None and train_set is not self._train_set:
            self.reset_training_data(train_set)
        if fobj is None:
            return self._impl.train_one_iter()
        # custom objective path (__boost, basic.py:1891)
        grad, hess = fobj(self.__pred_for_fobj(), self._train_set)
        return self.__boost(grad, hess)

    def __getstate__(self):
        """Pickle as the model text (reference basic.py __getstate__
        drops the native handle the same way): the unpickled booster
        predicts and serializes; training state (datasets, device arrays,
        compiled programs) intentionally does not survive."""
        state = {
            "params": self.params,
            "best_iteration": self.best_iteration,
            "best_score": dict(self.best_score),
            "pandas_categorical": self.pandas_categorical,
            "model_str": (self.model_to_string(num_iteration=-1)
                          if self._impl is not None and self._impl.models
                          else None),
        }
        return state

    def __setstate__(self, state):
        self.__init__(params=state.get("params"),
                      model_str=state.get("model_str"))
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        if state.get("pandas_categorical") is not None:
            self.pandas_categorical = state["pandas_categorical"]

    def __pred_for_fobj(self) -> np.ndarray:
        scores = np.array(self._impl.scores)
        return scores[:, 0] if scores.shape[1] == 1 else scores.reshape(-1, order="F")

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, np.float32)
        hess = np.asarray(hess, np.float32)
        return self._impl.train_one_iter(grad, hess)

    def rollback_one_iter(self) -> "Booster":
        self._impl.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        # LightGBM exposes this as a method; keep method semantics
        return self._impl.current_iteration

    def num_trees(self) -> int:
        return len(self._impl.models)

    def num_model_per_iteration(self) -> int:
        return self._impl.num_tree_per_iteration

    def num_feature(self) -> int:
        if self._train_set is not None:
            return self._train_set.num_feature()
        return len(self._feature_names_loaded)

    # ------------------------------------------------------------ evaluation
    def eval_train(self, feval=None):
        return self.__inner_eval(self.train_set_name, 0, feval)

    def eval_valid(self, feval=None):
        out = []
        for i in range(len(self._valid_sets)):
            out.extend(self.__inner_eval(self.name_valid_sets[i], i + 1, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        if data is self._train_set:
            return self.eval_train(feval)
        for i, vs in enumerate(self._valid_sets):
            if data is vs:
                return self.__inner_eval(name, i + 1, feval)
        raise LightGBMError("Data should be a validation set added via add_valid")

    def __inner_eval(self, name: str, data_idx: int, feval=None):
        out = [(name, m, v, bb)
               for _, m, v, bb in self._impl.get_eval_at(data_idx)]
        if feval is not None:
            if data_idx == 0:
                ds = self._train_set
                scores = np.array(self._impl.scores)
            else:
                ds = self._valid_sets[data_idx - 1]
                scores = np.array(
                    self._impl._valid_pred_cache[data_idx - 1]["scores"])
            preds = scores[:, 0] if scores.shape[1] == 1 \
                else scores.reshape(-1, order="F")
            res = feval(preds, ds)
            if isinstance(res, list):
                for r in res:
                    out.append((name, r[0], r[1], r[2]))
            elif res is not None:
                out.append((name, res[0], res[1], res[2]))
        return out

    # ------------------------------------------------------------ prediction
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        if isinstance(data, Dataset):
            raise LightGBMError("Cannot use Dataset instance for prediction, "
                                "please use raw data instead")
        if hasattr(data, "dtypes") and hasattr(data, "columns") \
                and self.pandas_categorical is not None:
            data = _pandas_frame_to_array(data, self.pandas_categorical)[0]
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 \
                else None
        if hasattr(data, "toarray"):
            # sparse input: densify in bounded row blocks (~128 MB of f64),
            # never the whole matrix (PredictForCSR streams rows the same
            # way; an Allstate-shaped 13.2M x 4228 CSR would otherwise
            # materialize ~450 GB). Each block is one device call.
            block = max(256, (1 << 24) // max(int(data.shape[1]), 1))
            if data.shape[0] > block:
                mat = data.tocsr()
                outs = [self.predict(
                            mat[lo:lo + block].toarray(),
                            num_iteration=num_iteration,
                            raw_score=raw_score, pred_leaf=pred_leaf,
                            pred_contrib=pred_contrib, **kwargs)
                        for lo in range(0, mat.shape[0], block)]
                return np.concatenate(outs, axis=0)
        X = _to_2d_float(data)
        if pred_contrib:
            return self._impl_predict_contrib(X, num_iteration)
        return self._impl.predict(
            X, num_iteration=num_iteration, raw_score=raw_score,
            pred_leaf=pred_leaf,
            pred_early_stop=kwargs.get("pred_early_stop", False),
            pred_early_stop_freq=kwargs.get("pred_early_stop_freq", 10),
            pred_early_stop_margin=kwargs.get("pred_early_stop_margin", 10.0))

    def _impl_predict_contrib(self, X, num_iteration):
        from .core.shap import predict_contrib
        return predict_contrib(self._impl, X, num_iteration)

    def reset_training_data(self, train_set: Dataset) -> "Booster":
        """Swap the training dataset under the current model
        (LGBM_BoosterResetTrainingData -> GBDT::ResetTrainingData,
        gbdt.cpp:622-660): bin mappers must align with the old data, the
        model is kept, and train scores are recomputed by replaying every
        tree on the new binned features."""
        check(self._impl is not None, "no training state to reset")
        check(isinstance(train_set, Dataset),
              "Training data should be Dataset instance")
        old_binned = self._train_set.construct()._binned \
            if self._train_set is not None else None
        if train_set._binned is None:
            if train_set.reference is None and self._train_set is not None:
                train_set.reference = self._train_set
            train_set.params = {**(train_set.params or {}), **self.params}
        train_set.construct()
        if old_binned is not None:
            # CheckAlign (gbdt.cpp:624-626): identical bin mappers or fatal
            check(train_set._binned.get_feature_infos()
                  == old_binned.get_feature_infos(),
                  "Cannot reset training data: new training data has "
                  "different bin mappers")

        import jax.numpy as jnp
        old = self._impl
        models = copy.deepcopy(old.models)   # materializes pending work
        new_impl = create_boosting(
            self.config, train_set._binned, create_objective(self.config),
            [m for m in (create_metric(n, self.config)
                         for n in getattr(self, "_metric_names", [])) if m])
        new_impl._models = models
        new_impl.iter_ = old.iter_
        new_impl.num_init_iteration = getattr(old, "num_init_iteration", 0)
        new_impl.boost_from_average_done = True
        offs = getattr(old, "init_score_offsets", None)
        if offs is not None and np.any(np.asarray(offs) != 0):
            new_impl.scores = new_impl.scores + jnp.asarray(
                np.asarray(offs, np.float32))[None, :]
            new_impl.init_score_offsets = np.asarray(offs, np.float32)
        k = max(new_impl.num_tree_per_iteration, 1)
        scores = new_impl.scores
        for i, ht in enumerate(models):
            leaf = new_impl._replay_leaves_binned(ht, new_impl.xb)
            scores = scores.at[:, i % k].add(
                jnp.asarray(ht.leaf_value.astype(np.float32))[leaf])
        new_impl.scores = scores
        # validation sets survive the swap (the reference keeps its
        # valid_score_updaters; add_valid_data replays the model on each)
        for vset, vname in zip(self._valid_sets, self.name_valid_sets):
            mets = [m for m in (create_metric(n, self.config)
                                for n in getattr(self, "_metric_names", []))
                    if m]
            new_impl.add_valid_data(vset.construct()._binned, mets)
        self._impl = new_impl
        self._objective = new_impl.objective
        self._train_set = train_set
        return self

    def as_serving_bundle(self, model_id: str = "default"):
        """Package this booster for lightgbm_tpu.serving: trees stacked
        ``[iterations, trees_per_iteration, ...]`` on device, immutable.
        Register on a ServingEngine with
        ``engine.registry.register(booster.as_serving_bundle(id))``."""
        from .serving.registry import ModelBundle
        check(self._impl is not None and self._impl.models,
              "Cannot serve: no trained model")
        return ModelBundle.from_booster(model_id, self)

    def refit(self, data, label, decay_rate: float = 0.9, weight=None,
              group=None, **kwargs) -> "Booster":
        """Refit existing tree structures to new data (RefitTree,
        gbdt.cpp:263-286 + FitByExistingTree, serial_tree_learner.cpp:235-265):
        every split is kept, leaf outputs are re-estimated from the new data's
        gradients and blended with the old outputs by ``decay_rate``.

        Dense inputs take the device path (fleet/refit.py: one flat-forest
        traversal + one scan over iterations, compiled once and reused;
        ``refit_device=false`` forces this host loop). Sparse inputs stay
        on the host's streamed-block path — it never densifies."""
        import jax
        import jax.numpy as jnp
        from .core import tree as tree_mod
        from .io.dataset import Metadata

        check(self._impl is not None and self._impl.models,
              "Cannot refit: no trained model")
        check(self._objective is not None,
              "Cannot refit a model trained with a custom objective")
        sparse_in = hasattr(data, "toarray") and not hasattr(data, "dtypes")
        if not sparse_in and self.config.refit_device:
            from .fleet.refit import refit_booster
            return refit_booster(self, data, label, decay_rate=decay_rate,
                                 weight=weight, group=group)
        if sparse_in:
            data = data.tocsr()
            n = int(data.shape[0])
        else:
            X = _to_2d_float(data)
            n = X.shape[0]
        k = self._impl.num_tree_per_iteration
        models = self._impl.models

        md = Metadata(n)
        md.set_label(_to_1d(label))
        if weight is not None:
            md.set_weight(_to_1d(weight))
        if group is not None:
            md.set_query(np.asarray(group, np.int64))
        obj = copy.deepcopy(self._objective)
        obj.init(md, n)
        cfg = self.config
        l1, l2, mds = cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step

        if sparse_in:
            # bounded-block leaf routing: never materialize the full dense
            # matrix (the sparse-predict contract; PredictForCSR streams)
            blk = max(256, (1 << 24) // max(int(data.shape[1]), 1))

            def leaves_of(pt):
                return np.concatenate([
                    np.asarray(tree_mod.predict_tree_leaves_raw(
                        pt, jnp.asarray(data[lo:lo + blk].toarray(),
                                        jnp.float32)))
                    for lo in range(0, n, blk)])
        else:
            xj = jnp.asarray(X, jnp.float32)

            def leaves_of(pt):
                return np.asarray(tree_mod.predict_tree_leaves_raw(pt, xj))
        scores = np.zeros((n, k), np.float32)
        g = h = None
        new_trees = []
        for i, ht in enumerate(models):
            c = i % k
            if c == 0:  # gradients refresh once per boosting iteration
                if k == 1:
                    gj, hj = obj.get_gradients(jnp.asarray(scores[:, 0]))
                    g, h = np.asarray(gj)[:, None], np.asarray(hj)[:, None]
                else:
                    gj, hj = obj.get_gradients(jnp.asarray(scores))
                    g, h = np.asarray(gj), np.asarray(hj)
            nl = ht.num_leaves_actual
            pt = jax.tree.map(jnp.asarray,
                              ht.predict_table(max(len(ht.split_leaf), 1),
                                               max(len(ht.leaf_value), 1)))
            leaves = leaves_of(pt)
            sg = np.bincount(leaves, weights=g[:, c].astype(np.float64),
                             minlength=nl)
            sh = np.bincount(leaves, weights=h[:, c].astype(np.float64),
                             minlength=nl)
            # CalculateSplittedLeafOutput (feature_histogram.hpp:454-462)
            out = -np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0) \
                / (sh + l2 + 1e-15)
            if mds > 0:
                out = np.clip(out, -mds, mds)
            out *= getattr(ht, "shrinkage", 1.0)
            nh = copy.deepcopy(ht)
            old = ht.leaf_value[:nl].astype(np.float64)
            nh.leaf_value = ht.leaf_value.copy()
            nh.leaf_value[:nl] = decay_rate * old + (1.0 - decay_rate) * out
            scores[:, c] += nh.leaf_value[leaves].astype(np.float32)
            new_trees.append(nh)

        refitted = Booster(model_str=self.model_to_string())
        refitted._impl.models = new_trees
        return refitted

    # ------------------------------------------------------------ model IO
    def _feature_names(self) -> List[str]:
        if self._train_set is not None:
            return self._train_set.get_feature_name()
        return list(self._feature_names_loaded)

    def _feature_infos(self) -> List[str]:
        if self._train_set is not None:
            return self._train_set.construct()._binned.get_feature_infos()
        return list(self._feature_infos_loaded)

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 \
                else -1
        out = model_text.model_to_string(
            self._impl, self._feature_names(), self._feature_infos(),
            num_iteration=num_iteration, start_iteration=start_iteration,
            parameters=param_dict_to_str(self.params))
        # the reference's python package appends this sidecar line so raw
        # pandas category values survive save/load (basic.py
        # _dump_pandas_categorical); keep the format identical for interop
        import json as _json

        def _cat_value(v):
            # numeric category values must stay numeric through JSON or
            # set_categories() at load time matches nothing
            if isinstance(v, np.integer):
                return int(v)
            if isinstance(v, np.floating):
                return float(v)
            return str(v)

        out += "\npandas_categorical:%s\n" % _json.dumps(
            self.pandas_categorical, default=_cat_value)
        return out

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        with open(filename, "w") as fh:
            fh.write(self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None) -> Dict:
        import json
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 \
                else -1
        return json.loads(model_text.model_to_json(
            self._impl, self._feature_names(), self._feature_infos(),
            num_iteration=num_iteration))

    # ------------------------------------------------------------ insight
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        imp = self._impl.feature_importance(importance_type, iteration)
        if importance_type == "split":
            return imp.astype(np.int64)
        return imp

    def feature_name(self) -> List[str]:
        return self._feature_names()

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of threshold values this feature was split on
        (basic.py get_split_value_histogram; reference test
        test_engine.py:1247)."""
        if isinstance(feature, str):
            names = self._feature_names()
            check(feature in names, "Feature %s not found" % feature)
            feature = names.index(feature)
        values = []
        for ht in self._impl.models:
            nn = ht.num_leaves_actual - 1
            for t in range(max(nn, 0)):
                if (ht.split_feature[t] == feature
                        and not ht.is_categorical[t]):
                    values.append(float(ht.threshold[t]))
        values = np.asarray(values, np.float64)
        if bins is None:
            bins = max(min(len(values), 255), 1)
        hist, edges = np.histogram(values, bins=bins)
        if xgboost_style:
            rows = [(edges[i + 1], int(hist[i])) for i in range(len(hist))
                    if hist[i] > 0]
            return np.asarray(rows, np.float64).reshape(-1, 2)
        return hist, edges

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Value of a single leaf (reference basic.py:2329 /
        LGBM_BoosterGetLeafValue)."""
        models = self._impl.models
        if not 0 <= tree_id < len(models):
            raise LightGBMError("tree_id %d out of range" % tree_id)
        t = models[tree_id]
        if not 0 <= leaf_id < int(t.num_leaves_actual):
            raise LightGBMError("leaf_id %d out of range" % leaf_id)
        return float(t.leaf_value[leaf_id])

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """basic.py reset_parameter → learning-rate etc. mid-training."""
        self.params.update(params)
        self.config.set(params)
        if self._impl is not None:
            self._impl.shrinkage_rate = self.config.learning_rate
        return self

    def set_network(self, machines, local_listen_port=12400,
                    listen_time_out=120, num_machines=1) -> "Booster":
        """Multi-host topology configuration (basic.py:1734). On TPU the
        actual collectives ride the ICI/DCN mesh via jax.distributed."""
        from .parallel import network
        network.init(machines=machines, local_listen_port=local_listen_port,
                     time_out=listen_time_out, num_machines=num_machines)
        return self

    def free_network(self) -> "Booster":
        from .parallel import network
        network.free()
        return self
