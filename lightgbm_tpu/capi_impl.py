"""Python side of the C ABI (native/src/c_api.cpp).

The embedded interpreter calls these flat functions with primitive
arguments (memoryviews over caller-owned buffers, strings, ints) and gets
primitives/bytes back, keeping the C++ shim free of object-protocol
details. The reference implements the same surface natively
(src/c_api.cpp:46-363 Booster wrapper + the LGBM_* bodies); here the
runtime IS the Python package, so the ABI marshals into it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .basic import Booster, Dataset
from .log import LightGBMError

_DTYPES = {0: np.float32, 1: np.float64, 2: np.int32, 3: np.int64}


def parse_params(s: Optional[str]) -> Dict[str, str]:
    """"k1=v1 k2=v2" -> dict (Config::KV2Map semantics, config.cpp)."""
    out: Dict[str, str] = {}
    for tok in (s or "").replace("\t", " ").split(" "):
        tok = tok.strip()
        if not tok or tok.startswith("#"):
            continue
        if "=" in tok:
            k, v = tok.split("=", 1)
            out[k.strip()] = v.strip()
        else:
            out[tok] = "true"
    return out


def _mat(mv: memoryview, dtype_code: int, nrow: int, ncol: int,
         row_major: int) -> np.ndarray:
    dt = _DTYPES[dtype_code]
    arr = np.frombuffer(mv, dtype=dt, count=nrow * ncol)
    if row_major:
        return arr.reshape(nrow, ncol)
    return arr.reshape(ncol, nrow).T


def dataset_from_file(filename: str, params: str,
                      reference: Optional[Dataset]) -> Dataset:
    p = parse_params(params)
    label_kw = {}
    ds = Dataset(filename, reference=reference, params=p,
                 free_raw_data=False, **label_kw)
    ds.construct()
    return ds


def dataset_from_mat(mv: memoryview, dtype_code: int, nrow: int, ncol: int,
                     row_major: int, params: str,
                     reference: Optional[Dataset]) -> Dataset:
    # the C contract lets the host free its buffer as soon as the call
    # returns; copy=True guards against astype's no-op fast path handing
    # back a view of caller memory
    data = _mat(mv, dtype_code, nrow, ncol, row_major) \
        .astype(np.float64, copy=True)
    ds = Dataset(data, reference=reference, params=parse_params(params),
                 free_raw_data=False)
    return ds


def _csr_parts(indptr_mv, indptr_code, indices_mv, data_mv, data_code,
               nindptr, nelem):
    """Copy CSR pieces out of caller-owned memory (the host may free its
    buffers on return); nelem == 0 (all-zero rows) is a valid matrix."""
    indptr = np.frombuffer(indptr_mv, dtype=_DTYPES[indptr_code],
                           count=nindptr).copy()
    if nelem == 0:
        return indptr, np.zeros(0, np.int32), np.zeros(0, np.float64)
    indices = np.frombuffer(indices_mv, dtype=np.int32, count=nelem).copy()
    vals = np.frombuffer(data_mv, dtype=_DTYPES[data_code],
                         count=nelem).copy()
    return indptr, indices, vals


def dataset_from_csr(indptr_mv: memoryview, indptr_code: int,
                     indices_mv: memoryview, data_mv: memoryview,
                     data_code: int, nindptr: int, nelem: int,
                     num_col: int, params: str,
                     reference: Optional[Dataset]) -> Dataset:
    from scipy.sparse import csr_matrix
    indptr, indices, vals = _csr_parts(
        indptr_mv, indptr_code, indices_mv, data_mv, data_code, nindptr,
        nelem)
    mat = csr_matrix((vals, indices, indptr),
                     shape=(nindptr - 1, num_col))
    return Dataset(mat, reference=reference, params=parse_params(params),
                   free_raw_data=False)


def dataset_set_field(ds: Dataset, name: str, mv: Optional[memoryview],
                      num_element: int, dtype_code: int) -> None:
    arr = None if (mv is None or num_element == 0) else np.array(
        np.frombuffer(mv, dtype=_DTYPES[dtype_code], count=num_element))
    if isinstance(ds, PendingDataset) and not hasattr(ds, "_final") \
            and not ds.finished:
        # streaming construction: the reference allows SetField at any
        # point before FinishLoad; stash and apply at finalize
        ds.pending_fields[name] = arr
        return
    _as_dataset(ds).set_field(name, arr)


def dataset_num_data(ds) -> int:
    if isinstance(ds, PendingDataset) and not hasattr(ds, "_final"):
        # the reference reports num_total_row before FinishLoad
        return int(ds.raw.shape[0])
    return int(_as_dataset(ds).construct().num_data())


def dataset_num_feature(ds) -> int:
    if isinstance(ds, PendingDataset) and not hasattr(ds, "_final"):
        return int(ds.raw.shape[1])
    return int(_as_dataset(ds).construct().num_feature())


def dataset_set_feature_names(ds, names: List[str]) -> None:
    _as_dataset(ds).feature_name = list(names)


def booster_create(train, params: str) -> Booster:
    return Booster(params=parse_params(params), train_set=_as_dataset(train))


def booster_from_file(filename: str) -> Tuple[Booster, int]:
    bst = Booster(model_file=filename)
    return bst, bst.current_iteration


def booster_from_string(model_str: str) -> Tuple[Booster, int]:
    bst = Booster(model_str=model_str)
    return bst, bst.current_iteration


def booster_add_valid(bst: Booster, valid) -> None:
    bst.add_valid(_as_dataset(valid),
                  "valid_%d" % (len(bst._valid_sets) + 1))


def booster_update(bst: Booster) -> int:
    return int(bool(bst.update()))


def booster_update_custom(bst: Booster, grad_mv: memoryview,
                          hess_mv: memoryview, n: int) -> int:
    grad = np.frombuffer(grad_mv, dtype=np.float32, count=n)
    hess = np.frombuffer(hess_mv, dtype=np.float32, count=n)
    return int(bool(bst._impl.train_one_iter(np.array(grad),
                                             np.array(hess))))


def booster_num_classes(bst: Booster) -> int:
    return int(bst._impl.num_class)


def booster_num_train_rows_times_classes(bst: Booster) -> int:
    impl = bst._impl
    return int(impl.num_data * impl.num_tree_per_iteration)


def booster_rollback(bst: Booster) -> None:
    bst.rollback_one_iter()


def booster_current_iteration(bst: Booster) -> int:
    return int(bst.current_iteration)


def booster_num_model_per_iteration(bst: Booster) -> int:
    return int(bst.num_model_per_iteration())


def booster_num_total_model(bst: Booster) -> int:
    return int(bst.num_trees())


def booster_merge(dst: Booster, src: Booster) -> None:
    """GBDT::MergeFrom (gbdt.h:53-64): src's trees go FIRST (deep copies),
    dst's own trees follow; num_init_iteration tracks the prefix."""
    import copy as _copy
    k = max(dst._impl.num_tree_per_iteration, 1)
    if max(src._impl.num_tree_per_iteration, 1) != k:
        raise LightGBMError("cannot merge boosters with different "
                            "trees-per-iteration")
    merged = _copy.deepcopy(src._impl.models) + list(dst._impl.models)
    dst._impl.models = merged
    dst._impl.num_init_iteration = len(src._impl.models) // k
    dst._impl.iter_ = len(merged) // k


def booster_eval(bst: Booster, data_idx: int) -> bytes:
    if data_idx == 0:
        res = bst.eval_train()
    else:
        res = [r for r in bst.eval_valid()
               if r[0] == ("valid_%d" % data_idx)]
    return np.asarray([v for _, _, v, _ in res], np.float64).tobytes()


def booster_eval_names(bst: Booster) -> List[str]:
    names = []
    for m in bst._impl.train_metrics:
        names.extend(m.names)
    return names


def _predict_kwargs(predict_type: int, num_iteration: int,
                    parameter: str) -> Dict:
    """One predict-kwargs builder for every prediction entry point, so
    the mat/CSR paths cannot drift."""
    kw = dict(num_iteration=(num_iteration if num_iteration > 0 else None))
    if predict_type == 1:
        kw["raw_score"] = True
    elif predict_type == 2:
        kw["pred_leaf"] = True
    elif predict_type == 3:
        kw["pred_contrib"] = True
    p = parse_params(parameter)
    if "pred_early_stop" in p:
        kw["pred_early_stop"] = p["pred_early_stop"] in ("true", "1")
    return kw


def booster_predict_csr(bst: Booster, indptr_mv: memoryview,
                        indptr_code: int, indices_mv: memoryview,
                        data_mv: memoryview, data_code: int, nindptr: int,
                        nelem: int, num_col: int, predict_type: int,
                        num_iteration: int, parameter: str) -> bytes:
    from scipy.sparse import csr_matrix
    indptr, indices, vals = _csr_parts(
        indptr_mv, indptr_code, indices_mv, data_mv, data_code, nindptr,
        nelem)
    mat = csr_matrix((vals, indices, indptr), shape=(nindptr - 1, num_col))
    kw = _predict_kwargs(predict_type, num_iteration, parameter)
    # Booster.predict streams sparse input in bounded row blocks itself
    # (the reference's CSR-row streaming); one code path for every caller
    return np.asarray(bst.predict(mat, **kw), np.float64).tobytes()


def booster_predict_mat(bst: Booster, mv: memoryview, dtype_code: int,
                        nrow: int, ncol: int, row_major: int,
                        predict_type: int, num_iteration: int,
                        parameter: str) -> bytes:
    data = _mat(mv, dtype_code, nrow, ncol, row_major)
    kw = _predict_kwargs(predict_type, num_iteration, parameter)
    out = np.asarray(bst.predict(np.ascontiguousarray(data, np.float64),
                                 **kw), np.float64)
    return out.tobytes()


def booster_save_model(bst: Booster, start_iteration: int,
                       num_iteration: int, filename: str) -> None:
    # C ABI: num_iteration <= 0 means "all" (not best_iteration)
    bst.save_model(filename,
                   num_iteration=(num_iteration if num_iteration > 0
                                  else -1),
                   start_iteration=max(start_iteration, 0))


def booster_model_to_string(bst: Booster, start_iteration: int,
                            num_iteration: int) -> str:
    return bst.model_to_string(
        num_iteration=(num_iteration if num_iteration > 0 else -1),
        start_iteration=max(start_iteration, 0))


def booster_dump_model(bst: Booster, start_iteration: int,
                       num_iteration: int) -> str:
    import json
    return json.dumps(bst.dump_model(
        num_iteration=(num_iteration if num_iteration > 0 else -1)))


def booster_feature_importance(bst: Booster, num_iteration: int,
                               importance_type: int) -> bytes:
    kind = "gain" if importance_type == 1 else "split"
    imp = bst.feature_importance(importance_type=kind,
                                 iteration=(num_iteration
                                            if num_iteration > 0 else None))
    return np.asarray(imp, np.float64).tobytes()


def network_init_with_functions(num_machines: int, rank: int,
                                reduce_scatter_ptr: int,
                                allgather_ptr: int) -> None:
    """LGBM_NetworkInitWithFunctions (c_api.h:958): register caller-
    provided collective function pointers as the host-side transport."""
    from .parallel import network
    network.init_with_functions(num_machines, rank,
                                reduce_scatter_ptr, allgather_ptr)


def network_init(machines: str, local_listen_port: int, listen_time_out: int,
                 num_machines: int) -> None:
    from .parallel import network
    network.init(machines=machines, local_listen_port=local_listen_port,
                 listen_time_out=listen_time_out, num_machines=num_machines)


def network_free() -> None:
    from .parallel import network
    network.free()


def booster_reset_parameter(bst: Booster, params: str) -> None:
    """LGBM_BoosterResetParameter: re-apply run-time tunable parameters
    (c_api.h:458; routed through Booster.reset_parameter)."""
    bst.reset_parameter(parse_params(params))


def booster_num_feature(bst: Booster) -> int:
    return int(bst.num_feature())


def booster_get_leaf_value(bst: Booster, tree_idx: int, leaf_idx: int) -> float:
    """LGBM_BoosterGetLeafValue (gbdt.h GetLeafValue analog)."""
    return float(bst.get_leaf_output(tree_idx, leaf_idx))


def dataset_feature_names(ds) -> list:
    b = _as_dataset(ds).construct()._binned
    return list(b.feature_names)


# ---------------------------------------------------------------- streaming
class PendingDataset:
    """Push-rows construction state (LGBM_DatasetCreateByReference /
    CreateFromSampledColumn + PushRows*, c_api.h:58-233): rows accumulate
    into a preallocated host matrix; the first consumer (BoosterCreate,
    GetSubset, SaveBinary, ...) finalizes it into a real Dataset, binned
    against the reference's mappers when one was given. The reference bins
    rows as they arrive (Dataset::PushRow); binning once at finish keeps
    the same observable contract — FinishLoad fires when
    start_row + nrow == num_total_row — at the cost of holding the raw
    block, which is the price of reusing the vectorized binning path."""

    def __init__(self, num_total_row: int, ncol: int,
                 reference: Optional[Dataset], params: str):
        self.raw = np.zeros((num_total_row, ncol), np.float64)
        self.pushed = np.zeros(num_total_row, bool)
        self.reference = reference
        self.params = params
        self.finished = False
        self.pending_fields: Dict[str, Optional[np.ndarray]] = {}

    def push(self, rows: np.ndarray, start_row: int) -> None:
        if self.finished:
            raise LightGBMError("dataset already finished loading")
        end = start_row + rows.shape[0]
        if end > self.raw.shape[0]:
            raise LightGBMError(
                "push exceeds num_total_row (%d > %d)"
                % (end, self.raw.shape[0]))
        self.raw[start_row:end] = rows
        self.pushed[start_row:end] = True
        if end == self.raw.shape[0]:
            self.finished = True

    def finalize(self) -> Dataset:
        if not self.pushed.all():
            raise LightGBMError(
                "dataset used before all rows were pushed (%d of %d)"
                % (int(self.pushed.sum()), len(self.pushed)))
        ds = Dataset(self.raw, reference=self.reference,
                     params=parse_params(self.params), free_raw_data=False)
        for name, arr in self.pending_fields.items():
            ds.set_field(name, arr)
        return ds


def _as_dataset(obj):
    """Every ABI entry point that consumes a DatasetHandle routes through
    here so a PendingDataset transparently finalizes on first use (the C
    handle keeps pointing at the same PyObject; the finalized Dataset is
    cached on it)."""
    if isinstance(obj, PendingDataset):
        if not hasattr(obj, "_final"):
            obj._final = obj.finalize()
            obj.raw = None            # release the raw block
        return obj._final
    return obj


def dataset_create_by_reference(reference, num_total_row: int):
    ref = _as_dataset(reference)
    ncol = int(ref.num_feature())
    return PendingDataset(int(num_total_row), ncol, ref, "")


def dataset_create_from_sampled_column(col_mvs: List[Optional[memoryview]],
                                       idx_mvs: List[Optional[memoryview]],
                                       num_per_col: List[int],
                                       num_sample_row: int,
                                       num_total_row: int, params: str):
    """Bin mappers come from the sampled values (DatasetLoader::
    CostructFromSampleData, c_api.h:66-73); rows arrive later via
    PushRows. The sample reconstitutes as a dense matrix (absent entries
    are zero, matching the reference's sparse sample semantics)."""
    ncol = len(col_mvs)
    sample = np.zeros((num_sample_row, ncol), np.float64)
    for j in range(ncol):
        cnt = num_per_col[j]
        if cnt == 0 or col_mvs[j] is None:
            continue
        vals = np.frombuffer(col_mvs[j], dtype=np.float64, count=cnt)
        rows = np.frombuffer(idx_mvs[j], dtype=np.int32, count=cnt)
        sample[rows, j] = vals
    ref = Dataset(sample, params=parse_params(params), free_raw_data=False)
    ref.construct()
    return PendingDataset(int(num_total_row), ncol, ref, params)


def dataset_push_rows(pd, mv: memoryview, dtype_code: int, nrow: int,
                      ncol: int, start_row: int) -> None:
    if not isinstance(pd, PendingDataset):
        raise LightGBMError("LGBM_DatasetPushRows needs a dataset created "
                            "by CreateByReference/CreateFromSampledColumn "
                            "that has not been used yet")
    rows = _mat(mv, dtype_code, nrow, ncol, 1).astype(np.float64, copy=True)
    pd.push(rows, int(start_row))


def dataset_push_rows_by_csr(pd, indptr_mv, indptr_code, indices_mv,
                             data_mv, data_code, nindptr: int, nelem: int,
                             num_col: int, start_row: int) -> None:
    if not isinstance(pd, PendingDataset):
        raise LightGBMError("LGBM_DatasetPushRowsByCSR needs a dataset "
                            "created by CreateByReference/"
                            "CreateFromSampledColumn not yet used")
    indptr, indices, vals = _csr_parts(
        indptr_mv, indptr_code, indices_mv, data_mv, data_code, nindptr,
        nelem)
    from scipy.sparse import csr_matrix
    dense = csr_matrix((vals, indices, indptr),
                       shape=(nindptr - 1, num_col)).toarray() \
        .astype(np.float64)
    pd.push(dense, int(start_row))


def dataset_from_csc(colptr_mv, colptr_code, indices_mv, data_mv,
                     data_code, ncol_ptr: int, nelem: int, num_row: int,
                     params: str, reference) -> Dataset:
    from scipy.sparse import csc_matrix
    colptr = np.frombuffer(colptr_mv, dtype=_DTYPES[colptr_code],
                           count=ncol_ptr).copy()
    if nelem:
        indices = np.frombuffer(indices_mv, dtype=np.int32,
                                count=nelem).copy()
        vals = np.frombuffer(data_mv, dtype=_DTYPES[data_code],
                             count=nelem).copy()
    else:
        indices = np.zeros(0, np.int32)
        vals = np.zeros(0, np.float64)
    mat = csc_matrix((vals, indices, colptr),
                     shape=(num_row, ncol_ptr - 1)).tocsr()
    return Dataset(mat, reference=_as_dataset(reference) if reference
                   else None, params=parse_params(params),
                   free_raw_data=False)


def dataset_from_mats(mvs: List[memoryview], dtype_code: int,
                      nrows: List[int], ncol: int, row_major: int,
                      params: str, reference) -> Dataset:
    parts = [_mat(mv, dtype_code, nr, ncol, row_major)
             for mv, nr in zip(mvs, nrows)]
    data = np.concatenate(parts, axis=0).astype(np.float64, copy=True)
    return Dataset(data, reference=_as_dataset(reference) if reference
                   else None, params=parse_params(params),
                   free_raw_data=False)


# ------------------------------------------------------------- dataset info
_FIELD_OUT_DTYPES = {"label": (np.float32, 0), "weight": (np.float32, 0),
                     "init_score": (np.float64, 1), "group": (np.int32, 2),
                     "query": (np.int32, 2)}


def dataset_get_field(ds, name: str):
    """-> (dtype_code, ndarray or None). The array is stashed on the
    dataset so the C caller's pointer stays valid for the handle's
    lifetime (the reference returns pointers into Metadata storage,
    c_api.h:335-339). group comes back as CUMULATIVE query boundaries
    (nq + 1 entries), matching Metadata::query_boundaries()."""
    ds = _as_dataset(ds)
    dt, code = _FIELD_OUT_DTYPES[name] if name in _FIELD_OUT_DTYPES \
        else (np.float32, 0)
    if name in ("group", "query"):
        m = ds.construct()._binned.metadata
        arr = m.query_boundaries
    else:
        arr = ds.get_field(name)
    if arr is None:
        return code, None
    arr = np.ascontiguousarray(np.asarray(arr), dtype=dt)
    if not hasattr(ds, "_capi_field_cache"):
        ds._capi_field_cache = {}
    pinned = ds._capi_field_cache.setdefault(name, [])
    # Every pointer ever handed to C stays valid until the handle is
    # freed (the header's lifetime contract, c_api.h:335-339), so pinned
    # arrays are never dropped — but a caller polling an unchanged field
    # gets the same pinned array back instead of growing the pin list.
    if pinned:
        cached = pinned[-1]
        if cached.shape == arr.shape and cached.dtype == arr.dtype \
                and np.array_equal(cached, arr, equal_nan=True):
            return code, cached
    pinned.append(arr)
    return code, arr


def dataset_save_binary(ds, filename: str) -> None:
    _as_dataset(ds).save_binary(filename)


def dataset_get_subset(ds, idx_mv: memoryview, num_used: int,
                       params: str) -> Dataset:
    idx = np.frombuffer(idx_mv, dtype=np.int32, count=num_used).copy()
    sub = _as_dataset(ds).subset(idx, params=parse_params(params))
    sub.construct()
    return sub


# Parameters baked into the binned representation at construction time;
# Dataset::ResetConfig refuses to change them on a live handle
# (dataset.cpp:327-348). We reject rather than warn so C callers can't
# silently train with a stale max_bin.
_BIN_AFFECTING = frozenset([
    "max_bin", "bin_construct_sample_cnt", "min_data_in_bin",
    "use_missing", "zero_as_missing", "sparse_threshold",
])


def dataset_update_param(ds, params: str) -> None:
    p = parse_params(params)
    ds = _as_dataset(ds)
    if ds.params is None:
        ds.params = {}
    if ds._binned is not None:
        from .config import _CANON, Config, _coerce
        from .log import Log
        # authoritative: the effective values recorded when the binned
        # representation was built (survives .bin round-trips and subsets)
        effective = getattr(ds._binned, "bin_params", {}) or {}
        for k, v in p.items():
            ck = Config.resolve_key(k)
            if ck not in _BIN_AFFECTING:
                continue
            cur = effective.get(ck)
            if cur is None and ck == "max_bin":
                cur = ds._binned.max_bin
            if cur is None:
                # pre-bin_params .bin file: can't verify — warn like the
                # reference's ResetConfig and accept
                Log.warning("Cannot verify %s against the constructed "
                            "Dataset; accepting unchecked." % ck)
                continue
            ty = _CANON.get(ck, (str, None))[0]
            if _coerce(ck, ty, cur) != _coerce(ck, ty, v):
                raise LightGBMError(
                    "Cannot change %s after constructed Dataset handle." % ck)
    ds.params.update(p)


def dataset_dump_text(ds, filename: str) -> None:
    """Dataset::DumpTextFile analog (c_api.h:306): feature names, per-
    feature bin boundaries, then the binned row matrix."""
    b = _as_dataset(ds).construct()._binned
    with open(filename, "w") as f:
        f.write("num_data: %d\n" % b.num_data)
        f.write("num_feature: %d\n" % b.num_features)
        f.write("feature_names: %s\n" % ",".join(b.feature_names))
        for info in b.get_feature_infos():
            f.write("feature_info: %s\n" % info)
        xb = np.asarray(b.X_binned)
        for i in range(b.num_data):
            f.write(" ".join(str(int(v)) for v in xb[i]) + "\n")


def dataset_add_features_from(target, source) -> None:
    """LGBM_DatasetAddFeaturesFrom (c_api.h:373): append source's feature
    columns to target. Both raw blocks must still be held (the ABI always
    constructs with free_raw_data=False); the merged dataset re-bins, which
    reproduces the reference's merged FeatureGroup layout."""
    t = _as_dataset(target)
    s = _as_dataset(source)
    if t.num_data() != s.num_data():
        raise LightGBMError("cannot add features: row counts differ "
                            "(%d vs %d)" % (t.num_data(), s.num_data()))
    if t.data is None or s.data is None:
        raise LightGBMError("cannot add features: raw data was freed")
    td = _to_2d(t.data)
    sd = _to_2d(s.data)
    t.data = np.concatenate([td, sd], axis=1)
    if t.feature_name and s.feature_name:
        t.feature_name = list(t.feature_name) + list(s.feature_name)
    else:
        t.feature_name = None
    t._binned = None          # force re-construct with the merged block
    t.construct()


def _to_2d(data) -> np.ndarray:
    from .basic import _to_2d_float
    return _to_2d_float(data)


# ------------------------------------------------------------- booster info
def booster_get_feature_names(bst: Booster) -> List[str]:
    return list(bst.feature_name())


def booster_calc_num_predict(bst: Booster, num_row: int, predict_type: int,
                             num_iteration: int) -> int:
    """LGBM_BoosterCalcNumPredict (c_api.cpp:771-789)."""
    impl = bst._impl
    k = max(impl.num_tree_per_iteration, 1)
    total_iter = impl.iter_ + getattr(impl, "num_init_iteration", 0)
    ni = total_iter if num_iteration <= 0 else min(num_iteration, total_iter)
    if predict_type == 2:      # leaf index
        return int(num_row) * k * ni
    if predict_type == 3:      # SHAP contributions
        return int(num_row) * max(impl.num_class, 1) \
            * (int(bst.num_feature()) + 1)
    return int(num_row) * max(impl.num_class, 1)


def booster_get_num_predict(bst: Booster, data_idx: int) -> int:
    impl = bst._impl
    if data_idx == 0:
        n = impl.num_data_orig
    else:
        if data_idx - 1 >= len(impl.valid_data):
            raise LightGBMError("data_idx %d out of range" % data_idx)
        n = impl.valid_data[data_idx - 1].num_data
    return n * max(impl.num_class, 1)


def booster_get_predict(bst: Booster, data_idx: int) -> bytes:
    """LGBM_BoosterGetPredict: objective-converted scores for the train
    (0) or a valid (1..) set, CLASS-MAJOR like GBDT::GetPredictAt
    (gbdt.cpp:585-620: out[j * num_data + i])."""
    impl = bst._impl
    if data_idx == 0:
        scores = np.asarray(impl.scores)[: impl.num_data_orig]    # [n, k]
    else:
        if data_idx - 1 >= len(impl.valid_data):
            raise LightGBMError("data_idx %d out of range" % data_idx)
        impl._materialize()
        scores = np.asarray(impl._valid_pred_cache[data_idx - 1]["scores"])
    if impl.objective is not None:
        out = np.asarray(impl.objective.convert_output(scores), np.float64)
    else:
        out = scores.astype(np.float64)
    return out.T.reshape(-1).tobytes()                # class-major


def booster_refit_with_leaves(bst: Booster, mv: memoryview, nrow: int,
                              ncol: int) -> None:
    """LGBM_BoosterRefit (c_api.h:484) -> GBDT::RefitTree
    (gbdt.cpp:263-286): keep every tree's structure, re-estimate leaf
    outputs from the TRAIN data's gradients at the running scores, with
    leaf assignments supplied by the caller ([nrow, num_models] int32 —
    what PredictForMat with predict_type=leaf returns)."""
    leaf_preds = np.frombuffer(mv, dtype=np.int32,
                               count=nrow * ncol).reshape(nrow, ncol).copy()
    impl = bst._impl
    impl._materialize()
    models = impl.models
    if len(models) != ncol:
        raise LightGBMError("leaf_preds has %d columns but the model has "
                            "%d trees" % (ncol, len(models)))
    if impl.num_data_orig != nrow:
        raise LightGBMError("leaf_preds row count %d != train rows %d"
                            % (nrow, impl.num_data_orig))
    if impl.objective is None:
        raise LightGBMError("cannot refit without an objective")
    cfg = impl.config
    k = max(impl.num_tree_per_iteration, 1)
    n = impl.num_data_orig
    decay = float(getattr(cfg, "refit_decay_rate", 0.9))
    l1, l2, mds = cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step
    import jax.numpy as jnp
    scores = np.zeros((n, k), np.float32)
    if getattr(impl, "init_score_offsets", None) is not None:
        scores += np.asarray(impl.init_score_offsets, np.float32)[None, :]
    g = h = None
    for i, ht in enumerate(models):
        c = i % k
        if c == 0:
            if k == 1:
                gj, hj = impl.objective.get_gradients(
                    jnp.asarray(scores[:, 0]))
                g, h = np.asarray(gj)[:, None], np.asarray(hj)[:, None]
            else:
                gj, hj = impl.objective.get_gradients(jnp.asarray(scores))
                g, h = np.asarray(gj), np.asarray(hj)
        nl = ht.num_leaves
        leaves = leaf_preds[:, i]
        if leaves.max(initial=0) >= nl:
            raise LightGBMError("leaf index out of range in tree %d" % i)
        sg = np.bincount(leaves, weights=g[:n, c].astype(np.float64),
                         minlength=nl)
        sh = np.bincount(leaves, weights=h[:n, c].astype(np.float64),
                         minlength=nl) + 1e-15
        out = -np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0) / (sh + l2)
        if mds > 0:
            out = np.clip(out, -mds, mds)
        out *= getattr(ht, "shrinkage", 1.0)
        old = ht.leaf_value[:nl].astype(np.float64)
        ht.leaf_value[:nl] = decay * old + (1.0 - decay) * out
        scores[:, c] += ht.leaf_value[leaves].astype(np.float32)
    impl.models = models      # invalidate materialized prediction tables


def booster_reset_training_data(bst: Booster, new_train) -> None:
    bst.reset_training_data(_as_dataset(new_train))


def booster_set_leaf_value(bst: Booster, tree_idx: int, leaf_idx: int,
                           val: float) -> None:
    """LGBM_BoosterSetLeafValue -> Tree::SetLeafOutput (c_api.h:921)."""
    impl = bst._impl
    impl._materialize()
    models = impl.models
    if not (0 <= tree_idx < len(models)):
        raise LightGBMError("tree_idx %d out of range" % tree_idx)
    ht = models[tree_idx]
    if not (0 <= leaf_idx < ht.num_leaves):
        raise LightGBMError("leaf_idx %d out of range" % leaf_idx)
    ht.leaf_value[leaf_idx] = float(val)
    impl.models = models      # refresh prediction tables


def booster_shuffle_models(bst: Booster, start_iter: int,
                           end_iter: int) -> None:
    """LGBM_BoosterShuffleModels (c_api.h:423) — random within-range
    permutation of whole iterations (used before Refit)."""
    impl = bst._impl
    impl._materialize()
    models = list(impl.models)
    k = max(impl.num_tree_per_iteration, 1)
    n_iter = len(models) // k
    lo = max(0, start_iter)
    hi = n_iter if end_iter <= 0 else min(end_iter, n_iter)
    # deterministic but distinct across successive calls: fold a
    # per-booster shuffle counter into the seed
    n_shuffles = getattr(impl, "_n_model_shuffles", 0)
    impl._n_model_shuffles = n_shuffles + 1
    perm = np.random.RandomState(
        (impl.config.seed + n_shuffles) % (2 ** 31)).permutation(
        np.arange(lo, hi))
    shuffled = list(models)
    for dst_it, src_it in zip(range(lo, hi), perm):
        for c in range(k):
            shuffled[dst_it * k + c] = models[src_it * k + c]
    impl.models = shuffled


def booster_predict_for_file(bst: Booster, data_filename: str,
                             data_has_header: int, predict_type: int,
                             num_iteration: int, parameter: str,
                             result_filename: str) -> None:
    """LGBM_BoosterPredictForFile (c_api.h:615) — parse, predict, write
    one line per row (tab-separated for multi-output), the reference
    Predictor::SaveTextAsResult contract."""
    from .io.parser import parse_file
    X, _, _names = parse_file(data_filename,
                              has_header=bool(data_has_header))
    kw = _predict_kwargs(predict_type, num_iteration, parameter)
    out = np.asarray(bst.predict(np.asarray(X, np.float64), **kw))
    with open(result_filename, "w") as f:
        if out.ndim == 1:
            for v in out:
                f.write("%.17g\n" % float(v))
        else:
            for row in out:
                f.write("\t".join("%.17g" % float(v) for v in row) + "\n")


def booster_predict_csc(bst: Booster, colptr_mv, colptr_code, indices_mv,
                        data_mv, data_code, ncol_ptr: int, nelem: int,
                        num_row: int, predict_type: int, num_iteration: int,
                        parameter: str) -> bytes:
    from scipy.sparse import csc_matrix
    colptr = np.frombuffer(colptr_mv, dtype=_DTYPES[colptr_code],
                           count=ncol_ptr).copy()
    indices = np.frombuffer(indices_mv, dtype=np.int32, count=nelem).copy() \
        if nelem else np.zeros(0, np.int32)
    vals = np.frombuffer(data_mv, dtype=_DTYPES[data_code],
                         count=nelem).copy() if nelem \
        else np.zeros(0, np.float64)
    mat = csc_matrix((vals, indices, colptr),
                     shape=(num_row, ncol_ptr - 1)).tocsr()
    kw = _predict_kwargs(predict_type, num_iteration, parameter)
    return np.asarray(bst.predict(mat, **kw), np.float64).tobytes()
