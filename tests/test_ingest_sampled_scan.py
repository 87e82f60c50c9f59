"""``from_matrix`` finds the bins of a dense table larger than
``bin_construct_sample_cnt`` from the sampled rows' values alone (ISSUE 37).
Every case holds the bin mappers, the EFB layout and the binned bytes to the
scan as it was before: the whole column tested for non-zeros
(``flatnonzero``), each such row looked up in an N-sized table of sample
positions, the sampled kept. ``whole_column_scan`` restates that scan.
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.binning import BinMapper, BinType
from lightgbm_tpu.io.bundle import bundle_offsets, find_bundles
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs import trace

sp = pytest.importorskip("scipy.sparse")

N, SAMPLE = 4000, 600


def plain(rng):
    return 5.0 + rng.normal(size=(N, 6))


def missing(rng):
    """NaN, exact zeros, negatives, values the zero test takes for zero."""
    X = rng.normal(size=(N, 7))
    X[:, 1] = np.floor(np.exp(rng.normal(1.0, 1.0, N)))     # many zeros
    X[:, 2] = -np.abs(X[:, 2])
    X[rng.random((N, 7)) < 0.1] = np.nan
    X[rng.random((N, 7)) < 0.2] = 0.0
    tiny = rng.random((N, 7)) < 0.05
    X[tiny] = rng.choice([1e-35, -1e-35, 1e-36, -1e-37, -0.0, 2e-35],
                         tiny.sum())
    X[:, 6] = np.where(rng.random(N) < 0.5, np.nan, 0.0)    # NaN or zero
    return X


def constant(rng):
    X = rng.normal(size=(N, 4))
    X[:, 1] = 3.25      # one value: a trivial mapper
    X[:, 3] = 0.0       # no non-zero entry at all
    return X


def categorical(rng):
    X = rng.normal(size=(N, 5))
    X[:, 3] = np.floor(40 * rng.random(N) ** 3)             # skewed ids
    X[rng.random(N) < 0.05, 3] = np.nan
    X[rng.random(N) < 0.02, 3] = -1.0
    X[:, 4] = rng.integers(0, 3000, N)     # more ids than the sample holds
    return X


def onehot(rng):
    """Three exclusive groups of five indicator columns (EFB bundles them)
    and two dense columns."""
    X = np.zeros((N, 17))
    for g in range(3):
        which = rng.integers(0, 6, N)                       # 5 = none
        for k in range(5):
            X[which == k, g * 5 + k] = 1.0
    X[:, 15:] = rng.normal(size=(N, 2))
    return X


# id: (table, how the caller holds it, params)
CASES = {
    "float64_column_major": (plain, np.asfortranarray, {}),
    "float32_row_major": (plain,
                          lambda X: np.ascontiguousarray(X, np.float32), {}),
    "missing_values": (missing, np.asfortranarray, {}),
    "missing_values_zero_as_missing": (missing, np.asarray,
                                       {"zero_as_missing": True}),
    "constant_column": (constant, np.asfortranarray, {}),
    "categorical": (categorical, np.asfortranarray,
                    {"categorical_feature": "3,4"}),
    "efb_bundles": (onehot, np.asarray, {}),
    "scipy_csr": (onehot, sp.csr_matrix, {}),
    "scipy_csr_missing_values": (missing, sp.csr_matrix, {}),
    "table_is_its_own_sample": (missing, np.asfortranarray,
                                {"bin_construct_sample_cnt": N}),
}


def whole_column_scan(data, config, cat_idx):
    """The scan before ISSUE 37, a column at a time. Returns a dataset that
    holds the mappers and the EFB layout found from it (to bin against),
    the bundled features, and how many values the zero test read."""
    n, f = data.shape
    sample_cnt = min(n, config.bin_construct_sample_cnt)
    sample_pos = None
    if sample_cnt < n:
        rng = np.random.RandomState(config.data_random_seed)
        sample_rows = np.sort(rng.choice(n, sample_cnt, replace=False))
        sample_pos = np.full(n, -1, np.int64)
        sample_pos[sample_rows] = np.arange(sample_cnt)
    csc = data.tocsc() if sp.issparse(data) else None
    X64 = None if csc is not None else np.asarray(data, np.float64)
    mappers, nz_sample, scanned = [], [], 0
    for j in range(f):
        if csc is not None:
            sl = slice(csc.indptr[j], csc.indptr[j + 1])
            rows, vals = csc.indices[sl], np.asarray(csc.data[sl], np.float64)
            scanned += len(rows)
        else:
            col = X64[:, j]
            rows = np.flatnonzero(~((col >= -1e-35) & (col <= 1e-35)))
            vals = col[rows]
            scanned += n
        if sample_pos is not None:
            pos = sample_pos[rows]
            keep = pos >= 0
            rows, vals = pos[keep], vals[keep]
        nz_sample.append(rows.astype(np.int64))
        m = BinMapper()
        m.find_bin(vals, total_sample_cnt=sample_cnt, max_bin=config.max_bin,
                   min_data_in_bin=config.min_data_in_bin,
                   min_split_data=config.min_data_in_leaf,
                   bin_type=(BinType.CATEGORICAL if j in cat_idx
                             else BinType.NUMERICAL),
                   use_missing=config.use_missing,
                   zero_as_missing=config.zero_as_missing)
        mappers.append(m)
    want = BinnedDataset()
    want.num_total_features = f
    want.feature_names = ["Column_%d" % j for j in range(f)]
    want.bin_mappers = mappers
    want.used_features = used = [j for j in range(f)
                                 if not mappers[j].is_trivial]
    bundles = find_bundles([nz_sample[j] for j in used], sample_cnt,
                           [mappers[j].num_bin for j in used],
                           config.max_conflict_rate,
                           sparse_threshold=config.sparse_threshold)
    want.col_features = [[used[i] for i in b] for b in bundles]
    num_bin_of = {j: mappers[j].num_bin for j in used}
    layout = [bundle_offsets(b, num_bin_of) for b in want.col_features]
    want.col_offsets = [offs for offs, _ in layout]
    want.col_num_bin = [total for _, total in layout]
    want.col_packed = [False] * len(layout)
    return want, scanned


@pytest.mark.parametrize("case", list(CASES))
def test_bins_from_the_sampled_rows_are_the_whole_column_scans(case):
    make, hold, params = CASES[case]
    data = hold(make(np.random.default_rng(37)))
    config = Config(dict({"verbose": -1, "bin_construct_sample_cnt": SAMPLE,
                          "enable_nbit_packing": False}, **params))
    cat_idx = {int(c) for c in
               params.get("categorical_feature", "").split(",") if c}
    sampled = config.bin_construct_sample_cnt < N

    mark = max((s["id"] for s in trace.recorded_spans()), default=0)
    got = BinnedDataset.from_matrix(data, config)
    (span,) = [s for s in trace.recorded_spans()
               if s["name"] == "ingest.find_bins" and s["id"] > mark]
    want, scanned_before = whole_column_scan(data, config, cat_idx)

    assert len(got.bin_mappers) == len(want.bin_mappers)
    for j, (a, b) in enumerate(zip(got.bin_mappers, want.bin_mappers)):
        assert (a.num_bin, a.missing_type, a.default_bin, a.bin_type,
                a.is_trivial) == (b.num_bin, b.missing_type, b.default_bin,
                                  b.bin_type, b.is_trivial), j
        assert np.asarray(a.bin_upper_bound).tobytes() \
            == np.asarray(b.bin_upper_bound).tobytes(), j
        assert list(a.bin_2_categorical) == list(b.bin_2_categorical), j
    assert got.used_features == want.used_features
    assert got.col_features == want.col_features
    assert got.col_offsets == want.col_offsets
    assert got.col_num_bin == want.col_num_bin
    # every row binned by the whole-column scan's mappers and layout
    binned = BinnedDataset.from_matrix(data, config, reference=want).X_binned
    assert got.X_binned.dtype == np.uint8
    assert got.X_binned.shape == (N, len(want.col_features))
    assert got.X_binned.tobytes() == binned.tobytes()

    # the cases are what their names say
    if case == "constant_column":
        assert [m.is_trivial for m in got.bin_mappers] \
            == [False, True, False, True]
    if case == "categorical":
        assert [m.bin_type == BinType.CATEGORICAL
                for m in got.bin_mappers] == [False] * 3 + [True] * 2
    if make is onehot:
        assert sum(len(b) > 1 for b in got.col_features) >= 3

    # the zero test read the sampled rows' values and no others; sparse
    # input still costs its stored entries, a table that is its own sample
    # its every value
    f = data.shape[1]
    counts = span["counts"]
    assert counts["sample_rows"] == min(N, config.bin_construct_sample_cnt)
    if sp.issparse(data):
        assert counts["values_scanned"] == data.nnz == scanned_before
    elif sampled:
        assert counts["values_scanned"] == SAMPLE * f < scanned_before
    else:
        assert counts["values_scanned"] == N * f == scanned_before
