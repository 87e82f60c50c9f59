// Native hot path of feature quantization (BinMapper::ValueToBin applied to
// a whole column) — the OpenMP analog of the reference's bin assignment
// (include/LightGBM/bin.h:457-493 binary search; src/io/dataset.cpp
// PushOneRow). Python's per-column numpy searchsorted is single-threaded;
// this parallelizes across rows and is wired through lightgbm_tpu.native
// with a numpy fallback.
#include <algorithm>
#include <cmath>
#include <cstdint>

extern "C" {

// values [n] float64 -> out [n] int32 bin indices.
// bounds [n_search] are the numeric upper bounds (excluding the +inf
// sentinel): the assigned bin is the first index whose bound >= value
// (searchsorted "left"), matching BinMapper.values_to_bins.
// nan_bin >= 0 routes NaN to that bin (MissingType NaN); nan_bin < 0
// treats NaN as 0.0 (MissingType None/Zero).
void LGBMT_BinNumeric(const double* values, int64_t n, const double* bounds,
                      int32_t n_search, int32_t nan_bin, int32_t* out) {
#pragma omp parallel for schedule(static)
  for (int64_t i = 0; i < n; ++i) {
    double v = values[i];
    if (std::isnan(v)) {
      if (nan_bin >= 0) {
        out[i] = nan_bin;
        continue;
      }
      v = 0.0;
    }
    out[i] = static_cast<int32_t>(
        std::lower_bound(bounds, bounds + n_search, v) - bounds);
  }
}

// values [n] float64 category ids -> out [n] int32 bin indices
// (BinMapper.values_to_bins' categorical path). keys [n_keys] are the kept
// categories in ascending order, bins [n_keys] their bins; a value is read
// as upstream's static_cast<int>(value) reads it (truncated toward zero).
// NaN, an infinity, a negative id and an id that is not a key go to bin 0,
// this framework's catch-all.
void LGBMT_BinCategorical(const double* values, int64_t n,
                          const int64_t* keys, const int32_t* bins,
                          int32_t n_keys, int32_t* out) {
#pragma omp parallel for schedule(static)
  for (int64_t i = 0; i < n; ++i) {
    const double v = values[i];
    int32_t b = 0;
    // also false for NaN; the bounds keep the cast defined
    if (v > -1.0 && v < 9.2e18) {
      const int64_t iv = static_cast<int64_t>(v);
      const int64_t* at = std::lower_bound(keys, keys + n_keys, iv);
      if (at != keys + n_keys && *at == iv) b = bins[at - keys];
    }
    out[i] = b;
  }
}

}  // extern "C"
