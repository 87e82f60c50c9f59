"""lightgbm_tpu: a TPU-native gradient-boosting framework.

Re-designed from scratch for JAX/XLA/Pallas with the capabilities of
LightGBM v2.2.4 (reference: mark5434/LightGBM): histogram-based GBDT with
leaf-wise growth, EFB-style binning, GOSS/DART/RF boosting modes, the full
objective/metric suite, distributed training over jax.sharding meshes, and a
LightGBM-compatible Python API and model format.
"""

import time as _time

_IMPORTED_AT = _time.monotonic()     # before the package runs another line

from .config import Config
from .log import Log, LightGBMError

__version__ = "0.1.0"

__all__ = [
    "Config", "Log", "LightGBMError",
    "Dataset", "Booster", "train", "cv",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
]


def _seconds_before_import():
    """From the process's start to this package's import: what the
    interpreter, ``import jax`` and the accelerator runtime's start-up cost
    before the program runs a line. Linux only (``/proc/self/stat`` field
    22, the start time in clock ticks since boot); None elsewhere."""
    import os
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        alive = _time.clock_gettime(_time.CLOCK_BOOTTIME) \
            - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return alive - (_time.monotonic() - _IMPORTED_AT)


def _recorded_import(module):
    """``basic`` or ``engine``, imported on first use, that import recorded
    as the span ``import.<module>`` (the recorder's own import, and jax's
    where nothing imported it before, inside it), and ahead of the first of
    them ``runtime.before_import``, once, ending where this package's
    import began: set-up is under spans from the process's start
    (docs/Observability.md). These two import jax anyway; the recorder is
    imported here and not at the top, and the other lazy imports stay plain,
    so ``import lightgbm_tpu`` and the tooling's names stay as light as the
    comment below says."""
    global _IMPORTED_AT
    import importlib
    import sys
    full = __name__ + "." + module
    if full in sys.modules:
        return sys.modules[full]
    t0 = _time.perf_counter()
    from .obs.trace import record_span
    if _IMPORTED_AT is not None:
        before = _seconds_before_import()
        since = _time.monotonic() - _IMPORTED_AT
        _IMPORTED_AT = None                 # recorded once a process
        if before is not None:
            record_span("runtime.before_import", before, ended_ago_s=since)
    mod = importlib.import_module(full)
    record_span("import." + module, _time.perf_counter() - t0)
    return mod


def __getattr__(name):
    # lazy imports keep `import lightgbm_tpu` light and avoid jax init at
    # import time for tooling that only wants Config/version
    if name in ("Dataset", "Booster"):
        return getattr(_recorded_import("basic"), name)
    if name in ("train", "cv"):
        return getattr(_recorded_import("engine"), name)
    if name in ("LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name in ("plot_importance", "plot_metric", "plot_tree", "create_tree_digraph"):
        from . import plotting
        return getattr(plotting, name)
    if name in ("early_stopping", "print_evaluation", "record_evaluation",
                "reset_parameter"):
        from . import callback
        return getattr(callback, name)
    # NOTE: the checkpoint *callback factory* lives at callback.checkpoint;
    # `lightgbm_tpu.checkpoint` is the subsystem package itself
    if name == "CheckpointManager":
        from .checkpoint import CheckpointManager
        return CheckpointManager
    raise AttributeError("module 'lightgbm_tpu' has no attribute %r" % name)
