"""Compile accounting and the persistent compile cache, plus two
deterministic summaries: a latency window's quantiles (serving) and a
grown tree's frontier-wave counts (the perf gate).

Phase TIMES are not taken here: the iteration names its own phases
(``jax.named_scope("lgbm.*")``, docs/Observability.md) and
``obs.trace.capture_phases`` / ``tools/trace_phases.py`` read them from a
``jax.profiler`` capture of the program as it runs.
"""
from __future__ import annotations

import os
import threading
from typing import Dict

import jax
import numpy as np
from jax._src.core import trace_state_clean

# ---------------------------------------------------------------- compiles
# Process-wide compile accounting, shared by serving.metrics and the
# training-side zero-recompile invariant (chip_smoke, compile_cache_smoke):
#
# - ``backend_compiles`` rides jax.monitoring's backend-compile duration
#   event, so it counts REAL XLA compilations — including accidental
#   retraces a cache key cannot see (shape leaks, weak-type flips);
#   ``backend_compile_seconds`` sums the same event's durations;
# - ``persistent_cache_hits``/``misses`` ride the compilation-cache events,
#   so a warm cache directory shows up as hits. (The backend-compile
#   duration event fires on cache hits too in this jax — it then times
#   the load — so hits/misses, not the backend count, are what
#   distinguish a warm start.)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_counts_lock = threading.Lock()
_hooks_installed = False

# the counters themselves live on the process-wide obs registry
# (lightgbm_tpu/obs/registry.py) so one Prometheus scrape sees them next
# to serving/training series; this module keeps its historical API as a
# thin shim over those series
from .log import Log  # noqa: E402
from .obs.registry import get_registry  # noqa: E402
from .obs.trace import record_span  # noqa: E402

_c_backend = get_registry().counter(
    "lgbm_jax_backend_compiles_total",
    "XLA backend compilations observed via jax.monitoring.")
_c_backend_secs = get_registry().counter(
    "lgbm_jax_backend_compile_seconds_total",
    "Seconds spent in XLA backend compilation (or loading a cached "
    "executable) observed via jax.monitoring.")
_c_trace_secs = get_registry().counter(
    "lgbm_jax_trace_seconds_total",
    "Seconds spent tracing Python functions to jaxprs, observed via "
    "jax.monitoring.")
_c_lower_secs = get_registry().counter(
    "lgbm_jax_lower_seconds_total",
    "Seconds spent lowering jaxprs to MLIR modules, observed via "
    "jax.monitoring.")
_c_cache_hit = get_registry().counter(
    "lgbm_jax_compile_cache_hits_total",
    "Persistent compilation-cache hits.")
_c_cache_miss = get_registry().counter(
    "lgbm_jax_compile_cache_misses_total",
    "Persistent compilation-cache misses.")


# event -> (span name, seconds counter): each becomes a recorded span under
# the program span open on the compiling thread, with jax's ``fun_name``
# kept, so a compile has a name and a block. These three are every duration
# jax 0.9.0 reports besides the cache's two (looked for at PR 38: no
# backend-initialisation event exists, so the chip runtime's start-up has
# no span of its own; a later jax that reports one gets a row here)
_COMPILE_PHASES = {
    _TRACE_EVENT: ("jax.trace", _c_trace_secs),
    _LOWER_EVENT: ("jax.lower", _c_lower_secs),
    _BACKEND_COMPILE_EVENT: ("jax.backend_compile", _c_backend_secs),
}


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    if event == _TRACE_EVENT and not trace_state_clean():
        return   # a jit traced inside another's trace: the outer one holds it
    name, seconds = phase
    if event == _BACKEND_COMPILE_EVENT:
        _c_backend.inc()
    seconds.inc(duration)
    record_span(name, duration, fun_name=kwargs.get("fun_name", ""))


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _c_cache_hit.inc()
    elif event == _CACHE_MISS_EVENT:
        _c_cache_miss.inc()


def install_compile_hook() -> None:
    """Register the compile/cache listeners (idempotent, process-wide)."""
    global _hooks_installed
    with _counts_lock:
        if _hooks_installed:
            return
        _hooks_installed = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)


def backend_compile_count() -> int:
    """XLA backend compilations observed since the hook was installed."""
    return int(_c_backend.value)


def compile_cache_stats() -> Dict[str, int]:
    """Snapshot of the compile counters (installs the hooks first, so the
    first caller anchors counting at zero)."""
    install_compile_hook()
    return {"backend_compiles": int(_c_backend.value),
            "backend_compile_seconds": float(_c_backend_secs.value),
            "trace_seconds": float(_c_trace_secs.value),
            "lower_seconds": float(_c_lower_secs.value),
            "persistent_cache_hits": int(_c_cache_hit.value),
            "persistent_cache_misses": int(_c_cache_miss.value)}


# <checkout>/.jax_cache, listed in .gitignore: a fixed place, so every run
# of a checkout finds what the last one compiled (never a temp name, a pid
# or the time)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache(requested: str = "") -> str:
    """Place jax's persistent compilation cache and install the counters;
    train, serve and the smokes all go through here. Returns the cache
    directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` in the environment decides when set
    (jax reads it itself; no code sets another). Otherwise the first
    directory placed in this process stays — jax initialises its cache
    once, at the first compile — and that is ``requested`` (the
    ``compile_cache_dir`` param) or, by default, ``<checkout>/.jax_cache``.
    A ``requested`` that disagrees with the directory in effect is ignored
    with one log line. Every compile is cacheable (no min-time / min-size
    floor), so a warm directory means zero backend compiles on restart."""
    current = jax.config.jax_compilation_cache_dir or ""
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or current
                 or os.fspath(requested) or DEFAULT_COMPILE_CACHE_DIR)
    if cache_dir != current:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    if requested and \
            os.path.abspath(requested) != os.path.abspath(cache_dir):
        Log.warning("compile_cache_dir=%s ignored: the compile cache of "
                    "this process is already placed at %s",
                    requested, cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_compile_hook()
    return cache_dir


def latency_summary(samples_ms) -> Dict[str, float]:
    """Quantile summary of a latency sample window (milliseconds) — the
    serving-side SLO view (p50/p90/p99) shared by serving.metrics and any
    offline analysis of its JSON-lines output."""
    a = np.asarray(list(samples_ms), np.float64)
    if a.size == 0:
        return {"count": 0, "mean_ms": 0.0, "p50_ms": 0.0, "p90_ms": 0.0,
                "p99_ms": 0.0, "max_ms": 0.0}
    p50, p90, p99 = np.percentile(a, [50.0, 90.0, 99.0])
    return {"count": int(a.size), "mean_ms": round(float(a.mean()), 4),
            "p50_ms": round(float(p50), 4), "p90_ms": round(float(p90), 4),
            "p99_ms": round(float(p99), 4),
            "max_ms": round(float(a.max()), 4)}


def frontier_tree_stats(tree, params) -> Dict[str, float]:
    """Deterministic per-tree wave accounting from a grown HostTree:
    waves, dataset sweeps, occupancy and slot-sweeps under the
    bucketing ladder. An internal node's depth IS the wave that
    committed it (every positive-gain leaf splits at the first wave
    after it appears), so per-depth internal-node counts reconstruct
    each wave's live width exactly. Read by the perf gate
    (obs/perfgate.py) — semantic counters, no timing."""
    from . import bucketing
    bucketed = getattr(params, "frontier_bucketing", False)
    kb = bucketing.frontier_max_width(params.num_leaves, params.max_depth)
    live_at: Dict[int, int] = {}
    stack = [(0, 0)] if tree.num_leaves > 1 else []
    while stack:
        nd, d = stack.pop()
        live_at[d] = live_at.get(d, 0) + 1
        for ch in (int(tree.left_child[nd]), int(tree.right_child[nd])):
            if ch >= 0:              # ~leaf encoding: negative = leaf
                stack.append((ch, d + 1))
    waves = (max(live_at) + 1) if live_at else 0
    live = [live_at.get(w, 0) for w in range(waves)]
    paid = [(bucketing.wave_width_bucket(lv, params.num_leaves,
                                         params.max_depth)
             if bucketed else kb) for lv in live]
    # occupancy: live slots / paid bucket width, occupancy-weighted over
    # the tree's waves; slot_sweeps is what the hist builder actually
    # swept (fixed width pays waves*kb)
    return {"waves": float(waves),
            "sweeps_per_tree": float(waves + 1),
            "wave_occupancy": (float(sum(live))
                               / max(float(sum(paid)), 1.0)),
            "slot_sweeps_per_tree": float(sum(paid)),
            "slot_sweeps_fixed_width": float(waves * kb)}
