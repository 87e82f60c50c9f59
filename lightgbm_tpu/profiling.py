"""Phase timing probes — the TIMETAG analog (serial_tree_learner.cpp:15-43).

The boosting iteration is one fused jit program, so per-phase time cannot be
read from inside it; instead each phase's op is re-run standalone on the
booster's real shapes and timed. The phase list mirrors the reference's
(init/hist/find-split/split) plus the TPU-specific partition/gather phase.
``jax.profiler`` traces can be layered on via trace_dir for a full timeline.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
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
# kept, so a compile has a name and a block
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


def _timed(fn, *args, reps=3, **kw) -> float:
    out = fn(*args, **kw)
    jax.block_until_ready(out)  # lgbm-lint: disable=LGL103 bench warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)  # lgbm-lint: disable=LGL103 bench barrier
    return (time.perf_counter() - t0) / reps


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


def phase_probe(booster, trace_dir: Optional[str] = None) -> Dict[str, float]:
    """Per-phase seconds for one boosting iteration's building blocks, using
    the booster's actual data/shapes. Keys: grad, hist_full,
    partition_hist_fused, hist_leaf_half, find_split,
    compile_cache_hits/misses, plus frontier_hist / frontier_hist_w<k> /
    frontier_waves / frontier_sweeps_per_tree / frontier_wave_occupancy /
    frontier_slot_sweeps_per_tree when the booster grows in frontier mode
    (docs/Performance.md describes each)."""
    from .core.histogram import build_histogram
    from .core.partition import (frontier_slots_from_partition, hist_for_leaf,
                                 init_partition, make_row_gather,
                                 partition_and_hist, stack_vals,
                                 window_placement)
    from .core.split import find_best_split

    from .obs.trace import perfetto_trace

    xb = booster.xb
    n = booster.num_data
    params = booster.grow_params
    meta = booster.feature_meta
    out: Dict[str, float] = {}

    # trace_dir rides the shared Perfetto helper (obs/trace.py), which
    # degrades to a warning when the profiler backend is unavailable or a
    # capture is already active instead of crashing the probe
    with perfetto_trace(trace_dir):
        scores = booster.scores
        if booster.objective is not None:
            obj = booster.objective
            if booster.num_tree_per_iteration == 1:
                grad_fn = jax.jit(lambda s: obj.get_gradients(s[:, 0]))
            else:
                grad_fn = jax.jit(lambda s: obj.get_gradients(s))
            out["grad"] = _timed(grad_fn, scores)
            g, h = grad_fn(scores)
            if g.ndim == 2:           # multiclass: probe class 0's tree
                g, h = g[:, 0], h[:, 0]
        else:
            g = jnp.zeros((n,), jnp.float32)
            h = jnp.ones((n,), jnp.float32)
        mask = jnp.ones((n,), jnp.float32)

        packed = int(getattr(params, "word_packed_cols", 0) or 0)
        out["hist_full"] = _timed(
            build_histogram, xb, g, h, mask, num_bins=params.num_bins,
            row_chunk=params.row_chunk, impl=params.hist_impl,
            packed_cols=packed)
        hist = build_histogram(xb, g, h, mask, num_bins=params.num_bins,
                               row_chunk=params.row_chunk,
                               impl=params.hist_impl, packed_cols=packed)

        part = init_partition(n, params.num_leaves, params.row_chunk)
        # sized to the partition TILE, not n: the decision closure below
        # is sliced per row tile, which is row_chunk wide even when the
        # dataset is smaller
        half = jnp.asarray(
            np.arange(max(n, params.row_chunk), dtype=np.int64) % 2 == 0)
        # probe in f32 regardless of ambient x64: the gather closure owns
        # the packed bins/values boundary, so dtypes must be consistent
        # the partition machinery gathers plain uint8 columns — probe it
        # on a transient unpacked view when the device matrix is
        # word-packed (the frontier grower routes from words directly;
        # these two probes price the EXACT grower's phases)
        if packed:
            from .core.binpack import unpack_words
            xb_cols = unpack_words(xb, packed)
        else:
            xb_cols = xb
        gr = make_row_gather(
            xb_cols, stack_vals(g.astype(jnp.float32),
                                h.astype(jnp.float32),
                                mask.astype(jnp.float32)))
        ncols = xb_cols.shape[1]
        # the real growth path: one fused pass that partitions the root and
        # prices both children — same placement selection as grow_tree
        windows = window_placement(params.hist_impl, params.vmapped_classes)
        fused = jax.jit(lambda p: partition_and_hist(
            p, jnp.zeros((n,), jnp.int32), jnp.int32(0), jnp.int32(1),
            lambda rows: half[:rows.shape[0]],
            jnp.asarray(True), params.row_chunk, gr, ncols,
            params.num_bins, params.hist_impl, windows=windows))
        out["partition_hist_fused"] = _timed(lambda p: fused(p)[0], part)
        part2 = fused(part)[0]
        out["hist_leaf_half"] = _timed(
            jax.jit(lambda p: hist_for_leaf(
                p, jnp.int32(0), gr, n, ncols, params.num_bins,
                params.row_chunk, impl=params.hist_impl)), part2)

        if getattr(params, "frontier_mode", False):
            from . import bucketing
            from .core.histogram import build_histogram_frontier
            # the frontier wave cost: the partition hands the builder the
            # wave's LEAF IDS and one leaf-indexed sweep prices them all.
            # kb is the clamped maximum wave width; with bucketing on,
            # early waves run at the smaller pow-2 ladder widths, so the
            # per-width probes below show the per-sweep cost the grower
            # actually pays per wave
            bucketed = getattr(params, "frontier_bucketing", False)
            kb = bucketing.frontier_max_width(params.num_leaves,
                                              params.max_depth)
            ladder = (bucketing.wave_width_ladder(params.num_leaves,
                                                  params.max_depth)
                      if bucketed else [kb])
            for w in sorted({ladder[0], ladder[len(ladder) // 2],
                             ladder[-1]}):
                slots_w = frontier_slots_from_partition(
                    part2, jnp.arange(w, dtype=jnp.int32), n)
                t_w = _timed(
                    build_histogram_frontier, xb, slots_w, g, h, mask,
                    num_bins=params.num_bins, num_slots=w,
                    row_chunk=params.row_chunk, impl=params.hist_impl,
                    packed_cols=packed)
                out["frontier_hist_w%d" % w] = t_w
                if w == ladder[-1]:      # full width: the pre-bucketing key
                    out["frontier_hist"] = t_w
            # dataset sweeps per tree scale with DEPTH, not leaf count:
            # wave w splits the leaves created in wave w-1, so waves = max
            # leaf depth of the grown tree, sweeps = waves + 1 (the root).
            # An internal node's depth IS the wave that committed it (every
            # positive-gain leaf splits at the first wave after it
            # appears), so per-depth internal-node counts reconstruct each
            # wave's live width exactly.
            if booster.models:
                for k, v in frontier_tree_stats(booster.models[0],
                                                params).items():
                    out["frontier_" + k] = v

        sum_g = jnp.sum(g)
        sum_h = jnp.sum(h)
        cnt = jnp.asarray(float(n), jnp.float32)
        fmask = jnp.ones((meta.num_bin.shape[0],), bool)
        split_fn = jax.jit(lambda hh: find_best_split(
            hh, meta, params.split, sum_g, sum_h, cnt, fmask,
            with_categorical=params.with_categorical))
        # find_split works on per-feature views; without EFB hist == view
        if not params.with_efb:
            out["find_split"] = _timed(split_fn, hist)

        # persistent-compile-cache accounting (compile_cache_dir): both
        # stay 0 unless the cache is enabled; a warm cache shows as hits
        stats = compile_cache_stats()
        out["compile_cache_hits"] = float(stats["persistent_cache_hits"])
        out["compile_cache_misses"] = float(stats["persistent_cache_misses"])

        # checkpoint overhead (lightgbm_tpu.checkpoint): one full-state
        # snapshot save + restore on the booster's real model/shapes, so
        # the per-period cost shows up next to the phases it competes with
        out.update(_checkpoint_probe(booster))

        # roofline attribution (obs/costmodel.py): join extracted XLA
        # per-call costs with this probe's standalone wall times + any
        # span totals the run accumulated. Best-effort — a probe must
        # never fail because cost extraction cannot run here.
        try:
            from .obs.costmodel import (detect_peaks, roofline_table,
                                        span_wall_times)
            booster.extract_cost_model(force=True)
            wall = span_wall_times()
            for k, v in out.items():
                if k.startswith("frontier_hist_w"):
                    wall[k] = (float(v), 1.0)
            out["roofline"] = roofline_table(wall, peaks=detect_peaks())
        except Exception:  # noqa: BLE001
            pass
    return {k: (round(v, 5) if isinstance(v, float) else v)
            for k, v in out.items()}


def frontier_tree_stats(tree, params) -> Dict[str, float]:
    """Deterministic per-tree wave accounting from a grown HostTree:
    waves, dataset sweeps, occupancy and slot-sweeps under the
    bucketing ladder. An internal node's depth IS the wave that
    committed it (every positive-gain leaf splits at the first wave
    after it appears), so per-depth internal-node counts reconstruct
    each wave's live width exactly. Shared by phase_probe and the perf
    gate (obs/perfgate.py) — semantic counters, no timing."""
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


def _checkpoint_probe(booster) -> Dict[str, float]:
    """checkpoint_save_s / checkpoint_restore_s: wall time of one snapshot
    write (state npz + manifest + model text) and one verified load back
    into the same driver. Restoring the state it just saved is a no-op for
    the booster. Empty dict when the booster has no trained trees yet."""
    import shutil
    import tempfile
    try:
        if not booster.models:
            return {}
        from .checkpoint.manager import CheckpointManager
        tmp = tempfile.mkdtemp(prefix="lgbm_tpu_ckpt_probe_")
        try:
            mgr = CheckpointManager(tmp, keep_last_n=1)
            t0 = time.perf_counter()
            mgr.save(booster)
            save_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            handle = mgr.load_latest()
            booster.load_training_state(handle.meta, handle.arrays)
            restore_s = time.perf_counter() - t0
            return {"checkpoint_save_s": save_s,
                    "checkpoint_restore_s": restore_s}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    except Exception:  # noqa: BLE001 - a probe must not kill the caller
        return {}
