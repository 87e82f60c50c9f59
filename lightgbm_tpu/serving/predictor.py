"""Compiled-predictor cache: shape-bucketed, zero-recompile batch inference.

XLA compiles one executable per input shape, so serving arbitrary request
sizes naively would retrace on every new batch size — the exact failure
mode the ROADMAP's "heavy traffic" goal cannot afford. The cache here is
keyed ``(model_id, bucket, raw_score, num_iteration)``:

- request rows are padded up to a POWER-OF-TWO bucket (floored at
  ``min_bucket``, capped at ``max_batch``; larger requests are chunked),
  so at most ``log2(max_batch / min_bucket) + 1`` shapes exist per key
  prefix and a warmup pass over them makes every later request a cache
  hit with zero new compilations;
- each cache entry owns ONE jit-compiled function closed over nothing —
  trees ride in as device-resident arguments — so entries never interfere
  and a cache miss maps 1:1 to a compilation request;
- the raw->output transform (sigmoid / softmax / exp) and the
  average-output division are baked INTO the compiled function, keeping a
  whole request one device round-trip.

Multi-device: with a serving mesh (parallel/mesh.py serving_mesh) the
padded batch is row-sharded and trees replicated; GSPMD partitions the
forest apply. Buckets smaller than the mesh run replicated — the dispatch
decision is a static property of the cache key, so warmup covers it.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..bucketing import pow2_bucket, pow2_ladder
from ..core import tree as tree_mod
from ..log import LightGBMError, Log, check
from ..parallel.mesh import replicated, row_sharding, serving_mesh
from ..config import SERVING_BACKENDS
from ..resilience import faults
from . import traversal as traversal_mod
from .metrics import ServingMetrics
from .registry import ModelBundle, ModelRegistry


def bucket_rows(n: int, min_bucket: int = 16, max_batch: int = 4096) -> int:
    """Power-of-two padded size for an ``n``-row request (chunks of
    ``max_batch`` beyond the cap). Thin wrapper over the shared
    ``lightgbm_tpu.bucketing`` ladder, which the frontier grower's wave
    widths also ride."""
    check(n >= 1, "empty prediction request")
    return pow2_bucket(n, min_bucket, max_batch)


def bucket_sizes(min_bucket: int = 16, max_batch: int = 4096) -> List[int]:
    """Every bucket the cache can produce — the warmup schedule."""
    return pow2_ladder(min_bucket, max_batch)


class _CompiledPredictor:
    """One cache entry: a jit function pinned to (trees, bucket, transform).

    ``backend="traversal"`` (default) serves from the bundle's packed
    ``FlatForest`` (serving/traversal.py): O(depth) fused gather steps
    over all rows x all trees instead of the replay path's
    O(num_leaves) sequential split replays — same bit-exact outputs.
    ``backend="replay"`` keeps the training-side path (also the
    fallback for bundles without host-side trees)."""

    def __init__(self, bundle: ModelBundle, bucket: int, raw_score: bool,
                 num_iteration: int, mesh=None, backend: str = "traversal",
                 cascade_trees: int = 0, cascade_margin: float = 10.0,
                 quantize_leaves: bool = False):
        self.bucket = bucket
        use_traversal = (backend == "traversal"
                         and bundle.host_models is not None)
        self.backend = "traversal" if use_traversal else "replay"
        if use_traversal:
            trees, depth = bundle.flat_for(num_iteration,
                                           quantize=quantize_leaves)
        else:
            trees = bundle.trees_for(num_iteration)
            depth = 0
        self._mesh = mesh
        # static per-entry dispatch: shard rows when the bucket tiles the
        # mesh evenly, otherwise replicate the batch too (tiny buckets)
        self._shard = (mesh is not None
                       and bucket % mesh.devices.size == 0)
        if mesh is not None:
            trees = jax.device_put(trees, replicated(mesh))
            self._x_sharding = (row_sharding(mesh, extra_dims=1)
                                if self._shard else replicated(mesh))
        else:
            self._x_sharding = None
        self._trees = trees
        convert = (None if raw_score or bundle.objective is None
                   else bundle.objective.convert_output)
        avg_iters = num_iteration if bundle.average_output else 0
        k = bundle.num_tree_per_iteration

        def apply(t, x):
            if use_traversal:
                out = traversal_mod.forest_scores_flat(
                    t, x, k, depth, cascade_trees=cascade_trees,
                    cascade_margin=cascade_margin)      # [bucket, K] f32
            else:
                out = tree_mod.predict_forest_scores(t, x)
            if avg_iters:
                out = out / np.float32(avg_iters)
            if convert is not None:
                out = convert(out)
            return out

        self._fn = jax.jit(apply)

    def __call__(self, xpad: np.ndarray) -> jnp.ndarray:
        x = (jax.device_put(xpad, self._x_sharding)
             if self._x_sharding is not None else jnp.asarray(xpad))
        return self._fn(self._trees, x)


class ServingEngine:
    """Registry + predictor cache + (optional) mesh: the serve path's core.

    ``predict`` is thread-safe and synchronous; the micro-batching queue
    (serving/batching.py) sits in front of it for concurrent traffic.
    """

    def __init__(self, registry: Optional[ModelRegistry] = None,
                 max_batch: int = 4096, min_bucket: int = 16,
                 num_devices: int = 1,
                 metrics: Optional[ServingMetrics] = None,
                 backend: str = "traversal", cascade_trees: int = 0,
                 cascade_margin: float = 10.0,
                 quantize_leaves: bool = False,
                 guard_hot_roll: bool = True, canary_rows: int = 16,
                 roll_max_latency_ms: float = 0.0,
                 drift: bool = True, drift_warn_psi: float = 0.25,
                 drift_min_rows: int = 256, drift_decay: float = 0.999):
        check(max_batch >= 1 and min_bucket >= 1,
              "serve_max_batch and serve_min_bucket must be >= 1")
        check(backend in SERVING_BACKENDS,
              "serving_backend should be one of %s, got %r"
              % (list(SERVING_BACKENDS), backend))
        check(cascade_trees >= 0 and cascade_margin >= 0,
              "serving_cascade_trees and serving_cascade_margin must be >= 0")
        # normalize both to powers of two so bucket_rows' ladder is exact
        self.min_bucket = 1 << (int(min_bucket) - 1).bit_length()
        self.max_batch = max(1 << (int(max_batch) - 1).bit_length(),
                             self.min_bucket)
        self.backend = backend
        self.cascade_trees = int(cascade_trees)
        self.cascade_margin = float(cascade_margin)
        self.quantize_leaves = bool(quantize_leaves)
        self.guard_hot_roll = bool(guard_hot_roll)
        self.canary_rows = max(int(canary_rows), 1)
        self.roll_max_latency_ms = max(float(roll_max_latency_ms), 0.0)
        self.registry = registry if registry is not None else ModelRegistry()
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.mesh = serving_mesh(num_devices) if num_devices != 1 else None
        self._cache: Dict[Tuple, _CompiledPredictor] = {}
        self._lock = threading.Lock()
        # train/serve drift (obs/drift.py): one DriftMonitor per live
        # (model, generation), created lazily on the first predict so a
        # pre-profile bundle costs one dict lookup per request and a
        # profile-less registry costs nothing at boot
        self.drift_enabled = bool(drift)
        self.drift_warn_psi = float(drift_warn_psi)
        self.drift_min_rows = int(drift_min_rows)
        self.drift_decay = float(drift_decay)
        self._drift: Dict[str, Tuple[int, object]] = {}
        self._drift_hooks: List = []   # attached to every (future) monitor
        self._health_monitor = None  # lazy HealthMonitor, warn-only routing
        # atomic re-registration (checkpoint hot-roll): purge this model's
        # compiled predictors when its bundle is swapped
        self.registry.add_replace_listener(self._invalidate_model)

    # ------------------------------------------------------------ cache
    def _invalidate_model(self, model_id: str) -> None:
        """Drop cache entries compiled against generations OTHER than the
        model's current one. The generation in the cache key already
        prevents stale *hits*; this reclaims dead entries' device memory
        while keeping entries a hot-roll prewarm compiled for the
        just-committed generation (prewarm_bundle)."""
        current = self.registry.generation(model_id)
        with self._lock:
            for key in [k for k in self._cache
                        if k[0] == model_id and k[1] != current]:
                del self._cache[key]
            held = self._drift.get(model_id)
            if held is not None and held[0] != current:
                # the new generation may carry a different (or no) training
                # profile — drop the monitor; the next predict rebuilds it
                del self._drift[model_id]
                from ..obs.drift import unregister_monitor
                unregister_monitor(model_id)

    def _active_margin(self) -> float:
        """The cascade margin live entries are compiled against; part of
        the cache key so a retune (set_cascade_margin) can build the new
        margin's entries while the old ones keep serving. Constant 0.0
        when no cascade is configured — margin writes then never churn
        the cache."""
        return float(self.cascade_margin) if self.cascade_trees > 0 else 0.0

    def _predictor(self, bundle: ModelBundle, bucket: int, raw_score: bool,
                   iters: int) -> _CompiledPredictor:
        margin = self._active_margin()
        key = (bundle.model_id, getattr(bundle, "generation", 0), bucket,
               bool(raw_score), iters, margin)
        with self._lock:
            entry = self._cache.get(key)
            if entry is None:
                entry = _CompiledPredictor(
                    bundle, bucket, raw_score, iters, mesh=self.mesh,
                    backend=self.backend, cascade_trees=self.cascade_trees,
                    cascade_margin=margin if self.cascade_trees > 0
                    else self.cascade_margin,
                    quantize_leaves=self.quantize_leaves)
                self._cache[key] = entry
                hit = False
            else:
                hit = True
        self.metrics.record_cache(hit)
        return entry

    def cache_size(self) -> int:
        with self._lock:
            return len(self._cache)

    def set_cascade_margin(self, margin: float) -> int:
        """Retune the early-exit cascade margin OFF the request path (the
        fleet CascadeAutotuner's apply hook): compile + execute every
        bucket at the new margin inside a warmup-credit window — exactly
        the ``stage_and_prewarm`` accounting, so the zero-recompile
        serving invariant survives the retune — then purge the old
        margin's entries. Returns the number of entries re-warmed (0 for
        a no-op or when no cascade is configured)."""
        margin = float(margin)
        check(margin >= 0, "cascade margin must be >= 0, got %s" % margin)
        if self.cascade_trees <= 0 or margin == self.cascade_margin:
            self.cascade_margin = margin
            return 0
        from ..profiling import backend_compile_count
        c0 = backend_compile_count()
        m0 = self.metrics.cache_misses
        self.cascade_margin = margin
        warmed = 0
        try:
            for mid in self.registry.ids():
                warmed += self._warm_bundle(self.registry.get(mid),
                                            (False,), (None,))
        finally:
            self.metrics.add_warmup_credit(backend_compile_count() - c0,
                                           self.metrics.cache_misses - m0)
        with self._lock:
            for key in [k for k in self._cache if k[5] != margin]:
                del self._cache[key]
        return warmed

    # ------------------------------------------------------------ drift
    def drift_monitor(self, bundle: ModelBundle):
        """The DriftMonitor for ``bundle``'s current generation (created
        and ``register_monitor``-ed on first use, so ``/drift`` and the
        cluster federation see it).  A monitor exists even when the bundle
        carries no training profile — it then reports ``no_profile``
        instead of silently vanishing from the status surfaces.  Returns
        None only when drift monitoring is disabled engine-wide."""
        if not self.drift_enabled:
            return None
        gen = getattr(bundle, "generation", 0)
        with self._lock:
            held = self._drift.get(bundle.model_id)
            if held is not None and held[0] == gen:
                return held[1]
        from ..obs.drift import DriftMonitor, register_monitor
        mon = DriftMonitor(
            getattr(bundle, "profile", None), model_id=bundle.model_id,
            warn_psi=self.drift_warn_psi, min_rows=self.drift_min_rows,
            decay=self.drift_decay, monitor=self._drift_health())
        for hook in list(self._drift_hooks):
            mon.on_drift(hook)
        with self._lock:
            held = self._drift.get(bundle.model_id)
            if held is not None and held[0] == gen:
                return held[1]   # raced another request; keep the winner
            self._drift[bundle.model_id] = (gen, mon)
        register_monitor(mon)
        return mon

    def add_drift_hook(self, hook) -> None:
        """Subscribe ``hook(report_dict)`` to ok->warn drift transitions
        of EVERY model this engine serves — current monitors and ones not
        yet created (they are lazy, per generation).  This is how
        ``CheckpointWatcher`` arms its refit-trigger poll without knowing
        which bundle will drift first."""
        self._drift_hooks.append(hook)
        with self._lock:
            monitors = [held[1] for held in self._drift.values()]
        for mon in monitors:
            mon.on_drift(hook)

    def _drift_health(self):
        """Warn-only HealthMonitor shared by this engine's drift monitors
        (note_drift never escalates, so ``action="warn"`` is exact)."""
        if self._health_monitor is None:
            from ..obs.health import HealthMonitor
            self._health_monitor = HealthMonitor(action="warn")
        return self._health_monitor

    def drift_status(self) -> Dict:
        """Worst drift status across this engine's live monitors — the
        ``drift`` field of the serving ``/healthz`` payload.  ``disabled``
        when the engine runs with ``serve_drift=false``; ``no_profile``
        when no monitored model carries a training profile yet."""
        if not self.drift_enabled:
            return {"status": "disabled", "models": {}}
        with self._lock:
            monitors = [held[1] for held in self._drift.values()]
        rank = {"warn": 2, "ok": 1, "no_profile": 0}
        worst, models = "no_profile", {}
        for mon in monitors:
            st = mon.status()
            models[st.get("model", "")] = st
            if rank.get(st["status"], 0) > rank[worst]:
                worst = st["status"]
        return {"status": worst, "models": models}

    # ------------------------------------------------------------ predict
    def predict(self, model_id: str, X, raw_score: bool = False,
                num_iteration: Optional[int] = None,
                _record_request: bool = True, _span=None) -> np.ndarray:
        """Serve one request; output matches ``Booster.predict`` (same f32
        accumulation order, same transform) for any request size.
        ``_record_request=False`` is for the micro-batch queue, which
        accounts its callers itself (per-caller count + queue-inclusive
        latency) so a fused dispatch is not double-counted.

        ``_span`` is an optional trace span (obs/reqtrace.py): when
        present, each bucket pass is split into a ``device_dispatch``
        child (the async jit call returning a device future) and a
        ``device_wait`` child (the host blocking on the transfer) — the
        split only exists on the traced path; the untraced fast path is
        the exact pre-trace statement, same compiled entries either way."""
        t0 = time.perf_counter()
        # serve_predict seam: "request" = dispatched predict, counted by
        # the plan's per-point counter (fused queue batches count once)
        faults.inject("serve_predict", model=model_id)
        bundle = self.registry.get(model_id)
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        check(X.ndim == 2, "prediction input must be 2-D")
        if bundle.num_features:
            check(X.shape[1] == bundle.num_features,
                  "model %r expects %d features, request has %d"
                  % (model_id, bundle.num_features, X.shape[1]))
        iters = bundle.effective_iterations(num_iteration)
        if _span is not None and self.cascade_trees > 0:
            # cascade stages run inside the compiled program; the trace
            # records the configuration the pass was compiled against
            _span.annotate(cascade_trees=self.cascade_trees,
                           cascade_margin=self.cascade_margin)
        n = X.shape[0]
        outs = []
        for lo in range(0, n, self.max_batch):
            xc = X[lo:lo + self.max_batch]
            b = bucket_rows(xc.shape[0], self.min_bucket, self.max_batch)
            xpad = xc
            if b != xc.shape[0]:
                xpad = np.zeros((b, X.shape[1]), np.float32)
                xpad[:xc.shape[0]] = xc
            entry = self._predictor(bundle, b, raw_score, iters)
            t1 = time.perf_counter()
            if _span is not None:
                dspan = _span.child("device_dispatch", bucket=b)
                dev = entry(xpad)
                dspan.end()
                wspan = _span.child("device_wait", bucket=b)
                out = np.asarray(dev, np.float64)[:xc.shape[0]]
                wspan.end()
            else:
                out = np.asarray(entry(xpad), np.float64)[:xc.shape[0]]
            self.metrics.record_batch(b)
            self.metrics.record_bucket_latency(
                b, (time.perf_counter() - t1) * 1000.0)
            outs.append(out)
        out = outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)
        if bundle.num_tree_per_iteration == 1:
            out = out[:, 0]
        if self.drift_enabled:
            mon = self.drift_monitor(bundle)
            if mon is not None:
                try:
                    mon.observe(X, scores=out)
                except Exception as e:  # diagnostics must not fail serving
                    Log.debug("drift observe failed for %r: %s",
                              model_id, e)
        if _record_request:
            self.metrics.record_request(n, time.perf_counter() - t0)
        return out

    # ------------------------------------------------------------ warmup
    def warmup(self, model_ids: Optional[Iterable[str]] = None,
               raw_scores: Iterable[bool] = (False,),
               num_iterations: Iterable[Optional[int]] = (None,),
               extract_costs: bool = False) -> int:
        """Compile every bucket for the given key prefixes so live traffic
        never compiles; returns the number of entries warmed. Marks the
        metrics recompile floor when done.

        ``extract_costs=True`` additionally runs the obs cost model over
        each warmed bucket (``predict_b<bucket>`` entries: XLA FLOPs /
        bytes per forward pass, feeding ``GET /roofline``).
        AOT extraction shares nothing with the serving executables, so it
        cannot retrace them — and it runs BEFORE the recompile floor is
        marked, so its own one-time compiles never trip the serving
        zero-recompile assertion."""
        ids = list(model_ids) if model_ids is not None else self.registry.ids()
        cm = None
        if extract_costs:
            from ..obs.costmodel import get_cost_model
            cm = get_cost_model()
        warmed = 0
        for mid in ids:
            warmed += self._warm_bundle(self.registry.get(mid), raw_scores,
                                        num_iterations, cm)
        self.metrics.mark_warmup_done()
        return warmed

    def _warm_bundle(self, bundle: ModelBundle, raw_scores, num_iterations,
                     cm=None) -> int:
        """Compile + execute every bucket for one bundle (shared by
        boot-time ``warmup`` and hot-roll ``prewarm_bundle``)."""
        nf = max(bundle.num_features, 1)
        warmed = 0
        for b in bucket_sizes(self.min_bucket, self.max_batch):
            zeros = np.zeros((b, nf), np.float32)
            for raw in raw_scores:
                for ni in num_iterations:
                    iters = bundle.effective_iterations(ni)
                    entry = self._predictor(bundle, b, raw, iters)
                    # lgbm-lint: disable=LGL103 serving warmup sync
                    jax.block_until_ready(entry(zeros))
                    warmed += 1
                    if cm is not None:
                        cm.analyze(
                            "predict_b%d" % b, entry._fn,
                            jax.tree_util.tree_map(
                                lambda a: jax.ShapeDtypeStruct(
                                    a.shape, a.dtype), entry._trees),
                            jax.ShapeDtypeStruct((b, nf), jnp.float32),
                            extra_key="model=%s;raw=%d;iters=%d"
                            % (bundle.model_id, int(raw), iters))
        return warmed

    def prewarm_bundle(self, bundle: ModelBundle,
                       raw_scores: Iterable[bool] = (False,),
                       num_iterations: Iterable[Optional[int]] = (None,)
                       ) -> int:
        """Compile a STAGED bundle's predictors before it is registered
        (registry.stage_file -> prewarm_bundle -> register): a hot-roll
        pays its compilations here, off the request path, and the
        compiles/misses are credited to the metrics floors so the
        zero-recompile-after-warmup assertion survives the roll. Entries
        are cached under the staged generation; the generation-aware
        purge keeps them when the swap commits."""
        from ..profiling import backend_compile_count
        c0 = backend_compile_count()
        m0 = self.metrics.cache_misses
        warmed = self._warm_bundle(bundle, raw_scores, num_iterations)
        self.metrics.add_warmup_credit(backend_compile_count() - c0,
                                       self.metrics.cache_misses - m0)
        return warmed

    def stage_and_prewarm(self, model_id: str, path: str,
                          raw_scores: Iterable[bool] = (False,),
                          num_iterations: Iterable[Optional[int]] = (None,)
                          ) -> ModelBundle:
        """The full off-path half of a hot-roll: stage ``path`` as the
        next generation of ``model_id`` and prewarm it, crediting EVERY
        compilation in the window — the staged bundle's device stacking
        included, not just the predictor compiles — to the warmup
        floors. Caller commits with ``registry.register(bundle,
        replace=True)`` (CheckpointWatcher.poll does exactly this).

        Guarded roll (``guard_hot_roll``, docs/Resilience.md): canary
        rows are scored on the staged bundle — finite outputs,
        traversal-vs-replay parity, optional latency cap — and a failing
        bundle is REFUSED: its compiled entries are purged, the
        ``lgbm_serving_rollbacks_total`` counter ticks, and the raised
        LightGBMError leaves the prior generation serving untouched."""
        from ..profiling import backend_compile_count
        c0 = backend_compile_count()
        m0 = self.metrics.cache_misses
        try:
            bundle = self.registry.stage_file(model_id, path)
            self._warm_bundle(bundle, raw_scores, num_iterations)
            if self.guard_hot_roll:
                try:
                    self._validate_bundle(bundle)
                except LightGBMError as e:
                    self.metrics.record_rollback()
                    self._purge_generation(model_id,
                                           getattr(bundle, "generation", 0))
                    Log.warning("hot-roll REFUSED for %r (%s): prior "
                                "generation stays live", model_id, e)
                    raise
        finally:
            # validation compiles (if any) are staged-roll work, never
            # serving recompiles — credit even on refusal
            self.metrics.add_warmup_credit(backend_compile_count() - c0,
                                           self.metrics.cache_misses - m0)
        return bundle

    # ------------------------------------------------------------ guard
    def _purge_generation(self, model_id: str, generation: int) -> None:
        """Drop every compiled entry of one (model, generation) — the
        refused staged bundle's predictors must not linger in device
        memory or ever serve a request."""
        with self._lock:
            for key in [k for k in self._cache
                        if k[0] == model_id and k[1] == generation]:
                del self._cache[key]

    def _canary(self, bundle: ModelBundle) -> np.ndarray:
        """Deterministic canary rows: a fixed grid spanning a wide value
        range (zeros, extremes, and a dense ramp), enough to route down
        both sides of any split and surface NaN/inf leaves."""
        nf = max(bundle.num_features, 1)
        n = self.canary_rows
        X = np.linspace(-1e3, 1e3, num=n * nf,
                        dtype=np.float32).reshape(n, nf)
        X[0, :] = 0.0
        if n > 1:
            X[1, :] = np.float32(1e30)
        return X

    def _validate_bundle(self, bundle: ModelBundle) -> None:
        """Score canary rows on the STAGED bundle; raise LightGBMError on
        any failed check. Runs inside the stage_and_prewarm credit window
        so nothing here counts as a serving recompile."""
        if getattr(bundle, "profile", None) is None:
            # warn, don't refuse: pre-profile snapshots/model files are
            # valid models — they just cannot be drift-monitored, and the
            # /drift route will say "no_profile" for them
            Log.warning(
                "staged model %r carries no training data profile "
                "(pre-profile snapshot or bare model file); train/serve "
                "drift detection is unavailable for this generation",
                bundle.model_id)
        X = self._canary(bundle)
        iters = bundle.effective_iterations(None)
        b = bucket_rows(X.shape[0], self.min_bucket, self.max_batch)
        xpad = X
        if b != X.shape[0]:
            xpad = np.zeros((b, X.shape[1]), np.float32)
            xpad[:X.shape[0]] = X
        entry = self._predictor(bundle, b, False, iters)
        # lgbm-lint: disable=LGL103 canary probe, sync is the point
        jax.block_until_ready(entry(xpad))   # warm before timing
        t1 = time.perf_counter()
        # lgbm-lint: disable=LGL103 canary latency measurement
        out = np.asarray(jax.block_until_ready(entry(xpad)))[:X.shape[0]]
        latency_ms = (time.perf_counter() - t1) * 1000.0
        if not np.isfinite(out).all():
            bad = int(np.count_nonzero(~np.isfinite(out)))
            raise LightGBMError(
                "staged model %r failed canary validation: %d non-finite "
                "output(s) across %d canary rows"
                % (bundle.model_id, bad, X.shape[0]))
        if bundle.host_models is not None:
            # eager traversal-vs-replay parity on the canary rows: both
            # paths must agree before the flat forest serves traffic
            flat, depth = bundle.flat_for(iters)
            trees = bundle.trees_for(iters)
            xj = jnp.asarray(X)
            a = np.asarray(traversal_mod.forest_scores_flat(
                flat, xj, bundle.num_tree_per_iteration, depth))
            r = np.asarray(tree_mod.predict_forest_scores(trees, xj))
            if not (np.isfinite(a).all() and np.isfinite(r).all()):
                raise LightGBMError(
                    "staged model %r failed canary validation: non-finite "
                    "raw scores (traversal/replay)" % bundle.model_id)
            if not np.allclose(a, r, rtol=1e-5, atol=1e-5):
                raise LightGBMError(
                    "staged model %r failed canary validation: traversal "
                    "vs replay diverge (max |diff| %.3g)"
                    % (bundle.model_id, float(np.max(np.abs(a - r)))))
        if self.roll_max_latency_ms and \
                latency_ms > self.roll_max_latency_ms:
            raise LightGBMError(
                "staged model %r failed canary validation: warmed predict "
                "took %.1f ms > serve_roll_max_latency_ms=%.1f"
                % (bundle.model_id, latency_ms, self.roll_max_latency_ms))
