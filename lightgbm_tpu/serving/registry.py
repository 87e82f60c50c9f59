"""Model registry: model files -> immutable device-resident tree bundles.

The serving analog of the reference's prediction application layer
(src/application/predictor.hpp): a model is loaded ONCE, its trees are
packed to model-wide fixed shapes (core/tree.py pack_predict_table) and
stacked ``[iterations, num_tree_per_iteration, ...]`` on device, and every
request thereafter only reads the bundle. Bundles are immutable — capping
``num_iteration`` slices the stacked arrays (cheap device slice, cached),
never mutates them — so concurrent request threads need no locking past
the registry dict itself.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..log import LightGBMError, check


def _sibling_profile(model_path: str):
    """Recover the training data profile for a model-text file from the
    checkpoint meta.json written next to it (``snap_N.model.txt`` ->
    ``snap_N.meta.json``).  Snapshots double as servable models, and the
    profile travels in their JSON meta — this is how a hot-rolled bundle
    gets its drift reference.  Returns None for bare model files or
    pre-profile snapshots (always legal)."""
    import json
    import os
    if not model_path.endswith(".model.txt"):
        return None
    meta_path = model_path[:-len(".model.txt")] + ".meta.json"
    if not os.path.exists(meta_path):
        return None
    try:
        with open(meta_path, "r") as fh:
            meta = json.load(fh)
        from ..obs.drift import DataProfile
        return DataProfile.from_json_dict(meta.get("data_profile"))
    except Exception:  # noqa: BLE001 - a corrupt sibling never blocks a load
        return None


class ModelBundle:
    """One loaded model, ready to serve.

    ``trees`` holds the PredictTree arrays stacked ``[I, K, ...]`` where
    ``I`` is boosting iterations and ``K`` trees-per-iteration (1 unless
    multiclass); ``objective`` supplies ``convert_output`` for non-raw
    scores (None for custom-objective models, which serve raw only).
    """

    def __init__(self, model_id: str, trees, num_class: int, k: int,
                 num_features: int, objective=None,
                 average_output: bool = False,
                 feature_names: Optional[List[str]] = None,
                 pandas_categorical=None, host_models=None,
                 profile=None):
        self.model_id = model_id
        self.trees = trees
        self.num_class = num_class
        self.num_tree_per_iteration = k
        self.num_features = num_features
        self.objective = objective
        self.average_output = average_output
        self.feature_names = list(feature_names or [])
        self.pandas_categorical = pandas_categorical
        self.total_iterations = int(trees.leaf_value.shape[0])
        self.generation = 0       # bumped by ModelRegistry.register
        # host-side trees (HostTree/LoadedTree), kept for the serving
        # traversal's SoA pack (serving/traversal.py); None disables the
        # traversal backend for this bundle (replay fallback)
        self.host_models = host_models
        # training data profile (obs.drift.DataProfile) or None: the
        # reference distribution drift monitoring scores against.
        # Optional EVERYWHERE — models loaded from bare text files or
        # pre-profile snapshots legally carry none (drift reports
        # "no_profile" for them)
        self.profile = profile
        self._capped: Dict[int, "jnp.ndarray"] = {}
        self._flat: Dict[bool, tuple] = {}        # quantize -> (forest, depth)
        self._flat_capped: Dict[tuple, object] = {}
        self._lock = threading.Lock()

    @classmethod
    def from_impl(cls, model_id: str, impl,
                  feature_names: Optional[List[str]] = None,
                  pandas_categorical=None) -> "ModelBundle":
        """Bundle a boosting driver (basic.Booster._impl or a GBDT built
        directly)."""
        models = impl.models
        check(len(models) > 0, "cannot serve an empty model")
        k = max(impl.num_tree_per_iteration, 1)
        total = (len(models) // k) * k   # drop a partial trailing iteration
        stacked = impl._stacked_predict_trees(0, total)
        trees = jax.tree.map(
            lambda a: a.reshape((total // k, k) + a.shape[1:]), stacked)
        if feature_names is None and getattr(impl, "train_data", None) is not None:
            feature_names = list(impl.train_data.feature_names)
        nf = len(feature_names) if feature_names else int(max(
            (int(np.max(t.split_feature, initial=0)) for t in models),
            default=0)) + 1
        profile = None
        if getattr(impl, "train_data", None) is not None:
            try:
                profile = impl.train_data.data_profile()
            except Exception:  # noqa: BLE001 - profile is best-effort
                profile = None
        return cls(model_id, trees, num_class=impl.num_class, k=k,
                   num_features=nf, objective=impl.objective,
                   average_output=impl.average_output,
                   feature_names=feature_names,
                   pandas_categorical=pandas_categorical,
                   host_models=list(models[:total]), profile=profile)

    @classmethod
    def from_booster(cls, model_id: str, booster) -> "ModelBundle":
        return cls.from_impl(model_id, booster._impl,
                             feature_names=booster._feature_names(),
                             pandas_categorical=booster.pandas_categorical)

    def effective_iterations(self, num_iteration: Optional[int]) -> int:
        if num_iteration is None or num_iteration <= 0:
            return self.total_iterations
        return min(int(num_iteration), self.total_iterations)

    def trees_for(self, num_iteration: Optional[int]):
        """Stacked trees capped to ``num_iteration`` (the
        GBDT::Predict num_iteration contract); full model returns the
        original arrays, capped views are sliced once and cached."""
        iters = self.effective_iterations(num_iteration)
        if iters == self.total_iterations:
            return self.trees
        with self._lock:
            if iters not in self._capped:
                self._capped[iters] = jax.tree.map(lambda a: a[:iters],
                                                   self.trees)
            return self._capped[iters]

    def flat_for(self, num_iteration: Optional[int] = None,
                 quantize: bool = False):
        """``(FlatForest, depth)`` for the serving traversal backend:
        packed ONCE per bundle (== per model generation — a hot-roll swaps
        the whole bundle, so stale tables die with it), device-put, and
        sliced/cached per ``num_iteration`` cap like ``trees_for``. The
        full-ensemble depth bounds every capped slice too."""
        if self.host_models is None:
            raise LightGBMError(
                "model %r has no host-side trees; the traversal backend "
                "needs a bundle built by from_impl/from_booster "
                "(serving_backend=replay serves bare-tree bundles)"
                % self.model_id)
        iters = self.effective_iterations(num_iteration)
        t = iters * self.num_tree_per_iteration
        q = bool(quantize)
        with self._lock:
            if q not in self._flat:
                from .traversal import pack_flat_forest
                host, depth = pack_flat_forest(self.host_models, quantize=q)
                self._flat[q] = (jax.tree.map(jnp.asarray, host), depth)
            full, depth = self._flat[q]
            if t == self.total_iterations * self.num_tree_per_iteration:
                return full, depth
            key = (t, q)
            if key not in self._flat_capped:
                self._flat_capped[key] = jax.tree.map(lambda a: a[:t], full)
            return self._flat_capped[key], depth


class ModelRegistry:
    """Named, immutable model bundles (the serving fleet's model store).

    Bundles never mutate; re-registration with ``replace=True`` swaps the
    whole bundle atomically under the registry lock and bumps that model's
    generation counter. Replace listeners (ServingEngine's predictor-cache
    purge) fire after the swap, outside the lock.
    """

    def __init__(self):
        self._bundles: Dict[str, ModelBundle] = {}
        self._generation: Dict[str, int] = {}
        self._replace_listeners: List = []
        self._lock = threading.Lock()

    def load_file(self, model_id: str, path: str,
                  replace: bool = False) -> ModelBundle:
        """Load a LightGBM model-text file (io/model_text.py format)."""
        return self.register(self.stage_file(model_id, path), replace=replace)

    def stage_file(self, model_id: str, path: str) -> ModelBundle:
        """Build a bundle from a model file WITHOUT registering it, its
        generation pre-set to the value ``register`` will assign. Lets a
        hot-roller compile the next generation's predictors off the
        request path (ServingEngine.prewarm_bundle) before the atomic
        ``register(..., replace=True)`` swap."""
        from ..basic import Booster
        from ..io.model_text import parse_model_file
        parse_model_file(path)   # fail fast with a format error, not mid-serve
        booster = Booster(model_file=path)
        bundle = ModelBundle.from_booster(model_id, booster)
        if bundle.profile is None:
            bundle.profile = _sibling_profile(path)
        with self._lock:
            bundle.generation = self._generation.get(model_id, 0) + 1
        return bundle

    def register_booster(self, model_id: str, booster,
                         replace: bool = False) -> ModelBundle:
        return self.register(ModelBundle.from_booster(model_id, booster),
                             replace=replace)

    def register_impl(self, model_id: str, impl,
                      replace: bool = False) -> ModelBundle:
        return self.register(ModelBundle.from_impl(model_id, impl),
                             replace=replace)

    def register(self, bundle: ModelBundle,
                 replace: bool = False) -> ModelBundle:
        replaced = False
        with self._lock:
            if bundle.model_id in self._bundles and not replace:
                raise LightGBMError("model id %r already registered "
                                    "(pass replace=True to swap it)"
                                    % bundle.model_id)
            replaced = bundle.model_id in self._bundles
            gen = self._generation.get(bundle.model_id, 0) + 1
            self._generation[bundle.model_id] = gen
            bundle.generation = gen
            self._bundles[bundle.model_id] = bundle
            listeners = list(self._replace_listeners)
        if replaced:
            # outside the lock: listeners may take their own locks
            # (ServingEngine purges its compiled-predictor cache here)
            for fn in listeners:
                fn(bundle.model_id)
        return bundle

    def generation(self, model_id: str) -> int:
        with self._lock:
            return self._generation.get(model_id, 0)

    def add_replace_listener(self, fn) -> None:
        """``fn(model_id)`` is called after an existing model is replaced."""
        with self._lock:
            self._replace_listeners.append(fn)

    def get(self, model_id: str) -> ModelBundle:
        with self._lock:
            b = self._bundles.get(model_id)
        if b is None:
            raise LightGBMError("unknown model id %r (registered: %s)"
                                % (model_id, sorted(self._bundles)))
        return b

    def ids(self) -> List[str]:
        with self._lock:
            return sorted(self._bundles)

    # ------------------------------------------------- checkpoint hot-roll
    def watch_dir(self, model_id: str, checkpoint_dir: str,
                  poll_interval: float = 10.0,
                  start: bool = False, engine=None) -> "CheckpointWatcher":
        """Hot-roll the newest valid snapshot of a lightgbm_tpu.checkpoint
        directory into this registry under ``model_id``. Returns a watcher;
        call ``poll()`` for one synchronous check (the first poll registers
        the current snapshot) or pass ``start=True`` for a daemon-thread
        loop. Replacement is atomic and invalidates the model's compiled
        predictors via the replace listeners.

        With ``engine`` (a ServingEngine), every poll that finds a newer
        snapshot PREWARMS it first — the staged bundle's predictors are
        compiled off the request path and credited to the warmup floor,
        then the swap commits; live traffic never waits on a compile and
        the zero-recompile-after-warmup invariant survives the roll."""
        w = CheckpointWatcher(self, model_id, checkpoint_dir, poll_interval,
                              engine=engine)
        if start:
            w.start()
        return w


class CheckpointWatcher:
    """Polls a checkpoint directory's manifest; loads newer snapshots."""

    def __init__(self, registry: ModelRegistry, model_id: str,
                 checkpoint_dir: str, poll_interval: float = 10.0,
                 engine=None):
        self.registry = registry
        self.model_id = model_id
        self.checkpoint_dir = checkpoint_dir
        self.poll_interval = float(poll_interval)
        self.engine = engine
        self._last_id = -1
        self._rejected_ids: set = set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if engine is not None and hasattr(engine, "add_drift_hook"):
            # refit trigger: a drift warn on ANY model this engine serves
            # polls the checkpoint directory immediately (off-thread) —
            # see arm_drift_refit for the contract
            engine.add_drift_hook(self._drift_poll)

    def poll(self) -> bool:
        """One check: register the newest valid snapshot if it is newer
        than what we already rolled in. Returns True when a (re)load
        happened; verification failures fall back exactly like resume
        does (manifest checksums, newest -> oldest). With an attached
        engine the staged bundle is prewarmed BEFORE the swap — and a
        bundle the engine's guarded roll REFUSES (canary validation,
        docs/Resilience.md) is remembered and skipped on later polls, the
        prior generation left serving."""
        from ..checkpoint.manager import CheckpointManager
        from ..log import Log
        latest = CheckpointManager(self.checkpoint_dir).latest_model()
        if latest is None:
            return False
        snap_id, model_path = latest
        if snap_id <= self._last_id or snap_id in self._rejected_ids:
            return False
        if self.engine is not None:
            from ..log import LightGBMError
            try:
                bundle = self.engine.stage_and_prewarm(self.model_id,
                                                       model_path)
            except LightGBMError as e:
                self._rejected_ids.add(snap_id)
                live = (self.model_id in self.registry.ids())
                Log.warning("serving: snapshot %d REJECTED for model %r "
                            "(%s); %s", snap_id, self.model_id, e,
                            "prior generation stays live" if live
                            else "no prior generation registered")
                return False
        else:
            bundle = self.registry.stage_file(self.model_id, model_path)
        self.registry.register(bundle, replace=True)
        self._last_id = snap_id
        Log.info("serving: hot-rolled snapshot %d from %s into model %r",
                 snap_id, self.checkpoint_dir, self.model_id)
        return True

    def arm_drift_refit(self, monitor) -> None:
        """Subscribe this watcher to a DriftMonitor (obs/drift.py): when
        serving traffic drifts past the warn threshold, poll the
        checkpoint directory immediately — if a refit loop has produced a
        newer snapshot, it hot-rolls in without waiting out the poll
        interval. This is the refit-trigger contract from
        docs/Observability.md: the hook never trains anything itself; it
        closes the loop between "the data moved" and "pick up the
        retrained model". Watchers built with ``engine=`` arm themselves
        through ``ServingEngine.add_drift_hook`` — this method is the
        manual seam for monitors created outside an engine."""
        monitor.on_drift(self._drift_poll)

    def _drift_poll(self, report) -> None:
        """Drift hooks fire on the serving request thread that crossed
        the threshold — the poll (which may compile a staged bundle) runs
        on its own daemon thread so the triggering request never waits."""
        t = threading.Thread(target=self._safe_poll, daemon=True,
                             name="ckpt-drift-poll-%s" % self.model_id)
        t.start()

    def _safe_poll(self) -> None:
        try:
            self.poll()
        except Exception as e:  # noqa: BLE001 - keep serving alive
            from ..log import Log
            Log.warning("drift-triggered checkpoint poll %r: %s",
                        self.model_id, e)

    def start(self) -> "CheckpointWatcher":
        if self._thread is not None:
            return self

        def loop():
            while not self._stop.wait(self.poll_interval):
                try:
                    self.poll()
                except Exception as e:  # noqa: BLE001 - keep serving alive
                    from ..log import Log
                    Log.warning("checkpoint watcher %r: %s",
                                self.model_id, e)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="ckpt-watch-%s" % self.model_id)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
