"""Training and cross-validation entry points.

Reference: python-package/lightgbm/engine.py — ``train`` (:19, boost loop
:211-236) and ``cv`` (:336, stratified folds :270, aggregation :325). Same
semantics: callbacks run before/after each iteration, ``EarlyStopException``
unwinds and truncates to best_iteration, ``evals_result`` records history.
"""
from __future__ import annotations

import collections
import copy
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from . import callback
from .basic import Booster, Dataset, _InnerPredictor
from .config import Config
from .log import Log, LightGBMError
from .obs.trace import recorder


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None,
          feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name: Union[str, List[str]] = "auto",
          categorical_feature: Union[str, List] = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates: Optional[Union[List[float], Callable]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          resume_from: Optional[str] = None,
          supervise: Optional[bool] = None) -> Booster:
    """engine.py:19 — train with the reference's full signature, plus
    ``resume_from``: a lightgbm_tpu.checkpoint directory to continue from
    (``num_boost_round`` stays the TOTAL target — a run checkpointed at
    iteration k trains the remaining ``num_boost_round - k`` rounds and
    produces a model byte-identical to the uninterrupted run;
    docs/Checkpointing.md), and ``supervise`` (or ``supervise=true`` in
    params): run under the resilience supervisor — a watchdog over the
    per-iteration heartbeat (``supervise_hang_timeout_s``; warmup-aware
    so a slow first compile never false-fires) plus a restart loop that
    flight-dumps on crash and auto-resumes from the newest valid
    checkpoint under bounded exponential backoff, byte-identical to the
    uninterrupted run (docs/Resilience.md)."""
    if supervise is None:
        raw = (params or {}).get("supervise",
                                 (params or {}).get("supervised", False))
        supervise = str(raw).strip().lower() in ("true", "1", "yes", "+")
    # the entry layer's span: its own cost is its duration less its
    # children's (train.booster_init, train.make_block_fn, train.block,
    # materialize)
    with recorder.span("train.engine"):
        if supervise:
            return _train_supervised(
                params, train_set, num_boost_round, valid_sets, valid_names,
                fobj, feval, init_model, feature_name, categorical_feature,
                early_stopping_rounds, evals_result, verbose_eval,
                learning_rates, keep_training_booster, callbacks,
                resume_from)
        return _train_once(
            params, train_set, num_boost_round, valid_sets, valid_names,
            fobj, feval, init_model, feature_name, categorical_feature,
            early_stopping_rounds, evals_result, verbose_eval,
            learning_rates, keep_training_booster, callbacks, resume_from)


def _train_supervised(params, train_set, num_boost_round, valid_sets,
                      valid_names, fobj, feval, init_model, feature_name,
                      categorical_feature, early_stopping_rounds,
                      evals_result, verbose_eval, learning_rates,
                      keep_training_booster, callbacks,
                      resume_from) -> Booster:
    from .resilience.supervisor import Supervisor, heartbeat_file_callback
    cfg = Config(copy.deepcopy(params) if params else {})
    sup = Supervisor(cfg.checkpoint_dir,
                     max_restarts=cfg.supervise_max_restarts,
                     backoff_s=cfg.supervise_backoff_s,
                     backoff_max_s=cfg.supervise_backoff_max_s,
                     hang_timeout_s=cfg.supervise_hang_timeout_s,
                     warmup_grace_s=cfg.supervise_warmup_grace_s)

    def attempt(resume, watchdog):
        cbs = list(callbacks or [])
        if watchdog is not None:
            cbs.append(watchdog.callback())
        if cfg.supervise_heartbeat_file:
            cbs.append(heartbeat_file_callback(cfg.supervise_heartbeat_file))
        return _train_once(
            params, train_set, num_boost_round, valid_sets, valid_names,
            fobj, feval, init_model, feature_name, categorical_feature,
            early_stopping_rounds, evals_result, verbose_eval,
            learning_rates, keep_training_booster, cbs,
            resume if resume is not None else resume_from)

    return sup.run(attempt)


def _train_once(params: Dict[str, Any], train_set: Dataset,
                num_boost_round: int = 100,
                valid_sets: Optional[List[Dataset]] = None,
                valid_names: Optional[List[str]] = None,
                fobj: Optional[Callable] = None,
                feval: Optional[Callable] = None,
                init_model: Optional[Union[str, Booster]] = None,
                feature_name: Union[str, List[str]] = "auto",
                categorical_feature: Union[str, List] = "auto",
                early_stopping_rounds: Optional[int] = None,
                evals_result: Optional[Dict] = None,
                verbose_eval: Union[bool, int] = True,
                learning_rates: Optional[Union[List[float], Callable]] = None,
                keep_training_booster: bool = False,
                callbacks: Optional[List[Callable]] = None,
                resume_from: Optional[str] = None) -> Booster:
    params = copy.deepcopy(params) if params else {}
    # resolve num_boost_round aliases out of params (engine.py:96-107)
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "num_tree", "num_trees", "num_round", "num_rounds",
                  "n_estimators"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping"):
        if alias in params and params[alias] is not None:
            early_stopping_rounds = int(params.pop(alias))
    if fobj is not None:
        params["objective"] = "none"
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    predictor = None
    if isinstance(init_model, str):
        predictor = _InnerPredictor(Booster(model_file=init_model))
    elif isinstance(init_model, Booster):
        predictor = _InnerPredictor(init_model)
    if predictor is not None:
        train_set._set_predictor(predictor)

    if not train_set.params:
        train_set.params = params
    with recorder.span("train.booster_init"):
        booster = Booster(params=params, train_set=train_set)
    is_valid_contain_train = False
    train_data_name = "training"
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        name_valid_sets = valid_names or \
            ["valid_%d" % i for i in range(len(valid_sets))]
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                is_valid_contain_train = True
                train_data_name = name_valid_sets[i]
                continue
            if vs.reference is None:
                vs.reference = train_set
            booster.add_valid(vs, name_valid_sets[i])
    booster.train_set_name = train_data_name

    # a list, not a set: equal-`order` callbacks must run in a deterministic
    # (registration) order — Python's stable sort preserves list order
    cbs = list(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.append(callback.early_stopping(
            early_stopping_rounds,
            first_metric_only=bool(params.get("first_metric_only", False))))
    if verbose_eval is True:
        cbs.append(callback.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval:
        cbs.append(callback.print_evaluation(verbose_eval))
    if evals_result is not None:
        cbs.append(callback.record_evaluation(evals_result))
    # every evaluated iteration also lands in the process metrics registry
    # (lgbm_eval_metric gauges) for the stats endpoint / cluster federation;
    # only_consumes_evals, so eval-free runs still fuse on device
    if not any(isinstance(c, callback._ExportEvalMetrics) for c in cbs):
        cbs.append(callback.export_eval_metrics())
    if learning_rates is not None:
        cbs.append(callback.reset_parameter(learning_rate=learning_rates))
    # checkpoint_dir in params auto-attaches the checkpoint callback (the
    # CLI's config-driven path; Python users can pass callback.checkpoint
    # explicitly instead)
    if booster.config.checkpoint_dir and \
            not any(getattr(c, "is_checkpoint", False) for c in cbs):
        cbs.append(callback.checkpoint(
            booster.config.checkpoint_dir,
            period=booster.config.checkpoint_period,
            keep_last_n=booster.config.checkpoint_keep))
    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration", False)]
    cbs_before.sort(key=lambda c: getattr(c, "order", 0))
    cbs_after.sort(key=lambda c: getattr(c, "order", 0))
    # the checkpoint callback reads loop-level state (early stopping) off
    # the booster when it snapshots
    booster._callbacks = cbs_before + cbs_after

    # resume (lightgbm_tpu.checkpoint): restore driver + callback state,
    # shrink the remaining-round budget to the original total
    resumed = False
    if resume_from is None and booster.config.resume:
        resume_from = booster.config.resume
    if resume_from:
        from . import checkpoint as ckpt_mod
        handle = ckpt_mod.load_latest(resume_from)
        if handle is None:
            Log.info("resume_from=%s: no checkpoint found; starting fresh",
                     resume_from)
        else:
            completed = ckpt_mod.restore(booster, handle,
                                         cbs_before + cbs_after)
            num_boost_round = max(num_boost_round - completed, 0)
            resumed = True

    # boosting loop (engine.py:211-246); a crash anywhere in it triggers
    # a flight-recorder dump (when armed) and the dump path rides the
    # exception for the supervisor / operator
    init_iteration = booster.current_iteration
    finished_early = False
    evaluation_result_list = []
    try:
        if valid_sets is None and fobj is None and not cbs_before and \
                not resumed and \
                all(getattr(c, "only_consumes_evals", False)
                    for c in cbs_after):
            # nothing needs the host between iterations (eval-display
            # callbacks are no-ops with no valid sets): fuse the whole
            # loop into on-device blocks (GBDT.train_many)
            booster._impl.train_many(num_boost_round)
            num_boost_round = 0
        for i in range(init_iteration, init_iteration + num_boost_round):
            for cb in cbs_before:
                cb(callback.CallbackEnv(
                    model=booster, params=params, iteration=i,
                    begin_iteration=init_iteration,
                    end_iteration=init_iteration + num_boost_round,
                    evaluation_result_list=None))
            stopped = booster.update(fobj=fobj)

            evaluation_result_list = []
            if valid_sets is not None or cbs_after:
                if is_valid_contain_train:
                    evaluation_result_list.extend(booster.eval_train(feval))
                if valid_sets is not None and booster._valid_sets:
                    evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in cbs_after:
                    cb(callback.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=init_iteration,
                        end_iteration=init_iteration + num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except callback.EarlyStopException as earlyStopException:
                booster.best_iteration = earlyStopException.best_iteration + 1
                evaluation_result_list = earlyStopException.best_score
                finished_early = True
                break
            if stopped:
                break
    except callback.EarlyStopException:
        raise
    except Exception as train_err:
        obs = getattr(booster._impl, "obs", None)
        if obs is not None and not getattr(train_err,
                                           "flight_dump_path", None):
            try:
                dump = obs.crash_flush("train-exception: %s: %s"
                                       % (type(train_err).__name__,
                                          train_err))
                if dump:
                    train_err.flight_dump_path = dump
            except Exception:   # the dump must never mask the crash
                pass
        raise

    booster.best_score = collections.defaultdict(dict)
    for dataset_name, eval_name, score, _ in (evaluation_result_list or []):
        booster.best_score[dataset_name][eval_name] = score
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration
    obs = getattr(booster._impl, "obs", None)
    if obs is not None and obs.enabled:
        # flush the event stream / close any open Perfetto window; the
        # stats endpoint stays up for post-train scrapes
        obs.finish()
    return booster


class CVBooster:
    """Ensemble of per-fold boosters returned by cv(return_cvbooster=True)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)


def _make_n_folds(full_data: Dataset, folds, nfold: int, params, seed: int,
                  stratified: bool, shuffle: bool):
    """engine.py:270-325: fold construction (sklearn-style if available)."""
    full_data = full_data.construct()
    num_data = full_data.num_data()
    if folds is not None:
        if not hasattr(folds, "__iter__") and hasattr(folds, "split"):
            group = full_data.get_group()
            group_info = None if group is None else np.asarray(group, np.int64)
            flattened = (np.repeat(range(len(group_info)), repeats=group_info)
                         if group_info is not None else None)
            folds = folds.split(X=np.zeros(num_data),
                                y=full_data.get_label(), groups=flattened)
        fold_list = list(folds)
    else:
        rng = np.random.RandomState(seed)
        group = full_data.get_group()
        if group is not None:
            # group-aware folds: whole queries assigned to folds
            num_group = len(group)
            gidx = np.arange(num_group)
            if shuffle:
                rng.shuffle(gidx)
            boundaries = np.concatenate([[0], np.cumsum(np.asarray(group))])
            fold_list = []
            for k in range(nfold):
                test_g = gidx[k::nfold]
                test_idx = np.concatenate(
                    [np.arange(boundaries[g], boundaries[g + 1])
                     for g in test_g]) if len(test_g) else np.array([], np.int64)
                mask = np.ones(num_data, bool)
                mask[test_idx] = False
                fold_list.append((np.where(mask)[0], test_idx))
        elif stratified:
            label = np.asarray(full_data.get_label())
            classes = np.unique(label)
            test_folds = [[] for _ in range(nfold)]
            for c in classes:
                cls_idx = np.where(label == c)[0]
                if shuffle:
                    rng.shuffle(cls_idx)
                for k in range(nfold):
                    test_folds[k].append(cls_idx[k::nfold])
            fold_list = []
            for k in range(nfold):
                test_idx = np.sort(np.concatenate(test_folds[k]))
                mask = np.ones(num_data, bool)
                mask[test_idx] = False
                fold_list.append((np.where(mask)[0], test_idx))
        else:
            idx = np.arange(num_data)
            if shuffle:
                rng.shuffle(idx)
            fold_list = []
            for k in range(nfold):
                test_idx = np.sort(idx[k::nfold])
                mask = np.ones(num_data, bool)
                mask[test_idx] = False
                fold_list.append((np.where(mask)[0], test_idx))
    return fold_list


def _agg_cv_result(raw_results):
    """engine.py:325-334: aggregate across folds -> mean/std per metric."""
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = one_line[0] + " " + one_line[1]
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k], float(np.std(v)))
            for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False):
    """engine.py:336 — k-fold cross-validation."""
    params = copy.deepcopy(params) if params else {}
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "num_tree", "num_trees", "num_round", "num_rounds",
                  "n_estimators"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping"):
        if alias in params and params[alias] is not None:
            early_stopping_rounds = int(params.pop(alias))
    if fobj is not None:
        params["objective"] = "none"
    if metrics is not None:
        params["metric"] = metrics
    if isinstance(params.get("metric"), str):
        params["metric"] = [params["metric"]]

    train_set = train_set.construct() if train_set._binned is None else train_set
    if params.get("objective") not in ("binary", "multiclass",
                                       "multiclassova") and folds is None:
        stratified = False
    folds_list = _make_n_folds(train_set, folds, nfold, params, seed,
                               stratified, shuffle)

    # build per-fold boosters
    cvbooster = CVBooster()
    raw_X = _raw_matrix(train_set)
    label = np.asarray(train_set.get_label())
    weight = train_set.get_weight()
    for train_idx, test_idx in folds_list:
        dtrain = Dataset(raw_X[train_idx], label=label[train_idx],
                         weight=None if weight is None else
                         np.asarray(weight)[train_idx],
                         params=dict(params),
                         categorical_feature=train_set.categorical_feature)
        dtest = dtrain.create_valid(
            raw_X[test_idx], label=label[test_idx],
            weight=None if weight is None else np.asarray(weight)[test_idx])
        if fpreproc is not None:
            dtrain, dtest, fold_params = fpreproc(dtrain, dtest, dict(params))
        else:
            fold_params = params
        bst = Booster(params=dict(fold_params), train_set=dtrain)
        bst.add_valid(dtest, "valid")
        cvbooster.append(bst)

    results = collections.defaultdict(list)
    # list, not set: deterministic order among equal-`order` callbacks
    cbs = list(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.append(callback.early_stopping(early_stopping_rounds,
                                           verbose=False))
    if verbose_eval is True:
        cbs.append(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval:
        cbs.append(callback.print_evaluation(verbose_eval, show_stdv))
    cbs_before = sorted((c for c in cbs if getattr(c, "before_iteration", False)),
                        key=lambda c: getattr(c, "order", 0))
    cbs_after = sorted((c for c in cbs if not getattr(c, "before_iteration", False)),
                       key=lambda c: getattr(c, "order", 0))

    for i in range(num_boost_round):
        fold_results = []
        for bst in cvbooster.boosters:
            for cb in cbs_before:
                cb(callback.CallbackEnv(
                    model=bst, params=params, iteration=i, begin_iteration=0,
                    end_iteration=num_boost_round,
                    evaluation_result_list=None))
            bst.update(fobj=fobj)
            one = []
            if eval_train_metric:
                one.extend(bst.eval_train(feval))
            one.extend(bst.eval_valid(feval))
            fold_results.append(one)
        agg = _agg_cv_result(fold_results)
        for _, key, mean, _, std in agg:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in cbs_after:
                cb(callback.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=agg))
        except callback.EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for k in list(results):
                results[k] = results[k][:cvbooster.best_iteration]
            break

    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return dict(results)


def _raw_matrix(ds: Dataset) -> np.ndarray:
    """Raw feature matrix for fold slicing; requires raw data retained."""
    if isinstance(ds.data, str):
        from .io.parser import parse_file
        X, _, _ = parse_file(ds.data, has_header=Config(ds.params).header,
                             label_column=Config(ds.params).label_column)
        return X
    from .basic import _to_2d_float
    return _to_2d_float(ds.data)
