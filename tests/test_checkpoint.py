"""lightgbm_tpu.checkpoint: preemption-safe snapshots, deterministic resume.

The contract under test is the headline guarantee from docs/Checkpointing.md:
a run killed at iteration k and resumed from its checkpoint directory
produces a model file BYTE-identical to the uninterrupted run (same
checkpoint callback attached to both — the callback pins the per-iteration
training path, see the determinism note in checkpoint/callback.py), plus the
failure-containment half: corrupt/truncated snapshots are detected by the
manifest checksums and resume falls back to the newest valid one.
"""
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback, engine
from lightgbm_tpu.checkpoint import CheckpointManager, load_latest
from lightgbm_tpu.log import LightGBMError, Log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=200, f=6, seed=7):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] + X[:, 1] * 2 + 0.3 * r.randn(n) > 0).astype(np.float64)
    return X, y


_BASE = dict(objective="binary", num_leaves=5, learning_rate=0.2,
             min_data_in_leaf=5, verbosity=0)


def _train(params, ckpt_dir, num_rounds, resume=False, valid=False,
           early_stop=False, X=None, y=None):
    if X is None:
        X, y = _data()
    ds = lgb.Dataset(X, label=y, params=dict(params))
    valid_sets = None
    if valid:
        Xv, yv = _data(n=100, seed=8)
        valid_sets = [ds.create_valid(Xv, label=yv)]
    cbs = [callback.checkpoint(ckpt_dir, period=1)]
    if early_stop:
        cbs.append(callback.early_stopping(3, verbose=False))
    ev = {}
    bst = engine.train(dict(params), ds, num_boost_round=num_rounds,
                       valid_sets=valid_sets, callbacks=cbs, evals_result=ev,
                       resume_from=(ckpt_dir if resume else None),
                       verbose_eval=False)
    return bst, ev


def _resume_matches_golden(tmp_path, params, valid=False, early_stop=False,
                           total=8, kill_at=3):
    golden, ev_g = _train(params, str(tmp_path / "g"), total, valid=valid,
                          early_stop=early_stop)
    # "killed" run: only kill_at rounds reach the checkpoint directory
    _train(params, str(tmp_path / "i"), kill_at, valid=valid,
           early_stop=early_stop)
    resumed, ev_r = _train(params, str(tmp_path / "i"), total, resume=True,
                           valid=valid, early_stop=early_stop)
    assert golden.model_to_string() == resumed.model_to_string()
    assert ev_g == ev_r
    assert golden.best_iteration == resumed.best_iteration


# --------------------------------------------------------- byte-identity
def test_resume_byte_identical_gbdt(tmp_path):
    # bagging + feature_fraction: both RNG streams must survive the snapshot
    _resume_matches_golden(tmp_path, dict(
        _BASE, bagging_fraction=0.7, bagging_freq=1, feature_fraction=0.8))


def test_resume_byte_identical_dart(tmp_path):
    # DART adds drop-RNG + mutable per-tree weights to the state surface
    _resume_matches_golden(tmp_path, dict(_BASE, boosting="dart",
                                          drop_rate=0.3))


def test_resume_byte_identical_goss(tmp_path):
    _resume_matches_golden(tmp_path, dict(_BASE, boosting="goss"))


def test_resume_restores_eval_history_and_early_stopping(tmp_path):
    _resume_matches_golden(tmp_path, dict(
        _BASE, bagging_fraction=0.7, bagging_freq=1), valid=True,
        early_stop=True)


def test_resume_from_empty_dir_is_fresh_start(tmp_path):
    bst, _ = _train(_BASE, str(tmp_path / "fresh"), 3, resume=True)
    assert bst.current_iteration == 3


def test_resume_past_target_trains_nothing(tmp_path):
    # num_boost_round is the TOTAL target: a checkpoint already at (or past)
    # it must resume to the same model without another boosting step
    _train(_BASE, str(tmp_path / "c"), 4)
    bst, _ = _train(_BASE, str(tmp_path / "c"), 4, resume=True)
    assert bst.current_iteration == 4


# ------------------------------------------------------ kill-and-resume
@pytest.mark.slow
def test_sigterm_kill_and_resume_byte_identical(tmp_path):
    """The full preemption story in real processes: the victim dies with
    the signal's exit status (143 / -SIGTERM) AFTER the callback snapshots
    at the iteration boundary; resume completes the run byte-identically."""
    worker = os.path.join(REPO, "tests", "ckpt_worker.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}

    def run(ckpt_dir, mode):
        return subprocess.run([sys.executable, worker, ckpt_dir, mode],
                              env=env, cwd=REPO, capture_output=True,
                              text=True, timeout=540)

    g_dir, i_dir = str(tmp_path / "g"), str(tmp_path / "i")
    p = run(g_dir, "golden")
    assert p.returncode == 0, p.stderr[-2000:]
    p = run(i_dir, "victim")
    assert p.returncode in (-15, 143), (p.returncode, p.stderr[-2000:])
    assert glob.glob(os.path.join(i_dir, "snap_*.model.txt"))
    p = run(i_dir, "resume")
    assert p.returncode == 0, p.stderr[-2000:]
    with open(os.path.join(g_dir, "final_model.txt")) as f:
        golden = f.read()
    with open(os.path.join(i_dir, "final_model.txt")) as f:
        resumed = f.read()
    assert golden == resumed


# ------------------------------------------------- corruption / fallback
def _corrupt(path, truncate=False):
    if truncate:
        with open(path, "r+b") as f:
            f.truncate(10)
    else:
        with open(path, "r+b") as f:
            f.seek(0)
            f.write(b"\x00" * 64)


def test_corrupt_newest_snapshot_falls_back(tmp_path):
    d = str(tmp_path)
    _train(_BASE, d, 5)
    assert load_latest(d).iteration == 5
    _corrupt(sorted(glob.glob(os.path.join(d, "snap_*.state.npz")))[-1])
    assert load_latest(d).iteration == 4
    # a truncated write (the classic preemption artifact) is also caught
    _corrupt(sorted(glob.glob(os.path.join(d, "snap_*.meta.json")))[-2],
             truncate=True)
    assert load_latest(d).iteration == 3


def test_corrupt_fallback_still_resumes_byte_identical(tmp_path):
    golden, _ = _train(_BASE, str(tmp_path / "g"), 8)
    d = str(tmp_path / "i")
    _train(_BASE, d, 4)
    _corrupt(sorted(glob.glob(os.path.join(d, "snap_*.state.npz")))[-1])
    resumed, _ = _train(_BASE, d, 8, resume=True)   # falls back to snap 3
    assert golden.model_to_string() == resumed.model_to_string()


def test_all_snapshots_corrupt_raises(tmp_path):
    d = str(tmp_path)
    _train(dict(_BASE, checkpoint_keep=2), d, 2)
    for p in glob.glob(os.path.join(d, "snap_*.state.npz")):
        _corrupt(p)
    with pytest.raises(LightGBMError, match="none passed verification"):
        load_latest(d)


def test_manifest_bak_fallback(tmp_path):
    d = str(tmp_path)
    _train(_BASE, d, 3)
    os.remove(os.path.join(d, "MANIFEST.json"))
    assert load_latest(d).iteration >= 2   # .bak holds the previous publish


def test_retention_keeps_last_n(tmp_path):
    d = str(tmp_path)
    _train(dict(_BASE, checkpoint_keep=2), d, 6)
    ids = sorted(int(os.path.basename(p)[5:13]) for p in
                 glob.glob(os.path.join(d, "snap_*.state.npz")))
    assert ids[-2:] == [5, 6]
    assert len(ids) <= 3   # last 2 + at most one best-flagged survivor


def test_dataset_fingerprint_mismatch_raises(tmp_path):
    d = str(tmp_path)
    _train(_BASE, d, 3)
    X, y = _data(seed=99)   # different data, same shapes
    with pytest.raises(LightGBMError, match="fingerprint"):
        _train(_BASE, d, 6, resume=True, X=X, y=y)


# ------------------------------------------------------------- serving
def test_registry_replace_and_hot_roll(tmp_path):
    from lightgbm_tpu.serving import ModelRegistry, ServingEngine
    d = str(tmp_path)
    X, y = _data()
    _train(_BASE, d, 3)
    reg = ModelRegistry()
    eng = ServingEngine(registry=reg)
    w = reg.watch_dir("m", d)
    assert w.poll() is True          # first poll registers snapshot 3
    assert w.poll() is False         # nothing newer
    assert reg.generation("m") == 1
    p1 = eng.predict("m", X[:8])
    assert eng.cache_size() > 0
    # bare re-registration of a live id must be refused...
    with pytest.raises(LightGBMError, match="replace=True"):
        reg.load_file("m", CheckpointManager(d).latest_model()[1])
    # ...while a newer snapshot hot-rolls atomically: generation bump,
    # compiled-predictor purge, and predictions from the new forest
    _train(_BASE, d, 8, resume=True)
    assert w.poll() is True
    assert reg.generation("m") == 2
    assert eng.cache_size() == 0     # replace listener purged the old entries
    p2 = eng.predict("m", X[:8])
    assert not np.allclose(p1, p2)


# ------------------------------------------------- config / API surface
def test_config_validation():
    with pytest.raises(LightGBMError):
        lgb.Config({"objective": "binary", "checkpoint_period": 0})
    with pytest.raises(LightGBMError):
        lgb.Config({"objective": "binary", "checkpoint_keep": 0})
    cfg = lgb.Config({"objective": "binary", "checkpoint_dir": "/tmp/x",
                      "checkpoint_freq": 5})
    assert cfg.checkpoint_period == 5


def test_checkpoint_dir_param_auto_attaches_callback(tmp_path):
    d = str(tmp_path / "auto")
    X, y = _data()
    ds = lgb.Dataset(X, label=y, params=dict(_BASE))
    engine.train(dict(_BASE, checkpoint_dir=d, checkpoint_period=2), ds,
                 num_boost_round=4, verbose_eval=False)
    assert load_latest(d).iteration == 4


def test_lossy_init_model_continuation_warns(tmp_path):
    params = dict(_BASE, bagging_fraction=0.7, bagging_freq=1)
    bst, _ = _train(params, str(tmp_path), 3)
    msgs = []
    Log.reset_callback(lambda m: msgs.append(m))
    try:
        X, y = _data()
        ds = lgb.Dataset(X, label=y, params=dict(params))
        engine.train(dict(params), ds, num_boost_round=2, init_model=bst,
                     verbose_eval=False)
    finally:
        Log.reset_callback(None)
    assert any("resume_from" in m for m in msgs)
